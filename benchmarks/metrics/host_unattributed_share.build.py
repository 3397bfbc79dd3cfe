"""Share of a build's time that no leaf stage of its thread covers: 100 -
the request thread's leaves (`stages.BUILD_THREAD`: not `scan.parse` and
`scan.read`, which run beside them) / `serve_op_latency_ms{op=build}`.
It says how far the other shares can be trusted."""

import stages

META = {'layer': 'obs', 'source': 'program_span', 'unit': '%', 'better': 'lower',
        'moves': 'build_records_per_s'}


def read(r):
    return stages.unattributed_pct(r, 'build', stages.BUILD_THREAD)
