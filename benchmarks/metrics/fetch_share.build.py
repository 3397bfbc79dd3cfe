"""Share of a build's time in which its thread fetches results from the
device (the drains at an epoch's end): `stage_ms{scan.fetch}` over the
window / `serve_op_latency_ms{op=build}`."""

import stages

META = {'layer': 'engine', 'source': 'program_span', 'unit': '%', 'better': 'lower',
        'moves': 'build_records_per_s'}


def read(r):
    return stages.share_pct(r, 'build', 'scan.fetch')
