"""Share of a build's time that its thread waits for the device with
nothing else to do: `stage_ms{scan.device_wait}` over the window /
`serve_op_latency_ms{op=build}`.  Near 0 while the host sets the pace."""

import stages

META = {'layer': 'engine', 'source': 'program_span', 'unit': '%', 'better': 'lower',
        'moves': 'build_records_per_s'}


def read(r):
    return stages.share_pct(r, 'build', 'scan.device_wait')
