"""Device dispatches a parsed batch of a build: growth of
`device_pipe_dispatches` / growth of `scan_batches_handed` over the
window.  An exact count: 1 where the build's scans are stacked into one
program, the number of metrics on the per-scan loop."""

import stages

META = {'layer': 'engine', 'source': 'program_counter', 'unit': 'count', 'better': 'lower',
        'moves': 'build_records_per_s'}


def read(r):
    return stages.ratio(r, 'device_pipe_dispatches', 'scan_batches_handed')
