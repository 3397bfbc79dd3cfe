"""Milliseconds a finished build's thread spends merging the chips'
sparse sets (m1's, at every epoch flip: reading their counts, the
all-gather and the extra fold's dispatch, the wait for it):
`stage_ms{scan.sparse_merge}` over the window / builds."""

import readers

META = {'layer': 'mesh', 'source': 'program_span', 'unit': 'ms', 'better': 'lower',
        'moves': 'build_records_per_s'}


def read(r):
    ms, builds = readers.stage_ms(r, 'scan.sparse_merge'), len(r.done('build'))
    return ms / builds if ms is not None and builds else None
