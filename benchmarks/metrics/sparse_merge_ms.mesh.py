"""Milliseconds a finished scan's thread spends merging the chips'
sparse sets (reading their counts, the all-gather and the extra fold's
dispatch, the wait for it): `stage_ms{scan.sparse_merge}` over the
window / scans."""

import readers

META = {'layer': 'mesh', 'source': 'program_span', 'unit': 'ms', 'better': 'lower',
        'moves': 'scan_records_per_s'}


def read(r):
    ms, scans = readers.stage_ms(r, 'scan.sparse_merge'), len(r.done('scan'))
    return ms / scans if ms is not None and scans else None
