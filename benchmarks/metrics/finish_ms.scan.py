"""Milliseconds of a scan's thread between its last batch and its
result: every scanner's deferred merge, as self time (the fetches,
emits and merges it opens inside are leaves of their own):
S(`scan.finish`) a finished request."""

import spans

META = {'layer': 'engine', 'source': 'program_span', 'unit': 'ms', 'better': 'lower',
        'moves': 'scan_records_per_s'}


def read(r):
    return spans.per_request_ms(r, 'scan', 'scan.finish')
