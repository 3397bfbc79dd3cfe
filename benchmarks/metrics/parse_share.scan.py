"""Share of a scan's time in which the native parser ran:
`stage_ms{scan.parse}` over the window / `serve_op_latency_ms{op=scan}`.
The parser runs on a thread of its own since PR 30, beside the
request's thread, so this is no part of a sum to 100."""

import stages

META = {'layer': 'parse', 'source': 'program_span', 'unit': '%', 'better': 'lower',
        'moves': 'scan_records_per_s'}


def read(r):
    return stages.share_pct(r, 'scan', 'scan.parse')
