"""Share of a scan's time in which its thread turns the fetched set
into result tuples: `stage_ms{scan.emit}` over the window /
`serve_op_latency_ms{op=scan}`.  The reply's formatting comes after it
and lies under no leaf (`host_unattributed_share.scan`)."""

import stages

META = {'layer': 'engine', 'source': 'program_span', 'unit': '%', 'better': 'lower',
        'moves': 'scan_records_per_s'}


def read(r):
    return stages.share_pct(r, 'scan', 'scan.emit')
