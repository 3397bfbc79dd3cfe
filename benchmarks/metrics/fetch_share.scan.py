"""Share of a scan's time in which its thread fetches from the device
(the sparse lane's guard reading the set's fill, and the flush that
brings the tuples back): `stage_ms{scan.fetch}` over the window /
`serve_op_latency_ms{op=scan}`."""

import stages

META = {'layer': 'engine', 'source': 'program_span', 'unit': '%', 'better': 'lower',
        'moves': 'scan_records_per_s'}


def read(r):
    return stages.share_pct(r, 'scan', 'scan.fetch')
