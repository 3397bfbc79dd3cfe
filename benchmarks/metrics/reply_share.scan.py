"""Share of a scan's time on the server that is its reply: the
aggregate's emission in output order (`scan.order`) and the formatting
of the lines (`reply.format`), S(`scan.order`) + S(`reply.format`) over
`serve_op_latency_ms{op=scan}`."""

import stages

META = {'layer': 'serve', 'source': 'program_span', 'unit': '%', 'better': 'lower',
        'moves': 'scan_records_per_s'}


def read(r):
    if r.delta('stage_ms_count', stage='reply.format') is None:
        return None
    return stages.share_pct(r, 'scan', 'scan.order', 'reply.format')
