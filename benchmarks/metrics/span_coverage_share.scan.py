"""Share of a scan's time on the server that the program itself counts as
covered: 100 x (growth of `serve_leaf_ms_sum{op=scan}`, what the request's
own thread spent under leaf stages, + growth of `serve_queue_wait_ms_sum`,
its wait for an execution slot) / growth of
`serve_op_latency_ms_sum{op=scan}`.  A leaf that a later change adds or
renames is counted with no list kept here (`spans.coverage_pct`)."""

import spans

META = {'layer': 'obs', 'source': 'program_span', 'unit': '%', 'better': 'higher',
        'moves': 'scan_records_per_s'}


def read(r):
    return spans.coverage_pct(r, 'scan')
