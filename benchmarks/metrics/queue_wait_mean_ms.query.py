"""Mean wait for an execution slot: `serve_queue_wait_ms_sum` /
`_count` over the window.  Exact, where `queue_wait_p95_ms.query` is
a bucket's bound."""

import stages

META = {'layer': 'serve', 'source': 'program_span', 'unit': 'ms', 'better': 'lower',
        'moves': 'query_completed_per_s'}


def read(r):
    return stages.ratio(r, 'serve_queue_wait_ms_sum',
                        'serve_queue_wait_ms_count')
