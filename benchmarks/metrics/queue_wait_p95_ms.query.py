"""95th percentile of the wait for an execution slot, from the
`serve_queue_wait_ms` histogram (the upper bound of the bucket it
falls in)."""

import readers

META = {'layer': 'serve', 'source': 'program_span', 'unit': 'ms', 'better': 'lower',
        'moves': 'query_completed_per_s'}


def read(r):
    return readers.queue_wait_p95_ms(r)
