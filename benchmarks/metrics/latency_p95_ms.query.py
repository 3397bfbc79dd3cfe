"""95th percentile of an index query's latency under the cell's load.
Beside the end-to-end rate, not in its place: on a one-chip machine a
tail spreads too widely to carry a bound (PERF.md section 6, PR 25)."""

import readers

META = {'layer': 'serve', 'source': 'host_clock', 'unit': 'ms',
        'better': 'lower', 'moves': 'query_completed_per_s'}


def read(r):
    return readers.latency_ms(r, 'query', 0.95)
