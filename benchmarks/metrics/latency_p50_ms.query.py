"""Median latency of an index query, from sent to the last byte of the
reply, under the cell's fixed closed-loop load (queueing included)."""

import readers

META = {'layer': 'serve', 'source': 'host_clock', 'unit': 'ms',
        'better': 'lower', 'moves': 'query_completed_per_s'}


def read(r):
    return readers.latency_ms(r, 'query', 0.5)
