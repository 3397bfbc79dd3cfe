"""Milliseconds of a query's thread before its shards load: the
request's resolution on the server (`serve.resolve`, which the queries
that the result cache answers spend too), the shard walk
(`index_query.paths`) and the pruning, the integrity check and the
rollup planner (`index_query.prune`), summed, a query that reached the
device."""

import stages

META = {'layer': 'index query', 'source': 'program_span', 'unit': 'ms', 'better': 'lower',
        'moves': 'query_completed_per_s'}

PARTS = ('serve.resolve', 'index_query.paths', 'index_query.prune')


def read(r):
    parts = [stages.per_device_query(r, s) for s in PARTS]
    if parts[1] is None:
        # no walk under a leaf: a program older than PR 42, or no query
        # that reached the device
        return None
    return sum(p or 0.0 for p in parts)
