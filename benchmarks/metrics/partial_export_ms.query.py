"""Milliseconds a member spends turning a partial's stacked aggregate
into the wire's per-shard key items: `stage_ms{index_query_stack.export}`
sum / count over the window (a partial, not a query: a routed query has
one a partition)."""

META = {'layer': 'index query', 'source': 'program_span', 'unit': 'ms', 'better': 'lower',
        'moves': 'query_completed_per_s'}

STAGE = 'index_query_stack.export'


def read(r):
    n = r.delta('stage_ms_count', stage=STAGE)
    if not n:
        return None
    return (r.delta('stage_ms_sum', stage=STAGE) or 0.0) / n
