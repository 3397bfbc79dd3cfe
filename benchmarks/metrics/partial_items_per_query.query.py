"""Key items the routers merged a finished query, all partitions
together: growth of `router_partial_items_total` / finished queries.
The per-shard wire carries shards x groups; the stacked partial at
most partitions x tuples."""

META = {'layer': 'router', 'source': 'program_counter', 'unit': 'count', 'better': 'lower',
        'moves': 'query_completed_per_s'}


def read(r):
    items, n = r.delta('router_partial_items_total'), len(r.done('query'))
    return items / n if items is not None and n else None
