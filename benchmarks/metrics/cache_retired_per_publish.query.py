"""What one publish retires of the server's caches, in entries: growth
of (`serve_result_cache_retired_total` + `device_residency_retired_total`
+ `index_shard_handles_retired_total`) / growth of
`index_publishes_total` over the window: the result cache's entries
and the device's pins that the epoch's bump dropped, and the shard
handles `invalidate_index_tree` closed.  A counter never written has
not grown; a program that counts no publishes reads nothing."""

META = {'layer': 'serve', 'source': 'program_counter', 'unit': 'entries',
        'better': 'lower', 'moves': 'query_completed_per_s'}

RETIRED = ('serve_result_cache_retired_total',
           'device_residency_retired_total',
           'index_shard_handles_retired_total')


def read(r):
    n = r.delta('index_publishes_total')
    if not n:
        return None
    return sum(r.delta(name) or 0.0 for name in RETIRED) / n
