"""Share of the partials the members served that the device fold
summed: growth of `cluster_partials_total{lane="device"}` over that of
every lane (`device`, `stacked`: the stack on the host's bincount,
`shard`: the per-shard loop)."""

META = {'layer': 'index query', 'source': 'program_counter', 'unit': '%', 'better': 'higher',
        'moves': 'query_completed_per_s'}

LANES = ('device', 'stacked', 'shard')


def read(r):
    grew = [r.delta('cluster_partials_total', lane=lane) for lane in LANES]
    total = sum(g or 0.0 for g in grew)
    if all(g is None for g in grew) or not total:
        return None
    return 100.0 * (grew[0] or 0.0) / total
