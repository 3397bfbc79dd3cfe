"""Share of the shards the window's queries asked for that a rollup
shard answered in their place: growth of the lane counter `index
shards via rollup` (the planner's covered fine shards) over that of
`index shards queried` (the walk's kept shards), both read where the
engagement counters are, `/stats` `counters`.  100 where every window
falls on whole days of a tree whose rollups are fresh."""

META = {'layer': 'index query', 'source': 'program_counter', 'unit': '%', 'better': 'higher',
        'moves': 'query_completed_per_s'}

VIA, QUERIED = 'index shards via rollup', 'index shards queried'


def growth(r, name):
    """A `/stats` counter's growth over the window; None where the
    server never wrote it."""
    after = (r.stats_after.get('counters') or {}).get(name)
    if after is None:
        return None
    return after - ((r.stats_before.get('counters') or {}).get(name) or 0)


def read(r):
    via, queried = growth(r, VIA), growth(r, QUERIED)
    if via is None or not queried:
        return None
    return 100.0 * via / queried
