"""Directory snapshots the index walk read anew for one publish:
growth of `index_walk_snapshot_rebuilds_total`, every `reason`, /
growth of `index_publishes_total` over the window.  A tree that stands
still reads its directory twice a server, both in the warm-up; a
publish changes the directory when its tmp is prepared and when it is
renamed, and the invalidation drops the snapshot besides."""

from obs import prom

META = {'layer': 'index query', 'source': 'program_counter',
        'unit': 'count', 'better': 'lower',
        'moves': 'query_completed_per_s'}

NAME = prom.PREFIX + 'index_walk_snapshot_rebuilds_total'


def read(r):
    n = r.delta('index_publishes_total')
    if not n:
        return None
    grown = sum(v - r.before.get(key, 0.0)
                for key, v in r.after.items() if key[0] == NAME)
    return grown / n
