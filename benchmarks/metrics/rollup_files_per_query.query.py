"""Index files a finished query opens under the planner: the rollup
shards it reads (growth of `rollup shards queried`) and the fine shards
no rollup covered (`index shards queried` less `index shards via
rollup`), over the window's finished queries.  The fine walk of the
same windows opens `index shards queried` a query."""

from loader import load_module

META = {'layer': 'index query', 'source': 'program_counter', 'unit': 'count', 'better': 'lower',
        'moves': 'query_completed_per_s'}

coverage = load_module('metrics', 'rollup_coverage_share.query')


def read(r):
    rollups = coverage.growth(r, 'rollup shards queried')
    via, queried = (coverage.growth(r, coverage.VIA),
                    coverage.growth(r, coverage.QUERIED))
    n = len(r.done('query'))
    if rollups is None or via is None or queried is None or not n:
        return None
    r.say('rollup_files_per_query.query: %.1f shards a query asked for, '
          '%.2f rollup files and %.2f fine files opened'
          % (queried / n, rollups / n, (queried - via) / n))
    return (rollups + queried - via) / n
