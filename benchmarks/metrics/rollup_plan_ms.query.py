"""Milliseconds of the rollup planner a finished query: the leaf
`index_query.plan` around `rollup.plan_query` (each level's manifest
read and parsed, every fine source a candidate rollup vouches for
re-statted) summed over the window / queries.  Nothing to read from a
program that plans inside `index_query.prune`."""

import spans

META = {'layer': 'index query', 'source': 'program_span', 'unit': 'ms', 'better': 'lower',
        'moves': 'query_completed_per_s'}


def read(r):
    return spans.per_request_ms(r, 'query', 'index_query.plan')
