"""Milliseconds the router spends merging a routed query's partials:
`stage_ms{router.merge}` (the shards put in global find order and their
key items replayed into the aggregate) / finished queries."""

import spans

META = {'layer': 'router', 'source': 'program_span', 'unit': 'ms', 'better': 'lower',
        'moves': 'query_completed_per_s'}


def read(r):
    return spans.per_request_ms(r, 'query', 'router.merge')
