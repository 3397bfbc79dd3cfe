"""Milliseconds a routed query's thread waits for its partials:
`stage_ms{router.scatter}` (the leaf from the first partial's dispatch
to the last one's answer, local partials included) summed over the
members' scrapes / finished queries."""

import spans

META = {'layer': 'router', 'source': 'program_span', 'unit': 'ms', 'better': 'lower',
        'moves': 'query_completed_per_s'}


def read(r):
    return spans.per_request_ms(r, 'query', 'router.scatter')
