"""Host milliseconds spent loading shards per query:
`stage_ms{index_query_stack.load}` summed over the window / queries."""

import readers

META = {'layer': 'index query', 'source': 'program_span', 'unit': 'ms', 'better': 'lower',
        'moves': 'query_completed_per_s'}


def read(r):
    ms, n = readers.stage_ms(r, 'index_query_stack.load'), len(r.done('query'))
    return ms / n if ms is not None and n else None
