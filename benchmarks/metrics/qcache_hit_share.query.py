"""Share of the window's queries that the server's result cache
answered: hits / (hits + misses)."""

import readers

META = {'layer': 'serve', 'source': 'program_counter', 'unit': '%', 'better': 'higher',
        'moves': 'query_completed_per_s'}


def read(r):
    hits = r.delta('serve_result_cache_hits_total')
    misses = r.delta('serve_result_cache_misses_total')
    if hits is None and misses is None:
        return None
    total = (hits or 0.0) + (misses or 0.0)
    return 100.0 * (hits or 0.0) / total if total else None
