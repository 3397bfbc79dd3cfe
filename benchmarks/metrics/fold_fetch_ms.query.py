"""Host milliseconds fetching the device fold's result, a query that
reached the device: `stage_ms{index_fold.fetch}` over the window /
(finished queries - `serve_result_cache_hits_total`)."""

import stages

META = {'layer': 'index query', 'source': 'program_span', 'unit': 'ms', 'better': 'lower',
        'moves': 'query_completed_per_s'}


def read(r):
    return stages.per_device_query(r, 'index_fold.fetch')
