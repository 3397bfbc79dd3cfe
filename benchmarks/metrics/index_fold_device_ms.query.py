"""Device milliseconds of the index fold per query that reached the
device (queries the result cache answered are left out)."""

import readers

META = {'layer': 'kernels', 'source': 'device_trace', 'unit': 'ms', 'better': 'lower',
        'moves': 'query_completed_per_s'}


def read(r):
    busy = readers.device_seconds(r)
    ran = len(r.done('query')) - (
        r.delta('serve_result_cache_hits_total') or 0.0)
    return 1000.0 * busy / ran if busy is not None and ran > 0 else None
