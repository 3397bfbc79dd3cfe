"""Share of a query's time that neither the wait for an execution slot
nor a leaf stage covers: 100 - (`serve_queue_wait_ms` +
`stages.QUERY_THREAD`) / `serve_op_latency_ms{op=query}`.  It says how
far the other readings of the query path can be trusted."""

import stages

META = {'layer': 'obs', 'source': 'program_span', 'unit': '%', 'better': 'lower',
        'moves': 'query_completed_per_s'}


def read(r):
    return stages.unattributed_pct(
        r, 'query', stages.QUERY_THREAD,
        waited_ms=r.delta('serve_queue_wait_ms_sum') or 0.0)
