"""Rows the device fold summed a query that reached it: growth of
`index_fold_rows` over the window's finished queries less those the
result cache answered.  A rollup shard holds its fine shards' rows and
not their sum, so a planned query folds what the fine walk would; the
line says what the ladder's padding adds (`index_fold_padded_rows` /
`index_fold_rows`)."""

META = {'layer': 'kernels', 'source': 'program_counter', 'unit': 'rows', 'better': 'lower',
        'moves': 'query_completed_per_s'}


def read(r):
    rows, padded = r.delta('index_fold_rows'), r.delta('index_fold_padded_rows')
    reached = len(r.done('query')) - \
        (r.delta('serve_result_cache_hits_total') or 0.0)
    if not rows or reached <= 0:
        return None
    if padded:
        r.say('index_fold_rows_per_query.query: %.0f padded rows a query, '
              '%.3f of the rows summed' % (padded / reached, padded / rows))
    return rows / reached
