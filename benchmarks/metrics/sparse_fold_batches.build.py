"""Batches of a metric that went through the sparse sort-merge fold
(`kernels.sparse_fold`), a finished build: growth of
`device_sparse_fold_batches` / builds.  A count that repeats exactly."""

META = {'layer': 'kernels', 'source': 'program_counter', 'unit': 'count', 'better': 'lower',
        'moves': 'build_records_per_s'}


def read(r):
    batches = r.delta('device_sparse_fold_batches')
    builds = len(r.done('build'))
    return batches / builds if batches is not None and builds else None
