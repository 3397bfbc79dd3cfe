"""Batches of a finished scan that went through the sparse sort-merge
fold (`kernels.sparse_fold`): growth of `device_sparse_fold_batches` /
scans.  In a high-cardinality scan that is every batch of the scan; a
count that repeats exactly."""

META = {'layer': 'kernels', 'source': 'program_counter', 'unit': 'count', 'better': 'lower',
        'moves': 'scan_records_per_s'}


def read(r):
    batches = r.delta('device_sparse_fold_batches')
    scans = len(r.done('scan'))
    return batches / scans if batches is not None and scans else None
