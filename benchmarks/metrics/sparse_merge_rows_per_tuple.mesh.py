"""Live slots the chips bring to a merge per tuple that comes out of
it: `device_sparse_merge_rows` / `device_sparse_merge_tuples` over the
window.  1 where the chips' keys are disjoint, the number of chips
where every chip saw every key: what the all-gather moves beyond the
reply.  An exact count."""

import stages

META = {'layer': 'mesh', 'source': 'program_counter', 'unit': 'rows/tuple', 'better': 'lower',
        'moves': 'scan_records_per_s'}


def read(r):
    return stages.ratio(r, 'device_sparse_merge_rows',
                        'device_sparse_merge_tuples')
