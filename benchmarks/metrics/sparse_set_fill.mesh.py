"""How full the fullest chip's sparse set is when it is flushed:
`device_sparse_set_live` / `device_sparse_set_slots` over the window
(live tuples of the fullest chip over its capacity, summed over the
flushes).  An exact count."""

import stages

META = {'layer': 'engine', 'source': 'program_counter', 'unit': '%', 'better': 'higher',
        'moves': 'scan_records_per_s'}


def read(r):
    fill = stages.ratio(r, 'device_sparse_set_live', 'device_sparse_set_slots')
    return None if fill is None else 100.0 * fill
