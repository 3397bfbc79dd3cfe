"""The fold and compaction programs' share of their roofline: the least
time the chip could take over the bytes they must move (memory
bound; trace/costs.py, trace/peaks.json) / the device time they took."""

import readers

META = {'layer': 'kernels', 'source': 'device_trace', 'unit': '%', 'better': 'higher',
        'moves': 'scan_records_per_s'}


def read(r):
    return readers.fold_roofline_pct(r)
