"""The mesh's sparse merge against its roofline: the least seconds a
chip could take over the bytes one merge must move
(trace/costs_sparse_merge.py: the other chips' live slots over the
interconnect, every live slot through HBM twice; rows a merge from
`device_sparse_merge_rows` / the window's `scan.sparse_merge` stages) /
the most loaded chip's seconds a run of module `jit_sparse_merge` in
the trace."""

import readers
from loader import load_module

META = {'layer': 'mesh', 'source': 'device_trace', 'unit': '%', 'better': 'higher',
        'moves': 'scan_records_per_s'}


def read(r):
    rows = r.delta('device_sparse_merge_rows')
    merges = r.delta('stage_ms_count', stage='scan.sparse_merge')
    runs = [c['modules'].get('jit_sparse_merge')
            for c in (r.trace['chips'] if r.trace else [])]
    runs = [m for m in runs if m and m[0] and m[1] > 0]
    if not rows or not merges or not runs:
        return None
    costs = load_module('trace', 'costs_sparse_merge')
    need = costs.least_seconds(rows / merges, r.config['chips'],
                               readers.peaks(r))
    return 100.0 * need / max(secs / n for n, secs in runs)
