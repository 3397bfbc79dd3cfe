"""Share of the traced steady window in which no operation ran on the
chip (the most idle chip of a mesh)."""

import readers

META = {'layer': 'device', 'source': 'device_trace', 'unit': '%', 'better': 'lower',
        'moves': 'query_completed_per_s'}


def read(r):
    return readers.idle_share_pct(r)
