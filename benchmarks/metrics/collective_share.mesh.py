"""Device time of collective operations (all-reduce and kin) / device
busy time, summed over the chips of the mesh."""

import readers

META = {'layer': 'mesh', 'source': 'device_trace', 'unit': '%', 'better': 'lower',
        'moves': 'scan_records_per_s'}


def read(r):
    chips = r.trace['chips'] if r.trace else []
    busy = sum(c['busy_s'] for c in chips)
    if not busy:
        return None
    return 100.0 * sum(c['collective_s'] for c in chips) / busy
