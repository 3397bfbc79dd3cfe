"""Device time of collective operations (all-reduce and kin) / device
busy time, summed over the chips of the mesh, in a build's window:
`collective_share.mesh`'s reading under the build's name."""

from loader import load_module

META = {'layer': 'mesh', 'source': 'device_trace', 'unit': '%', 'better': 'lower',
        'moves': 'build_records_per_s'}


def read(r):
    return load_module('metrics', 'collective_share.mesh').read(r)
