"""Multi-host execution: jax.distributed control plane + input
partitioning.

The reference distributes work by submitting Manta jobs (one map task per
object, code shipped as a tarball asset, 1-second job polling:
lib/datasource-manta.js:461-638).  Here every host runs the same program:

* DN_COORDINATOR / DN_NUM_PROCESSES / DN_PROCESS_ID (or the standard JAX
  cluster env) select the jax.distributed coordinator over DCN,
* each process scans files[process_id::num_processes] — the map-phase
  partitioning, pruned by the same strftime/time-bounds logic as local
  scans,
* the dense partial accumulators merge with psum over the global mesh
  (ICI within a pod, DCN across), replacing the reduce-phase object
  hand-off; every process computes the full result, process 0 prints.
"""

import os

from ..ops import get_jax

_initialized = False


def maybe_initialize():
    """Initialize jax.distributed when multi-host env vars are present.
    Returns (num_processes, process_id).

    Single-process (no coordinator configured and jax.distributed not
    already initialized) returns (1, 0) WITHOUT touching the backend:
    jax.process_count() initializes devices, which takes seconds on a
    chip — a cost that informational
    callers (dry-run plans, file partitioning) must never pay."""
    global _initialized
    j = get_jax()
    if j is None:
        return (1, 0)
    jax, _ = j

    coord = os.environ.get('DN_COORDINATOR')
    if coord and not _initialized:
        nprocs = int(os.environ['DN_NUM_PROCESSES'])
        pid = int(os.environ['DN_PROCESS_ID'])
        jax.distributed.initialize(coordinator_address=coord,
                                   num_processes=nprocs,
                                   process_id=pid)
        _initialized = True

    if not _initialized and not _jax_dist_initialized(jax):
        return (1, 0)

    try:
        return (jax.process_count(), jax.process_index())
    except Exception:
        return (1, 0)


def _jax_dist_initialized(jax):
    """Whether jax.distributed was initialized by someone else (an
    outer launcher); does not initialize anything itself."""
    try:
        return bool(jax.distributed.is_initialized())
    except Exception:
        return False


def partition_files(files, num_processes, process_id):
    """Deterministic map-phase partitioning of the found file list."""
    return [f for i, f in enumerate(files)
            if i % num_processes == process_id]


def is_output_process():
    """Whether this process should print results (process 0; trivially
    true single-process).  The common case — no distributed env, no
    initialized runtime — answers WITHOUT importing jax: CLI output
    paths call this on every command, and a host-engine scan must not
    pay jax import (let alone distributed initialization) at print
    time.  With DN_COORDINATOR exported the launch is explicitly
    distributed and the full check is the point."""
    if not os.environ.get('DN_COORDINATOR') and not _initialized:
        import sys
        jax = sys.modules.get('jax')
        if jax is None or not _jax_dist_initialized(jax):
            return True
    _, pid = maybe_initialize()
    return pid == 0
