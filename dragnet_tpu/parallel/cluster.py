"""Cluster datasource: the distributed execution backend.

Same public surface as the file backend (scan/build/query/index-scan/
index-read), but execution is SPMD over the device mesh:

* the record axis of every batch shards across local devices, with the
  dense accumulator merged by psum over ICI (mesh.sharded_aggregate),
* under a multi-host launch (DN_COORDINATOR et al., see distributed.py),
  each process scans its slice of the found files — the map-phase
  partitioning — and the psum over the global mesh is the reduce phase,
* index builds write per-process partial artifacts that merge by
  addition (the same commutative-monoid property the reference's Manta
  reduce relied on).

Config-level the backend accepts `--backend=cluster` (and `manta` as a
compatibility alias).
"""

import os

import numpy as np

from ..errors import DNError
from ..engine import VectorScan
from ..device_scan import DeviceScan
from .. import datasource_file
from . import mesh as mod_mesh
from . import distributed as mod_dist


class MeshVectorScan(VectorScan):
    """VectorScan whose dense aggregation runs sharded over the mesh."""

    _warned_no_backend = False

    def _dense_aggregate(self, key_codes, radices, weights, alive, n):
        from ..ops import backend_ready
        if not backend_ready():
            # no usable devices (jax missing, or no platform came
            # up): host aggregation, same results — but say so once,
            # or the degradation is invisible
            if not MeshVectorScan._warned_no_backend:
                MeshVectorScan._warned_no_backend = True
                import sys
                sys.stderr.write(
                    'dn: warning: no usable accelerator backend; '
                    'cluster aggregation running on host\n')
            return super(MeshVectorScan, self)._dense_aggregate(
                key_codes, radices, weights, alive, n)
        codes = np.stack(key_codes)
        return mod_mesh.sharded_aggregate(codes, radices, weights, alive)


class MeshDeviceScan(DeviceScan, MeshVectorScan):
    """The cluster backend's full-pipeline SPMD scan: eligible batches
    run the entire DeviceScan program — predicate table-gathers, date
    and time-bounds masks, bucketize, fused-key reduction — under
    shard_map over the process-local device mesh, with psum merges for
    dense weights/counters and a pmin over global row indices for
    first-occurrence order (identical to host-engine insertion order).
    A key space past the dense budget runs the sparse program the same
    way, with no collective per batch: every chip sort-merges its
    shard into a set of its own, and at the flush the sets are
    all-gathered and folded once more (mesh.sparse_merge_program) —
    upstream's map -> reduce.  Batches the device program cannot take
    (a non-integral key, a cap past 2^31) fall back through the MRO to
    MeshVectorScan, whose dense aggregation is still mesh-sharded, and
    results match the host engine byte-for-byte (differential-tested).

    This replaces the round-3 design where only the final segment-sum
    was sharded and predicates/bucketize stayed on the host even in
    cluster mode."""

    ESCALATE_RECORDS = 0          # cluster mode is explicitly sharded
    REQUIRE_ACCELERATOR = False   # the CPU test mesh is a valid target

    _mesh_cache = None

    def _device_mesh(self):
        m = MeshDeviceScan._mesh_cache
        if m is None:
            from ..ops import backend_ready
            if not backend_ready():
                return None
            m = (mod_mesh.make_mesh(), 'd')
            MeshDeviceScan._mesh_cache = m
        return m


class DatasourceCluster(datasource_file.DatasourceFile):
    """File-layout datasource executed over the device mesh / process
    set."""

    def _find(self, root, timeformat, start_ms, end_ms, pipeline,
              skip=None):
        files = super(DatasourceCluster, self)._find(
            root, timeformat, start_ms, end_ms, pipeline, skip=skip)
        if isinstance(files, DNError):
            return files
        nprocs, pid = mod_dist.maybe_initialize()
        if nprocs > 1:
            files = mod_dist.partition_files(files, nprocs, pid)
        return files

    def _cached_index_walk(self, snap, timeformat, after, before,
                           pipeline):
        """The snapshot's walk lists what a single process would walk;
        this process keeps only its partition, mirroring the _find
        override."""
        files = super(DatasourceCluster, self)._cached_index_walk(
            snap, timeformat, after, before, pipeline)
        if files is None:
            return None
        nprocs, pid = mod_dist.maybe_initialize()
        if nprocs > 1:
            files = mod_dist.partition_files(files, nprocs, pid)
        return files

    def _vector_scan_cls(self):
        return MeshDeviceScan

    def build(self, metrics, interval, time_after=None, time_before=None,
              dry_run=False, warn_func=None):
        """Distributed index build: every process index-scans its file
        partition (map), the tagged partial aggregates merge across
        processes (reduce), and process 0 writes the index artifacts —
        the same phase structure as the reference's Manta build
        (lib/datasource-manta.js:265-384) without job orchestration."""
        nprocs, pid = mod_dist.maybe_initialize()
        if nprocs <= 1 or dry_run:
            result = super(DatasourceCluster, self).build(
                metrics, interval, time_after=time_after,
                time_before=time_before, dry_run=dry_run,
                warn_func=warn_func)
            if dry_run:
                result.dry_run_plan = self.execution_plan(
                    result.dry_run_files)
            return result

        # same argument validation as the single-process build; failing
        # here (on every process) beats a TypeError on process 0 and a
        # barrier hang on the rest
        error = self.check_time_args(time_after, time_before)
        if error is None:
            error = self.check_index_args(interval, True, True)
        if error is not None:
            raise error

        # index_scan (overridden below) already allgather-merges, so
        # every process holds the complete tagged aggregate here
        result = self.index_scan(metrics, interval,
                                 filter=self.ds_filter,
                                 time_after=time_after,
                                 time_before=time_before,
                                 warn_func=warn_func)
        merged = result.points
        # the barrier must be reached even if the write fails, or every
        # other process hangs in sync_global_devices until the
        # distributed-runtime heartbeat timeout
        write_err = None
        if pid == 0:
            try:
                self._index_write(metrics, interval, merged)
            except Exception as e:
                write_err = e
        from ..ops import get_jax
        jax, _ = get_jax()
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices('dn_build_done')
        if write_err is not None:
            raise write_err
        result.points = None
        return result

    def scan(self, query, dry_run=False, warn_func=None):
        """Local scan over this process's file partition, then a
        points-level cross-process merge (process_allgather of the
        partial aggregates — the reduce phase).  Merging serialized
        points rather than dense accumulators means per-process string
        dictionaries never need to agree, and it works for every engine
        path (vector, host, --warnings)."""
        result = super(DatasourceCluster, self).scan(
            query, dry_run=dry_run, warn_func=warn_func)
        nprocs, pid = mod_dist.maybe_initialize()
        if dry_run:
            result.dry_run_plan = self.execution_plan(
                result.dry_run_files)
            return result
        if nprocs <= 1 or not result.has_points:
            return result
        result.points = _allgather_merge_points(query, result.points)
        return result

    def index_scan(self, metrics, interval, filter=None, time_after=None,
                   time_before=None, warn_func=None):
        """Distributed index-scan: each process scans its file partition
        (the _find override), then the __dn_metric-tagged partial
        aggregates merge across processes.  Without this merge a
        cluster `dn index-scan` would print only process 0's partition
        as if it were complete (the CLI output protocol prints from
        process 0 only) — in the reference, map-phase points always
        reached a reduce consumer (lib/datasource-manta.js:36-44)."""
        result = super(DatasourceCluster, self).index_scan(
            metrics, interval, filter=filter, time_after=time_after,
            time_before=time_before, warn_func=warn_func)
        nprocs, pid = mod_dist.maybe_initialize()
        if nprocs <= 1 or not result.has_points:
            return result
        result.points = _allgather_merge_tagged(result.points)
        return result

    def query(self, query, interval, dry_run=False):
        """Distributed index query: each process queries its partition
        of the index files (the _find override), then the partial
        aggregates merge across processes with the same allgather
        points reduce as scan — mirroring the reference's one-map-task-
        per-index-file queries (lib/datasource-manta.js:392-433).

        Within each process the inherited file-backend query stacks
        its shard partition into one columnar batch and runs a single
        vectorized filter+group-by over it (index_query_stack; the
        DN_IQ_THREADS reader pool loads blocks, time-range pruning and
        the shard-handle cache still apply, and under DN_ENGINE=jax
        the per-tuple sums fold as one device scatter-add).  The
        parallelism axes compose: partition across processes — each
        process's stacked partial is a commutative aggregate — with
        the allgather points reduce merging partials exactly, the same
        monoid the psum merge exploits on the scan path."""
        result = super(DatasourceCluster, self).query(
            query, interval, dry_run=dry_run)
        nprocs, pid = mod_dist.maybe_initialize()
        if dry_run:
            result.dry_run_plan = self.execution_plan(
                result.dry_run_files)
            return result
        if nprocs <= 1 or not result.has_points:
            return result
        result.points = _allgather_merge_points(query, result.points)
        return result

    def execution_plan(self, partition_files):
        """The serializable execution plan (the reference printed its
        Manta job JSON on --dry-run, lib/datasource-manta.js:446-454):
        process topology, this process's input partition, and the local
        device mesh the sharded program would run over."""
        nprocs, pid = mod_dist.maybe_initialize()
        from ..byteparse import parse_mode
        from ..index_build_mt import build_threads
        from ..index_query_mt import iq_threads
        from ..index_query_stack import stack_mode
        plan = {
            'backend': 'cluster',
            'phases': [
                {'type': 'map',
                 'exec': 'scan partition on local device mesh'},
                {'type': 'reduce',
                 'exec': 'allgather points merge across processes'},
            ],
            'nprocesses': nprocs,
            'process': pid,
            'partition': list(partition_files or []),
            # index queries additionally fan out within the process
            # (reader pool over the shard partition, index_query_mt),
            # and index builds flush shards on the writer pool
            # (index_build_mt)
            'index_query_threads': iq_threads(),
            # stacked cross-shard execution mode (index_query_stack):
            # each process stacks its own shard partition into one
            # columnar batch (with the device scatter-add lane under
            # DN_ENGINE=jax) and the partial aggregates merge across
            # processes in the reduce phase
            'index_query_stack': stack_mode(),
            'index_build_threads': build_threads(),
            # raw-byte ingest lane (byteparse): auto routes eligible
            # flat-projection json scans through the vectorized byte
            # parser when the native toolchain is absent; vector/device
            # force it (device = structural scan staged through jax)
            'parse_mode': parse_mode(),
        }
        # scatter-gather serve topology (serve/topology.py): when the
        # environment names a cluster map, the plan reports the member/
        # partition layout resident `dn serve` processes would serve
        # under.  Informational only — a broken topology file must not
        # fail a dry run, so load errors report in-plan instead.
        topo_path = os.environ.get('DN_SERVE_TOPOLOGY')
        if topo_path:
            from ..serve import topology as mod_topology
            try:
                plan['serve_topology'] = \
                    mod_topology.load_topology(topo_path).summary()
            except DNError as e:
                plan['serve_topology'] = {'path': topo_path,
                                          'error': str(e)}
        # informational only — must never pay backend initialization
        # (seconds, on a chip; a dry run does no device execution).
        # Multi-process runs already initialized the backend, so
        # listing devices is free there.
        from ..ops import backend_probed, get_jax, platform_hint
        if backend_probed() or nprocs > 1:
            jax, _ = get_jax()
            plan['mesh'] = {'axis': 'd', 'local_devices':
                            [str(d) for d in jax.local_devices()]}
        else:
            plan['mesh'] = {'axis': 'd',
                            'platform_hint': platform_hint() or 'auto'}
        return plan


def _allgather_merge_tagged(points):
    """Cross-process merge of __dn_metric-tagged aggregated points (the
    index-build reduce): identical (metric, fields) tuples sum their
    weights — already bucket-min encoded, so plain addition is exact."""
    from ..ops import get_jax
    from .. import jsvalues as jsv
    import json
    jax, _ = get_jax()
    from jax.experimental import multihost_utils

    payload = json.dumps([[f, v] for f, v in points]).encode()
    data = np.frombuffer(payload, dtype=np.uint8)
    lens = multihost_utils.process_allgather(
        np.array([data.shape[0]], dtype=np.int64))
    maxlen = int(np.max(lens))
    padded = np.zeros(maxlen, dtype=np.uint8)
    padded[:data.shape[0]] = data
    gathered = multihost_utils.process_allgather(padded)

    merged = {}
    order = []
    for i in range(gathered.shape[0]):
        raw = bytes(gathered[i][:int(lens[i][0])])
        for fields, value in json.loads(raw.decode()):
            key = jsv.json_stringify(fields)
            if key not in merged:
                merged[key] = [fields, 0]
                order.append(key)
            merged[key][1] += value
    return [(merged[k][0], merged[k][1]) for k in order]


def _allgather_merge_points(query, points):
    """Exchange each process's partial aggregate (as serialized points —
    the same commutative-monoid wire format the reference's map->reduce
    used) and re-aggregate.  Every process computes the full result."""
    from ..ops import get_jax
    from .. import jsvalues as jsv
    from ..aggr import Aggregator
    import json
    jax, _ = get_jax()
    from jax.experimental import multihost_utils

    payload = json.dumps([[f, v] for f, v in points]).encode()
    data = np.frombuffer(payload, dtype=np.uint8)
    # pad to a common length across processes
    lens = multihost_utils.process_allgather(
        np.array([data.shape[0]], dtype=np.int64))
    maxlen = int(np.max(lens))
    padded = np.zeros(maxlen, dtype=np.uint8)
    padded[:data.shape[0]] = data
    gathered = multihost_utils.process_allgather(padded)

    aggr = Aggregator(query)
    for i in range(gathered.shape[0]):
        raw = bytes(gathered[i][:int(lens[i][0])])
        for fields, value in json.loads(raw.decode()):
            aggr.write(fields, value)
    return aggr.points()


def create_datasource(dsconfig):
    if not isinstance(dsconfig['ds_backend_config'].get('path'), str):
        return DNError('expected datasource "path" to be a string')
    return DatasourceCluster(dsconfig)
