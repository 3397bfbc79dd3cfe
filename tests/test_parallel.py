"""Multi-device tests on the virtual 8-device CPU mesh: sharded
aggregation (psum and reduce_scatter) must match single-device numpy
results, and the cluster datasource must match the file datasource
byte-for-byte."""

import os
import random
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from dragnet_tpu.ops import get_jax                  # noqa: E402

pytestmark = [
    pytest.mark.multichip,
    pytest.mark.skipif(get_jax() is None, reason='jax unavailable'),
]


def test_virtual_mesh_present():
    jax, _ = get_jax()
    assert len(jax.devices()) == 8, \
        'expected 8 virtual CPU devices (see tests/conftest.py)'


def _random_problem(rng, n, radices):
    ncols = len(radices)
    codes = np.stack([rng.integers(0, r, size=n) for r in radices]) \
        .astype(np.int64)
    weights = rng.integers(1, 5, size=n).astype(np.float64)
    alive = rng.random(n) < 0.8
    return codes, weights, alive


def _reference_dense(codes, radices, weights, alive):
    num = 1
    for r in radices:
        num *= r
    fused = np.zeros(codes.shape[1], dtype=np.int64)
    for i, r in enumerate(radices):
        fused = fused * r + codes[i]
    w = np.where(alive, weights, 0.0)
    return np.bincount(fused, weights=w, minlength=num)


@pytest.mark.parametrize('n', [64, 1000])
def test_sharded_psum_matches(n):
    from dragnet_tpu.parallel.mesh import sharded_aggregate
    rng = np.random.default_rng(42 + n)
    radices = (5, 7)
    codes, weights, alive = _random_problem(rng, n, radices)
    expected = _reference_dense(codes, radices, weights, alive)
    got = sharded_aggregate(codes, radices, weights, alive)
    np.testing.assert_array_equal(got, expected)


def test_sharded_reduce_scatter_matches():
    from dragnet_tpu.parallel.mesh import sharded_aggregate
    rng = np.random.default_rng(7)
    radices = (4, 16)   # 64 segments: divisible by 8 devices
    codes, weights, alive = _random_problem(rng, 512, radices)
    expected = _reference_dense(codes, radices, weights, alive)
    got = sharded_aggregate(codes, radices, weights, alive, scatter=True)
    np.testing.assert_array_equal(got, expected)


def test_cluster_datasource_matches_file(tmp_path):
    """cluster backend scan == file backend scan, byte for byte."""
    from dragnet_tpu import query as mod_query
    from dragnet_tpu import datasource_file
    from dragnet_tpu.parallel import cluster

    datadir = tmp_path / 'data'
    datadir.mkdir()
    rng = random.Random(3)
    import json
    with open(datadir / 'a.log', 'w') as f:
        for i in range(300):
            f.write(json.dumps({
                'host': rng.choice(['a', 'b', 'c']),
                'latency': rng.choice([1, 5, 80, 3000]),
                'req': {'method': rng.choice(['GET', 'PUT'])},
            }) + '\n')

    dsconfig = {
        'ds_backend': 'file',
        'ds_backend_config': {'path': str(datadir)},
        'ds_filter': None,
        'ds_format': 'json',
    }
    q1 = mod_query.query_load({'breakdowns': [
        {'name': 'host'}, {'name': 'latency', 'aggr': 'quantize'}]})
    q2 = mod_query.query_load({'breakdowns': [
        {'name': 'host'}, {'name': 'latency', 'aggr': 'quantize'}]})

    file_ds = datasource_file.DatasourceFile(dsconfig)
    cluster_ds = cluster.DatasourceCluster(dsconfig)
    p1 = file_ds.scan(q1).points
    p2 = cluster_ds.scan(q2).points
    assert p1 == p2


def test_cluster_full_pipeline_sharded(tmp_path, monkeypatch):
    """The cluster backend runs the WHOLE scan pipeline (predicates,
    synthetic dates, bucketize, reduction) as one shard_map'd device
    program over the 8-device mesh — proven by the ndevicebatches
    telemetry counter: every batch was folded by the device program,
    none by the host fallback — with output identical to the host
    engine (reference semantics: lib/stream-scan.js:40-96)."""
    import json
    from dragnet_tpu import query as mod_query
    from dragnet_tpu import native as mod_native
    from dragnet_tpu.parallel import cluster

    if mod_native.get_lib() is None:
        pytest.skip('native parser unavailable')

    datadir = tmp_path / 'data'
    datadir.mkdir()
    rng = random.Random(11)
    with open(datadir / 'a.log', 'w') as f:
        for i in range(4000):
            f.write(json.dumps({
                'time': '2014-05-%02dT%02d:00:0%dZ'
                        % (rng.choice([1, 2, 3]), rng.randrange(24),
                           rng.randrange(10)),
                'host': rng.choice(['a', 'b', 'c']),
                'latency': rng.choice([1, 5, 80, 3000]),
                'res': {'statusCode': rng.choice([200, 404, 500])},
                'req': {'method': rng.choice(['GET', 'PUT'])},
            }) + '\n')

    dsconfig = {
        'ds_backend': 'file',
        'ds_backend_config': {'path': str(datadir),
                              'timeField': 'time'},
        'ds_filter': None,
        'ds_format': 'json',
    }
    qconf = {
        'breakdowns': [{'name': 'host'},
                       {'name': 'req.method'},
                       {'name': 'latency', 'aggr': 'quantize'}],
        'filter': {'ne': ['res.statusCode', 500]},
    }

    monkeypatch.setenv('DN_ENGINE', 'host')
    expected = cluster.DatasourceCluster(dsconfig).scan(
        mod_query.query_load(qconf)).points
    monkeypatch.delenv('DN_ENGINE', raising=False)

    # force many small batches so several device folds happen
    import dragnet_tpu.engine as eng
    from dragnet_tpu import device_scan
    monkeypatch.setattr(eng, 'BATCH_SIZE', 512)
    monkeypatch.setattr(device_scan, 'BATCH_SIZE', 512)
    monkeypatch.setenv('DN_READ_SIZE', '65536')
    monkeypatch.setenv('DN_SCAN_THREADS', '0')

    scanners = []
    orig = cluster.MeshDeviceScan.__init__

    def record_init(self, *a, **kw):
        orig(self, *a, **kw)
        scanners.append(self)
    monkeypatch.setattr(cluster.MeshDeviceScan, '__init__', record_init)

    r = cluster.DatasourceCluster(dsconfig).scan(
        mod_query.query_load(qconf))
    assert r.points == expected

    assert len(scanners) == 1
    s = scanners[0]
    # the program really was the mesh-sharded one...
    mesh_info = s._device_mesh()
    assert mesh_info is not None
    assert int(mesh_info[0].devices.size) == 8
    # ...and it folded every batch (no host fallback produced output)
    parse_n = [st for st in r.pipeline.stages
               if st.name == 'Aggregator'][0]
    ndev = parse_n.counters.get('ndevicebatches', 0)
    assert ndev >= 4000 // 512, ndev
    assert parse_n.counters.get('nspillrecords', 0) == 0


def test_cluster_dry_run_plan(tmp_path, capsys):
    """--dry-run on the cluster backend prints the execution plan
    (process topology, mesh, input partition) the way the reference
    printed its Manta job JSON + inputs (lib/datasource-manta.js:
    446-454)."""
    import json
    from dragnet_tpu import query as mod_query
    from dragnet_tpu import cli as mod_cli
    from dragnet_tpu.parallel import cluster

    datadir = tmp_path / 'data'
    datadir.mkdir()
    with open(datadir / 'a.log', 'w') as f:
        f.write('{"host":"a"}\n')

    ds = cluster.DatasourceCluster({
        'ds_backend': 'cluster',
        'ds_backend_config': {'path': str(datadir)},
        'ds_filter': None, 'ds_format': 'json',
    })
    q = mod_query.query_load({'breakdowns': [{'name': 'host'}]})

    # never probed: the plan reports the platform hint, not devices
    # (a dry run must not pay backend initialization)
    from dragnet_tpu import ops
    if ops.backend_probed() is None:
        r0 = ds.scan(mod_query.query_load(
            {'breakdowns': [{'name': 'host'}]}), dry_run=True)
        assert 'platform_hint' in r0.dry_run_plan['mesh']

    ops.backend_ready()     # now devices are listable
    r = ds.scan(q, dry_run=True)
    plan = r.dry_run_plan
    assert plan['backend'] == 'cluster'
    assert plan['nprocesses'] == 1 and plan['process'] == 0
    assert plan['partition'] == [str(datadir / 'a.log')]
    assert [p['type'] for p in plan['phases']] == ['map', 'reduce']
    assert plan['mesh']['axis'] == 'd'
    assert len(plan['mesh']['local_devices']) == 8

    # the CLI rendering: plan JSON, then Inputs (reference flavor)
    class Opts(object):
        pass
    mod_cli.dn_output(q, Opts(), r, 'ds')
    err = capsys.readouterr().err
    head, _, inputs = err.partition('\nInputs:\n')
    parsed = json.loads(head)
    assert parsed['backend'] == 'cluster'
    assert 'partition' not in parsed      # moved to the Inputs section
    assert inputs.splitlines() == [str(datadir / 'a.log')]


def _mesh_scan_setup(monkeypatch, read=4096, cap0=4096, dense=64):
    """Small batches (128 records), a small dense budget (64 segments)
    and a small first set for a forced scan on the cluster backend;
    returns the StringIO that device_scan's debug records (the kernel
    records) go to."""
    import io
    from dragnet_tpu import log as mod_log
    from dragnet_tpu import device_scan
    import dragnet_tpu.engine as eng
    monkeypatch.setattr(eng, 'MAX_DENSE_SEGMENTS', dense)
    monkeypatch.setattr(device_scan, 'MAX_DENSE_SEGMENTS', dense)
    monkeypatch.setattr(device_scan, 'SPARSE_CAP0', cap0)
    monkeypatch.setattr(eng, 'BATCH_SIZE', 128)
    monkeypatch.setattr(device_scan, 'BATCH_SIZE', 128)
    monkeypatch.setenv('DN_READ_SIZE', str(read))
    monkeypatch.setenv('DN_SCAN_THREADS', '0')
    buf = io.StringIO()
    monkeypatch.setattr(device_scan, 'LOG', mod_log.Logger(
        'dn', component='device_scan', level=mod_log.DEBUG, stream=buf))
    return buf


def _no_prefetch(monkeypatch):
    """Keep the late-stream prefetch of the flush out of the way: the
    stream never gets as far as it asks."""
    from dragnet_tpu import device_scan
    monkeypatch.setattr(device_scan.DeviceScan, 'PREFETCH_PROGRESS', 2.0)


def _kernel_records(buf):
    import json
    recs = [json.loads(ln) for ln in buf.getvalue().splitlines()]
    return [(r['kernel'], r['mesh_devices'], r['merge'])
            for r in recs if r['msg'] == 'device aggregate kernel']


def _dsconfig(datadir, fmt='json'):
    return {'ds_backend': 'file',
            'ds_backend_config': {'path': str(datadir)},
            'ds_filter': None, 'ds_format': fmt}


def _scan_points(monkeypatch, ds_cls, dsconfig, qconf, engine):
    from dragnet_tpu import query as mod_query
    monkeypatch.setenv('DN_ENGINE', engine)
    return ds_cls(dsconfig).scan(mod_query.query_load(qconf))


def _counter(name):
    from dragnet_tpu.obs import metrics as obs_metrics
    return obs_metrics.global_registry().counter(name).value


def _ndevicebatches(result):
    return sum(st.counters.get('ndevicebatches', 0)
               for st in result.pipeline.stages)


HIGHCARD_Q = {'breakdowns': [{'name': 'host'}, {'name': 'latency'}]}


def test_cluster_highcard_runs_sparse_on_mesh(tmp_path, monkeypatch):
    """A key space beyond the dense budget runs the sparse program on
    every chip of the mesh: a set a chip, merged at the flush by
    all-gather and one more sparse_fold.  Every batch is the device's
    (`ndevicebatches`), the kernel record says which program and which
    merge, and the output is byte-identical, order included, to the
    per-record host engine (dragnet_tpu/scan.py) and to the one-chip
    device scan."""
    import json
    from dragnet_tpu import native as mod_native
    from dragnet_tpu import datasource_file
    from dragnet_tpu.parallel import cluster

    if mod_native.get_lib() is None:
        pytest.skip('native parser unavailable')

    datadir = tmp_path / 'data'
    datadir.mkdir()
    rng = random.Random(17)
    with open(datadir / 'a.log', 'w') as f:
        for i in range(1500):
            f.write(json.dumps({
                'host': 'h%d' % rng.randrange(60),
                'latency': rng.randrange(0, 4000),
            }) + '\n')
    dsconfig = _dsconfig(datadir)

    expected = _scan_points(monkeypatch, cluster.DatasourceCluster,
                            dsconfig, HIGHCARD_Q, 'host').points
    buf = _mesh_scan_setup(monkeypatch)
    one_chip = _scan_points(monkeypatch, datasource_file.DatasourceFile,
                            dsconfig, HIGHCARD_Q, 'jax')
    assert _kernel_records(buf) == [('sparse-sort-merge', 0, None)]
    buf.seek(0)
    buf.truncate()

    r = _scan_points(monkeypatch, cluster.DatasourceCluster, dsconfig,
                     HIGHCARD_Q, 'jax')
    assert len(expected) > 64
    assert r.points == expected
    assert r.points == one_chip.points
    assert _ndevicebatches(r) == _ndevicebatches(one_chip) >= 5
    assert _kernel_records(buf) == [
        ('sparse-sort-merge', 8, 'allgather+sparse-fold')]


def _write_mesh_case(case, path):
    """The corpus of one case of test_mesh_sparse_cases; returns the
    datasource's format."""
    import json
    rng = random.Random(hash(case) % 1000 + 23)
    lines = []
    if case == 'weights':
        # `dn scan --points` output as input: tuples with weights
        for i in range(1200):
            host, lat = (i % 40, i) if i < 100 else \
                (rng.randrange(40), rng.randrange(0, 100))
            lines.append(json.dumps({
                'fields': {'host': 'h%d' % host, 'latency': lat},
                'value': rng.randrange(1, 9)}))
        fmt = 'json-skinner'
    elif case == 'dense-then-sparse':
        # the build's m1 pattern: a key space that fits the dense
        # budget until new values arrive
        for i in range(600):
            lines.append(json.dumps({'host': 'h%d' % (i % 4),
                                     'latency': i % 3}))
        for i in range(900):
            host, lat = (i % 50, i) if i < 100 else \
                (rng.randrange(50), rng.randrange(0, 100))
            lines.append(json.dumps({'host': 'h%d' % host,
                                     'latency': lat}))
        fmt = 'json'
    else:
        # 1501 records: no multiple of the 8 chips; `everywhere` is in
        # every chip's shard of every batch, `once` in one record; the
        # first batch brings most values of both columns, so the key
        # space stays as it is (one epoch, one flush)
        for i in range(1501):
            if i == 777:
                rec = {'host': 'once', 'latency': 5}
            elif i % 4 == 0:
                rec = {'host': 'everywhere', 'latency': 1}
            elif i < 110:
                rec = {'host': 'h%d' % (i % 60), 'latency': i % 100}
            else:
                rec = {'host': 'h%d' % rng.randrange(60),
                       'latency': rng.randrange(0, 100)}
            lines.append(json.dumps(rec))
        fmt = 'json'
    with open(path, 'w') as f:
        f.write('\n'.join(lines) + '\n')
    return fmt


@pytest.mark.parametrize('case', [
    'odd-count', 'guard-grows', 'overflow-raises', 'weights',
    'dense-then-sparse', 'prefetch'])
def test_mesh_sparse_cases(case, tmp_path, monkeypatch):
    """The mesh's sparse lane against the per-record host engine, byte
    for byte and in order, where it has to do more than fold and merge
    once: a record count that the chips do not divide, with a key on
    every chip and a key on one; a set so small that the guard syncs,
    flushes and grows; a set that overflows with the guard taken away
    (loud, never a short reply); weighted tuples; a dense epoch
    followed by a sparse one; the late-stream prefetch of the flush."""
    from dragnet_tpu import native as mod_native
    from dragnet_tpu import device_scan
    from dragnet_tpu.parallel import cluster

    if mod_native.get_lib() is None:
        pytest.skip('native parser unavailable')
    datadir = tmp_path / 'data'
    datadir.mkdir()
    dsconfig = _dsconfig(datadir, _write_mesh_case(case, datadir / 'a.log'))
    expected = _scan_points(monkeypatch, cluster.DatasourceCluster,
                            dsconfig, HIGHCARD_Q, 'host').points

    small = case in ('guard-grows', 'overflow-raises')
    buf = _mesh_scan_setup(monkeypatch, cap0=32 if small else 4096)
    merges, prefetched, scanners = [], [], []
    orig_merge = cluster.MeshDeviceScan._merge_sparse
    orig_prefetch = cluster.MeshDeviceScan._prefetch_flush

    def spy_merge(self, acc, meta, ub):
        scanners.append(self)
        merges.append(meta['sparse_cap'])
        return orig_merge(self, acc, meta, ub)

    def spy_prefetch(self):
        prefetched.append(self._acc is not None and len(self._acc) == 5)
        return orig_prefetch(self)
    monkeypatch.setattr(cluster.MeshDeviceScan, '_merge_sparse', spy_merge)
    monkeypatch.setattr(cluster.MeshDeviceScan, '_prefetch_flush',
                        spy_prefetch)
    if case != 'prefetch':
        _no_prefetch(monkeypatch)
    if case == 'overflow-raises':
        monkeypatch.setattr(cluster.MeshDeviceScan, '_sparse_guard',
                            lambda self, n: True)
        with pytest.raises(RuntimeError, match='overflowed its resident'):
            _scan_points(monkeypatch, cluster.DatasourceCluster, dsconfig,
                         HIGHCARD_Q, 'jax')
        return

    syncs0 = _counter('device_sparse_guard_syncs')
    r = _scan_points(monkeypatch, cluster.DatasourceCluster, dsconfig,
                     HIGHCARD_Q, 'jax')
    assert r.points == expected
    assert len(expected) > 64
    kernels = _kernel_records(buf)
    assert ('sparse-sort-merge', 8, 'allgather+sparse-fold') in kernels
    assert _ndevicebatches(r) >= 5
    if case == 'guard-grows':
        # the first set of 32 slots a chip cannot hold the stream: the
        # guard read the chips' counts, flushed and grew the set
        assert _counter('device_sparse_guard_syncs') > syncs0
        assert len(merges) > 1 and merges[0] == 32
        assert scanners[0]._sparse_cap > 32
        assert not scanners[0]._disabled
    elif case == 'dense-then-sparse':
        assert set(kernels) == {
            ('segment-sum', 8, 'psum+pmin'),
            ('sparse-sort-merge', 8, 'allgather+sparse-fold')}
        assert kernels[0][0] == 'segment-sum'
        assert len(merges) == 1
    elif case == 'prefetch':
        # the set so far was merged, compacted and fetched beside the
        # rest of the stream, and the rest merged at the end
        assert prefetched == [True]
        assert len(merges) == 2
    else:
        assert len(merges) == 1


def test_mesh_sparse_partials_tie(tmp_path, monkeypatch):
    """The chips' sets as they stand before the merge, fetched and
    re-aggregated on the host (weights added, the smallest `first`
    kept), are the merged set slot for slot and the host engine's
    answer: what the collective and the extra fold did is exactly the
    reduce of the chips' partial aggregates."""
    import numpy as np
    from dragnet_tpu import native as mod_native
    from dragnet_tpu.ops.kernels import I64MAX
    from dragnet_tpu.parallel import cluster

    if mod_native.get_lib() is None:
        pytest.skip('native parser unavailable')
    datadir = tmp_path / 'data'
    datadir.mkdir()
    dsconfig = _dsconfig(datadir,
                         _write_mesh_case('odd-count', datadir / 'a.log'))
    expected = _scan_points(monkeypatch, cluster.DatasourceCluster,
                            dsconfig, HIGHCARD_Q, 'host').points
    _mesh_scan_setup(monkeypatch)
    _no_prefetch(monkeypatch)
    seen = []
    orig = cluster.MeshDeviceScan._merge_sparse

    def spy(self, acc, meta, ub):
        before = [np.asarray(x) for x in acc]
        merged, tuples = orig(self, acc, meta, ub)
        seen.append((before, [np.asarray(x) for x in merged], tuples))
        return merged, tuples
    monkeypatch.setattr(cluster.MeshDeviceScan, '_merge_sparse', spy)
    r = _scan_points(monkeypatch, cluster.DatasourceCluster, dsconfig,
                     HIGHCARD_Q, 'jax')
    assert r.points == expected

    (before, merged, tuples), = seen
    keys, wsum, first, cvec, stats = before
    assert keys.shape == (8, 4096)
    live = keys != I64MAX
    assert (live.sum(axis=1) == stats[:, 0]).all()
    # several chips hold a key of their own copy: `everywhere` is in
    # every shard, `once` in one
    per_key = {}
    for k, w, f in zip(keys[live], wsum[live], first[live]):
        w0, f0, n0 = per_key.get(int(k), (0, I64MAX, 0))
        per_key[int(k)] = (w0 + int(w), min(f0, int(f)), n0 + 1)
    assert max(n for _, _, n in per_key.values()) == 8
    assert min(n for _, _, n in per_key.values()) == 1
    assert tuples == len(per_key) == len(expected) == int(merged[4][0])
    assert int(live.sum()) > tuples
    mk, mw, mf = merged[0][:tuples], merged[1][:tuples], merged[2][:tuples]
    assert mk.tolist() == sorted(per_key)
    assert [(int(w), int(f)) for w, f in zip(mw, mf)] == \
        [per_key[k][:2] for k in sorted(per_key)]
    assert (merged[0][tuples:] == I64MAX).all()
    assert (merged[3] == cvec.sum(axis=0)).all()
    assert sorted(int(w) for w in mw) == sorted(p[1] for p in expected)


def test_cluster_build_equals_file_build(tmp_path, monkeypatch):
    """A cluster `dn build` of three metrics writes the file backend's
    tree, file for file, with the metric whose key space passes the
    dense budget folded by the mesh's sparse program
    (`device_sparse_fold_batches`, the `allgather+sparse-fold`
    record)."""
    import test_device_build as tdb
    from dragnet_tpu import native as mod_native

    if mod_native.get_lib() is None:
        pytest.skip('native parser unavailable')
    datafile = tmp_path / 'data.log'
    tdb._write_data(datafile, 1500)
    tdb._build(monkeypatch, datafile, tmp_path / 'ifile', 'vector')

    buf = _mesh_scan_setup(monkeypatch, read=16384)
    monkeypatch.setenv('DN_PARSE_THREADS', '1')
    folded0 = _counter('device_sparse_fold_batches')
    monkeypatch.setenv('DN_ENGINE', 'jax')
    _cluster_ds(datafile, tmp_path / 'icluster').build(tdb._metrics(), 'day')
    assert _counter('device_sparse_fold_batches') > folded0
    assert ('sparse-sort-merge', 8, 'allgather+sparse-fold') in \
        _kernel_records(buf)
    _same_tree(tmp_path, 'ifile', 'icluster')


def _cluster_ds(datafile, indexdir=None):
    """A cluster-backend datasource over test_device_build's corpus."""
    from dragnet_tpu.parallel import cluster
    bc = {'path': str(datafile), 'timeField': 'time'}
    if indexdir is not None:
        bc['indexPath'] = str(indexdir)
    return cluster.DatasourceCluster({
        'ds_backend': 'cluster', 'ds_backend_config': bc,
        'ds_filter': None, 'ds_format': 'json'})


def _cluster_build(monkeypatch, datafile, indexdir):
    """A forced-device `dn build` of test_device_build's three metrics
    on the cluster backend, with a dense budget of 4096 segments:
    byhost (8 x 8 x 32 = 2048 while its days fit a cap of 8) is dense,
    bymethod (16384) and bylat sparse from the first batch.  Returns
    (the kernel records, nstackedbatches of each metric, batches handed
    by the parser, device dispatches, the sparse cap of each scan of
    each stacked batch)."""
    import test_device_build as tdb
    from dragnet_tpu import device_scan
    from dragnet_tpu.parallel import cluster
    from helpers.scan_differential import batches_handed

    caps = []
    orig = device_scan.DeviceScanStack._stacked_program

    def spy(self, staged, inputs):
        assert all(isinstance(s, cluster.MeshDeviceScan)
                   for s in self.scans)
        caps.append(tuple(st[1][-1] for st in staged))
        return orig(self, staged, inputs)
    monkeypatch.setattr(device_scan.DeviceScanStack, '_stacked_program',
                        spy)
    buf = _mesh_scan_setup(monkeypatch, read=16384, dense=4096)
    monkeypatch.setenv('DN_PARSE_THREADS', '1')
    monkeypatch.setenv('DN_ENGINE', 'jax')
    handed0 = batches_handed()
    dispatched0 = _counter('device_pipe_dispatches')
    result = _cluster_ds(datafile, indexdir).build(tdb._metrics(), 'day')
    stacked = [st.counters['nstackedbatches']
               for st in result.pipeline.stages
               if 'nstackedbatches' in st.counters]
    return (set(_kernel_records(buf)), stacked, batches_handed() - handed0,
            _counter('device_pipe_dispatches') - dispatched0, caps)


def _same_tree(tmp_path, a, b, shards=3):
    import test_device_build as tdb
    t_a = tdb._tree_bytes(tmp_path / a)
    t_b = tdb._tree_bytes(tmp_path / b)
    assert t_a.keys() == t_b.keys() and len(t_a) >= shards
    for rel in t_a:
        assert t_a[rel] == t_b[rel], rel


BOTH_MESH_KERNELS = {('segment-sum', 8, 'psum+pmin'),
                     ('sparse-sort-merge', 8, 'allgather+sparse-fold')}


@pytest.mark.parametrize('records,days', [(1536, 3), (1501, 12)],
                         ids=['even', 'odd-count-and-flip'])
def test_cluster_build_goes_through_the_stack(records, days, tmp_path,
                                              monkeypatch):
    """A cluster `dn build` folds its three metrics in one stacked
    dispatch a batch: `nstackedbatches` grows on every metric by the
    batches handed, `device_pipe_dispatches` by exactly as many, both
    mesh programs ran (the dense epochs' `psum+pmin`, the sparse sets'
    `allgather+sparse-fold`) and the tree is the file backend's, file
    for file: also where the chips do not divide the record count and
    a metric flips from its dense epoch to the sparse lane inside the
    build."""
    import test_device_build as tdb
    from dragnet_tpu import native as mod_native

    if mod_native.get_lib() is None:
        pytest.skip('native parser unavailable')
    datafile = tmp_path / 'data.log'
    tdb._write_data(datafile, records, days=days)
    tdb._build(monkeypatch, datafile, tmp_path / 'ifile', 'vector')

    kernels, stacked, handed, dispatched, caps = _cluster_build(
        monkeypatch, datafile, tmp_path / 'icluster')
    assert handed >= 5
    assert stacked == [handed] * 3
    assert dispatched == handed == len(caps)
    assert kernels == BOTH_MESH_KERNELS
    _same_tree(tmp_path, 'ifile', 'icluster', shards=days)
    # byhost begins dense beside two sparse metrics; the ninth day
    # takes its key space past the budget (16 x 16 x 32), so its flush
    # and its move to the sparse lane lie between two stacked batches
    assert [c > 0 for c in caps[0]] == [False, True, True]
    assert [c > 0 for c in caps[-1]] == [days > 8, True, True]


def test_cluster_build_batch_one_scan_cannot_stage(tmp_path, monkeypatch):
    """A batch that one metric cannot stage (an array for a key, a
    latency that is no integer) is folded by the per-scan path on the
    mesh, the others by the stack, and the tree is still the file
    backend's."""
    import test_device_build as tdb
    from dragnet_tpu import device_scan
    from dragnet_tpu import native as mod_native

    if mod_native.get_lib() is None:
        pytest.skip('native parser unavailable')
    datafile = tmp_path / 'data.log'
    tdb._write_data(datafile, 1500, with_edges=True)
    tdb._build(monkeypatch, datafile, tmp_path / 'ifile', 'vector')

    took = []
    orig = device_scan.DeviceScanStack._process_device

    def spy(self, provider, weights, alive):
        rv = orig(self, provider, weights, alive)
        if len(self.scans) == 3:     # not a declined batch's stacks of one
            took.append(rv)
        return rv
    monkeypatch.setattr(device_scan.DeviceScanStack, '_process_device', spy)
    kernels, stacked, handed, dispatched, caps = _cluster_build(
        monkeypatch, datafile, tmp_path / 'icluster')
    assert took.count(False) >= 2 and took.count(True) >= 5
    assert stacked == [took.count(True)] * 3
    assert len(took) == handed
    # a refused batch is dispatched by each scan that can take it
    assert took.count(True) < dispatched <= \
        took.count(True) + 3 * took.count(False)
    _same_tree(tmp_path, 'ifile', 'icluster')


def test_stacked_mesh_fold_of_sparse_sets_has_no_collective(tmp_path,
                                                            monkeypatch):
    """The stacked program of a batch whose three metrics all fold into
    sparse sets, as the virtual 8-device mesh compiles it: nothing
    crosses the chips, the completion token included (a token a scan,
    for a set one a chip: summed into one scalar they would be an
    all-reduce a batch)."""
    import jax
    import test_device_build as tdb
    from dragnet_tpu import device_scan
    from dragnet_tpu import native as mod_native

    if mod_native.get_lib() is None:
        pytest.skip('native parser unavailable')
    datafile = tmp_path / 'data.log'
    tdb._write_data(datafile, 400)
    texts, tokens = [], []
    orig = device_scan.DeviceScanStack._stacked_program

    def spy(self, staged, inputs):
        run = orig(self, staged, inputs)
        if not texts:
            assert all(st[1][-1] for st in staged)
            accs = tuple(s._acc for s in self.scans)
            texts.append(run.lower(inputs, accs).compile().as_text())
            tokens.append(jax.eval_shape(run, inputs, accs)[1])
        return run
    monkeypatch.setattr(device_scan.DeviceScanStack, '_stacked_program',
                        spy)
    _mesh_scan_setup(monkeypatch, read=16384)
    monkeypatch.setenv('DN_PARSE_THREADS', '1')
    monkeypatch.setenv('DN_ENGINE', 'jax')
    _cluster_ds(datafile, tmp_path / 'icluster').build(tdb._metrics(), 'day')
    text, = texts
    assert 'sort' in text
    for collective in ('all-reduce', 'all-gather', 'all-to-all',
                       'collective-permute', 'reduce-scatter'):
        assert collective not in text, collective
    assert [t.shape for t in tokens[0]] == [(8,)] * 3


def test_lone_mesh_scan_of_a_sparse_set_has_no_collective(tmp_path,
                                                          monkeypatch):
    """The lone form of the test above: a high-cardinality scan on the
    cluster backend is a stack of one, and its program, as the virtual
    8-device mesh compiles it, crosses no chips either, the completion
    token (one a chip) included."""
    import jax
    from dragnet_tpu import device_scan
    from dragnet_tpu import native as mod_native
    from dragnet_tpu.parallel import cluster

    if mod_native.get_lib() is None:
        pytest.skip('native parser unavailable')
    datadir = tmp_path / 'data'
    datadir.mkdir()
    dsconfig = _dsconfig(datadir,
                         _write_mesh_case('odd-count', datadir / 'a.log'))
    texts, tokens = [], []
    orig = device_scan.DeviceScanStack._stacked_program

    def spy(self, staged, inputs):
        run = orig(self, staged, inputs)
        if not texts:
            (scan,), (st,) = self.scans, staged
            assert isinstance(scan, cluster.MeshDeviceScan) and st[1][-1]
            texts.append(run.lower(inputs, (scan._acc,)).compile()
                         .as_text())
            tokens.append(jax.eval_shape(run, inputs, (scan._acc,))[1])
        return run
    monkeypatch.setattr(device_scan.DeviceScanStack, '_stacked_program',
                        spy)
    _mesh_scan_setup(monkeypatch)
    r = _scan_points(monkeypatch, cluster.DatasourceCluster, dsconfig,
                     HIGHCARD_Q, 'jax')
    assert _ndevicebatches(r) >= 5
    text, = texts
    assert 'sort' in text
    for collective in ('all-reduce', 'all-gather', 'all-to-all',
                       'collective-permute', 'reduce-scatter'):
        assert collective not in text, collective
    assert [t.shape for t in tokens[0]] == [(8,)]


def _spec_axes(specs):
    """{key: the mesh axis it is sharded over, or None}."""
    return {k: (tuple(v)[0] if tuple(v) else None)
            for k, v in specs.items()}


STACKED_ARGS = [
    'nvalid', 'alive', 'weights', 'tags_x', 'str_x', 'num_x', 'kv_x',
    'kvalid_x', 'tsf_time', 'terr_time|other',
    'm0_base', 'm0_key_x', 'm0_tab_0', 'm0_terr',
    'm1_base', 'm1_key_x', 'm1_ts_x', 'm1_terr', 'm1_tab_0', 'm1_ctab_0',
    'm1_trans_x',
    'm2_base', 'm2_ts_x', 'm2_ctab_0', 'm10_key_x']


@pytest.mark.parametrize('pfx,sharded,replicated', [
    ('m1_',
     ['alive', 'weights', 'tags_x', 'str_x', 'num_x', 'kv_x', 'kvalid_x',
      'tsf_time', 'terr_time|other', 'm1_key_x', 'm1_ts_x', 'm1_terr'],
     ['nvalid', 'm1_tab_0', 'm1_ctab_0', 'm1_trans_x']),
    ('m0_',
     ['alive', 'weights', 'tags_x', 'str_x', 'num_x', 'kv_x', 'kvalid_x',
      'tsf_time', 'terr_time|other', 'm0_key_x', 'm0_terr'],
     ['nvalid', 'm0_tab_0']),
], ids=['m1', 'm0'])
def test_record_specs_of_a_stacked_scan(pfx, sharded, replicated):
    """A stacked scan's shard_map specs: the shared parser columns and
    the keys under its own prefix, a value a record sharded over the
    mesh axis, tables and `nvalid` replicated; no sibling's key, and no
    batch base."""
    from dragnet_tpu import device_scan
    specs = _spec_axes(device_scan.record_specs(
        dict.fromkeys(STACKED_ARGS), pfx, 'd'))
    assert sorted(k for k, ax in specs.items() if ax == 'd') == \
        sorted(sharded)
    assert sorted(k for k, ax in specs.items() if ax is None) == \
        sorted(replicated)
    assert not [k for k in specs
                if k[0] == 'm' and not k.startswith(pfx)]


def test_record_specs_of_a_single_mesh_scan_are_pinned(tmp_path,
                                                       monkeypatch):
    """With no prefix the specs are what a single mesh scan has always
    traced, key for key: a filtered scan with a time bound on the
    cluster backend, its dense program's and its sparse program's."""
    import test_device_build as tdb
    from dragnet_tpu import device_scan
    from dragnet_tpu import native as mod_native
    from dragnet_tpu import query as mod_query

    if mod_native.get_lib() is None:
        pytest.skip('native parser unavailable')
    datafile = tmp_path / 'data.log'
    tdb._write_data(datafile, 1500)
    seen = []
    orig = device_scan.record_specs

    def spy(args, pfx, axis):
        specs = orig(args, pfx, axis)
        seen.append((pfx, axis, 'base' in args, _spec_axes(specs)))
        return specs
    monkeypatch.setattr(device_scan, 'record_specs', spy)
    _mesh_scan_setup(monkeypatch, read=16384)
    query = mod_query.query_load({
        'breakdowns': [{'name': 'host'},
                       {'name': 'latency', 'aggr': 'quantize'}],
        'filter': {'ne': ['req.method', 'PUT']},
        'timeAfter': '2014-05-01', 'timeBefore': '2014-05-03'})
    monkeypatch.setenv('DN_ENGINE', 'host')
    expected = _cluster_ds(datafile).scan(query).points
    assert not seen
    monkeypatch.setenv('DN_ENGINE', 'jax')
    r = _cluster_ds(datafile).scan(query)
    assert r.points == expected and _ndevicebatches(r) >= 5
    assert all(s[:3] == ('', 'd', True) for s in seen)
    pinned = {
        'nvalid': None, 'tags_req.method': 'd', 'str_req.method': 'd',
        'tsf_time': 'd', 'terr_time': 'd', 'kv_latency': 'd',
        'tab_0': None, 'ctab_0': None,
        # the time bounds: two scalars among the program's arguments
        # since PR 50, on every chip alike
        'tb_lo': None, 'tb_hi': None}
    host_keys = ({'key_host': 'd'}, {'str_host': 'd', 'trans_host': None})
    assert seen
    for s in seen:
        assert s[3] in [dict(pinned, **hk) for hk in host_keys], s[3]


def test_sparse_merge_counters_and_leaf_at_a_scrape(tmp_path, monkeypatch):
    """The merge's leaf stage and the sparse lane's counters are at a
    Prometheus scrape after a mesh scan, with what the scan did."""
    from dragnet_tpu import native as mod_native
    from dragnet_tpu.obs import export as obs_export
    from dragnet_tpu.obs import metrics as obs_metrics
    from dragnet_tpu.parallel import cluster

    if mod_native.get_lib() is None:
        pytest.skip('native parser unavailable')
    datadir = tmp_path / 'data'
    datadir.mkdir()
    dsconfig = _dsconfig(datadir,
                         _write_mesh_case('odd-count', datadir / 'a.log'))
    _mesh_scan_setup(monkeypatch)
    _no_prefetch(monkeypatch)
    names = ('device_sparse_merge_rows', 'device_sparse_merge_tuples',
             'device_sparse_set_slots', 'device_sparse_set_live')
    before = [_counter(n) for n in names]
    r = _scan_points(monkeypatch, cluster.DatasourceCluster, dsconfig,
                     HIGHCARD_Q, 'jax')
    rows, tuples, slots, live = [
        _counter(n) - b for n, b in zip(names, before)]
    assert tuples == len(r.points)
    assert slots == 4096 and 0 < live < tuples
    # the chips' live slots: a key that several chips hold counts once
    # a chip (`everywhere` is on all eight)
    assert tuples + 7 <= rows <= 8 * live
    text = obs_export.prometheus_text(obs_metrics.global_registry())
    for n in names:
        assert '\ndn_%s ' % n in text, n
    assert 'dn_stage_ms_count{stage="scan.sparse_merge"} ' in text


MESH4_SERVE = r'''
import json, os, sys
sys.path.insert(0, %(root)r)
sys.path.insert(0, os.path.join(%(root)r, 'tests'))
import test_serve as t
from dragnet_tpu.serve import server as mod_server
work = sys.argv[1]
os.environ['DRAGNET_CONFIG'] = os.path.join(work, 'rc.json')
os.environ['DN_ENGINE'] = 'jax'
ds = t.add_wide_datasource(work, 'ds_mesh', backend='cluster')
srv = mod_server.DnServer(socket_path=os.path.join(work, 's.sock'),
                          conf=t._conf()).start()
try:
    t.check_wide_reply(srv.socket_path, ds)
    os.environ['DN_COUNTERS_ALL'] = '1'     # the engine's own counters
    rc, out, err = t.run_cli(['scan', '--remote', srv.socket_path,
                              '--points', '--counters', '-b', 'host,seq',
                              ds])
finally:
    srv.stop()
import jax
print(json.dumps({'devices': len(jax.devices()), 'lines': out.count(b'\n'),
                  'counters': err.decode()}))
'''


def test_columnar_reply_on_the_cluster_backend_over_four_devices(tmp_path):
    """test_serve.check_wide_reply (a scan of 9,000 tuples through `dn
    serve` equals the local CLI's bytes, goes out by column and builds
    no dicts) on `--backend=cluster` with the device engine forced,
    in a process of its own with four host devices: the mesh cell's
    shape."""
    import json
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS='cpu',
               XLA_FLAGS='--xla_force_host_platform_device_count=4')
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    p = subprocess.run(
        [sys.executable, '-c', MESH4_SERVE % {'root': root},
         str(tmp_path)], env=env, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, timeout=300)
    assert p.returncode == 0, p.stderr.decode()[-4000:]
    doc = json.loads(p.stdout.decode().splitlines()[-1])
    assert doc['devices'] == 4 and doc['lines'] == 9000
    assert 'ndevicebatches' in doc['counters']
