"""Stacked multi-metric device build (DeviceScanStack): N metrics fold
through ONE combined device program per batch, and the index artifacts
must be BYTE-identical to the host engine's — the same differential
discipline as the scan path (the reference fed one parse stream into N
per-metric scanners, lib/datasource-file.js:403-427)."""

import json
import os
import random
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from dragnet_tpu import native as mod_native      # noqa: E402
from dragnet_tpu import query as mod_query        # noqa: E402
from dragnet_tpu.datasource_file import DatasourceFile  # noqa: E402
from dragnet_tpu.ops import get_jax, backend_ready  # noqa: E402
from helpers.scan_differential import (  # noqa: E402
    batches_handed, serial_loop, write_layout)

pytestmark = pytest.mark.skipif(
    mod_native.get_lib() is None or get_jax() is None or
    not backend_ready(),
    reason='native parser or jax unavailable')


METRICS = [
    # shared columns across metrics: time (all), host (2), latency (2)
    {'name': 'byhost', 'breakdowns': [
        {'name': 'timestamp', 'field': 'time', 'date': '',
         'aggr': 'lquantize', 'step': 86400},
        {'name': 'host', 'field': 'host'}]},
    {'name': 'bymethod', 'breakdowns': [
        {'name': 'timestamp', 'field': 'time', 'date': '',
         'aggr': 'lquantize', 'step': 86400},
        {'name': 'method', 'field': 'req.method'},
        {'name': 'latency', 'field': 'latency', 'aggr': 'quantize'}],
     'filter': {'ne': ['host', 'b']}},
    {'name': 'bylat', 'breakdowns': [
        {'name': 'timestamp', 'field': 'time', 'date': '',
         'aggr': 'lquantize', 'step': 86400},
        {'name': 'host', 'field': 'host'},
        {'name': 'latency', 'field': 'latency', 'aggr': 'lquantize',
         'step': 50}]},
]


def _write_data(path, n, with_edges=False, days=3):
    rng = random.Random(7)
    lines = []
    for i in range(n):
        day = 1 + (i * days // n)
        lines.append(json.dumps({
            'time': '2014-05-%02dT%02d:%02d:%02dZ' % (
                day, rng.randrange(24), rng.randrange(60),
                rng.randrange(60)),
            'host': rng.choice(['a', 'b', 'c', 'host-%d'
                                % rng.randrange(20)]),
            'req': {'method': rng.choice(['GET', 'PUT', 'DELETE'])},
            'latency': rng.choice([0, 1, 3, 17, 200, 4096]),
        }))
    if with_edges:
        # array-valued key field and non-integral latency force
        # per-batch staging failures mid-stream
        lines.insert(n // 3, json.dumps({
            'time': '2014-05-01T05:00:00Z', 'host': [1, 'two'],
            'req': {'method': 'GET'}, 'latency': 3}))
        lines.insert(2 * n // 3, json.dumps({
            'time': '2014-05-02T05:00:00Z', 'host': 'a',
            'req': {'method': 'PUT'}, 'latency': 2.5}))
    with open(path, 'w') as f:
        f.write('\n'.join(lines) + '\n')


def _ds(datafile, indexdir):
    return DatasourceFile({
        'ds_backend': 'file',
        'ds_backend_config': {'path': str(datafile),
                              'indexPath': str(indexdir),
                              'timeField': 'time'},
        'ds_filter': None, 'ds_format': 'json',
    })


def _tree_bytes(root):
    out = {}
    for dirpath, dirs, files in os.walk(root):
        for fn in sorted(files):
            p = os.path.join(dirpath, fn)
            with open(p, 'rb') as f:
                out[os.path.relpath(p, root)] = f.read()
    return out


def _metrics():
    return [mod_query.metric_deserialize(m) for m in METRICS]


def _build(monkeypatch, datafile, indexdir, engine, batch=None,
           read_size=None, metrics=None):
    monkeypatch.setenv('DN_ENGINE', engine)
    monkeypatch.setenv('DN_PARSE_THREADS', '1')
    if batch is not None:
        from dragnet_tpu import engine as mod_engine
        from dragnet_tpu import device_scan as mod_ds
        monkeypatch.setattr(mod_engine, 'BATCH_SIZE', batch)
        monkeypatch.setattr(mod_ds, 'BATCH_SIZE', batch)
        monkeypatch.setenv('DN_READ_SIZE', str(read_size or batch * 64))
    result = _ds(datafile, indexdir).build(metrics or _metrics(), 'day')
    stacked = 0
    for stage in result.pipeline.stages:
        stacked += stage.counters.get('nstackedbatches', 0)
    return result, stacked


def test_stacked_build_byte_identical(tmp_path, monkeypatch):
    datafile = tmp_path / 'data.log'
    _write_data(datafile, 3000)

    _, s_host = _build(monkeypatch, datafile, tmp_path / 'ih', 'vector')
    assert s_host == 0
    _, s_dev = _build(monkeypatch, datafile, tmp_path / 'id', 'jax')
    assert s_dev > 0, 'combined device program never engaged'

    host_tree = _tree_bytes(tmp_path / 'ih')
    dev_tree = _tree_bytes(tmp_path / 'id')
    assert host_tree.keys() == dev_tree.keys()
    # three daily shards plus integrity metadata (the catalog —
    # itself compared byte-for-byte in the loop below — and its
    # flock sidecar)
    from dragnet_tpu import index_journal as mod_journal
    assert len([p for p in host_tree
                if not mod_journal.is_durable_metadata(p)]) == 3
    for rel in host_tree:
        assert host_tree[rel] == dev_tree[rel], \
            'index shard %s differs between stacked-device and host ' \
            'builds' % rel


def test_stacked_build_with_fallback_batches(tmp_path, monkeypatch):
    """Batches a metric cannot stage (array key values, non-integral
    quantize values) drop the whole batch to the per-scan paths;
    results must still match the host build byte-for-byte."""
    datafile = tmp_path / 'data.log'
    _write_data(datafile, 1500, with_edges=True)

    _, _ = _build(monkeypatch, datafile, tmp_path / 'ih', 'vector')
    # small batches so the edge lines land in their own mid-stream
    # batches (several staging transitions)
    _, s_dev = _build(monkeypatch, datafile, tmp_path / 'id', 'jax',
                      batch=128)
    assert s_dev > 0

    host_tree = _tree_bytes(tmp_path / 'ih')
    dev_tree = _tree_bytes(tmp_path / 'id')
    assert host_tree.keys() == dev_tree.keys()
    for rel in host_tree:
        assert host_tree[rel] == dev_tree[rel], rel


@pytest.mark.parametrize('layout,read_size', [
    ('line-spans-files', 100),          # under one line: all span chunks
    ('empty-file-between', 16384),
    ('no-final-newline', 1 << 24),      # the production chunk: one batch
])
def test_handoff_build_byte_identical(tmp_path, monkeypatch, layout,
                                      read_size):
    """The stacked device build behind the parser's thread writes the
    index the serial loop and the host engine write, shard for shard:
    over chunk sizes and over files that end in the middle of a line,
    are empty, or lack the last newline."""
    plain = tmp_path / 'plain.log'
    _write_data(plain, 1200, with_edges=True)
    with open(plain) as f:
        datapath = write_layout(tmp_path, f.read().splitlines(), layout)

    _build(monkeypatch, datapath, tmp_path / 'ih', 'vector')
    h0 = batches_handed()
    _, s_dev = _build(monkeypatch, datapath, tmp_path / 'id', 'jax',
                      batch=128, read_size=read_size)
    h1 = batches_handed()
    with monkeypatch.context() as mp:
        serial_loop(mp)
        _, s_ser = _build(mp, datapath, tmp_path / 'is', 'jax',
                          batch=128, read_size=read_size)
    assert s_dev == s_ser > 0
    assert h1 - h0 >= (4 if read_size < (1 << 24) else 1)
    assert batches_handed() == h1

    host_tree = _tree_bytes(tmp_path / 'ih')
    for other in ('id', 'is'):
        tree = _tree_bytes(tmp_path / other)
        assert host_tree.keys() == tree.keys()
        for rel in host_tree:
            assert host_tree[rel] == tree[rel], (other, rel)


def test_stacked_index_scan_points_identical(tmp_path, monkeypatch):
    """index-scan (tagged points, insertion order) through the stack
    equals the host engine's exactly."""
    datafile = tmp_path / 'data.log'
    _write_data(datafile, 2000)

    monkeypatch.setenv('DN_PARSE_THREADS', '1')
    monkeypatch.setenv('DN_ENGINE', 'vector')
    host = _ds(datafile, tmp_path / 'ih').index_scan(_metrics(), 'day')
    monkeypatch.setenv('DN_ENGINE', 'jax')
    dev = _ds(datafile, tmp_path / 'id').index_scan(_metrics(), 'day')

    assert [(f, v) for f, v in host.points] == \
        [(f, v) for f, v in dev.points]


def _hidden(result, name):
    return sum(st.counters.get(name, 0) for st in result.pipeline.stages)


def _dispatches():
    from dragnet_tpu.obs import metrics as obs_metrics
    return obs_metrics.global_registry().counter(
        'device_pipe_dispatches').value


@pytest.mark.parametrize('mi', range(len(METRICS)),
                         ids=[m['name'] for m in METRICS])
def test_one_metric_build_byte_identical(tmp_path, monkeypatch, mi):
    """A forced-device build of ONE metric is a stack of one: every
    batch is the device's, one dispatch a batch, no `nstackedbatches`
    (no two scans share the program), and the tree is the vector
    engine's byte for byte."""
    datafile = tmp_path / 'data.log'
    _write_data(datafile, 1500)
    metric = [_metrics()[mi]]

    _build(monkeypatch, datafile, tmp_path / 'ih', 'vector',
           metrics=metric)
    h0, d0 = batches_handed(), _dispatches()
    result, stacked = _build(monkeypatch, datafile, tmp_path / 'id', 'jax',
                             batch=256, metrics=metric)
    handed = batches_handed() - h0
    assert handed >= 5 and _dispatches() - d0 == handed
    assert _hidden(result, 'ndevicebatches') == handed and stacked == 0

    host_tree = _tree_bytes(tmp_path / 'ih')
    dev_tree = _tree_bytes(tmp_path / 'id')
    assert host_tree.keys() == dev_tree.keys() and len(host_tree) >= 3
    for rel in host_tree:
        assert host_tree[rel] == dev_tree[rel], rel


def test_lone_scan_and_one_metric_build_share_one_path(tmp_path,
                                                       monkeypatch):
    """A forced-device lone scan and a one-metric index-scan of the
    same query go through DeviceScanStack._process_device as stacks of
    one, a dispatch a batch handed, and through the same cached jitted
    programs: the second of them adds no entry (no second jit of what
    the first compiled).  Equal points, no `nstackedbatches`."""
    from dragnet_tpu import engine as mod_engine
    from dragnet_tpu import device_scan as mod_ds
    datafile = tmp_path / 'data.log'
    _write_data(datafile, 1500)
    metric = _metrics()[1]          # two key columns and a filter
    query = mod_query.metric_query(metric, None, None, 'all', 'time')

    took = []
    orig = mod_ds.DeviceScanStack._process_device

    def spy(self, provider, weights, alive):
        took.append((len(self.scans), orig(self, provider, weights, alive)))
        return took[-1][1]
    monkeypatch.setattr(mod_ds.DeviceScanStack, '_process_device', spy)
    monkeypatch.setattr(mod_ds, '_STACK_CACHE', {})
    monkeypatch.setattr(mod_engine, 'BATCH_SIZE', 256)
    monkeypatch.setattr(mod_ds, 'BATCH_SIZE', 256)
    monkeypatch.setenv('DN_READ_SIZE', str(256 * 64))
    monkeypatch.setenv('DN_PARSE_THREADS', '1')
    monkeypatch.setenv('DN_ENGINE', 'jax')

    runs = []
    for run in (lambda ds: ds.index_scan([metric], 'all'),
                lambda ds: ds.scan(query)):
        h0, d0 = batches_handed(), _dispatches()
        result = run(_ds(datafile, tmp_path / 'idx'))
        handed = batches_handed() - h0
        assert handed >= 5 and _dispatches() - d0 == handed
        assert took == [(1, True)] * handed
        assert _hidden(result, 'ndevicebatches') == handed
        assert _hidden(result, 'nstackedbatches') == 0
        runs.append((result, sorted(mod_ds._STACK_CACHE, key=repr)))
        del took[:]
    (built, programs), (scanned, programs_after) = runs
    assert programs and all(len(key) == 1 for key in programs)
    assert programs_after == programs
    assert [(dict(f, __dn_metric=0), v) for f, v in scanned.points] == \
        [(f, v) for f, v in built.points]


def test_sparse_fold_batches_counter(tmp_path, monkeypatch):
    """`device_sparse_fold_batches` counts one per metric and batch
    that went through the sparse sort-merge fold: with the dense budget
    forced small, a stacked build grows it by the sparse metrics'
    batches (the dense metric beside them adds nothing), and a dense
    build leaves it alone."""
    from dragnet_tpu import engine as mod_engine
    from dragnet_tpu import device_scan as mod_ds
    from dragnet_tpu.obs import metrics as obs_metrics
    datafile = tmp_path / 'data.log'
    _write_data(datafile, 1500)

    def folded():
        return obs_metrics.global_registry().counter(
            'device_sparse_fold_batches').value

    # (sparse, dense) metric-batches by the programs the stack staged
    seen = [0, 0]
    orig = mod_ds.DeviceScanStack._stacked_program

    def spy(self, staged, inputs):
        for st in staged:
            seen[0 if st[1][-1] else 1] += 1
        return orig(self, staged, inputs)
    monkeypatch.setattr(mod_ds.DeviceScanStack, '_stacked_program', spy)

    before = folded()
    _, stacked = _build(monkeypatch, datafile, tmp_path / 'i1', 'jax',
                        batch=256)
    assert stacked > 0 and seen[0] == 0
    assert folded() == before

    # byhost (the day axis twice, 32 x 32, x 32 hosts) stays dense
    # under 2^16; the two metrics with a latency column pass it
    monkeypatch.setattr(mod_engine, 'MAX_DENSE_SEGMENTS', 1 << 16)
    monkeypatch.setattr(mod_ds, 'MAX_DENSE_SEGMENTS', 1 << 16)
    seen[:] = [0, 0]
    _, stacked = _build(monkeypatch, datafile, tmp_path / 'i2', 'jax',
                        batch=256)
    assert stacked > 0 and seen[0] > 0 and seen[1] > 0
    assert folded() == before + seen[0]

    t1 = _tree_bytes(tmp_path / 'i1')
    t2 = _tree_bytes(tmp_path / 'i2')
    assert t1.keys() == t2.keys()
    for rel in t1:
        assert t1[rel] == t2[rel], rel
