"""Batched device index-query engine (dragnet_tpu/device_index.py):
differential byte identity against the host path across formats,
intervals, predicate shapes, and the cardinality sweep
(dense -> sparse -> overflow -> host fallback); lane routing
(DN_INDEX_DEVICE off/forced/auto-audition) and the persisted `iq:`
audition family; residency integration (shard-tensor pins, the
whole-result accumulator pin, writer-epoch staleness, the shard-share
eviction contract); the probed DN_PARALLEL_FETCH capability; and
index_device_config validation.

Byte identity is the contract under test everywhere: every device
result (engaged, audited, pinned, or fallen back) must equal the host
path's points and visible counters exactly — string-key
first-occurrence order and NULL-SUM -> 0 included."""

import json
import os
import random
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from dragnet_tpu import config as mod_config  # noqa: E402
from dragnet_tpu import device_index as mod_di  # noqa: E402
from dragnet_tpu import device_scan as mod_ds  # noqa: E402
from dragnet_tpu import index_query_mt as mod_iqmt  # noqa: E402
from dragnet_tpu import query as mod_query  # noqa: E402
from dragnet_tpu.datasource_file import DatasourceFile  # noqa: E402
from dragnet_tpu.engine import MAX_DENSE_SEGMENTS  # noqa: E402
from dragnet_tpu.errors import DNError  # noqa: E402
from dragnet_tpu.serve import residency  # noqa: E402

NDAYS = 8


def _need_jax():
    from dragnet_tpu.ops import get_jax
    if get_jax() is None:
        pytest.skip('jax unavailable')


def _make_data(path, n=4000, nhosts=30, seed=99):
    rng = random.Random(seed)
    with open(path, 'w') as f:
        for i in range(n):
            rec = {
                'host': 'host%d' % rng.randrange(nhosts),
                'operation': 'op%d' % rng.randrange(8),
                'latency': rng.randrange(1, 1500),
                'time': '2014-05-%02dT%02d:10:0%d.000Z'
                        % (rng.randrange(1, NDAYS + 1),
                           rng.randrange(24), rng.randrange(10)),
            }
            f.write(json.dumps(rec, separators=(',', ':')) + '\n')


def _ds(datafile, idx):
    return DatasourceFile({
        'ds_backend': 'file',
        'ds_backend_config': {'path': datafile, 'timeField': 'time',
                              'indexPath': idx},
        'ds_filter': None, 'ds_format': 'json'})


def _metric():
    return mod_query.metric_deserialize({'name': 'm', 'breakdowns': [
        {'name': 'ts', 'field': 'time', 'date': '', 'aggr': 'lquantize',
         'step': 86400},
        {'name': 'host', 'field': 'host'},
        {'name': 'operation', 'field': 'operation'},
        {'name': 'latency', 'field': 'latency', 'aggr': 'quantize'}]})


def _query(conf):
    q = mod_query.query_load(dict(conf))
    assert not isinstance(q, DNError), q
    return q


def _run(ds, interval, conf, device, monkeypatch):
    monkeypatch.setenv('DN_INDEX_DEVICE', device)
    r = ds.query(_query(conf), interval)
    counters = [(s.name, {c: v for c, v in s.counters.items()
                          if c not in s.hidden})
                for s in r.pipeline.stages]
    return r.points, counters


def _built(tmp_path, interval='day', n=4000, nhosts=30):
    datafile = str(tmp_path / 'data.log')
    idx = str(tmp_path / 'idx')
    _make_data(datafile, n=n, nhosts=nhosts)
    ds = _ds(datafile, idx)
    ds.build([_metric()], interval)
    return ds, datafile, idx


@pytest.fixture(autouse=True)
def _fresh_lane(monkeypatch):
    """Every test starts with a cold shard cache, an undecided device
    verdict, zeroed engagement, and no residency manager."""
    monkeypatch.setenv('DN_IQ_STACK', 'auto')
    monkeypatch.setenv('DN_IQ_THREADS', 'auto')
    monkeypatch.delenv('DN_ENGINE', raising=False)
    monkeypatch.delenv('DN_INDEX_DEVICE', raising=False)
    monkeypatch.delenv('DN_INDEX_DEVICE_BATCH_ROWS', raising=False)
    mod_iqmt.shard_cache_clear()
    mod_di._reset_device_state()
    mod_di._reset_engagement()
    residency.deconfigure()
    yield
    mod_iqmt.shard_cache_clear()
    mod_di._reset_device_state()
    mod_di._reset_engagement()
    residency.deconfigure()


# -- differential fuzz: byte identity across the predicate grid -------------

FUZZ_QUERIES = [
    {'breakdowns': [{'name': 'host'},
                    {'name': 'latency', 'aggr': 'quantize'}]},
    {'breakdowns': [{'name': 'host'}, {'name': 'operation'}],
     'filter': {'eq': ['operation', 'op3']}},
    {'breakdowns': [{'name': 'latency', 'aggr': 'lquantize',
                     'step': 32}]},
    {'breakdowns': []},                        # bare SUM
    {'breakdowns': [],                         # NULL SUM -> 0
     'filter': {'eq': ['host', 'no-such-host']}},
    {'breakdowns': [{'name': 'host'}],         # window + zero shards
     'filter': {'eq': ['host', 'host7']},
     'timeAfter': '2014-05-02', 'timeBefore': '2014-05-07'},
    {'breakdowns': [{'name': 'host'},          # empty WITH breakdowns
                    {'name': 'operation'}],
     'filter': {'eq': ['host', 'no-such-host']}},
]


@pytest.mark.parametrize('index_format', ['dnc', 'sqlite'])
@pytest.mark.parametrize('interval', ['hour', 'day', 'all'])
def test_device_differential_sweep(tmp_path, index_format, interval,
                                   monkeypatch):
    """Forced device lane (DN_INDEX_DEVICE=1) vs host (=0) over
    formats x intervals x predicate shapes: points AND visible
    counters byte-identical — string-key first-occurrence order is
    part of the points contract."""
    _need_jax()
    monkeypatch.setenv('DN_INDEX_FORMAT', index_format)
    ds, _, _ = _built(tmp_path, interval=interval)
    engaged_somewhere = False
    for conf in FUZZ_QUERIES:
        ref, cref = _run(ds, interval, conf, '0', monkeypatch)
        before = mod_di.stats_doc()['dispatches']
        pts, cnt = _run(ds, interval, conf, '1', monkeypatch)
        assert pts == ref, conf
        assert cnt == cref, conf
        if mod_di.stats_doc()['dispatches'] > before:
            engaged_somewhere = True
    if mod_di._DEVICE_STATE['ready'] is False:
        pytest.skip('device lane unavailable on this rig')
    assert engaged_somewhere


def test_cardinality_sweep_dense_sparse_overflow(monkeypatch):
    """aggregate_weights at the seam: dense, sparse, and
    past-the-dense-ceiling cardinalities all equal np.bincount; the
    overflow case must route host (the structural refusal)."""
    _need_jax()
    monkeypatch.setenv('DN_INDEX_DEVICE', '1')
    rng = np.random.RandomState(11)
    for nuniq in (8, 1000, 50000):
        n = max(nuniq * 3, 512)
        inv = rng.randint(0, nuniq, size=n).astype(np.int64)
        # every segment id present at least once: inv from _unique_rows
        # is surjective by construction, and staging relies on that
        inv[:nuniq] = np.arange(nuniq)
        w = rng.randint(0, 1000, size=n).astype(np.int64)
        sid = np.sort(rng.randint(0, 37, size=n).astype(np.int64))
        got = mod_di.aggregate_weights(
            inv, w, nuniq, shard_ctx=(sid, [(None, None)] * 37, None))
        ref = np.bincount(inv, weights=w, minlength=nuniq)
        assert np.array_equal(got, ref), nuniq
    if mod_di._DEVICE_STATE['ready'] is False:
        pytest.skip('device lane unavailable on this rig')
    assert mod_di._ENGAGE['last_lane'] == 'device'
    # overflow: nuniq past the dense ceiling refuses the device lane
    nuniq = MAX_DENSE_SEGMENTS + 1
    inv = np.arange(nuniq, dtype=np.int64)
    w = np.ones(nuniq, dtype=np.int64)
    got = mod_di.aggregate_weights(inv, w, nuniq)
    assert np.array_equal(got, np.ones(nuniq))
    assert mod_di._ENGAGE['last_lane'] == 'host'


# -- lane routing -----------------------------------------------------------

def test_lane_off_forced_and_auto(tmp_path, monkeypatch):
    """DN_INDEX_DEVICE=0 pins host (no dispatches ever);
    =1 forces the device lane; auto with a cold process and no
    audition hint stays host (a bare `dn query` pays nothing)."""
    _need_jax()
    ds, _, _ = _built(tmp_path, n=1500)
    conf = FUZZ_QUERIES[0]

    _run(ds, 'day', conf, '0', monkeypatch)
    assert mod_di.stats_doc()['dispatches'] == 0

    # auto + cold backend + no verdict: host, no backend init (earlier
    # tests already probed the process-wide backend, so pin coldness)
    monkeypatch.setenv('DN_AUDITION_CACHE', '0')
    monkeypatch.setattr(mod_di, '_audition_warm', lambda: False)
    _run(ds, 'day', conf, 'auto', monkeypatch)
    assert mod_di.stats_doc()['dispatches'] == 0
    monkeypatch.undo()

    ref, _ = _run(ds, 'day', conf, '0', monkeypatch)
    pts, _ = _run(ds, 'day', conf, '1', monkeypatch)
    assert pts == ref
    if mod_di._DEVICE_STATE['ready'] is False:
        pytest.skip('device lane unavailable on this rig')
    assert mod_di.stats_doc()['dispatches'] > 0


def test_auto_audition_persists_iq_verdict(tmp_path, monkeypatch):
    """Auto mode with a warm backend auditions: both paths run, the
    result ships byte-identical, and the timed verdict persists under
    the `iq:` family in the audition cache the next process routes
    on."""
    _need_jax()
    cache_dir = str(tmp_path / 'xla')
    monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR', cache_dir)
    monkeypatch.setenv('DN_AUDITION_CACHE', '1')
    ds, _, _ = _built(tmp_path, n=1500)
    conf = FUZZ_QUERIES[0]
    ref, _ = _run(ds, 'day', conf, '0', monkeypatch)

    # a residency-armed process counts as warm (serve); this is what
    # lets the audition touch the backend at all
    residency.configure(16 << 20)
    pts, _ = _run(ds, 'day', conf, 'auto', monkeypatch)
    assert pts == ref
    if mod_di._DEVICE_STATE['ready'] is False:
        pytest.skip('device lane unavailable on this rig')
    assert mod_di.stats_doc()['auditions'] >= 1
    path = os.path.join(cache_dir, 'dn_auditions.json')
    with open(path) as f:
        entries = json.load(f)
    iq_keys = [k for k in entries if k.startswith('iq:')]
    assert iq_keys, entries
    assert all('@' in k for k in iq_keys)      # backend-scoped
    ent = entries[iq_keys[0]]
    assert 'won' in ent and 'device_rate' in ent


# -- residency integration --------------------------------------------------

def test_acc_pin_and_pinned_shard_repeat(tmp_path, monkeypatch):
    """Residency-armed repeats: an exact repeat answers from the
    whole-result pin with zero new dispatches; after host-pin churn
    (drop_host_pins) the repeat re-folds from PINNED shard tensors —
    hits > 0, H2D bytes measurably skipped — and stays
    byte-identical."""
    _need_jax()
    ds, _, _ = _built(tmp_path, n=3000)
    conf = FUZZ_QUERIES[0]
    ref, cref = _run(ds, 'day', conf, '0', monkeypatch)

    mgr = residency.configure(64 << 20)
    pts, cnt = _run(ds, 'day', conf, '1', monkeypatch)
    if mod_di._DEVICE_STATE['ready'] is False:
        pytest.skip('device lane unavailable on this rig')
    assert pts == ref and cnt == cref
    assert mgr.stats()['shard_bytes'] > 0      # shard tensors pinned

    base = mod_di.stats_doc()['dispatches']
    pts, cnt = _run(ds, 'day', conf, '1', monkeypatch)
    assert pts == ref and cnt == cref
    assert mod_di.stats_doc()['dispatches'] == base   # acc pin hit
    assert mgr.stats()['d2h_saved_bytes'] > 0

    mgr.drop_host_pins()
    mod_di._reset_engagement()
    pts, cnt = _run(ds, 'day', conf, '1', monkeypatch)
    assert pts == ref and cnt == cref
    eng = mod_di.stats_doc()
    assert eng['dispatches'] > 0               # re-folded on device
    assert eng['pinned_shard_hits'] > 0        # from HBM, not H2D
    assert eng['h2d_saved_bytes'] > 0
    assert eng['pinned_shard_hits'] == eng['shards']


def test_writer_epoch_retires_pinned_shards(tmp_path, monkeypatch):
    """The staleness hazard: shard identity is pinned past a content
    change (monkeypatched to path-only, simulating an in-place rewrite
    that preserves statkey), the index is rebuilt with different data,
    and the writer-epoch signal — the serve write hook's contract —
    must retire the pinned tensors so the next query matches the host
    path on the NEW content."""
    _need_jax()
    datafile = str(tmp_path / 'data.log')
    idx = str(tmp_path / 'idx')
    _make_data(datafile, n=2000, seed=1)
    ds = _ds(datafile, idx)
    ds.build([_metric()], 'day')
    monkeypatch.setattr(mod_di, '_shard_identity',
                        lambda path, statkey: ('path', path))
    residency.configure(64 << 20)
    conf = FUZZ_QUERIES[0]
    pts1, _ = _run(ds, 'day', conf, '1', monkeypatch)
    if mod_di._DEVICE_STATE['ready'] is False:
        pytest.skip('device lane unavailable on this rig')
    assert residency.stats()['shard_bytes'] > 0

    # publish new content at the same paths, then fire the writer
    # invalidation exactly as serve's install_writer_invalidation does
    _make_data(datafile, n=2600, seed=2)
    ds2 = _ds(datafile, idx)
    ds2.build([_metric()], 'day')
    mod_iqmt.invalidate_index_tree(idx)

    mod_iqmt.shard_cache_clear()
    ref, cref = _run(ds2, 'day', conf, '0', monkeypatch)
    assert ref != pts1                         # the data really moved
    pts2, cnt2 = _run(ds2, 'day', conf, '1', monkeypatch)
    assert pts2 == ref and cnt2 == cref        # never the stale pin
    assert residency.stats()['stale_drops'] >= 1


def test_shard_share_and_eviction_preference():
    """The budget split: shard pins are capped at the share, a
    too-big shard pin is shed, get() never leaks a device-only pin,
    and global-budget pressure evicts whole-result pins BEFORE shard
    pins (_evict_global_locked)."""
    mgr = residency.DeviceResidency(200, shard_share=0.5)
    # share cap: 0.5 * 200 = 100 -> a 120-byte shard pin is shed
    assert mgr.put_device('s-big', 1, ('d',), nbytes=120) is False
    assert mgr.stats()['shed'] == 1
    assert mgr.put_device('s1', 1, ('d1',), nbytes=60)
    assert mgr.put_device('s2', 1, ('d2',), nbytes=40)
    # the kind guard: a shard pin never answers the host protocol
    assert mgr.get('s1', 1) is None
    assert mgr.get_device('s1', 1) == ('d1',)
    # a third shard pin overflows the share: the shard LRU (s2 — s1
    # was just touched) goes, never the host pin added below
    host = np.zeros(8)                         # 64 bytes
    assert mgr.put('acc', 1, host, host, h2d_bytes=7)
    assert mgr.put_device('s3', 1, ('d3',), nbytes=40)
    st = mgr.stats()
    assert st['shard_bytes'] <= 100
    assert mgr.get('acc', 1) is not None       # host pin survived
    # global pressure from a host put evicts the OTHER host pin
    # first, not the shard tensors
    big = np.zeros(12)                         # 96 bytes
    assert mgr.put('acc2', 1, big, big, h2d_bytes=0)
    assert mgr.get('acc', 1) is None           # host pin was the prey
    assert mgr.get_device('s1', 1) == ('d1',)  # shards survived
    assert mgr.get_device('s3', 1) == ('d3',)


def test_get_device_epoch_and_hit_accounting():
    mgr = residency.DeviceResidency(1 << 10)
    assert mgr.put_device('k', 3, ('dev',), nbytes=64, h2d_bytes=640)
    assert mgr.get_device('k', 4) is None      # epoch moved on
    assert mgr.stats()['stale_drops'] == 1
    assert mgr.put_device('k', 4, ('dev',), nbytes=64, h2d_bytes=640)
    assert mgr.get_device('k', 4) == ('dev',)
    st = mgr.stats()
    assert st['h2d_saved_bytes'] == 640        # a hit skips the upload
    assert st['d2h_saved_bytes'] == 0          # ...but fetches nothing


def test_drop_host_pins_keeps_shards():
    mgr = residency.DeviceResidency(1 << 10)
    host = np.zeros(8)
    mgr.put('acc', 1, host, host, h2d_bytes=0)
    mgr.put_device('s', 1, ('d',), nbytes=64)
    mgr.drop_host_pins()
    st = mgr.stats()
    assert st['entries'] == 1 and st['shard_bytes'] == 64
    assert mgr.get_device('s', 1) == ('d',)


# -- the probed DN_PARALLEL_FETCH capability --------------------------------

@pytest.fixture()
def _fresh_fetch(monkeypatch):
    monkeypatch.delenv('DN_PARALLEL_FETCH', raising=False)
    mod_ds._reset_parallel_fetch()
    yield
    mod_ds._reset_parallel_fetch()


def test_parallel_fetch_env_overrides_both_ways(monkeypatch,
                                                _fresh_fetch):
    monkeypatch.setenv('DN_PARALLEL_FETCH', '1')
    assert mod_ds.parallel_fetch_enabled() is True
    assert mod_ds.parallel_fetch_doc()['source'] == 'env'
    mod_ds._reset_parallel_fetch()
    monkeypatch.setenv('DN_PARALLEL_FETCH', '0')
    assert mod_ds.parallel_fetch_enabled() is False
    doc = mod_ds.parallel_fetch_doc()
    assert doc['source'] == 'env' and doc['probe_ms'] is None


def test_parallel_fetch_probe_sets_default(_fresh_fetch):
    """No env override: the first call runs the one guarded
    concurrent-fetch probe and the verdict memoizes."""
    _need_jax()
    assert mod_ds.parallel_fetch_doc()['enabled'] is None   # unprobed
    v = mod_ds.parallel_fetch_enabled()
    doc = mod_ds.parallel_fetch_doc()
    assert doc['source'] == 'probe'
    assert doc['probe_ms'] is not None
    assert doc['enabled'] is v
    if v is False:
        assert doc['reason']
    # memoized: a second call answers without re-probing
    assert mod_ds.parallel_fetch_enabled() is v


def test_parallel_fetch_probe_failure_disables(monkeypatch,
                                               _fresh_fetch):
    _need_jax()
    monkeypatch.setattr(
        mod_ds, '_probe_parallel_fetch',
        lambda: (_ for _ in ()).throw(RuntimeError('deadlock')))
    assert mod_ds.parallel_fetch_enabled() is False
    doc = mod_ds.parallel_fetch_doc()
    assert doc['source'] == 'probe'
    assert 'deadlock' in doc['reason']


# -- config validation ------------------------------------------------------

def test_index_device_config_defaults(monkeypatch):
    for k in ('DN_INDEX_DEVICE', 'DN_INDEX_DEVICE_BATCH_ROWS',
              'DN_INDEX_RESIDENCY_SHARE'):
        monkeypatch.delenv(k, raising=False)
    conf = mod_config.index_device_config()
    assert conf == {'mode': 'auto', 'batch_rows': 1 << 20,
                    'residency_share': 0.5}


def test_index_device_config_rejects_bad_values(monkeypatch):
    monkeypatch.setenv('DN_INDEX_DEVICE', 'yes')
    err = mod_config.index_device_config()
    assert isinstance(err, DNError)
    assert 'DN_INDEX_DEVICE' in err.message
    monkeypatch.setenv('DN_INDEX_DEVICE', '1')
    monkeypatch.setenv('DN_INDEX_DEVICE_BATCH_ROWS', '12')
    err = mod_config.index_device_config()
    assert isinstance(err, DNError)
    assert 'DN_INDEX_DEVICE_BATCH_ROWS' in err.message
    monkeypatch.setenv('DN_INDEX_DEVICE_BATCH_ROWS', '8192')
    monkeypatch.setenv('DN_INDEX_RESIDENCY_SHARE', '1.5')
    err = mod_config.index_device_config()
    assert isinstance(err, DNError)
    assert 'DN_INDEX_RESIDENCY_SHARE' in err.message
    monkeypatch.setenv('DN_INDEX_RESIDENCY_SHARE', '0.25')
    conf = mod_config.index_device_config()
    assert conf == {'mode': '1', 'batch_rows': 8192,
                    'residency_share': 0.25}


def test_stats_doc_shape():
    mod_di._reset_engagement()
    doc = mod_di.stats_doc()
    assert doc['dispatches'] == 0
    assert doc['shards_per_dispatch'] == 0.0
    assert set(doc) >= {'dispatches', 'shards', 'rows',
                        'pinned_shard_hits', 'h2d_bytes',
                        'h2d_saved_bytes', 'auditions', 'last_lane'}
