"""Batched device index-query engine (dragnet_tpu/device_index.py):
differential byte identity against the host path across formats,
intervals, predicate shapes, and the cardinality sweep
(dense -> sparse -> overflow -> host fallback); lane routing
(DN_INDEX_DEVICE off/forced/auto-audition) and the persisted `iq:`
audition family; the packed fold (one upload and one dispatch a fold,
the row ladder that decides the compiles); residency integration (the
whole-result accumulator pin, writer-epoch staleness); the probed
DN_PARALLEL_FETCH capability; and index_device_config validation.

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
        # every segment id present at least once, as inv from
        # _unique_rows is by construction
        inv[:nuniq] = np.arange(nuniq)
        w = rng.randint(0, 1000, size=n).astype(np.int64)
        sid = np.sort(rng.randint(0, 37, size=n).astype(np.int64))
        got = mod_di.aggregate_weights(inv, w, nuniq,
                                       shard_ctx=(sid, 37))
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


# -- the packed fold --------------------------------------------------------

def _batch(nshards, nuniq, empty=(), seed=5):
    """A stacked batch as run_stacked hands it over: `inv` covering
    every segment, integer weights as f64, shard ids ascending; the
    shards of `empty` hold no row."""
    rng = np.random.RandomState(seed)
    live = [s for s in range(nshards) if s not in empty]
    per = max(nuniq // len(live), 1) + 3
    sid = np.repeat(np.array(live, dtype=np.int64), per)
    n = len(sid)
    inv = rng.randint(0, nuniq, size=n).astype(np.int64)
    inv[:min(nuniq, n)] = np.arange(min(nuniq, n))
    nuniq = int(inv.max()) + 1
    w = rng.randint(0, 1 << 20, size=n).astype(np.float64)
    return inv, w, nuniq, sid


FOLD_CASES = [(nshards, nuniq, ())
              for nshards in (1, 7, 57, 365)
              for nuniq in (35, 400, 70000)] + [
    (57, 400, (0, 1)), (57, 400, (20, 21, 22)), (57, 400, (55, 56))]


@pytest.mark.parametrize('nshards,nuniq,empty', FOLD_CASES)
def test_packed_fold_equals_bincount(nshards, nuniq, empty,
                                     monkeypatch):
    """The packed fold against np.bincount over shard counts, segment
    counts and empty shards at the start, in the middle and at the
    end: the same bits, one dispatch, and the non-empty shards
    counted."""
    _need_jax()
    monkeypatch.setenv('DN_INDEX_DEVICE', '1')
    inv, w, nuniq, sid = _batch(nshards, nuniq, empty)
    got = mod_di.aggregate_weights(inv, w, nuniq,
                                   shard_ctx=(sid, nshards))
    if mod_di._DEVICE_STATE['ready'] is False:
        pytest.skip('device lane unavailable on this rig')
    assert got.dtype == np.float64
    assert np.array_equal(got, np.bincount(inv, weights=w,
                                           minlength=nuniq))
    doc = mod_di.stats_doc()
    assert doc['last_lane'] == 'device' and doc['dispatches'] == 1
    assert doc['shards'] == nshards and doc['rows'] == len(inv)


def test_packed_fold_without_shard_context(monkeypatch):
    """The anonymous call (shard_ctx=None) takes the same path."""
    _need_jax()
    monkeypatch.setenv('DN_INDEX_DEVICE', '1')
    inv, w, nuniq, _sid = _batch(7, 400)
    got = mod_di.aggregate_weights(inv, w, nuniq)
    if mod_di._DEVICE_STATE['ready'] is False:
        pytest.skip('device lane unavailable on this rig')
    assert np.array_equal(got, np.bincount(inv, weights=w,
                                           minlength=nuniq))
    doc = mod_di.stats_doc()
    assert doc['dispatches'] == 1 and doc['shards'] == 1


@pytest.mark.parametrize('nshards', [1, 7, 57, 365])
def test_one_dispatch_and_one_upload_a_fold(nshards, monkeypatch):
    """Whatever the shard count: one dispatch, and 16 bytes a padded
    row uploaded (the i64 pair), the padded rows from the ladder."""
    _need_jax()
    monkeypatch.setenv('DN_INDEX_DEVICE', '1')
    inv, w, nuniq, sid = _batch(nshards, 400)
    calls = []
    real = mod_di.sums_program

    def counting(rows, segments):
        prog = real(rows, segments)

        def run(pair):
            calls.append(pair.shape)
            return prog(pair)
        return run
    monkeypatch.setattr(mod_di, 'sums_program', counting)
    mod_di.aggregate_weights(inv, w, nuniq, shard_ctx=(sid, nshards))
    if mod_di._DEVICE_STATE['ready'] is False:
        pytest.skip('device lane unavailable on this rig')
    rows = mod_di.pad_rows(len(inv))
    assert calls == [(2, rows)]
    doc = mod_di.stats_doc()
    assert doc['dispatches'] == 1
    assert doc['padded_rows'] == rows
    assert doc['h2d_bytes'] == 16 * rows
    assert doc['shards_per_dispatch'] == float(nshards)


def test_pack_pair_pads_onto_the_last_segment():
    inv = np.array([0, 2, 1, 2], dtype=np.int64)
    w = np.array([5.0, 7.0, 11.0, 13.0])
    pair = mod_di.pack_pair(inv, w, 8, 4)
    assert pair.dtype == np.int64 and pair.shape == (2, 8)
    assert pair[0].tolist() == [0, 2, 1, 2, 3, 3, 3, 3]
    assert pair[1].tolist() == [5, 7, 11, 13, 0, 0, 0, 0]


# the programs the fold may compile for any batch of up to 2^18 rows:
# the builder's stated bound (device_index.ladder)
LADDER_PROGRAMS = 4


def test_ladder_bounds_the_programs():
    """Every row count from 1 to 2^18 maps onto one of four padded
    row counts, never below itself, and past 2^18 the ladder turns to
    powers of two (at most twice the rows)."""
    n = np.arange(1, (1 << 18) + 1)
    rungs = np.array(mod_di.ladder())
    assert len(rungs) == LADDER_PROGRAMS
    assert rungs.tolist() == [1 << 12, 1 << 14, 1 << 16, 1 << 18]
    padded = rungs[np.searchsorted(rungs, n)]
    # the vectorized reading above is pad_rows': spot-check the edges
    for k in (1, 4096, 4097, 16384, 16385, 65536, 65537, 1 << 18):
        assert mod_di.pad_rows(k) == padded[k - 1]
    assert (padded >= n).all()
    assert len(np.unique(padded)) == LADDER_PROGRAMS
    assert mod_di.pad_rows((1 << 18) + 1) == 1 << 19
    assert mod_di.pad_rows((1 << 20) + 1) == 1 << 21
    assert [mod_di.pad_segments(u) for u in (1, 35, 400, 512, 513,
                                             70000)] == \
        [512, 512, 512, 512, 1024, 131072]


# tuples a daily shard holds for the four templates of the query
# cells (benchmarks/configs/muskie-365d-index.json: the key space
# bounds them from above, 5.5 k records a day nearly fill them), as
# (fewest, most) rows a shard brings to the batch
CLASS_ROWS = {'m1': (370, 400), 'm2': (28, 30), 'm3': (290, 340),
              'm1-host-latency-get': (100, 120)}


@pytest.mark.parametrize('days', [7, 30, 90, 365])
@pytest.mark.parametrize('template', sorted(CLASS_ROWS))
def test_class_shape_does_not_move_with_the_start_day(template, days):
    """A class of the query cell (template x window) takes one program
    wherever its window starts: its fewest and its most rows pad to
    the same rung, so the warm-up's one request a class compiles all
    the window can ask for."""
    lo, hi = CLASS_ROWS[template]
    assert mod_di.pad_rows(days * lo) == mod_di.pad_rows(days * hi)
    assert mod_di.pad_segments(hi) == mod_di.SEGMENT_FLOOR


# -- residency integration --------------------------------------------------

def test_acc_pin_answers_the_repeat(tmp_path, monkeypatch):
    """Residency-armed repeats: an exact repeat answers from the
    whole-result pin with no new dispatch and no new upload, byte-
    identical; a different query folds again."""
    _need_jax()
    ds, _, _ = _built(tmp_path, n=3000)
    conf = FUZZ_QUERIES[0]
    ref, cref = _run(ds, 'day', conf, '0', monkeypatch)

    mgr = residency.configure(64 << 20)
    pts, cnt = _run(ds, 'day', conf, '1', monkeypatch)
    if mod_di._DEVICE_STATE['ready'] is False:
        pytest.skip('device lane unavailable on this rig')
    assert pts == ref and cnt == cref
    assert mgr.stats()['entries'] == 1         # the accumulator pinned

    base = mod_di.stats_doc()
    assert base['dispatches'] == 1
    pts, cnt = _run(ds, 'day', conf, '1', monkeypatch)
    assert pts == ref and cnt == cref
    again = mod_di.stats_doc()
    assert again['dispatches'] == base['dispatches']    # acc pin hit
    assert again['h2d_bytes'] == base['h2d_bytes']
    st = mgr.stats()
    assert st['hits'] == 1
    assert st['d2h_saved_bytes'] > 0 and st['h2d_saved_bytes'] > 0

    other = FUZZ_QUERIES[2]
    oref, _ = _run(ds, 'day', other, '0', monkeypatch)
    opts, _ = _run(ds, 'day', other, '1', monkeypatch)
    assert opts == oref
    assert mod_di.stats_doc()['dispatches'] == base['dispatches'] + 1
    assert mgr.stats()['entries'] == 2


def test_writer_epoch_retires_the_acc_pin(tmp_path, monkeypatch):
    """The staleness hazard: the accumulator is pinned, the index is
    rebuilt with different data at the same paths, and the writer-
    epoch signal — the serve write hook's contract — retires the pin,
    so the next query folds the NEW content and matches the host
    path; a repeat under the old epoch's content cannot be served."""
    _need_jax()
    datafile = str(tmp_path / 'data.log')
    idx = str(tmp_path / 'idx')
    _make_data(datafile, n=2000, seed=1)
    ds = _ds(datafile, idx)
    ds.build([_metric()], 'day')
    residency.configure(64 << 20)
    conf = FUZZ_QUERIES[0]
    pts1, _ = _run(ds, 'day', conf, '1', monkeypatch)
    if mod_di._DEVICE_STATE['ready'] is False:
        pytest.skip('device lane unavailable on this rig')
    assert residency.stats()['entries'] == 1
    # the same tree, the same epoch: the pin answers
    assert _run(ds, 'day', conf, '1', monkeypatch)[0] == pts1
    assert mod_di.stats_doc()['dispatches'] == 1
    epoch = mod_iqmt.cache_epoch()

    # publish new content at the same paths, then fire the writer
    # invalidation exactly as serve's install_writer_invalidation does
    _make_data(datafile, n=2600, seed=2)
    ds2 = _ds(datafile, idx)
    ds2.build([_metric()], 'day')
    mod_iqmt.invalidate_index_tree(idx)
    assert mod_iqmt.cache_epoch() > epoch

    mod_iqmt.shard_cache_clear()
    ref, cref = _run(ds2, 'day', conf, '0', monkeypatch)
    assert ref != pts1                         # the data really moved
    mgr = residency.active()
    stale0 = mgr.stats()['stale_drops']
    pts2, cnt2 = _run(ds2, 'day', conf, '1', monkeypatch)
    assert pts2 == ref and cnt2 == cref        # never the stale pin
    assert mod_di.stats_doc()['dispatches'] == 2     # folded anew
    # the old epoch's entry went where the new epoch was first met
    # (the first lookup under it), and only the new pin is left
    st = mgr.stats()
    assert st['stale_drops'] == stale0 + 1 and st['entries'] == 1
    for key in list(mgr._entries):
        assert mgr.get(key, mod_iqmt.cache_epoch()) is not None
    assert mgr.stats()['stale_drops'] == stale0 + 1


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
    monkeypatch.delenv('DN_INDEX_DEVICE', raising=False)
    assert mod_config.index_device_config() == {'mode': 'auto'}
    # the two knobs of the slot-packed fold went with it: set, they
    # are not read
    monkeypatch.setenv('DN_INDEX_DEVICE_BATCH_ROWS', '12')
    monkeypatch.setenv('DN_INDEX_RESIDENCY_SHARE', '1.5')
    assert mod_config.index_device_config() == {'mode': 'auto'}


def test_index_device_config_rejects_bad_values(monkeypatch):
    monkeypatch.setenv('DN_INDEX_DEVICE', 'yes')
    err = mod_config.index_device_config()
    assert isinstance(err, DNError)
    assert 'DN_INDEX_DEVICE' in err.message
    for mode in ('auto', '0', '1'):
        monkeypatch.setenv('DN_INDEX_DEVICE', mode)
        assert mod_config.index_device_config() == {'mode': mode}
    monkeypatch.setenv('DN_INDEX_DEVICE', '')
    assert mod_config.index_device_config() == {'mode': 'auto'}


def test_stats_doc_shape():
    mod_di._reset_engagement()
    doc = mod_di.stats_doc()
    assert doc['dispatches'] == 0
    assert doc['shards_per_dispatch'] == 0.0
    assert set(doc) == {'dispatches', 'shards', 'rows', 'padded_rows',
                        'h2d_bytes', 'auditions', 'last_lane',
                        'shards_per_dispatch'}
