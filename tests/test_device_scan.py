"""DeviceScan (full-pipeline-on-device) vs the host engine.

Differentials run the datasource scan with DN_ENGINE=jax (which routes
to DeviceScan; jit executes on the XLA:CPU test backend) against the
host engine and the per-record reference path, over inputs that force
batch-level fallbacks (arrays in filter fields, non-integral values),
window growth across batches (time ordinals), dictionary growth, and
mid-stream escalation — asserting identical points (including emission
order) and identical pipeline counters."""

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

pytestmark = pytest.mark.skipif(
    mod_native.get_lib() is None or get_jax() is None or
    not backend_ready(),
    reason='native parser or jax unavailable')


def _mklines(rng, n):
    hosts = ['a', 'b', 'c', 'host-%d', None, True, 17]
    methods = ['GET', 'PUT', 'DELETE', None]
    lines = []
    import json
    for i in range(n):
        rec = {}
        h = rng.choice(hosts)
        if h == 'host-%d':
            h = 'host-%d' % rng.randrange(40)
        if rng.random() < 0.95:
            rec['host'] = h
        if rng.random() < 0.9:
            rec['req'] = {'method': rng.choice(methods)}
        if rng.random() < 0.95:
            rec['latency'] = rng.choice(
                [0, 1, 3, 17, 200, 4096, 123456, -2, '26', 'x', None])
        if rng.random() < 0.95:
            rec['code'] = rng.choice([200, 204, 404, 500, '500'])
        if rng.random() < 0.95:
            day = 1 + (i * 3 // n)
            rec['time'] = '2014-05-%02dT%02d:%02d:%02dZ' % (
                day, rng.randrange(24), rng.randrange(60),
                rng.randrange(60))
        elif rng.random() < 0.5:
            rec['time'] = 'invalid'
        lines.append(json.dumps(rec))
    return lines


EDGE_LINES = [
    # array value in a filter/key field -> batch fallback
    '{"host":[1,"two"],"latency":3,"code":200,'
    '"time":"2014-05-01T01:00:00Z"}',
    # non-integral latency -> batch fallback for quantize queries
    '{"host":"a","latency":2.5,"code":200,'
    '"time":"2014-05-01T02:00:00Z"}',
    # out-of-i32 number in a field
    '{"host":"a","latency":3,"code":123456789012345,'
    '"time":"2014-05-01T03:00:00Z"}',
    '{"host":{"x":1},"latency":4,"code":204,'
    '"time":"2014-05-01T04:00:00Z"}',
    'not json',
    '{"latency":9}',
]


QUERIES = [
    {},
    {'breakdowns': [{'name': 'host'}]},
    {'breakdowns': [{'name': 'req.method'}, {'name': 'host'}]},
    {'breakdowns': [{'name': 'latency', 'aggr': 'quantize'}]},
    {'breakdowns': [{'name': 'host'},
                    {'name': 'latency', 'aggr': 'lquantize',
                     'step': 100}]},
    {'breakdowns': [{'name': 'code'}],
     'filter': {'eq': ['req.method', 'GET']}},
    {'breakdowns': [{'name': 'host'}],
     'filter': {'or': [{'eq': ['code', '200']},
                       {'and': [{'gt': ['latency', 100]},
                                {'ne': ['host', 'a']}]}]}},
    {'breakdowns': [{'name': 'code'}],
     'filter': {'le': ['latency', 17]}},
    {'breakdowns': [{'name': 'ts', 'field': 'time', 'date': '',
                     'aggr': 'lquantize', 'step': 3600},
                    {'name': 'req.method'}]},
    {'timeAfter': '2014-05-01T06:00:00Z',
     'timeBefore': '2014-05-02T12:00:00Z',
     'breakdowns': [{'name': 'host'}]},
]


from helpers.one_batch import one_batch_parser             # noqa: E402
from helpers.scan_differential import scan_points_counters  # noqa: E402


def _scan(monkeypatch, datafile, qconf, engine, batch=None):
    monkeypatch.setenv('DN_PARSE_THREADS', '1')
    return scan_points_counters(
        monkeypatch, datafile, qconf, engine, batch=batch,
        time_field='time', ds_filter={'ne': ['host', 'zzz']})


@pytest.mark.parametrize('qi', range(len(QUERIES)))
def test_device_differential(tmp_path, monkeypatch, qi):
    rng = random.Random(99 + qi)
    lines = _mklines(rng, 700)
    # interleave edge lines so some batches fall back mid-stream
    for i, el in enumerate(EDGE_LINES):
        lines.insert((i + 1) * 90, el)
    datafile = str(tmp_path / 'data.log')
    with open(datafile, 'w') as f:
        f.write('\n'.join(lines) + '\n')
    qconf = QUERIES[qi]
    host_points, host_counters = _scan(monkeypatch, datafile, qconf,
                                       engine='auto')
    dev_points, dev_counters = _scan(monkeypatch, datafile, qconf,
                                     engine='jax', batch=128)
    assert host_points == dev_points, qconf
    assert host_counters == dev_counters, qconf


@pytest.mark.parametrize('qi', range(len(QUERIES)))
def test_device_differential_clean(tmp_path, monkeypatch, qi):
    """Clean input: every batch must actually take the device path (no
    vacuous pass via fallback), results byte-identical to host."""
    from dragnet_tpu import device_scan as mod_ds
    ran = []
    orig = mod_ds.DeviceScanStack._process_device

    def spy(self, provider, weights, alive):
        rv = orig(self, provider, weights, alive)
        ran.append(rv)
        return rv
    rng = random.Random(7 + qi)
    lines = [ln for ln in _mklines(rng, 500)
             if '"x"' not in ln and '"26"' not in ln]
    datafile = str(tmp_path / 'data.log')
    with open(datafile, 'w') as f:
        f.write('\n'.join(lines) + '\n')
    qconf = QUERIES[qi]
    host_points, host_counters = _scan(monkeypatch, datafile, qconf,
                                       engine='auto')
    monkeypatch.setattr(mod_ds.DeviceScanStack, '_process_device', spy)
    dev_points, dev_counters = _scan(monkeypatch, datafile, qconf,
                                     engine='jax', batch=128)
    assert host_points == dev_points, qconf
    assert host_counters == dev_counters, qconf
    assert ran and all(ran), 'device path never ran'


def test_device_batches_actually_ran(tmp_path, monkeypatch):
    """The differential is vacuous if every batch fell back — assert the
    device path processed batches."""
    from dragnet_tpu import device_scan as mod_ds
    ran = []
    orig = mod_ds.DeviceScanStack._process_device

    def spy(self, provider, weights, alive):
        rv = orig(self, provider, weights, alive)
        ran.append(rv)
        return rv
    monkeypatch.setattr(mod_ds.DeviceScanStack, '_process_device', spy)
    rng = random.Random(5)
    datafile = str(tmp_path / 'data.log')
    with open(datafile, 'w') as f:
        f.write('\n'.join(_mklines(rng, 400)) + '\n')
    _scan(monkeypatch, datafile, QUERIES[4], engine='jax', batch=64)
    assert any(ran)


def test_escalation_preserves_order(tmp_path, monkeypatch):
    """auto-style escalation: host batches first, device after, same
    emission order as all-host."""
    from dragnet_tpu import device_scan as mod_ds
    rng = random.Random(11)
    datafile = str(tmp_path / 'data.log')
    with open(datafile, 'w') as f:
        f.write('\n'.join(_mklines(rng, 600)) + '\n')
    host_points, _ = _scan(monkeypatch, datafile, QUERIES[2],
                           engine='auto')
    monkeypatch.setattr(mod_ds.DeviceScan, 'ESCALATE_RECORDS', 256)
    dev_points, _ = _scan(monkeypatch, datafile, QUERIES[2],
                          engine='jax', batch=128)
    assert host_points == dev_points


def test_numeric_leaf_plan_matches_outcome():
    """The device's integer compare plans must agree with Leaf.outcome
    (the JS semantics reference) for every int32 value."""
    from dragnet_tpu.device_scan import (
        numeric_leaf_plan, NUM_FALSE, NUM_TRUE, NUM_EQ, NUM_NE,
        NUM_LE, NUM_GE, I32MIN, I32MAX)
    from dragnet_tpu.engine import Leaf
    from dragnet_tpu.ops.kernels import TRUE, FALSE

    consts = [0, 1, -1, 5, 2.5, -2.5, 100.0, '26', '26.9', 'x', '',
              True, False, 2 ** 31, -(2 ** 31) - 1, 2 ** 53 + 1,
              1e300, -1e300, '0x1A', ' 7 ', 'Infinity']
    probes = [I32MIN, I32MIN + 1, -101, -3, -2, -1, 0, 1, 2, 3, 5, 6,
              25, 26, 27, 99, 100, 101, I32MAX - 1, I32MAX]
    for const in consts:
        for op in ('eq', 'ne', 'lt', 'le', 'gt', 'ge'):
            plan = numeric_leaf_plan(op, const)
            assert plan is not None, (op, const)
            mode, t = plan
            leaf = Leaf('f', op, const)
            for v in probes:
                expect = leaf.outcome(float(v))
                if mode == NUM_FALSE:
                    got = FALSE
                elif mode == NUM_TRUE:
                    got = TRUE
                elif mode == NUM_EQ:
                    got = TRUE if v == t else FALSE
                elif mode == NUM_NE:
                    got = TRUE if v != t else FALSE
                elif mode == NUM_LE:
                    got = TRUE if v <= t else FALSE
                else:
                    got = TRUE if v >= t else FALSE
                assert got == expect, (op, const, v, plan)


def test_device_pallas_program(tmp_path, monkeypatch):
    """The one-hot MXU variant of the device program (interpret mode on
    the CPU test backend) produces identical results."""
    monkeypatch.setenv('DN_PALLAS', 'force')
    rng = random.Random(21)
    lines = [ln for ln in _mklines(rng, 300)
             if '"x"' not in ln and '"26"' not in ln]
    datafile = str(tmp_path / 'data.log')
    with open(datafile, 'w') as f:
        f.write('\n'.join(lines) + '\n')
    qconf = QUERIES[4]
    host_points, _ = _scan(monkeypatch, datafile, qconf, engine='auto')
    dev_points, _ = _scan(monkeypatch, datafile, qconf, engine='jax',
                          batch=128)
    assert host_points == dev_points


def test_large_dictionary_i16_gather(monkeypatch, tmp_path):
    """Narrowed (i16) string codes indexing a leaf table padded past
    32767 entries must not overflow JAX's gather index normalization
    (regression: OverflowError at trace time with 16385-32768-entry
    dictionaries)."""
    import json
    from dragnet_tpu import native as mod_native
    if mod_native.get_lib() is None:
        pytest.skip('native parser unavailable')
    p = tmp_path / 'big_dict.log'
    nrec = 20000
    with open(p, 'w') as f:
        for i in range(nrec):
            f.write(json.dumps({'k': 'v%05d' % i,
                                'g': 'a' if i % 2 else 'b'}) + '\n')

    def scan(engine, qconf):
        monkeypatch.setenv('DN_ENGINE', engine)
        ds = DatasourceFile({
            'ds_backend': 'file',
            'ds_backend_config': {'path': str(p)},
            'ds_filter': None, 'ds_format': 'json',
        })
        return ds.scan(mod_query.query_load(dict(qconf))).points

    # filter leaf-table gather at >16384 dictionary entries
    q1 = {'breakdowns': [{'name': 'g'}],
          'filter': {'ne': ['k', 'v00042']}}
    host = scan('host', q1)
    dev = scan('jax', q1)
    assert dev == host
    assert sum(v for _, v in dev) == nrec - 1

    # translate-table gather: breakdown BY the 20k-entry field
    q2 = {'breakdowns': [{'name': 'k'}],
          'filter': {'eq': ['g', 'a']}}
    host2 = scan('host', q2)
    dev2 = scan('jax', q2)
    assert dev2 == host2


@pytest.mark.parametrize('k0', [1 << 16, 4])
def test_compact_flush_differential(tmp_path, monkeypatch, k0):
    """Device-side flush compaction (argsort + gather of occurred
    segments, fetching O(occurred) instead of O(ns)): forced to engage
    via a tiny threshold, results and counters must still equal the
    host engine exactly.  k0=4 forces the over-capacity refetch loop
    (more occurred tuples than the speculative fetch width)."""
    from dragnet_tpu import device_scan as mod_ds
    monkeypatch.setattr(mod_ds.DeviceScan, 'COMPACT_MIN_SEGMENTS', 1)
    monkeypatch.setattr(mod_ds.DeviceScan, 'COMPACT_K', k0)

    rng = random.Random(41)
    lines = _mklines(rng, 600)
    datafile = str(tmp_path / 'data.log')
    with open(datafile, 'w') as f:
        f.write('\n'.join(lines) + '\n')
    qconf = {'breakdowns': [{'name': 'host'},
                            {'name': 'latency', 'aggr': 'quantize'}]}
    host_points, host_counters = _scan(monkeypatch, datafile, qconf,
                                       engine='auto')

    compacted = []
    orig = mod_ds._compact_program

    def spy(acc_len, k):
        # covers the sync flush (_compact_fetch) AND the async
        # prefetch (_prefetch_flush) — either counts as engagement
        compacted.append((acc_len, k))
        return orig(acc_len, k)
    monkeypatch.setattr(mod_ds, '_compact_program', spy)
    dev_points, dev_counters = _scan(monkeypatch, datafile, qconf,
                                     engine='jax', batch=128)
    assert host_points == dev_points
    assert host_counters == dev_counters
    assert compacted, 'compact fetch never engaged'


@pytest.mark.parametrize('cap0', [1 << 18, 64])
def test_sparse_device_differential(tmp_path, monkeypatch, cap0):
    """High-cardinality device path (fused i64 keys sort-merged into a
    device-resident compacted set): with the dense budget forced tiny,
    forced-device scans must take the sparse program and match the
    host engine exactly — points, emission order, counters.  cap0=64
    forces the pressure guard's flush+grow cycles mid-stream (several
    epochs merged through the deferred columnar path)."""
    from dragnet_tpu import engine as mod_engine
    from dragnet_tpu import device_scan as mod_ds
    monkeypatch.setattr(mod_engine, 'MAX_DENSE_SEGMENTS', 64)
    monkeypatch.setattr(mod_ds, 'MAX_DENSE_SEGMENTS', 64)
    monkeypatch.setattr(mod_ds, 'SPARSE_CAP0', cap0)
    monkeypatch.setattr(mod_ds, 'SPARSE_CAP_MAX', max(cap0, 1024))

    rng = random.Random(77)
    lines = _mklines(rng, 900)
    datafile = str(tmp_path / 'data.log')
    with open(datafile, 'w') as f:
        f.write('\n'.join(lines) + '\n')
    qconf = {'breakdowns': [{'name': 'host'}, {'name': 'latency'}]}

    host_points, host_counters = _scan(monkeypatch, datafile, qconf,
                                       engine='vector')
    dev_points, dev_counters = _scan(monkeypatch, datafile, qconf,
                                     engine='jax', batch=128)
    assert host_points == dev_points
    assert host_counters == dev_counters
    assert len(dev_points) > 64


def test_sparse_device_engages(tmp_path, monkeypatch):
    """The sparse program must actually process batches (not fall back
    to the host sparse merge) — asserted via ndevicebatches."""
    from dragnet_tpu import engine as mod_engine
    from dragnet_tpu import device_scan as mod_ds
    from dragnet_tpu.datasource_file import DatasourceFile
    monkeypatch.setattr(mod_engine, 'MAX_DENSE_SEGMENTS', 64)
    monkeypatch.setattr(mod_ds, 'MAX_DENSE_SEGMENTS', 64)
    monkeypatch.setenv('DN_ENGINE', 'jax')
    monkeypatch.setenv('DN_SCAN_THREADS', '0')
    monkeypatch.setenv('DN_PARSE_THREADS', '1')

    rng = random.Random(78)
    lines = [ln for ln in _mklines(rng, 600)
             if '[1,"two"]' not in ln and '{"x":1}' not in ln]
    datafile = str(tmp_path / 'data.log')
    with open(datafile, 'w') as f:
        f.write('\n'.join(lines) + '\n')

    ds = DatasourceFile({
        'ds_backend': 'file',
        'ds_backend_config': {'path': datafile},
        'ds_filter': None, 'ds_format': 'json',
    })
    q = mod_query.query_load(
        {'breakdowns': [{'name': 'host'}, {'name': 'latency'}]})
    from dragnet_tpu.obs import metrics as obs_metrics
    folded = obs_metrics.global_registry().counter(
        'device_sparse_fold_batches')
    before = folded.value
    r = ds.scan(q)
    ndev = sum(s.counters.get('ndevicebatches', 0)
               for s in r.pipeline.stages)
    assert ndev > 0, 'sparse device path never ran'
    assert folded.value == before + ndev
    # a dense scan runs device batches and folds none of them sparsely
    monkeypatch.setattr(mod_engine, 'MAX_DENSE_SEGMENTS', 1 << 24)
    monkeypatch.setattr(mod_ds, 'MAX_DENSE_SEGMENTS', 1 << 24)
    r = ds.scan(q)
    assert sum(s.counters.get('ndevicebatches', 0)
               for s in r.pipeline.stages) > 0
    assert folded.value == before + ndev


def test_prefetch_flush_differential(tmp_path, monkeypatch):
    """The one-time async flush prefetch (issued mid-stream, drained at
    finish) must be invisible: identical points, order, and counters
    to the host engine, with host-fallback batches interleaved after
    the prefetch point."""
    from dragnet_tpu import device_scan as mod_ds
    monkeypatch.setattr(mod_ds.DeviceScan, 'PREFETCH_PROGRESS', 0.01)
    monkeypatch.setattr(mod_ds.DeviceScan, 'COMPACT_MIN_SEGMENTS', 1)

    fired = []
    orig = mod_ds.DeviceScan._prefetch_flush

    def spy(self):
        fired.append(self._acc is not None)
        return orig(self)
    monkeypatch.setattr(mod_ds.DeviceScan, '_prefetch_flush', spy)

    rng = random.Random(55)
    lines = _mklines(rng, 900)
    # edge lines in the tail: host-fallback batches AFTER the prefetch
    for i, el in enumerate(EDGE_LINES):
        lines.insert(600 + i * 40, el)
    datafile = str(tmp_path / 'data.log')
    with open(datafile, 'w') as f:
        f.write('\n'.join(lines) + '\n')
    qconf = {'breakdowns': [{'name': 'host'},
                            {'name': 'latency', 'aggr': 'quantize'}]}

    host_points, host_counters = _scan(monkeypatch, datafile, qconf,
                                       engine='vector')
    # small reads -> many progress+flush cycles, so the prefetch
    # trigger sees a live accumulator mid-stream
    dev_points, dev_counters = scan_points_counters(
        monkeypatch, datafile, qconf, 'jax', batch=128,
        read_size=8192, time_field='time',
        ds_filter={'ne': ['host', 'zzz']})
    assert fired and any(fired), 'prefetch never fired'
    assert host_points == dev_points
    assert host_counters == dev_counters


def test_prefetch_flush_sparse_differential(tmp_path, monkeypatch):
    """Prefetch over the SPARSE accumulator (ub-sized fetch width,
    narrow-column decode) drained at finish."""
    from dragnet_tpu import engine as mod_engine
    from dragnet_tpu import device_scan as mod_ds
    monkeypatch.setattr(mod_engine, 'MAX_DENSE_SEGMENTS', 64)
    monkeypatch.setattr(mod_ds, 'MAX_DENSE_SEGMENTS', 64)
    monkeypatch.setattr(mod_ds.DeviceScan, 'PREFETCH_PROGRESS', 0.01)

    drained = []
    orig = mod_ds.DeviceScan._drain_pending

    def spy(self):
        drained.append(len(self._pending_flush))
        return orig(self)
    monkeypatch.setattr(mod_ds.DeviceScan, '_drain_pending', spy)

    rng = random.Random(56)
    lines = _mklines(rng, 900)
    datafile = str(tmp_path / 'data.log')
    with open(datafile, 'w') as f:
        f.write('\n'.join(lines) + '\n')
    qconf = {'breakdowns': [{'name': 'host'}, {'name': 'latency'}]}

    host_points, host_counters = _scan(monkeypatch, datafile, qconf,
                                       engine='vector')
    dev_points, dev_counters = scan_points_counters(
        monkeypatch, datafile, qconf, 'jax', batch=128,
        read_size=8192, time_field='time',
        ds_filter={'ne': ['host', 'zzz']})
    assert any(n > 0 for n in drained), 'no prefetched epoch drained'
    assert host_points == dev_points
    assert host_counters == dev_counters


def test_sparse_cap_overflow_falls_back(tmp_path, monkeypatch):
    """A single bucketized column whose ordinal span exceeds 2^31
    cannot use the device (per-record codes are computed in i32): the
    scan must fall back to the host engine with identical results
    rather than wrapping key codes."""
    import json as _json
    from dragnet_tpu import engine as mod_engine
    from dragnet_tpu import device_scan as mod_ds
    monkeypatch.setattr(mod_engine, 'MAX_DENSE_SEGMENTS', 32)
    monkeypatch.setattr(mod_ds, 'MAX_DENSE_SEGMENTS', 32)

    rng = random.Random(91)
    datafile = str(tmp_path / 'data.log')
    with open(datafile, 'w') as f:
        for i in range(300):
            # exact-i32 values spanning ~4.2e9 -> lquantize(step=1)
            # ordinal span > 2^31
            f.write(_json.dumps({
                'v': rng.choice([-2100000000, -5, 0, 7,
                                 2100000000]) + i,
                'host': 'h%d' % (i % 7),
            }) + '\n')
    qconf = {'breakdowns': [{'name': 'v', 'aggr': 'lquantize',
                             'step': 1}]}
    host_points, host_counters = _scan(monkeypatch, datafile, qconf,
                                       engine='vector')
    dev_points, dev_counters = _scan(monkeypatch, datafile, qconf,
                                     engine='jax', batch=64)
    assert host_points == dev_points
    assert host_counters == dev_counters


# -- the sparse fold program alone ------------------------------------------

I64MAX = (1 << 63) - 1


def _np_sparse_fold(acc, cvec_b, kb, wb, fb):
    """The fold's contract as a plain numpy sort-merge, nothing of the
    program: per live key the exact i64 weight sum and the smallest
    first occurrence, keys ascending, the first `cap` kept."""
    import numpy as np
    keys0, wsum0, first0, cvec0, stats0 = acc
    cap = len(keys0)
    k = np.concatenate([keys0, kb])
    w = np.concatenate([wsum0, wb])
    f = np.concatenate([first0, fb])
    live = k != I64MAX
    uniq, inv = np.unique(k[live], return_inverse=True)
    sums = np.zeros(len(uniq), dtype=np.int64)
    np.add.at(sums, inv, w[live])
    firsts = np.full(len(uniq), I64MAX, dtype=np.int64)
    np.minimum.at(firsts, inv, f[live])
    keys1 = np.full(cap, I64MAX, dtype=np.int64)
    wsum1 = np.zeros(cap, dtype=np.int64)
    first1 = np.full(cap, I64MAX, dtype=np.int64)
    m = min(cap, len(uniq))
    keys1[:m], wsum1[:m], first1[:m] = uniq[:m], sums[:m], firsts[:m]
    over = max(int(stats0[1]), int(len(uniq) > cap))
    return (keys1, wsum1, first1, cvec0 + cvec_b.astype(np.int64),
            np.array([len(uniq), over], dtype=np.int64))


def _sparse_batches(case, rng, cap, bn):
    """At least four batches of (keys, weights, row index) for one
    case; a dead row has key I64MAX and weight 0."""
    import numpy as np

    def batch(keys, weights=None, dead=0.1, rows=None):
        keys = np.asarray(keys, dtype=np.int64).copy()
        w = np.ones(bn, dtype=np.int64) if weights is None \
            else np.asarray(weights, dtype=np.int64).copy()
        kill = rng.random(bn) < dead
        keys[kill] = I64MAX
        w[kill] = 0
        rows = np.arange(bn) if rows is None else rows
        return keys, w, rows

    def draw(hi):
        return rng.integers(0, hi, bn)

    if case == 'unit-weights':
        return [batch(draw(300)) for _ in range(5)]
    if case == 'signed-weights':
        # sums that cancel to 0 stay in the set with weight 0
        return [batch(draw(200), rng.integers(-(1 << 40), 1 << 40, bn))
                for _ in range(5)]
    if case == 'dead-batch':
        return [batch(draw(300)), batch(draw(300), dead=1.1),
                batch(draw(300)), batch(draw(300), dead=1.1),
                batch(draw(300))]
    if case == 'all-resident':
        first = batch(draw(1 << 40), dead=0.0)
        return [first] + [batch(rng.choice(first[0], bn))
                          for _ in range(4)]
    if case == 'past-capacity':
        # 3 x bn distinct keys into cap = 2 x bn; then batches whose
        # keys are all resident, so only the sticky flag says `over`
        wide = [batch(rng.permutation(1 << 20)[:bn] + (i << 20), dead=0.0)
                for i in range(3)]
        return wide + [batch(rng.choice(wide[0][0], bn))
                       for _ in range(2)]
    if case == 'later-batch-smaller-row':
        # the same keys every batch, met at ever smaller rows: the
        # first occurrence stays the first batch's
        keys = draw(100)
        return [batch(np.roll(keys, -i * 7), dead=0.0,
                      rows=np.arange(bn) if i == 0
                      else rng.permutation(bn) // (i + 1))
                for i in range(4)]
    raise AssertionError(case)


SPARSE_CASES = ('unit-weights', 'signed-weights', 'dead-batch',
                'all-resident', 'past-capacity',
                'later-batch-smaller-row')


@pytest.mark.parametrize('case', SPARSE_CASES)
def test_sparse_fold_program_matches_numpy_sort_merge(case):
    """kernels.sparse_fold, jitted alone, against a numpy sort-merge
    written here: several batches folded into one set, all five leaves
    compared after every batch."""
    import numpy as np
    from dragnet_tpu.ops import kernels
    jax, jnp = get_jax()
    bn = 256
    cap = 2 * bn if case == 'past-capacity' else 8 * bn
    ncnt = 3
    rng = np.random.default_rng(SPARSE_CASES.index(case))
    fold = jax.jit(lambda acc, cvec_b, kb, wb, fb: kernels.sparse_fold(
        jax, jnp, acc, cvec_b, kb, wb, fb))
    want = (np.full(cap, I64MAX, dtype=np.int64),
            np.zeros(cap, dtype=np.int64),
            np.full(cap, I64MAX, dtype=np.int64),
            np.zeros(ncnt, dtype=np.int64),
            np.zeros(2, dtype=np.int64))
    got = want
    batches = _sparse_batches(case, rng, cap, bn)
    assert len(batches) >= 4
    for i, (kb, wb, rows) in enumerate(batches):
        fb = np.where(kb != I64MAX, (np.int64(i) << 32) + rows,
                      I64MAX).astype(np.int64)
        cvec_b = rng.integers(0, bn, ncnt).astype(np.int32)
        want = _np_sparse_fold(want, cvec_b, kb, wb, fb)
        got = fold(got, cvec_b, kb, wb, fb)
        for name, g, w in zip(('keys', 'wsum', 'first', 'cvec', 'stats'),
                              got, want):
            assert np.array_equal(np.asarray(g), w), (case, i, name)
    nuniq, over = want[4]
    if case == 'past-capacity':
        assert over == 1 and nuniq == cap       # sticky: the last
        # batches add no key, and the set holds the first `cap` runs
    else:
        assert over == 0 and 0 < nuniq < cap
    if case == 'later-batch-smaller-row':
        assert (want[2][:nuniq] >> 32 == 0).all()
    if case == 'signed-weights':
        assert (want[1][:nuniq] < 0).any()


def test_sparse_program_has_no_scatter_or_long_gather(tmp_path,
                                                      monkeypatch):
    """The sparse program of a real staged batch, lowered on the CPU at
    sparse_cap 4096 and a 512-row batch: no scatter anywhere, and no
    gather over the capacity + batch axis (on the TPU both run one
    element at a time, which made the fold 221 ms a batch)."""
    import re
    import numpy as np
    from dragnet_tpu import engine as mod_engine
    from dragnet_tpu import device_scan as mod_ds
    from dragnet_tpu.vpipe import Pipeline
    jax, _ = get_jax()
    cap, bn = 1 << 12, 512
    monkeypatch.setattr(mod_engine, 'MAX_DENSE_SEGMENTS', 64)
    monkeypatch.setattr(mod_ds, 'MAX_DENSE_SEGMENTS', 64)
    monkeypatch.setattr(mod_ds, 'SPARSE_CAP0', cap)
    monkeypatch.setattr(mod_engine, 'BATCH_SIZE', bn)
    monkeypatch.setattr(mod_ds, 'BATCH_SIZE', bn)

    rng = random.Random(79)
    lines = [ln for ln in _mklines(rng, bn)
             if '[1,"two"]' not in ln and '{"x":1}' not in ln]
    datafile = str(tmp_path / 'data.log')
    with open(datafile, 'w') as f:
        f.write('\n'.join(lines) + '\n')
    scan = mod_ds.DeviceScan(mod_query.query_load(
        {'breakdowns': [{'name': 'host'}, {'name': 'latency'}]}),
        None, Pipeline())
    parser = one_batch_parser(datafile, scan, bn)
    assert scan._probe_backend()
    inputs = {}
    staged = scan._stage_device(
        mod_engine.NativeColumns(parser),
        np.ones(parser.batch_size(), dtype=np.float64), None, inputs)
    assert staged is not None and staged[0] == bn
    assert staged[1][-1] == cap             # the sparse lane, at cap
    # the one jitted program of a stack of one
    run = mod_ds.DeviceScanStack([scan])._stacked_program([staged], inputs)
    text = run.lower(inputs, (scan._acc,)).as_text()

    long_axis = 'tensor<%dx' % (cap + bn)
    assert long_axis + 'i64>' in text       # the concatenation is there
    # one sort: a second costs the cold build on the chip 20 s and more
    assert len(re.findall(r'stablehlo\.sort', text)) == 1
    assert 'scatter' not in text
    assert not [ln for ln in text.splitlines()
                if 'stablehlo.gather' in ln and long_axis in ln]
