"""Device-memory residency (serve/residency.py): the LRU pin/evict/
invalidate mechanics, the module singleton the index-query device lane
reads, the serve-start pre-warm, and the device fold's integration —
byte-identity against the recompute pinned throughout (a hit returns
the SAME bytes the first execution produced, by construction)."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from dragnet_tpu.serve import residency                  # noqa: E402
from dragnet_tpu.obs import metrics as obs_metrics       # noqa: E402


@pytest.fixture(autouse=True)
def _isolated_singleton():
    """Every test starts and ends with no residency configured (the
    manager is process-global, like the event journal)."""
    residency.deconfigure()
    yield
    residency.deconfigure()


def _arrs(nbytes=64, fill=1.0):
    dev = np.full(nbytes // 8, fill)
    return dev, dev.copy()


# -- DeviceResidency unit mechanics -----------------------------------------

def test_disabled_when_budget_zero():
    mgr = residency.DeviceResidency(0)
    assert not mgr.enabled()
    dev, host = _arrs()
    assert mgr.put('k', 1, dev, host, h2d_bytes=10) is False
    assert mgr.get('k', 1) is None
    st = mgr.stats()
    assert st['enabled'] is False
    assert st['hits'] == 0 and st['misses'] == 0


def test_pin_hit_books_saved_transfers():
    mgr = residency.DeviceResidency(1 << 20)
    dev, host = _arrs(64)
    assert mgr.put('k', 7, dev, host, h2d_bytes=1000)
    got = mgr.get('k', 7)
    assert got is host
    st = mgr.stats()
    assert st['hits'] == 1 and st['misses'] == 0
    assert st['entries'] == 1 and st['bytes'] == 64
    # a hit avoids the inputs' upload AND the accumulator's fetch
    assert st['h2d_saved_bytes'] == 1000
    assert st['d2h_saved_bytes'] == 64
    assert st['hit_rate'] == 1.0


def test_miss_then_hit_rate():
    mgr = residency.DeviceResidency(1 << 20)
    assert mgr.get('absent', 1) is None
    dev, host = _arrs()
    mgr.put('k', 1, dev, host, h2d_bytes=0)
    assert mgr.get('k', 1) is host
    assert mgr.stats()['hit_rate'] == 0.5


def test_lru_eviction_under_budget():
    mgr = residency.DeviceResidency(160)     # fits two 64B entries
    for i in range(3):
        dev, host = _arrs(64, fill=i)
        mgr.put('k%d' % i, 1, dev, host, h2d_bytes=0)
    st = mgr.stats()
    assert st['entries'] == 2 and st['evictions'] == 1
    assert mgr.get('k0', 1) is None          # the LRU victim
    assert mgr.get('k2', 1) is not None


def test_hit_refreshes_lru_order():
    mgr = residency.DeviceResidency(160)
    d0, h0 = _arrs(64, 0)
    d1, h1 = _arrs(64, 1)
    mgr.put('k0', 1, d0, h0, h2d_bytes=0)
    mgr.put('k1', 1, d1, h1, h2d_bytes=0)
    assert mgr.get('k0', 1) is h0            # k0 now most-recent
    d2, h2 = _arrs(64, 2)
    mgr.put('k2', 1, d2, h2, h2d_bytes=0)    # evicts k1, not k0
    assert mgr.get('k0', 1) is h0
    assert mgr.get('k1', 1) is None


def test_oversize_pin_is_shed():
    mgr = residency.DeviceResidency(32)
    dev, host = _arrs(64)
    assert mgr.put('big', 1, dev, host, h2d_bytes=0) is False
    st = mgr.stats()
    assert st['shed'] == 1 and st['entries'] == 0


def test_epoch_invalidation_drops_stale_pin():
    mgr = residency.DeviceResidency(1 << 20)
    dev, host = _arrs()
    mgr.put('k', 1, dev, host, h2d_bytes=0)
    assert mgr.get('k', 2) is None           # writer epoch moved on
    st = mgr.stats()
    assert st['stale_drops'] == 1 and st['entries'] == 0
    # the repin under the new epoch serves again
    mgr.put('k', 2, dev, host, h2d_bytes=0)
    assert mgr.get('k', 2) is host


def test_clear_releases_everything():
    mgr = residency.DeviceResidency(1 << 20)
    for i in range(4):
        dev, host = _arrs(64, i)
        mgr.put('k%d' % i, 1, dev, host, h2d_bytes=0)
    mgr.clear()
    st = mgr.stats()
    assert st['entries'] == 0 and st['bytes'] == 0


def test_content_key_separates_different_bytes():
    a = np.array([1, 2, 3], dtype=np.int64)
    b = np.array([1, 2, 4], dtype=np.int64)
    k1 = residency.content_key('iq', (a,), (8, 4, 3))
    k2 = residency.content_key('iq', (b,), (8, 4, 3))
    k3 = residency.content_key('iq', (a,), (8, 8, 3))
    assert k1 != k2 and k1 != k3
    assert k1 == residency.content_key('iq', (a.copy(),), (8, 4, 3))
    # dtype is part of the digest: same bytes, different meaning
    assert k1 != residency.content_key(
        'iq', (a.view(np.float64),), (8, 4, 3))


# -- the module singleton + gauges ------------------------------------------

def test_singleton_configure_active_deconfigure():
    assert residency.active() is None
    assert residency.stats() == {'enabled': False}
    mgr = residency.configure(1 << 20)
    assert residency.active() is mgr
    assert residency.stats()['enabled'] is True
    residency.deconfigure()
    assert residency.active() is None


def test_zero_budget_configure_reports_but_disables():
    residency.configure(0)
    assert residency.active() is None        # the lane's fast check
    st = residency.stats()
    assert st['enabled'] is False and st['budget_bytes'] == 0


def test_residency_gauges_flow_through_device_refresh():
    mgr = residency.configure(1 << 20)
    dev, host = _arrs(64)
    mgr.put('k', 1, dev, host, h2d_bytes=100)
    assert mgr.get('k', 1) is host
    reg = obs_metrics.Registry()
    obs_metrics.refresh_device_gauges({}, reg)
    gauges = {n: m.value for n, _lb, m in reg.snapshot()
              if m.kind == obs_metrics.GAUGE}
    assert gauges['device_residency_hit_rate'] == 1.0
    assert gauges['device_pinned_bytes'] == 64
    assert gauges['device_h2d_saved_bytes'] == 100
    assert gauges['device_d2h_saved_bytes'] == 64
    residency.deconfigure()
    reg2 = obs_metrics.Registry()
    obs_metrics.refresh_device_gauges({}, reg2)
    names = {n for n, _lb, m in reg2.snapshot()}
    assert 'device_residency_hit_rate' not in names


# -- index-query device lane integration (CPU jax backend) ------------------

def _need_jax():
    from dragnet_tpu.ops import get_jax
    if get_jax() is None:
        pytest.skip('jax unavailable')


def test_batched_sums_pins_and_serves_repeats():
    _need_jax()
    from dragnet_tpu import device_index as mod_di
    from dragnet_tpu import index_query_mt as mod_iqmt
    mod_di._reset_device_state()
    residency.configure(64 << 20)
    seg = np.array([0, 1, 1, 2, 2, 2], dtype=np.int64)
    w = np.array([1, 2, 3, 4, 5, 6], dtype=np.int64)
    first = mod_di.batched_sums(seg, w, 3)
    if first is None:
        pytest.skip('device lane unavailable on this rig')
    again = mod_di.batched_sums(seg, w, 3)
    assert np.array_equal(first, again)      # byte identity on a hit
    assert again.dtype == np.float64
    st = residency.stats()
    assert st['hits'] == 1 and st['entries'] >= 1
    assert st['h2d_saved_bytes'] > 0 and st['d2h_saved_bytes'] > 0
    # a returned hit is a private copy: mutating it must not poison
    # the pinned accumulator
    again[0] = 12345.0
    third = mod_di.batched_sums(seg, w, 3)
    assert np.array_equal(first, third)
    # an index write (epoch bump) retires the pin; recompute matches
    mod_iqmt.invalidate_index_tree('/nonexistent/tree')
    fourth = mod_di.batched_sums(seg, w, 3)
    assert np.array_equal(first, fourth)
    assert residency.stats()['stale_drops'] >= 1


def test_batched_sums_identical_with_and_without_residency():
    _need_jax()
    from dragnet_tpu import device_index as mod_di
    mod_di._reset_device_state()
    rng = np.random.RandomState(7)
    seg = rng.randint(0, 50, size=777).astype(np.int64)
    w = rng.randint(0, 1000, size=777).astype(np.int64)
    bare = mod_di.batched_sums(seg, w, 50)
    if bare is None:
        pytest.skip('device lane unavailable on this rig')
    residency.configure(64 << 20)
    pinned_miss = mod_di.batched_sums(seg, w, 50)
    pinned_hit = mod_di.batched_sums(seg, w, 50)
    assert np.array_equal(bare, pinned_miss)
    assert np.array_equal(bare, pinned_hit)
    host = np.bincount(seg, weights=w.astype(np.float64),
                       minlength=50)[:50]
    assert np.array_equal(bare, host)        # the host-parity pin


def test_prewarm_compiles_and_reports():
    _need_jax()
    from dragnet_tpu import device_index as mod_di
    mod_di._reset_device_state()
    doc = residency.prewarm(shapes=((1 << 6, 1 << 4),), deadline_s=120)
    assert doc['state'] == 'ok'
    assert doc['programs'] == 1
    assert doc['backend'] and doc['backend'] != 'unknown'
    assert doc['ms'] >= 0
    assert 'auditions' in doc and 'audition_wins' in doc
    # the compiled program is shared state: a real query of that
    # padded shape now skips its compile
    assert (1 << 6, 1 << 4) in mod_di._SUMS_CACHE


def test_prewarm_defaults_to_the_folds_ladder():
    """With no shapes given the pre-warm compiles what the index fold
    runs: every rung of its row ladder up to 2^20 at the smallest
    accumulator, the programs a query then finds compiled."""
    _need_jax()
    from dragnet_tpu import device_index as mod_di
    mod_di._reset_device_state()
    mod_di._SUMS_CACHE.clear()
    doc = residency.prewarm(deadline_s=120)
    assert doc['state'] == 'ok'
    rungs = mod_di.ladder(mod_di.ROW_PREWARM_TOP)
    assert rungs[:4] == mod_di.ladder() and rungs[4:] == [1 << 19,
                                                           1 << 20]
    assert doc['programs'] == len(rungs) == 6
    assert sorted(mod_di._SUMS_CACHE) == [
        (rows, mod_di.SEGMENT_FLOOR) for rows in rungs]
    # a year of a 400-tuple metric's daily shards takes the fourth,
    # a quarter of its hourly shards (read from rollups) the last
    assert (mod_di.pad_rows(365 * 397), mod_di.pad_segments(400)) \
        == (rungs[3], mod_di.SEGMENT_FLOOR)
    assert (mod_di.pad_rows(2160 * 311), mod_di.pad_segments(435)) \
        == (rungs[5], mod_di.SEGMENT_FLOOR)
