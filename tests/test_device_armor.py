"""Device-lane hardening (dragnet_tpu/device_scan.py): the persisted
audition-verdict cache — repeat CLI scans skip the ~5-batch shadow
probe when a fresh verdict for the same (query shape, backend) exists
— and the wedge armor that keeps a hung device backend from hanging
`dn scan`/`dn query` (probe deadlines around every first device op).

The conftest pins DN_AUDITION_CACHE=0 for hermeticity; tests here opt
back in with a tmp cache directory."""

import os
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from dragnet_tpu import device_scan                    # noqa: E402
from dragnet_tpu import query as mod_query             # noqa: E402
from dragnet_tpu.vpipe import Pipeline                 # noqa: E402


def _enable_cache(monkeypatch, tmp_path):
    monkeypatch.setenv('DN_AUDITION_CACHE', '1')
    monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR', str(tmp_path / 'xla'))


# -- cache mechanics -------------------------------------------------------

def test_audition_cache_roundtrip(tmp_path, monkeypatch):
    _enable_cache(monkeypatch, tmp_path)
    assert device_scan.audition_cache_get('k') is None
    device_scan.audition_cache_put('k', True, device_rate=1e6,
                                   host_rate=5e5)
    assert device_scan.audition_cache_get('k') is True
    device_scan.audition_cache_put('k', False)
    assert device_scan.audition_cache_get('k') is False
    # unknown keys stay unknown
    assert device_scan.audition_cache_get('other') is None


def test_audition_cache_ttl(tmp_path, monkeypatch):
    _enable_cache(monkeypatch, tmp_path)
    device_scan.audition_cache_put('k', True)
    monkeypatch.setenv('DN_AUDITION_TTL_S', '0.05')
    time.sleep(0.1)
    assert device_scan.audition_cache_get('k') is None
    # expired entries are pruned on the next write
    device_scan.audition_cache_put('k2', False)
    import json
    with open(device_scan._audition_cache_file()) as f:
        data = json.load(f)
    assert 'k' not in data and 'k2' in data


def test_audition_cache_disabled(tmp_path, monkeypatch):
    monkeypatch.setenv('DN_AUDITION_CACHE', '0')
    monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR', str(tmp_path / 'xla'))
    device_scan.audition_cache_put('k', True)
    assert device_scan.audition_cache_get('k') is None
    assert not os.path.exists(str(tmp_path / 'xla'))


def test_audition_cache_corrupt_file_reads_as_empty(tmp_path,
                                                    monkeypatch):
    _enable_cache(monkeypatch, tmp_path)
    os.makedirs(str(tmp_path / 'xla'), exist_ok=True)
    path = device_scan._audition_cache_file()
    with open(path, 'w') as f:
        f.write('{torn json')
    assert device_scan.audition_cache_get('k') is None
    device_scan.audition_cache_put('k', True)    # rewrites cleanly
    assert device_scan.audition_cache_get('k') is True


# -- engage-path integration -----------------------------------------------

def _auto_scan(monkeypatch):
    """An AutoDeviceScan positioned right at the audition decision:
    backend ok, switch worth it, shadow context armed."""

    class Eager(device_scan.AutoDeviceScan):
        ESCALATE_RECORDS = 0
        REQUIRE_ACCELERATOR = False
        MIN_REMAINING_SECONDS = 0.0
        UNKNOWN_SIZE_RECORDS = 0

    q = mod_query.query_load({'breakdowns': [{'name': 'host'}]})
    s = Eager(q, None, Pipeline())
    s._backend_ok = True
    s._shadow_ctx = (lambda: [], lambda snap: None, lambda snap, n: None,
                     None)
    s._t0 = time.monotonic() - 1.0
    s._records_seen = 1000
    s._host_records = 1000
    return s


def test_cached_win_skips_audition(tmp_path, monkeypatch):
    _enable_cache(monkeypatch, tmp_path)
    s = _auto_scan(monkeypatch)
    device_scan.audition_cache_put(s._audition_key(), True,
                                   device_rate=2e6, host_rate=1e6)
    assert s._engage_device() is True
    assert s._shadow is None          # no shadow probe was started
    assert s._escalated


def test_cached_loss_stays_on_host(tmp_path, monkeypatch):
    _enable_cache(monkeypatch, tmp_path)
    s = _auto_scan(monkeypatch)
    device_scan.audition_cache_put(s._audition_key(), False)
    assert s._engage_device() is False
    assert s._disabled
    assert s._shadow is None


def test_no_cached_verdict_starts_audition(tmp_path, monkeypatch):
    _enable_cache(monkeypatch, tmp_path)
    s = _auto_scan(monkeypatch)
    assert s._engage_device() is False    # audition now in flight
    assert s._shadow is not None
    s._shadow.close()


def test_audition_keys_distinguish_queries(tmp_path, monkeypatch):
    _enable_cache(monkeypatch, tmp_path)
    s1 = _auto_scan(monkeypatch)
    q2 = mod_query.query_load({'breakdowns': [
        {'name': 'latency', 'aggr': 'quantize'}]})

    class Eager(device_scan.AutoDeviceScan):
        REQUIRE_ACCELERATOR = False
    s2 = Eager(q2, None, Pipeline())
    assert s1._audition_key() != s2._audition_key()


# -- wedge armor -----------------------------------------------------------

def test_run_with_deadline_paths():
    assert device_scan.run_with_deadline(lambda: 42, 5.0, 't') == \
        ('ok', 42)
    status, err = device_scan.run_with_deadline(
        lambda: (_ for _ in ()).throw(ValueError('x')), 5.0, 't')
    assert status == 'error' and isinstance(err, ValueError)
    status, _ = device_scan.run_with_deadline(
        lambda: time.sleep(30), 0.1, 't')
    assert status == 'timeout'


def test_probe_deadline_env(monkeypatch):
    monkeypatch.delenv('DN_DEVICE_PROBE_TIMEOUT', raising=False)
    assert device_scan.probe_deadline_s() == 420.0
    monkeypatch.setenv('DN_DEVICE_PROBE_TIMEOUT', '7.5')
    assert device_scan.probe_deadline_s() == 7.5
    monkeypatch.setenv('DN_DEVICE_PROBE_TIMEOUT', 'junk')
    assert device_scan.probe_deadline_s() == 420.0


def test_forced_probe_timeout_is_an_error(monkeypatch):
    """DN_ENGINE=jax with a backend that never answers: the
    synchronous probe times out and the scan FAILS with the reason —
    a forced device lane never finishes on the host."""
    from dragnet_tpu.errors import DNError
    q = mod_query.query_load({'breakdowns': [{'name': 'host'}]})
    s = device_scan.DeviceScan(q, None, Pipeline())
    monkeypatch.setenv('DN_ENGINE', 'jax')
    monkeypatch.setenv('DN_DEVICE_PROBE_TIMEOUT', '0.1')
    monkeypatch.setattr(s, '_probe_ok', lambda: time.sleep(30))
    with pytest.raises(DNError) as ei:
        s._probe_backend()
    assert 'DN_ENGINE=jax' in ei.value.message
    assert 'device backend unresponsive' in ei.value.message


def test_unforced_probe_timeout_falls_back(monkeypatch, capsys):
    """The same timeout without DN_ENGINE=jax (the cluster backend's
    synchronous probe in auto mode): warns, and permanently routes the
    scan to the host engine."""
    q = mod_query.query_load({'breakdowns': [{'name': 'host'}]})
    s = device_scan.DeviceScan(q, None, Pipeline())
    monkeypatch.delenv('DN_ENGINE', raising=False)
    monkeypatch.setenv('DN_DEVICE_PROBE_TIMEOUT', '0.1')
    monkeypatch.setattr(s, '_probe_ok', lambda: time.sleep(30))
    assert s._probe_backend() is False
    assert s._disabled
    assert s.probe_status == 'timeout'
    assert 'device backend unresponsive' in capsys.readouterr().err


def test_forced_probe_error_is_an_error(monkeypatch):
    from dragnet_tpu.errors import DNError
    q = mod_query.query_load({'breakdowns': [{'name': 'host'}]})
    s = device_scan.DeviceScan(q, None, Pipeline())
    monkeypatch.setenv('DN_ENGINE', 'jax')

    def boom():
        raise RuntimeError('no chip')
    monkeypatch.setattr(s, '_probe_ok', boom)
    with pytest.raises(DNError) as ei:
        s._probe_backend()
    assert 'no chip' in ei.value.message


def test_forced_scan_without_native_columns_is_an_error(monkeypatch):
    """DN_ENGINE=jax and a batch from the Python record path (no
    native library): an error, not a silent host run."""
    from dragnet_tpu.errors import DNError
    q = mod_query.query_load({'breakdowns': [{'name': 'host'}]})
    s = device_scan.DeviceScan(q, None, Pipeline())
    s._backend_ok = True

    class _Records(object):     # a batch that is no NativeColumns
        n = 1
    stack = device_scan.DeviceScanStack([s])
    monkeypatch.setenv('DN_ENGINE', 'jax')
    with pytest.raises(DNError) as ei:
        stack.try_device(_Records(), [1], None)
    assert 'native column parser' in ei.value.message
    monkeypatch.setenv('DN_ENGINE', 'auto')
    assert stack.try_device(_Records(), [1], None) is False
    assert s._records_seen == 0 and s._acc is None


def test_auto_probe_deadline_disables(monkeypatch):
    """The auto path never blocks on its background probe, but a probe
    thread that exceeds the deadline stops being waited for."""
    s = _auto_scan(monkeypatch)
    s._backend_ok = None
    monkeypatch.setenv('DN_DEVICE_PROBE_TIMEOUT', '0.05')
    monkeypatch.setattr(s, '_probe_ok', lambda: time.sleep(30))
    assert s._engage_device() is False    # probe thread started
    time.sleep(0.1)
    assert s._engage_device() is False
    assert s._disabled
