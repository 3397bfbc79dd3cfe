"""Audition-verdict persistence across processes (dn_auditions.json):
a warm cache routes auto mode to the device lane on the first eligible
batch WITHOUT re-auditioning; a backend-identity or TTL mismatch
re-auditions instead of trusting a verdict measured on a different
chip (or a different era of this one).  Results stay byte-identical to
the host engine in every case — the cache only ever skips measurement,
never changes routing correctness."""

import json
import os
import sys
import threading
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from dragnet_tpu import query as mod_query            # noqa: E402
from dragnet_tpu import device_scan                   # noqa: E402
from dragnet_tpu.datasource_file import DatasourceFile  # noqa: E402

QUERY = {
    'breakdowns': [
        {'name': 'host'},
        {'name': 'req.method'},
        {'name': 'latency', 'aggr': 'quantize'},
    ],
    'filter': {'ne': ['res.statusCode', 599]},
}

NRECORDS = 40000
SMALL_BATCH = 512


def _gen_file(tmp_path):
    import importlib.machinery
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), 'tools', 'mktestdata')
    spec = importlib.util.spec_from_file_location(
        'mktestdata', path,
        loader=importlib.machinery.SourceFileLoader('mktestdata', path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mindate_ms = int(mod.MINDATE.timestamp() * 1000)
    maxdate_ms = int(mod.MAXDATE.timestamp() * 1000)
    p = tmp_path / 'persist.log'
    with open(p, 'w') as f:
        for i in range(NRECORDS):
            f.write(json.dumps(
                mod.make_record(i, NRECORDS, mindate_ms, maxdate_ms),
                separators=(',', ':')) + '\n')
    return str(p)


def _make_ds(datafile):
    return DatasourceFile({
        'ds_backend': 'file',
        'ds_backend_config': {'path': datafile},
        'ds_filter': None,
        'ds_format': 'json',
    })


def _scan(datafile, cls_override, monkeypatch, prewarm=True):
    from dragnet_tpu import native as mod_native
    if mod_native.get_lib() is None:
        pytest.skip('native parser unavailable')
    monkeypatch.setenv('DN_SCAN_THREADS', '2')
    monkeypatch.setenv('DN_READ_SIZE', '65536')
    monkeypatch.delenv('DN_ENGINE', raising=False)
    import dragnet_tpu.engine as eng
    monkeypatch.setattr(device_scan, 'BATCH_SIZE', SMALL_BATCH)
    monkeypatch.setattr(eng, 'BATCH_SIZE', SMALL_BATCH)
    instances = []

    class Recorder(cls_override):
        def __init__(self, *args, **kwargs):
            cls_override.__init__(self, *args, **kwargs)
            instances.append(self)

    if prewarm:
        # pre-warm backend + programs so decisions resolve inside
        # this short stream (same idiom as test_auto_mode).  Tests
        # that seed the audition cache and then rewrite it must pass
        # prewarm=False: a lingering Recorder monkeypatch from the
        # seeding scan would make this warm-up an AUTO scan that
        # re-records a fresh verdict over the rewritten file.
        from dragnet_tpu import ops
        ops.backend_ready()
        monkeypatch.setenv('DN_ENGINE', 'jax')
        _make_ds(datafile).scan(mod_query.query_load(QUERY))
        monkeypatch.delenv('DN_ENGINE', raising=False)

    monkeypatch.setattr(DatasourceFile, '_vector_scan_cls',
                        lambda self: Recorder)
    result = _make_ds(datafile).scan(mod_query.query_load(QUERY))
    return result, instances


@pytest.fixture(scope='module')
def datafile(tmp_path_factory):
    return _gen_file(tmp_path_factory.mktemp('persist'))


@pytest.fixture(scope='module')
def expected(datafile):
    os.environ['DN_ENGINE'] = 'host'
    try:
        pts = _make_ds(datafile).scan(
            mod_query.query_load(QUERY)).points
    finally:
        os.environ.pop('DN_ENGINE', None)
    return pts


@pytest.fixture
def cachedir(tmp_path, monkeypatch):
    """An isolated audition cache per test."""
    monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR', str(tmp_path))
    monkeypatch.delenv('DN_AUDITION_CACHE', raising=False)
    monkeypatch.delenv('DN_AUDITION_TTL_S', raising=False)
    return str(tmp_path)


class _Winner(device_scan.AutoDeviceScan):
    ESCALATE_RECORDS = 1024
    REQUIRE_ACCELERATOR = False     # CPU test backend
    MIN_REMAINING_SECONDS = 0.0
    UNKNOWN_SIZE_RECORDS = 0
    SHADOW_MARGIN = 0.0             # audition always passes


class _Unwinnable(_Winner):
    SHADOW_MARGIN = 1e9             # a live audition can never pass


def _cache_path(cachedir):
    return os.path.join(cachedir, 'dn_auditions.json')


def _seed_verdict_from_win(datafile, expected, monkeypatch, cachedir):
    """Scan with a winnable audition until the verdict lands on disk —
    the 'previous process' half of the persistence contract."""
    for attempt in range(4):
        result, instances = _scan(datafile, _Winner, monkeypatch)
        assert result.points == expected
        if os.path.exists(_cache_path(cachedir)):
            with open(_cache_path(cachedir)) as f:
                data = json.load(f)
            won = {k: v for k, v in data.items() if v.get('won')}
            if won:
                return data
    pytest.skip('audition never concluded on this rig '
                '(short stream raced the probe thread)')


def test_warm_cache_reaches_device_without_reaudition(
        datafile, expected, monkeypatch, cachedir):
    """A fresh scan (new instance, as a new process would build) with
    an UNWINNABLE live audition still takes the device lane, because
    the persisted verdict answers instead — proving the warm path
    never re-auditions.  Output stays byte-identical."""
    _seed_verdict_from_win(datafile, expected, monkeypatch, cachedir)
    s = None
    for attempt in range(4):
        result, instances = _scan(datafile, _Unwinnable, monkeypatch,
                              prewarm=False)
        assert result.points == expected
        s = instances[0]
        # the cached verdict skips the shadow probe entirely; had a
        # live audition run, SHADOW_MARGIN=1e9 would have disqualified
        # the device — escalation implies the cache answered
        if s._escalated:
            break
    assert s._escalated, 'warm cache never routed the device lane'
    assert s._shadow is None     # the verdict pre-empted the probe


def test_backend_identity_mismatch_reauditions(
        datafile, expected, monkeypatch, cachedir):
    """A verdict measured against a DIFFERENT backend identity must
    not route this one: the scan auditions live (and, unwinnable,
    stays on host)."""
    data = _seed_verdict_from_win(datafile, expected, monkeypatch,
                                  cachedir)
    # rewrite every verdict under a foreign backend identity
    foreign = {}
    for k, v in data.items():
        shape, _backend = k.rsplit('@', 1)
        foreign[shape + '@bogus/alien-chip'] = dict(v, won=True)
    with open(_cache_path(cachedir), 'w') as f:
        json.dump(foreign, f)
    result, instances = _scan(datafile, _Unwinnable, monkeypatch,
                              prewarm=False)
    assert result.points == expected
    s = instances[0]
    # the cached-skip path is escalation WITHOUT a shadow probe; a
    # foreign-backend verdict must never take it — any engagement
    # here must have come from a fresh live audition
    assert not (s._escalated and s._shadow is None), \
        'foreign-backend verdict routed this rig without re-audition'


def test_expired_verdict_reauditions(datafile, expected, monkeypatch,
                                     cachedir):
    """A verdict older than DN_AUDITION_TTL_S reads as absent: the
    scan auditions live instead of trusting a stale measurement."""
    data = _seed_verdict_from_win(datafile, expected, monkeypatch,
                                  cachedir)
    aged = {k: dict(v, ts=time.time() - 7 * 86400)
            for k, v in data.items()}
    with open(_cache_path(cachedir), 'w') as f:
        json.dump(aged, f)
    # the TTL knob is the only thing aging the verdict out: widen it
    # and the same entry reads back as a win (checked before the scan,
    # which will overwrite the file with its own live verdict)
    for k in aged:
        assert device_scan.audition_cache_get(k) is None
        monkeypatch.setenv('DN_AUDITION_TTL_S', str(30 * 86400))
        assert device_scan.audition_cache_get(k) is True
        monkeypatch.delenv('DN_AUDITION_TTL_S')
        break
    result, instances = _scan(datafile, _Unwinnable, monkeypatch,
                              prewarm=False)
    assert result.points == expected
    s = instances[0]
    # as in the backend-mismatch case: the stale verdict must not
    # take the cached-skip path (escalation with no live audition)
    assert not (s._escalated and s._shadow is None), \
        'expired verdict routed this rig without re-audition'


def test_cached_loss_stays_on_host(datafile, expected, monkeypatch,
                                   cachedir):
    """The symmetric verdict: a persisted LOSS pins the scan to the
    host lane without re-auditioning (no shadow probe at all)."""
    data = _seed_verdict_from_win(datafile, expected, monkeypatch,
                                  cachedir)
    lost = {k: dict(v, won=False) for k, v in data.items()}
    with open(_cache_path(cachedir), 'w') as f:
        json.dump(lost, f)
    result, instances = _scan(datafile, _Winner, monkeypatch,
                              prewarm=False)
    assert result.points == expected
    s = instances[0]
    assert not s._escalated
    if s._disabled:                  # the cached loss resolved
        assert s._shadow is None     # ...without a live audition


# -- the flock sidecar (concurrent writers keep every verdict) --------------

def test_concurrent_puts_lose_no_verdicts(cachedir):
    """audition_cache_put's read-modify-write runs under a `.lock`
    sidecar flock: N racing writers (a serve pre-warm and a build,
    say) must all land — the lost-update failure this PR closes."""
    nwriters = 8
    barrier = threading.Barrier(nwriters)

    def put(i):
        barrier.wait()
        device_scan.audition_cache_put('shape-%d@cpu/test' % i, True,
                                       device_rate=1.0, host_rate=0.5)

    threads = [threading.Thread(target=put, args=(i,))
               for i in range(nwriters)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    with open(_cache_path(cachedir)) as f:
        data = json.load(f)
    assert len(data) == nwriters
    path, entries, wins = device_scan.audition_cache_entries()
    assert path == _cache_path(cachedir)
    assert entries == nwriters and wins == nwriters


def test_shape_hint_reads_persisted_wins(cachedir):
    device_scan.audition_cache_put('myshape@cpu/test', True)
    assert device_scan.audition_cache_shape_hint('myshape') is True
    device_scan.audition_cache_put('othershape@cpu/test', False)
    assert device_scan.audition_cache_shape_hint('othershape') is False
    assert device_scan.audition_cache_shape_hint('never') is None
