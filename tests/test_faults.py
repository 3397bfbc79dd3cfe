"""Deterministic fault injection (dragnet_tpu/faults.py): spec
validation through the shared DNError contract, replayable seeded
draws, the error/delay kinds at the wired seams, injection counters,
and the miniature chaos soak (tools/soak_faults.py --fast covers the
full-surface version; the tier-1 subset here keeps every mechanism
exercised on every run)."""

import io
import json
import os
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from dragnet_tpu import config as mod_config        # noqa: E402
from dragnet_tpu import faults as mod_faults        # noqa: E402
from dragnet_tpu import vpipe as mod_vpipe          # noqa: E402
from dragnet_tpu.errors import DNError              # noqa: E402


@pytest.fixture(autouse=True)
def _clean_registry(monkeypatch):
    monkeypatch.delenv('DN_FAULTS', raising=False)
    mod_faults.reset()
    yield
    mod_faults.reset()


# -- spec validation (config.faults_config) --------------------------------

def test_faults_config_parses_spec():
    conf = mod_config.faults_config(env={
        'DN_FAULTS': 'sink.flush:error:0.5:7,iq.shard_read:delay:1.0'})
    assert conf == {'sites': {
        'sink.flush': ('error', 0.5, 7),
        'iq.shard_read': ('delay', 1.0, 0)}}
    assert mod_config.faults_config(env={}) == {'sites': {}}


def test_faults_config_rejects_malformed():
    def err(spec):
        rv = mod_config.faults_config(env={'DN_FAULTS': spec})
        assert isinstance(rv, DNError), spec
        return str(rv)

    assert 'expected site:kind:rate' in err('sink.flush')
    assert 'unknown site "bogus.site"' in err('bogus.site:error:1.0')
    assert 'unknown kind "explode"' in err('sink.flush:explode:1.0')
    assert 'rate must be in (0, 1]' in err('sink.flush:error:0')
    assert 'rate must be in (0, 1]' in err('sink.flush:error:1.5')
    assert 'rate must be in (0, 1]' in err('sink.flush:error:x')
    assert 'seed must be an integer' in err('sink.flush:error:1.0:s')
    assert 'armed twice' in \
        err('sink.flush:error:0.5,sink.flush:delay:0.5')


def test_malformed_spec_raises_at_first_fire(monkeypatch):
    monkeypatch.setenv('DN_FAULTS', 'nope:error:1.0')
    mod_faults.reset()
    with pytest.raises(DNError, match='unknown site'):
        mod_faults.fire('sink.flush')


# -- deterministic draws ---------------------------------------------------

def _draw_pattern(n):
    pattern = []
    for _ in range(n):
        try:
            mod_faults.fire('iq.shard_read')
            pattern.append(0)
        except mod_faults.FaultInjected:
            pattern.append(1)
    return pattern


def test_seeded_draws_replay_identically(monkeypatch):
    monkeypatch.setenv('DN_FAULTS', 'iq.shard_read:error:0.4:123')
    mod_faults.reset()
    first = _draw_pattern(200)
    mod_faults.reset()
    second = _draw_pattern(200)
    assert first == second
    assert 0 < sum(first) < 200       # rate 0.4 actually mixes
    st = mod_faults.stats()['iq.shard_read']
    assert st['checked'] == 200 and st['fired'] == sum(first)


def test_different_seeds_draw_differently(monkeypatch):
    monkeypatch.setenv('DN_FAULTS', 'iq.shard_read:error:0.4:123')
    mod_faults.reset()
    a = _draw_pattern(200)
    monkeypatch.setenv('DN_FAULTS', 'iq.shard_read:error:0.4:124')
    mod_faults.reset()
    b = _draw_pattern(200)
    assert a != b


def test_unarmed_sites_are_free(monkeypatch):
    monkeypatch.setenv('DN_FAULTS', 'sink.flush:error:1.0')
    mod_faults.reset()
    mod_faults.fire('iq.shard_read')     # not armed: no-op
    assert mod_faults.stats() == {
        'sink.flush': {'kind': 'error', 'rate': 1.0, 'seed': 0,
                       'checked': 0, 'fired': 0}}


def test_delay_kind_sleeps(monkeypatch):
    monkeypatch.setenv('DN_FAULTS', 'iq.shard_read:delay:1.0')
    monkeypatch.setenv('DN_FAULT_DELAY_MS', '40')
    mod_faults.reset()
    t0 = time.monotonic()
    mod_faults.fire('iq.shard_read')
    assert time.monotonic() - t0 >= 0.035


def test_counters_and_stats(monkeypatch):
    monkeypatch.setenv('DN_FAULTS', 'iq.shard_read:error:1.0')
    mod_faults.reset()
    mod_vpipe.reset_global_counters()
    for _ in range(3):
        with pytest.raises(mod_faults.FaultInjected):
            mod_faults.fire('iq.shard_read')
    g = mod_vpipe.global_counters()
    assert g['faults injected'] == 3
    assert g['fault injected iq.shard_read'] == 3
    assert mod_faults.total_fired() == 3


# -- seam wiring: injected faults surface as clean DNErrors ----------------

def _make_corpus(tmp_path):
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    datafile = str(tmp_path / 'data.log')
    import datetime
    t0 = 1388534400
    with open(datafile, 'w') as f:
        for i in range(400):
            ts = datetime.datetime.utcfromtimestamp(
                t0 + i * 800).strftime('%Y-%m-%dT%H:%M:%S.000Z')
            f.write(json.dumps({'time': ts, 'host': 'h%d' % (i % 3),
                                'latency': i % 50}) + '\n')
    return datafile


def _ds(datafile, idx):
    from dragnet_tpu.datasource_file import DatasourceFile
    return DatasourceFile({
        'ds_backend': 'file', 'ds_format': 'json',
        'ds_backend_config': {'path': datafile, 'indexPath': idx,
                              'timeField': 'time'},
        'ds_filter': None})


def _metric():
    from dragnet_tpu import query as mod_query
    return mod_query.metric_deserialize({
        'name': 'm1', 'datasource': 'd', 'filter': None,
        'breakdowns': [
            {'name': 'timestamp', 'field': 'time', 'date': 'time',
             'aggr': 'lquantize', 'step': 86400},
            {'name': 'host', 'field': 'host'}]})


def _query():
    from dragnet_tpu import query as mod_query
    return mod_query.query_load({'breakdowns': [
        {'name': 'host', 'field': 'host'}]})


def test_injected_shard_read_fault_is_clean_dnerror(tmp_path,
                                                    monkeypatch):
    datafile = _make_corpus(tmp_path)
    idx = str(tmp_path / 'idx')
    ds = _ds(datafile, idx)
    ds.build([_metric()], 'day')
    expected = ds.query(_query(), 'day').points

    monkeypatch.setenv('DN_FAULTS', 'iq.shard_read:error:1.0')
    mod_faults.reset()
    with pytest.raises(DNError, match='injected error fault'):
        ds.query(_query(), 'day')

    # disarmed: byte-identical output, no residue
    monkeypatch.delenv('DN_FAULTS')
    mod_faults.reset()
    assert ds.query(_query(), 'day').points == expected


def test_injected_sink_fault_fails_build_cleanly(tmp_path,
                                                 monkeypatch):
    datafile = _make_corpus(tmp_path)
    idx = str(tmp_path / 'idx')
    ds = _ds(datafile, idx)
    monkeypatch.setenv('DN_FAULTS', 'sink.create:error:1.0')
    mod_faults.reset()
    with pytest.raises(DNError, match='injected error fault'):
        ds.build([_metric()], 'day')
    # no litter: the failed build left a clean (or absent) tree
    for r, dirs, names in os.walk(idx):
        for name in names:
            assert not name.split('.')[-1].isdigit(), name


def test_injection_counters_in_counters_dump(tmp_path, monkeypatch):
    """DN_COUNTERS_ALL=1 surfaces the per-site injection counters in
    the --counters dump, and faults.stats() reports the same firing."""
    monkeypatch.setenv('DN_FAULTS', 'iq.shard_read:delay:1.0')
    monkeypatch.setenv('DN_FAULT_DELAY_MS', '1')
    mod_faults.reset()
    datafile = _make_corpus(tmp_path)
    idx = str(tmp_path / 'idx')
    ds = _ds(datafile, idx)
    ds.build([_metric()], 'day')
    r = ds.query(_query(), 'day')

    out = io.StringIO()
    r.pipeline.dump_counters(out)
    assert 'iq.shard_read' not in out.getvalue()
    monkeypatch.setenv('DN_COUNTERS_ALL', '1')
    out = io.StringIO()
    r.pipeline.dump_counters(out)
    assert 'faults injected' in out.getvalue()
    assert 'iq.shard_read:' in out.getvalue()
    st = mod_faults.stats()['iq.shard_read']
    assert 0 < st['fired'] <= st['checked']


# -- the miniature chaos soak ----------------------------------------------

def test_mini_soak_local_faults(tmp_path, monkeypatch):
    """A tier-1-sized slice of tools/soak_faults.py: mixed
    query/scan/build under seeded error injection, asserting the
    byte-identical-or-clean-error contract and zero torn shards."""
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), 'tools'))
    import soak_faults

    prior = os.environ.get('DRAGNET_CONFIG')
    mod_faults.reset()
    try:
        ctx = soak_faults.make_corpus(str(tmp_path), n=400)
        for fmt in soak_faults.FORMATS:
            soak_faults.build(ctx, fmt)
        s = soak_faults.Soak(ctx, verbose=False)
        s.local_rounds(soak_faults.LOCAL_SPEC, 2)
        summary = s.summary()
        assert summary['violations'] == []
        assert summary['faults_injected_total'] > 0
        assert summary['ops'] > 0
    finally:
        if prior is None:
            os.environ.pop('DRAGNET_CONFIG', None)
        else:
            os.environ['DRAGNET_CONFIG'] = prior
        mod_faults.reset()
