"""Stacked cross-shard index-query execution
(dragnet_tpu/index_query_stack.py): byte parity with the per-shard
loop across execution modes, formats, intervals, and worker counts;
the exactness-gate fallback; the corrupt-shard error contract; the
semver gate; the device lane's differential + clean fallback; and the
cluster dry-run plan reporting the stack mode.

Parity is checked on points AND visible counters: the stacked path
commits its fan-in counters in bulk, and totals must equal what the
sequential merge loop bumps shard by shard."""

import io
import json
import os
import random
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from dragnet_tpu import device_index as mod_di  # noqa: E402
from dragnet_tpu import query as mod_query  # noqa: E402
from dragnet_tpu import index_query_mt as mod_iqmt  # noqa: E402
from dragnet_tpu import index_query_stack as mod_iqs  # noqa: E402
from dragnet_tpu.datasource_file import DatasourceFile  # noqa: E402
from dragnet_tpu.errors import DNError  # noqa: E402

NDAYS = 10


def _make_data(path, n=5000):
    rng = random.Random(1234)
    with open(path, 'w') as f:
        for i in range(n):
            rec = {
                'host': 'host%d' % rng.randrange(30),
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


def _run(ds, interval, conf, stack, threads, monkeypatch):
    monkeypatch.setenv('DN_IQ_STACK', stack)
    monkeypatch.setenv('DN_IQ_THREADS', threads)
    r = ds.query(_query(conf), interval)
    counters = [(s.name, {c: v for c, v in s.counters.items()
                          if c not in s.hidden})
                for s in r.pipeline.stages]
    return r.points, counters


@pytest.fixture(autouse=True)
def fresh_cache():
    mod_iqmt.shard_cache_clear()
    yield
    mod_iqmt.shard_cache_clear()


# -- parity sweep ----------------------------------------------------------

QUERIES = [
    {'breakdowns': [{'name': 'host'},
                    {'name': 'latency', 'aggr': 'quantize'}]},
    {'breakdowns': [{'name': 'host'}, {'name': 'operation'}],
     'filter': {'eq': ['operation', 'op3']}},
    {'breakdowns': [{'name': 'latency', 'aggr': 'lquantize',
                     'step': 32}]},
    {'breakdowns': []},                        # bare SUM
    {'breakdowns': [],                         # NULL SUM -> 0 per shard
     'filter': {'eq': ['host', 'no-such-host']}},
    {'breakdowns': [{'name': 'host'}],         # zero-point shards
     'filter': {'eq': ['host', 'host7']},
     'timeAfter': '2014-05-02', 'timeBefore': '2014-05-09'},
    {'breakdowns': [{'name': 'host'},          # empty result WITH
                    {'name': 'operation'}],    # breakdowns: no stray
     'filter': {'eq': ['host', 'no-such-host']}},   # counter keys
]


@pytest.mark.parametrize('index_format', ['dnc', 'sqlite'])
@pytest.mark.parametrize('interval', ['hour', 'day', 'all'])
def test_stacked_parity_sweep(tmp_path, index_format, interval,
                              monkeypatch):
    """stacked x per-shard-parallel x sequential over formats x
    intervals x DN_IQ_THREADS 0/1/4: points and visible counters all
    byte-identical."""
    monkeypatch.setenv('DN_INDEX_FORMAT', index_format)
    datafile = str(tmp_path / 'data.log')
    idx = str(tmp_path / 'idx')
    _make_data(datafile)
    _ds(datafile, idx).build([_metric()], interval)

    ds = _ds(datafile, idx)
    for conf in QUERIES:
        ref, cref = _run(ds, interval, conf, '0', '0', monkeypatch)
        for stack in ('0', '1', 'auto'):
            for threads in ('0', '1', '4'):
                pts, cnt = _run(ds, interval, conf, stack, threads,
                                monkeypatch)
                assert pts == ref, (conf, stack, threads)
                assert cnt == cref, (conf, stack, threads)


def test_stacked_is_engaged_by_default(tmp_path, monkeypatch):
    """DN_IQ_STACK unset (auto) actually takes the stacked path: the
    Aggregator ends up columnar (set_columnar), which the per-shard
    merge loop never produces."""
    datafile = str(tmp_path / 'data.log')
    idx = str(tmp_path / 'idx')
    _make_data(datafile, n=1500)
    ds = _ds(datafile, idx)
    ds.build([_metric()], 'day')
    monkeypatch.delenv('DN_IQ_STACK', raising=False)
    seen = {}
    real = mod_iqs.run_stacked

    def spy(*args, **kwargs):
        rv = real(*args, **kwargs)
        seen['rv'] = rv
        return rv
    monkeypatch.setattr(mod_iqs, 'run_stacked', spy)
    ds.query(_query(QUERIES[0]), 'day')
    assert seen.get('rv') is True


def test_exactness_gate_falls_back(tmp_path, monkeypatch):
    """Non-integral weights fail the stacked gate; the query falls
    back to the per-shard loop with identical results."""
    datafile = str(tmp_path / 'data.log')
    idx = str(tmp_path / 'idx')
    _make_data(datafile, n=800)
    ds = _ds(datafile, idx)
    ds.build([_metric()], 'day')
    # poison the gate: pretend one shard reported a non-integer value
    real = mod_iqs._shard_values
    monkeypatch.setattr(mod_iqs, '_shard_values',
                        lambda sh: (real(sh)[0], False))
    p1, c1 = _run(ds, 'day', QUERIES[0], '1', '2', monkeypatch)
    monkeypatch.setattr(mod_iqs, '_shard_values', real)
    p0, c0 = _run(ds, 'day', QUERIES[0], '0', '0', monkeypatch)
    assert p1 == p0
    assert c1 == c0


def test_float_weights_real_fallback(tmp_path, monkeypatch):
    """Real non-integral weights (json-skinner points with float
    values) take the fallback end to end and match the per-shard
    loop."""
    idx = str(tmp_path / 'idx')
    ds = _ds(str(tmp_path / 'none.log'), idx)
    metric = mod_query.metric_deserialize({'name': 'm', 'breakdowns': [
        {'name': 'host', 'field': 'host'}]})
    lines = []
    for i, (host, value) in enumerate(
            [('a', 1.5), ('b', 2), ('a', 0.25), ('c', 3.75)]):
        lines.append(json.dumps(
            {'fields': {'host': host, '__dn_metric': 0},
             'value': value}))
    stream = io.BytesIO(('\n'.join(lines) + '\n').encode())
    ds.index_read([metric], 'all', stream)

    conf = {'breakdowns': [{'name': 'host'}]}
    p1, c1 = _run(ds, 'all', conf, '1', '0', monkeypatch)
    p0, c0 = _run(ds, 'all', conf, '0', '0', monkeypatch)
    assert p1 == p0
    assert c1 == c0
    assert p0 == [({'host': 'a'}, 1.75), ({'host': 'b'}, 2),
                  ({'host': 'c'}, 3.75)]


def test_null_field_values_stack(tmp_path, monkeypatch):
    """SQL-NULL key values (a point whose field is json null) decode
    to the "null" key in both execution modes, for both formats."""
    for fmt in ('dnc', 'sqlite'):
        monkeypatch.setenv('DN_INDEX_FORMAT', fmt)
        idx = str(tmp_path / ('idx_' + fmt))
        ds = _ds(str(tmp_path / 'none.log'), idx)
        metric = mod_query.metric_deserialize(
            {'name': 'm', 'breakdowns': [
                {'name': 'host', 'field': 'host'}]})
        lines = [
            json.dumps({'fields': {'host': None, '__dn_metric': 0},
                        'value': 2}),
            json.dumps({'fields': {'host': 'a', '__dn_metric': 0},
                        'value': 5}),
            json.dumps({'fields': {'host': None, '__dn_metric': 0},
                        'value': 1}),
        ]
        ds.index_read([metric], 'all',
                      io.BytesIO(('\n'.join(lines) + '\n').encode()))
        conf = {'breakdowns': [{'name': 'host'}]}
        p1, c1 = _run(ds, 'all', conf, '1', '0', monkeypatch)
        p0, c0 = _run(ds, 'all', conf, '0', '0', monkeypatch)
        assert p1 == p0, fmt
        assert c1 == c0, fmt
        assert ({'host': 'null'}, 3) in p0, (fmt, p0)


# -- error contracts -------------------------------------------------------

@pytest.mark.parametrize('index_format', ['dnc', 'sqlite'])
def test_corrupt_shard_error_contract(tmp_path, index_format,
                                      monkeypatch):
    """A corrupt shard mid-stack raises one DNError naming the shard
    path — the same message (first in find order) as the per-shard
    loop — unlinks nothing, and leaves the handle cache consistent
    (the bad handle is closed, healthy ones still serve)."""
    monkeypatch.setenv('DN_INDEX_FORMAT', index_format)
    datafile = str(tmp_path / 'data.log')
    idx = str(tmp_path / 'idx')
    _make_data(datafile, n=1200)
    ds = _ds(datafile, idx)
    ds.build([_metric()], 'day')
    shard_dir = os.path.join(idx, 'by_day')
    shards = sorted(os.listdir(shard_dir))
    bad = os.path.join(shard_dir, shards[3])
    with open(bad, 'wb') as f:
        f.write(b'not an index of any kind')
    listing_before = sorted(os.listdir(shard_dir))

    messages = {}
    for stack, threads in (('0', '0'), ('0', '4'), ('1', '0'),
                           ('1', '4')):
        monkeypatch.setenv('DN_IQ_STACK', stack)
        monkeypatch.setenv('DN_IQ_THREADS', threads)
        with pytest.raises(DNError) as ei:
            ds.query(_query(QUERIES[0]), 'day')
        messages[(stack, threads)] = ei.value.message
    assert len(set(messages.values())) == 1, messages
    assert shards[3] in next(iter(messages.values()))
    # no unlinks: the error path created and removed nothing
    assert sorted(os.listdir(shard_dir)) == listing_before
    # cache consistency: the failed shard was never cached; repairing
    # it serves again without a stale handle
    import shutil
    shutil.copyfile(os.path.join(shard_dir, shards[2]), bad)
    monkeypatch.setenv('DN_IQ_STACK', '1')
    r = ds.query(_query(QUERIES[0]), 'day')
    assert r.points


@pytest.mark.parametrize('index_format', ['dnc', 'sqlite'])
def test_truncated_shard_error_contract(tmp_path, index_format,
                                        monkeypatch):
    """Truncation (the other corruption mode) reports identically in
    stacked and per-shard modes."""
    monkeypatch.setenv('DN_INDEX_FORMAT', index_format)
    datafile = str(tmp_path / 'data.log')
    idx = str(tmp_path / 'idx')
    _make_data(datafile, n=1200)
    ds = _ds(datafile, idx)
    ds.build([_metric()], 'day')
    shard_dir = os.path.join(idx, 'by_day')
    shards = sorted(os.listdir(shard_dir))
    bad = os.path.join(shard_dir, shards[1])
    raw = open(bad, 'rb').read()
    with open(bad, 'wb') as f:
        f.write(raw[:max(8, len(raw) // 3)])

    # contract: one DNError naming the failing shard, whichever mode.
    # (Full-message equality is not required here: a truncated SQLite
    # shard can fail at execute time, where the two modes' SQL texts —
    # embedded in the message — legitimately differ.)
    for stack in ('0', '1'):
        monkeypatch.setenv('DN_IQ_STACK', stack)
        monkeypatch.setenv('DN_IQ_THREADS', '0')
        with pytest.raises(DNError) as ei:
            ds.query(_query(QUERIES[0]), 'day')
        assert shards[1] in ei.value.message, stack


@pytest.mark.parametrize('index_format', ['dnc', 'sqlite'])
def test_semver_gate(tmp_path, index_format, monkeypatch):
    """The ~2 semver gate on the embedded index version raises the
    same unsupported-version error in every execution mode."""
    from dragnet_tpu import index_sink as mod_sink
    from dragnet_tpu import index_dnc as mod_dnc
    monkeypatch.setenv('DN_INDEX_FORMAT', index_format)
    monkeypatch.setattr(mod_sink, 'INDEX_VERSION', '3.0.0')
    monkeypatch.setattr(mod_dnc, 'INDEX_VERSION', '3.0.0')
    datafile = str(tmp_path / 'data.log')
    idx = str(tmp_path / 'idx')
    _make_data(datafile, n=400)
    ds = _ds(datafile, idx)
    ds.build([_metric()], 'day')

    messages = {}
    for stack in ('0', '1'):
        monkeypatch.setenv('DN_IQ_STACK', stack)
        monkeypatch.setenv('DN_IQ_THREADS', '0')
        with pytest.raises(DNError) as ei:
            ds.query(_query(QUERIES[0]), 'day')
        messages[stack] = ei.value.message
    assert messages['0'] == messages['1']
    assert 'unsupported index version: "3.0.0"' in messages['0']


# -- shard-list (find) cache ----------------------------------------------

def test_cached_find_counters_match_fresh_walk(tmp_path, monkeypatch):
    """The memoized whole-tree walk replays the Find* stage counters
    byte-identically, and rebuilds invalidate it."""
    datafile = str(tmp_path / 'data.log')
    idx = str(tmp_path / 'idx')
    _make_data(datafile, n=1500)
    ds = _ds(datafile, idx)
    ds.build([_metric()], 'day')
    monkeypatch.setenv('DN_IQ_THREADS', '0')
    monkeypatch.setenv('DN_IQ_STACK', '1')

    r_fresh = ds.query(_query(QUERIES[0]), 'day')     # populates
    r_cached = ds.query(_query(QUERIES[0]), 'day')    # replays

    def find_counters(r):
        return [(s.name, dict(s.counters)) for s in r.pipeline.stages
                if s.name.startswith('Find')]
    assert find_counters(r_cached) == find_counters(r_fresh)
    assert r_cached.points == r_fresh.points

    # rebuild with different data: the cached listing must not serve
    # a stale shard set
    _make_data(datafile, n=300)
    ds.build([_metric()], 'day')
    r_after = ds.query(_query(QUERIES[0]), 'day')
    assert r_after.points != r_fresh.points


# -- device lane -----------------------------------------------------------

def test_device_lane_differential(tmp_path, monkeypatch):
    """DN_ENGINE=jax: the stacked sums fold as one device scatter-add
    and the result is bit-equal to the host path."""
    pytest.importorskip('jax')
    datafile = str(tmp_path / 'data.log')
    idx = str(tmp_path / 'idx')
    _make_data(datafile, n=2500)
    ds = _ds(datafile, idx)
    ds.build([_metric()], 'day')

    monkeypatch.setenv('DN_IQ_STACK', '1')
    monkeypatch.setenv('DN_IQ_THREADS', '0')
    host = ds.query(_query(QUERIES[0]), 'day').points

    mod_di._reset_device_state()
    monkeypatch.setenv('DN_ENGINE', 'jax')
    dev = ds.query(_query(QUERIES[0]), 'day').points
    assert mod_di._DEVICE_STATE['ready'] is True
    assert dev == host


def test_forced_device_lane_without_jax_is_an_error(tmp_path,
                                                    monkeypatch):
    """DN_ENGINE=jax and no jax: the forced device lane fails the
    query with the reason instead of answering from the host."""
    from dragnet_tpu.errors import DNError
    datafile = str(tmp_path / 'data.log')
    idx = str(tmp_path / 'idx')
    _make_data(datafile, n=900)
    ds = _ds(datafile, idx)
    ds.build([_metric()], 'day')
    monkeypatch.setenv('DN_IQ_STACK', '1')

    from dragnet_tpu import ops
    mod_di._reset_device_state()
    monkeypatch.setenv('DN_ENGINE', 'jax')
    monkeypatch.setattr(ops, 'get_jax', lambda: None)
    with pytest.raises(DNError) as ei:
        ds.query(_query(QUERIES[0]), 'day')
    assert 'device index-query lane unavailable' in ei.value.message
    assert 'jax unavailable' in ei.value.message


def test_auto_device_lane_clean_fallback(monkeypatch, capsys):
    """A lane auto mode chose (not forced) still warns once and leaves
    the answer to the host path."""
    mod_di._reset_device_state()
    monkeypatch.delenv('DN_ENGINE', raising=False)
    monkeypatch.delenv('DN_INDEX_DEVICE', raising=False)
    mod_di._warn_device('backend failed to initialize')
    mod_di._warn_device('backend failed to initialize')
    err = capsys.readouterr().err
    assert err.count('device index-query lane unavailable') == 1
    mod_di._reset_device_state()


def test_forced_device_lane_deadline_is_an_error(tmp_path, monkeypatch):
    """A backend whose first device op never returns trips the probe
    deadline: a forced lane fails with the reason instead of hanging
    the query (or answering from the host)."""
    pytest.importorskip('jax')
    import time as mod_time
    from dragnet_tpu.errors import DNError
    datafile = str(tmp_path / 'data.log')
    idx = str(tmp_path / 'idx')
    _make_data(datafile, n=900)
    ds = _ds(datafile, idx)
    ds.build([_metric()], 'day')
    monkeypatch.setenv('DN_IQ_STACK', '1')

    mod_di._reset_device_state()
    monkeypatch.setenv('DN_ENGINE', 'jax')
    monkeypatch.setenv('DN_DEVICE_PROBE_TIMEOUT', '0.2')
    monkeypatch.setattr(
        mod_di, 'sums_program',
        lambda rows, segments: (lambda pair: mod_time.sleep(60)))
    with pytest.raises(DNError) as ei:
        ds.query(_query(QUERIES[0]), 'day')
    assert 'unresponsive' in ei.value.message
    assert mod_di._DEVICE_STATE['ready'] is False
    mod_di._reset_device_state()


# -- CLI + cluster plan ----------------------------------------------------

@pytest.mark.parametrize('index_format', ['dnc', 'sqlite'])
def test_cli_iq_stack_byte_identical(tmp_path, index_format,
                                     monkeypatch):
    """`dn query --iq-stack=1` output (incl. --counters) is
    byte-identical to --iq-stack=0; a bad value is a usage error."""
    from parity.runner import DnRunner
    monkeypatch.setenv('DN_INDEX_FORMAT', index_format)
    datafile = str(tmp_path / 'data.log')
    idx = str(tmp_path / 'idx')
    _make_data(datafile, n=2000)

    r = DnRunner(tmp_path)
    r.clear_config()
    r.dn('datasource-add', 'input', '--path=' + datafile,
         '--index-path=' + idx, '--time-field=time')
    r.dn('metric-add', 'input', 'met', '-b',
         'timestamp[date,field=time,aggr=lquantize,step=86400],host,'
         'latency[aggr=quantize]')
    r.dn('build', 'input')

    runs = {}
    for stack in ('0', '1'):
        out, err, rc = r.run(['query', '--iq-stack=' + stack,
                              '-b', 'host', '--counters', 'input'])
        assert rc == 0
        runs[stack] = out + err
    assert runs['0'] == runs['1']

    out, err, rc = r.run(['query', '--iq-stack=bogus', '-b', 'host',
                          'input'], check=False)
    assert rc == 2
    assert 'bad value for "iq-stack"' in err


def test_cluster_plan_reports_stack_mode(tmp_path, monkeypatch):
    """A cluster dry-run's execution plan reports the stacked
    index-query mode."""
    from dragnet_tpu.parallel import cluster
    datafile = str(tmp_path / 'data.log')
    idx = str(tmp_path / 'idx')
    _make_data(datafile, n=300)
    ds = cluster.DatasourceCluster({
        'ds_backend': 'cluster',
        'ds_backend_config': {'path': datafile, 'timeField': 'time',
                              'indexPath': idx},
        'ds_filter': None, 'ds_format': 'json'})
    ds.build([_metric()], 'day')
    monkeypatch.delenv('DN_IQ_STACK', raising=False)
    r = ds.query(_query(QUERIES[0]), 'day', dry_run=True)
    assert r.dry_run_plan['index_query_stack'] == 'auto'
    monkeypatch.setenv('DN_IQ_STACK', '0')
    r = ds.query(_query(QUERIES[0]), 'day', dry_run=True)
    assert r.dry_run_plan['index_query_stack'] == '0'


def test_stack_mode_env(monkeypatch):
    monkeypatch.delenv('DN_IQ_STACK', raising=False)
    assert mod_iqs.stack_mode() == 'auto'
    assert mod_iqs.stack_enabled()
    monkeypatch.setenv('DN_IQ_STACK', '0')
    assert not mod_iqs.stack_enabled()
    monkeypatch.setenv('DN_IQ_STACK', '1')
    assert mod_iqs.stack_enabled()
    monkeypatch.setenv('DN_IQ_STACK', 'junk')
    assert mod_iqs.stack_mode() == 'auto'


def test_filtered_out_overflow_string_never_coerced(tmp_path,
                                                    monkeypatch):
    """A dictionary entry like '1e999' (coerces to inf; bucketizing it
    raises) belonging ONLY to filter-excluded rows must never reach
    the coercion tables — the per-shard lane only coerces selected
    groups, and the stacked path must match."""
    for fmt in ('dnc', 'sqlite'):
        monkeypatch.setenv('DN_INDEX_FORMAT', fmt)
        idx = str(tmp_path / ('oidx_' + fmt))
        ds = _ds(str(tmp_path / 'none.log'), idx)
        metric = mod_query.metric_deserialize(
            {'name': 'm', 'breakdowns': [
                {'name': 'host', 'field': 'host'},
                {'name': 'lat', 'field': 'lat'}]})
        lines = [
            json.dumps({'fields': {'host': 'a', 'lat': '26',
                                   '__dn_metric': 0}, 'value': 4}),
            json.dumps({'fields': {'host': 'b', 'lat': '1e999',
                                   '__dn_metric': 0}, 'value': 7}),
        ]
        ds.index_read([metric], 'all',
                      io.BytesIO(('\n'.join(lines) + '\n').encode()))
        conf = {'breakdowns': [{'name': 'lat', 'aggr': 'quantize'}],
                'filter': {'eq': ['host', 'a']}}
        p1, c1 = _run(ds, 'all', conf, '1', '0', monkeypatch)
        p0, c0 = _run(ds, 'all', conf, '0', '0', monkeypatch)
        assert p1 == p0, fmt
        assert c1 == c0, fmt
        assert p0 == [({'lat': 16}, 4)], (fmt, p0)


def test_text_value_storage_falls_back(tmp_path, monkeypatch):
    """A flexibly-typed SQLite shard whose value column holds TEXT (a
    foreign writer): the stacked gate must reject it gracefully — the
    per-shard path's SUM coercion answers, no crash."""
    import sqlite3
    monkeypatch.setenv('DN_INDEX_FORMAT', 'sqlite')
    datafile = str(tmp_path / 'data.log')
    idx = str(tmp_path / 'idx')
    _make_data(datafile, n=600)
    ds = _ds(datafile, idx)
    ds.build([_metric()], 'day')
    shard_dir = os.path.join(idx, 'by_day')
    bad = os.path.join(shard_dir, sorted(os.listdir(shard_dir))[0])
    db = sqlite3.connect(bad)
    db.execute("UPDATE dragnet_index_0 SET value = 'x' "
               "WHERE rowid IN (SELECT rowid FROM dragnet_index_0 "
               "LIMIT 1)")
    db.commit()
    db.close()

    p1, c1 = _run(ds, 'day', QUERIES[0], '1', '0', monkeypatch)
    p0, c0 = _run(ds, 'day', QUERIES[0], '0', '0', monkeypatch)
    assert p1 == p0
    assert c1 == c0


def test_mixed_format_tree_parity(tmp_path, monkeypatch):
    """A tree whose shards mix storage formats (half built as DNC,
    half as SQLite — the DNC sink's per-file fallback produces such
    trees) stacks correctly: per-breakdown columns arrive in different
    kinds per shard and still merge byte-identically to the per-shard
    loop."""
    datafile = str(tmp_path / 'data.log')
    idx = str(tmp_path / 'idx')
    _make_data(datafile)
    ds = _ds(datafile, idx)
    monkeypatch.setenv('DN_INDEX_FORMAT', 'dnc')
    ds.build([_metric()], 'day',
             time_after='2014-05-01', time_before='2014-05-06')
    monkeypatch.setenv('DN_INDEX_FORMAT', 'sqlite')
    ds.build([_metric()], 'day',
             time_after='2014-05-06', time_before='2014-05-11')

    from dragnet_tpu import native_index
    magic = native_index.MAGIC
    kinds = set()
    for name in os.listdir(os.path.join(idx, 'by_day')):
        with open(os.path.join(idx, 'by_day', name), 'rb') as f:
            kinds.add(f.read(len(magic)) == magic)
    assert kinds == {True, False}, 'tree is not actually mixed'

    for conf in QUERIES:
        ref, cref = _run(ds, 'day', conf, '0', '0', monkeypatch)
        pts, cnt = _run(ds, 'day', conf, '1', '0', monkeypatch)
        assert pts == ref, conf
        assert cnt == cref, conf


def test_stack_eligibility_gate():
    q = _query({'breakdowns': [
        {'name': 'ts', 'field': 'time', 'date': '',
         'aggr': 'lquantize', 'step': 86400}]})
    assert not mod_iqs.stack_eligible(q)     # field != name
    q = _query({'breakdowns': [{'name': 'host'}]})
    assert mod_iqs.stack_eligible(q)
