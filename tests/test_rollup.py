"""Multi-resolution rollup shards, background compaction, and the
server-side result cache (rollup.py, serve/qcache.py) — the three
legs of the repeat-traffic planner.

The headline contracts under test:

* BYTE-IDENTITY — a query planned over rollup shards (day-from-hour,
  month-from-day) returns points byte-identical to the plain
  fine-shard walk, in both DN_INDEX_FORMAT modes, including window
  edges where fine shards compose with coarse ones; a stale rollup
  (fine source rewritten, rollup not yet refreshed) silently falls
  back to the fine path.
* COMPACTION NEVER CHANGES BYTES — `dn follow --append`
  mini-generations answer queries byte-identically to a from-scratch
  build before, during, and after `dn compact`, and the compacted
  tree byte-equals the from-scratch build shard for shard.
* CACHING IS INVISIBLE — a served cache hit is byte-identical to
  recomputing; any in-process index write retires the entry (epoch),
  and the LRU/byte-budget/governor discipline sheds before it lies.

Plus the pool auto-degrade crossover (DN_IQ_SEQ_MS) and the /stats
`rollup` / `maintenance` / `caches.results` sections.
"""

import json
import os
import random
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from dragnet_tpu import cli                                # noqa: E402
from dragnet_tpu import config as mod_config               # noqa: E402
from dragnet_tpu import index_journal as mod_journal       # noqa: E402
from dragnet_tpu import index_query_mt as mod_iqmt         # noqa: E402
from dragnet_tpu import query as mod_query                 # noqa: E402
from dragnet_tpu import rollup as mod_rollup               # noqa: E402
from dragnet_tpu.datasource_file import DatasourceFile     # noqa: E402
from dragnet_tpu.errors import DNError                     # noqa: E402
from dragnet_tpu.serve import client as mod_client         # noqa: E402
from dragnet_tpu.serve import qcache as mod_qcache         # noqa: E402
from dragnet_tpu.serve import server as mod_server         # noqa: E402

import test_follow as tf                                   # noqa: E402


def run_cli(args):
    with mod_server.thread_stdio() as cap:
        rc = cli.main(list(args))
    out, err = cap.finish()
    return rc, out, err


# -- rollup planner: byte identity vs the fine-shard walk ------------------

def _gen_two_months(path, n=1200):
    """Records over 2014-04-01..07 and 2014-05-01..04 with hourly
    spread: two partial months, so by_month rollups and window-edge
    composition both matter."""
    rng = random.Random(7)
    with open(path, 'w') as f:
        for i in range(n):
            mon = rng.choice([4, 5])
            day = rng.randrange(1, 8 if mon == 4 else 5)
            f.write(json.dumps({
                'host': 'host%d' % rng.randrange(12),
                'operation': 'op%d' % rng.randrange(6),
                'latency': rng.randrange(1, 500),
                'time': '2014-%02d-%02dT%02d:%02d:00.000Z'
                        % (mon, day, rng.randrange(24),
                           rng.randrange(60)),
            }, separators=(',', ':')) + '\n')


def _make_ds(datafile, idx):
    return DatasourceFile({
        'ds_backend': 'file',
        'ds_backend_config': {'path': datafile, 'timeField': 'time',
                              'indexPath': idx},
        'ds_filter': None, 'ds_format': 'json'})


def _metric():
    return mod_query.metric_deserialize({'name': 'm', 'breakdowns': [
        {'name': 'ts', 'field': 'time', 'date': '',
         'aggr': 'lquantize', 'step': 3600},
        {'name': 'host', 'field': 'host'},
        {'name': 'operation', 'field': 'operation'},
        {'name': 'latency', 'field': 'latency',
         'aggr': 'quantize'}]})


def _q(conf):
    r = mod_query.query_load(conf)
    assert not isinstance(r, DNError), r
    return r


ROLLUP_QUERIES = [
    ('bare', {}),
    ('host', {'breakdowns': [{'name': 'host'}]}),
    ('host+lat', {'breakdowns': [
        {'name': 'host'}, {'name': 'latency', 'aggr': 'quantize'}]}),
    ('filtered', {'filter': {'eq': ['host', 'host3']},
                  'breakdowns': [{'name': 'operation'}]}),
    ('window-exact-month', {'breakdowns': [{'name': 'host'}],
                            'timeAfter': '2014-04-01',
                            'timeBefore': '2014-05-01'}),
    ('window-partial', {'breakdowns': [{'name': 'host'}],
                        'timeAfter': '2014-04-03',
                        'timeBefore': '2014-05-03'}),
    ('window-mid-day', {'breakdowns': [{'name': 'host'}],
                        'timeAfter': '2014-04-02T05:00:00',
                        'timeBefore': '2014-04-03T07:00:00'}),
]


def _hidden(result):
    h = {}
    for s in result.pipeline.stages:
        for c in ('index shards via rollup', 'rollup shards queried',
                  'index shards queried'):
            if c in s.counters:
                h[c] = h.get(c, 0) + s.counters[c]
    return h


@pytest.fixture(scope='module')
def two_month_datafile(tmp_path_factory):
    root = tmp_path_factory.mktemp('rollup_corpus')
    datafile = str(root / 'data.json')
    _gen_two_months(datafile)
    return datafile


@pytest.mark.parametrize('fmt', ('dnc', 'sqlite'))
@pytest.mark.parametrize('interval', ('hour', 'day'))
def test_rollup_byte_identity(two_month_datafile, tmp_path,
                              monkeypatch, fmt, interval):
    """Every query shape answers byte-identically before and after
    rollups exist; full-window queries actually engage them; a
    second build is a no-op and a stale fine source triggers exactly
    one bucket rebuild."""
    monkeypatch.setenv('DN_INDEX_FORMAT', fmt)
    monkeypatch.setenv('DN_IQ_THREADS', '0')
    monkeypatch.setenv('DN_IQ_STAT_TTL_MS', '0')
    idx = str(tmp_path / 'idx')
    ds = _make_ds(two_month_datafile, idx)
    ds.build([_metric()], interval)

    base = {}
    for name, conf in ROLLUP_QUERIES:
        base[name] = ds.query(_q(dict(conf)), interval).points

    doc = mod_rollup.build_rollups(idx, interval)
    assert doc['built'] > 0, doc

    for name, conf in ROLLUP_QUERIES:
        r = ds.query(_q(dict(conf)), interval)
        assert r.points == base[name], name
        if name == 'bare':
            h = _hidden(r)
            # the full-range walk must be answered from rollups
            assert h.get('index shards via rollup', 0) > 0, h
            assert h.get('rollup shards queried', 0) > 0, h

    # incremental: a second build with nothing stale is a no-op
    assert mod_rollup.build_rollups(idx, interval)['built'] == 0

    # stale source -> exactly that bucket rebuilds, bytes hold
    finedir = os.path.join(idx, 'by_%s' % interval)
    victim = sorted(os.listdir(finedir))[0]
    os.utime(os.path.join(finedir, victim))
    doc3 = mod_rollup.build_rollups(idx, interval)
    assert doc3['built'] >= 1, doc3
    r = ds.query(_q(dict(ROLLUP_QUERIES[2][1])), interval)
    assert r.points == base['host+lat']


def test_stale_rollup_falls_back_to_fine(two_month_datafile,
                                         tmp_path, monkeypatch):
    """A fine shard rewritten AFTER the rollup was built makes the
    covering rollup stale — the planner must silently take the fine
    path (correct bytes, zero rollup engagement), not serve the
    stale coarse shard."""
    monkeypatch.setenv('DN_INDEX_FORMAT', 'dnc')
    monkeypatch.setenv('DN_IQ_THREADS', '0')
    monkeypatch.setenv('DN_IQ_STAT_TTL_MS', '0')
    idx = str(tmp_path / 'idx')
    ds = _make_ds(two_month_datafile, idx)
    ds.build([_metric()], 'day')
    base = ds.query(_q({'breakdowns': [{'name': 'host'}]}),
                    'day').points
    assert mod_rollup.build_rollups(idx, 'day')['built'] > 0
    finedir = os.path.join(idx, 'by_day')
    for name in sorted(os.listdir(finedir)):
        os.utime(os.path.join(finedir, name))
    r = ds.query(_q({'breakdowns': [{'name': 'host'}]}), 'day')
    assert r.points == base
    assert _hidden(r).get('index shards via rollup', 0) == 0


def test_rollup_cli(two_month_datafile, tmp_path, monkeypatch):
    """`dn rollup --tree`: builds on the first run, no-op on the
    second; a bad interval is a clean `dn:` error."""
    monkeypatch.setenv('DN_INDEX_FORMAT', 'dnc')
    idx = str(tmp_path / 'idx')
    ds = _make_ds(two_month_datafile, idx)
    ds.build([_metric()], 'day')
    rc, out, err = run_cli(['rollup', '--tree', idx,
                            '--interval', 'day'])
    assert rc == 0, err
    rc, out2, err = run_cli(['rollup', '--tree', idx,
                             '--interval', 'day'])
    assert rc == 0, err
    rc, out, err = run_cli(['rollup', '--tree', idx,
                            '--interval', 'decade'])
    assert rc == 1 and b'dn:' in err and b'Traceback' not in err


# -- follow --append generations + compaction ------------------------------

COMPACT_QUERIES = [
    {},
    {'breakdowns': [{'name': 'host'}]},
    {'filter': {'eq': ['operation', 'get']},
     'breakdowns': [{'name': 'host'},
                    {'name': 'latency', 'aggr': 'quantize'}]},
    {'breakdowns': [{'name': 'host'}],
     'timeAfter': '2014-01-01T12:00:00',
     'timeBefore': '2014-01-03T06:00:00'},
]


def _ds_for(name):
    from dragnet_tpu import datasource_for_name
    err, conf = mod_config.ConfigBackendLocal().load()
    assert err is None, err
    ds = datasource_for_name(conf, name)
    assert not isinstance(ds, DNError), ds
    return ds


@pytest.mark.parametrize('fmt', ('dnc', 'sqlite'))
def test_append_compact_byte_identity(tmp_path, monkeypatch, fmt):
    """follow --append lands each batch as a mini-generation; queries
    over the generation-bearing tree byte-equal a from-scratch build
    (sequential and pooled), `dn compact` folds the generations, and
    the compacted tree byte-equals the from-scratch build shard for
    shard — twice (a second append/compact round must too)."""
    monkeypatch.setenv('DN_INDEX_FORMAT', fmt)
    monkeypatch.setenv('DN_IQ_STAT_TTL_MS', '0')
    ctx = tf._corpus(tmp_path, monkeypatch, n=200)
    idx = ctx['idx'][fmt]

    # the first follow creates the base shards; each later round's
    # batch publishes as one mini-generation per touched base
    assert tf._follow_once(fmt, env={'DN_FOLLOW_APPEND': '1'})[0] == 0
    n = 200
    for _ in range(2):
        tf._gen(ctx['datafile'], 40, start=n)
        n += 40
        assert tf._follow_once(
            fmt, env={'DN_FOLLOW_APPEND': '1'})[0] == 0
    ctx['n'] = n
    gens = mod_rollup.compaction_backlog(idx, 'day')
    assert gens > 0

    tf._rebuild_ref(ctx, fmt)
    for conf in COMPACT_QUERIES:
        for threads in ('0', '3'):
            monkeypatch.setenv('DN_IQ_THREADS', threads)
            got = _ds_for('f_' + fmt).query(_q(dict(conf)),
                                            'day').points
            ref = _ds_for('r_' + fmt).query(_q(dict(conf)),
                                            'day').points
            assert got == ref, (conf, threads)

    doc = mod_rollup.compact_tree(idx, 'day')
    assert doc['compacted'] > 0
    assert doc['generations_removed'] == gens
    tf._assert_trees_equal(ctx, fmt, 'post-compact')

    # round 2: another append + compact stays byte-equal
    tf._gen(ctx['datafile'], 60, start=ctx['n'])
    assert tf._follow_once(fmt, env={'DN_FOLLOW_APPEND': '1'})[0] == 0
    assert mod_rollup.compaction_backlog(idx, 'day') > 0
    mod_rollup.compact_tree(idx, 'day')
    tf._assert_trees_equal(ctx, fmt, 'round-2')


def test_compact_cli_min_gens(tmp_path, monkeypatch):
    """`dn compact --min-gens N` leaves groups below the threshold
    alone (the cost of a rewrite must buy a real fold), and a second
    run after more appends folds them."""
    monkeypatch.setenv('DN_INDEX_FORMAT', 'dnc')
    ctx = tf._corpus(tmp_path, monkeypatch, n=150)
    idx = ctx['idx']['dnc']
    assert tf._follow_once('dnc', env={'DN_FOLLOW_APPEND': '1'})[0] \
        == 0
    tf._gen(ctx['datafile'], 30, start=150)
    assert tf._follow_once('dnc', env={'DN_FOLLOW_APPEND': '1'})[0] \
        == 0
    ctx['n'] = 180
    gens = mod_rollup.compaction_backlog(idx, 'day')
    assert gens > 0
    # one generation per group < min-gens 4: nothing is rewritten
    rc, out, err = run_cli(['compact', '--tree', idx,
                            '--interval', 'day', '--min-gens', '4'])
    assert rc == 0, err
    assert mod_rollup.compaction_backlog(idx, 'day') == gens
    rc, out, err = run_cli(['compact', '--tree', idx,
                            '--interval', 'day', '--min-gens', '1'])
    assert rc == 0, err
    assert mod_rollup.compaction_backlog(idx, 'day') == 0
    tf._assert_trees_equal(ctx, 'dnc', 'cli-compact')


# -- qcache: the result cache discipline -----------------------------------

class _Res(object):
    """Minimal ScanResult stand-in for size estimation."""

    def __init__(self, points):
        self.points = points
        self.dry_run_files = None
        self.pipeline = type('P', (), {'stages': []})()


class _Gov(object):
    def __init__(self, allow=True):
        self.allow = allow
        self.reserved = 0
        self.released = 0

    def reserve_cache(self, n):
        if not self.allow:
            return False
        self.reserved += n
        return True

    def release_cache(self, n):
        self.released += n


def test_qcache_disabled():
    c = mod_qcache.ResultCache(0)
    assert not c.enabled()
    assert not c.put('k', 1, [], _Res([1]))
    assert c.get('k', 1) is None
    assert c.stats()['enabled'] is False


def test_qcache_hit_miss_epoch():
    c = mod_qcache.ResultCache(1 << 20)
    r = _Res([['a', 1]])
    assert c.get('k', 1) is None            # miss
    assert c.put('k', 1, [], r)
    assert c.get('k', 1) is r               # hit, same object
    # an epoch bump (any in-process index write) retires the entry
    assert c.get('k', 2) is None
    s = c.stats()
    assert s['hits'] == 1 and s['misses'] == 2
    assert s['stale_drops'] == 1 and s['entries'] == 0
    assert 0 < s['hit_rate'] < 1


def test_qcache_validator_staleness(tmp_path):
    """A cross-process writer renames into the tree's directories —
    the stat validators catch what the in-process epoch cannot."""
    idx = str(tmp_path / 'idx')
    os.makedirs(os.path.join(idx, 'by_day'))
    c = mod_qcache.ResultCache(1 << 20)
    vals = mod_qcache.tree_validators(idx)
    assert c.put('k', 1, vals, _Res([1])) is True
    assert c.get('k', 1) is not None
    # a publish renames a shard into by_day: its identity changes
    with open(os.path.join(idx, 'by_day', 'x.sqlite'), 'w') as f:
        f.write('shard')
    assert c.get('k', 1) is None
    assert c.stats()['stale_drops'] == 1
    # a directory APPEARING later is a change too
    vals = mod_qcache.tree_validators(idx)
    assert c.put('k2', 1, vals, _Res([2]))
    os.makedirs(os.path.join(idx, 'rollup', 'by_month'))
    assert c.get('k2', 1) is None


def test_qcache_lru_and_budget():
    payload = ['x' * 100]
    one = mod_qcache._estimate_nbytes(_Res(payload))
    c = mod_qcache.ResultCache(int(one * 2.5))
    for k in ('a', 'b', 'c'):
        assert c.put(k, 1, [], _Res(payload))
    s = c.stats()
    assert s['evictions'] >= 1 and s['bytes'] <= c.budget
    assert c.get('a', 1) is None            # LRU victim
    assert c.get('c', 1) is not None
    # touching 'b' re-orders it ahead of 'c'
    assert c.get('b', 1) is not None
    assert c.put('d', 1, [], _Res(payload))
    assert c.get('c', 1) is None and c.get('b', 1) is not None
    # an entry bigger than the whole budget is shed outright
    assert not c.put('huge', 1, [], _Res(['y' * (one * 3)]))
    assert c.stats()['shed'] >= 1


def test_qcache_governor_shed_and_release():
    gov = _Gov()
    c = mod_qcache.ResultCache(1 << 20, governor=gov)
    assert c.put('a', 1, [], _Res([1]))
    assert gov.reserved > 0
    # the shared memory pool refuses: evict everything, then shed —
    # request admission outranks cache residency
    gov.allow = False
    assert not c.put('b', 1, [], _Res([2]))
    s = c.stats()
    assert s['shed'] == 1 and s['entries'] == 0
    assert gov.released == gov.reserved     # every byte handed back
    gov.allow = True
    assert c.put('c', 1, [], _Res([3]))
    c.clear()
    assert gov.released == gov.reserved
    assert c.stats()['entries'] == 0 and c.stats()['bytes'] == 0


# -- pool auto-degrade crossover -------------------------------------------

def test_degrade_crossover(monkeypatch):
    """The fan-out drops to the sequential cached walk exactly when
    the measured warm per-shard cost sits below DN_IQ_SEQ_MS (or the
    fan-out is too small to amortize dispatch), and ONLY in auto
    mode — an explicit operator pool size is always honored."""
    for k in ('DN_IQ_THREADS', 'DN_QUERY_CONCURRENCY',
              'DN_IQ_SEQ_MS', 'DN_IQ_MIN_PER_WORKER'):
        monkeypatch.delenv(k, raising=False)
    try:
        mod_iqmt._seq_ema_set(None)
        # too few shards per worker: sequential regardless of cost
        assert mod_iqmt.degrade_to_sequential(7, 4)
        # wide fan-out, no measurement yet: keep the pool
        assert not mod_iqmt.degrade_to_sequential(365, 4)
        # measured warm cost below the threshold: sequential wins
        mod_iqmt._seq_ema_set(0.5)
        assert mod_iqmt.degrade_to_sequential(365, 4)
        # crossover: cost climbs back above the threshold
        mod_iqmt._seq_ema_set(5.0)
        assert not mod_iqmt.degrade_to_sequential(365, 4)
        # a raised threshold moves the crossover with it
        monkeypatch.setenv('DN_IQ_SEQ_MS', '8.0')
        assert mod_iqmt.degrade_to_sequential(365, 4)
        # 'off' disables the heuristic entirely
        monkeypatch.setenv('DN_IQ_SEQ_MS', 'off')
        mod_iqmt._seq_ema_set(0.1)
        assert not mod_iqmt.degrade_to_sequential(365, 4)
        monkeypatch.delenv('DN_IQ_SEQ_MS')
        # operator override: explicit pool size disables auto
        monkeypatch.setenv('DN_IQ_THREADS', '3')
        assert not mod_iqmt.degrade_to_sequential(365, 3)
    finally:
        mod_iqmt._seq_ema_set(None)


def test_choose_fanout_measured_winner(monkeypatch):
    """Once both fan-out strategies have a measured whole-fan-out
    cost, the empirical winner is chosen regardless of the per-shard
    EMA prior (which pool-worker GIL convoying can inflate); until
    then the threshold prior routes, and each side gets measured."""
    for k in ('DN_IQ_THREADS', 'DN_QUERY_CONCURRENCY',
              'DN_IQ_SEQ_MS', 'DN_IQ_MIN_PER_WORKER'):
        monkeypatch.delenv(k, raising=False)
    try:
        mod_iqmt._fanout_reset()
        mod_iqmt._seq_ema_set(None)
        # nothing measured, EMA prior silent: pool explores first
        assert mod_iqmt._choose_fanout(365, 4) == 'pool'
        # pool measured, seq not: measure the other side
        mod_iqmt._note_fanout('pool', 0.65)
        assert mod_iqmt._choose_fanout(365, 4) == 'seq'
        # both measured: empirical winner, even though the convoy-
        # inflated per-shard EMA (3 ms > DN_IQ_SEQ_MS) says pool
        mod_iqmt._note_fanout('seq', 0.40)
        mod_iqmt._seq_ema_set(3.0)
        assert mod_iqmt._choose_fanout(365, 4) == 'seq'
        # ... and the other way around when the pool wins
        mod_iqmt._note_fanout('pool', 0.20)
        mod_iqmt._note_fanout('pool', 0.20)
        mod_iqmt._note_fanout('pool', 0.20)
        mod_iqmt._note_fanout('pool', 0.20)
        assert mod_iqmt._choose_fanout(365, 4) == 'pool'
        # one worker can overlap nothing: always the cached loop
        assert mod_iqmt._choose_fanout(365, 1) == 'seq'
        # tiny fan-out degrades regardless of measurements
        assert mod_iqmt._choose_fanout(7, 4) == 'seq'
        # explicit operator pool size is always honored
        monkeypatch.setenv('DN_IQ_THREADS', '3')
        assert mod_iqmt._choose_fanout(365, 3) == 'pool'
        assert mod_iqmt._choose_fanout(365, 1) == 'pool'
        st = mod_iqmt.fanout_stats()
        assert st['pool_ms_per_shard'] is not None
        assert st['last_mode'] == 'pool'
    finally:
        mod_iqmt._fanout_reset()
        mod_iqmt._seq_ema_set(None)


# -- serve integration: cached repeats + invalidation on write -------------

@pytest.fixture
def cache_corpus(tmp_path, monkeypatch):
    monkeypatch.setenv('DRAGNET_CONFIG', str(tmp_path / 'rc.json'))
    monkeypatch.setenv('DN_INDEX_FORMAT', 'dnc')
    datafile = str(tmp_path / 'data.log')
    tf._gen(datafile, 250)
    idx = str(tmp_path / 'idx')
    assert run_cli(['datasource-add', '--path', datafile,
                    '--index-path', idx, '--time-field', 'time',
                    'dsq'])[0] == 0
    assert run_cli(['metric-add', '-b',
                    'timestamp[date,field=time,aggr=lquantize,'
                    'step=86400],host,latency[aggr=quantize]',
                    'dsq', 'm1'])[0] == 0
    assert run_cli(['build', 'dsq'])[0] == 0
    return {'datafile': datafile, 'idx': idx,
            'sock': str(tmp_path / 'dn.sock')}


def test_serve_cached_repeat_and_invalidation(cache_corpus):
    """Repeat remote queries hit the result cache byte-identically;
    an in-process index write retires the entry and the next repeat
    serves the NEW bytes."""
    sock = cache_corpus['sock']
    srv = mod_server.DnServer(
        socket_path=sock,
        conf={'max_inflight': 4, 'queue_depth': 16, 'deadline_ms': 0,
              'coalesce': False, 'drain_s': 10,
              'cache_mb': 8}).start()
    try:
        case = ['query', '-b', 'host', 'dsq']
        remote = case[:1] + ['--remote', sock] + case[1:]
        local1 = run_cli(case)
        assert local1[0] == 0, local1[2]
        r1 = run_cli(remote)
        r2 = run_cli(remote)
        assert r1 == local1 and r2 == local1
        doc = mod_client.stats(sock, timeout_s=30.0)
        rstats = doc['caches']['results']
        assert rstats['enabled'] and rstats['hits'] >= 1
        assert rstats['misses'] >= 1
        # the /stats sections the planner and timer report through
        assert set(doc['rollup']) == {
            'covered_shards', 'rollup_shards_read', 'shards_queried',
            'coverage_ratio', 'plan_verdicts', 'manifest_loads'}
        assert doc['maintenance'] is None   # no timer configured

        # an index write (append + rebuild) bumps the cache epoch:
        # the repeat must serve the new bytes, not the cached old
        tf._gen(cache_corpus['datafile'], 50, start=250)
        assert run_cli(['build', 'dsq'])[0] == 0
        local2 = run_cli(case)
        assert local2[0] == 0 and local2[1] != local1[1]
        r3 = run_cli(remote)
        assert r3 == local2
        rstats = mod_client.stats(
            sock, timeout_s=30.0)['caches']['results']
        assert rstats['stale_drops'] >= 1
    finally:
        srv.stop()


def test_serve_maintenance_stats(cache_corpus, monkeypatch):
    """With a rollup/compaction timer configured the /stats
    `maintenance` section reports its intervals and pass counters."""
    monkeypatch.setenv('DN_ROLLUP_INTERVAL_S', '3600')
    monkeypatch.setenv('DN_COMPACT_INTERVAL_S', '3600')
    sock = cache_corpus['sock']
    srv = mod_server.DnServer(
        socket_path=sock,
        conf={'max_inflight': 4, 'queue_depth': 16, 'deadline_ms': 0,
              'coalesce': False, 'drain_s': 10}).start()
    try:
        maint = mod_client.stats(sock, timeout_s=30.0)['maintenance']
        assert maint is not None
        assert maint['rollup_interval_s'] == 3600
        assert maint['compact_interval_s'] == 3600
        assert maint['runs'] >= 0 and maint['last_error'] is None
    finally:
        srv.stop()


def test_rollup_litter_free(two_month_datafile, tmp_path,
                            monkeypatch):
    """Rollup builds and compactions leave no litter outside the
    quarantine/rollup state directories."""
    monkeypatch.setenv('DN_INDEX_FORMAT', 'dnc')
    idx = str(tmp_path / 'idx')
    ds = _make_ds(two_month_datafile, idx)
    ds.build([_metric()], 'day')
    mod_rollup.build_rollups(idx, 'day')
    mod_journal.reset_sweep_memo()
    bad = []
    for r, dirs, names in os.walk(idx):
        bad.extend(os.path.join(r, n) for n in names
                   if mod_journal.is_index_litter(n)
                   and not mod_journal.is_durable_metadata(n))
    assert bad == []
