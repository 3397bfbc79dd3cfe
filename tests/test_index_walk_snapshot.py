"""The directory snapshot behind an index query's plan
(index_query_mt.TreeSnapshot): what it answers equals what the walk
(`_find`), `rollup.augment_generation_files`, `prune_shards` and
`count_pruned_shards` give on the same tree, counter for counter; a
change to the directory is seen by the next query; and a steady query
costs one `os.stat`.

The trees of the plan tests are empty files under the layouts' names:
the plan never opens a shard.  A snapshot is kept only when the
directory's mtime is older than the racy margin, so the tests age the
directory (`_age`) where they want the second call to be a hit."""

import json
import os
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from dragnet_tpu import index_journal as mod_journal  # noqa: E402
from dragnet_tpu import index_query_mt as mod_iqmt  # noqa: E402
from dragnet_tpu import query as mod_query  # noqa: E402
from dragnet_tpu import rollup as mod_rollup  # noqa: E402
from dragnet_tpu.datasource_file import DatasourceFile  # noqa: E402
from dragnet_tpu.errors import DNError  # noqa: E402
from dragnet_tpu.vpipe import Pipeline  # noqa: E402

T0 = 1398902400000          # 2014-05-01T00:00:00Z
TREES = {
    # interval: (subdir, unit ms, units in the tree, name of unit i)
    'day': ('by_day', 86400000, 40),
    'hour': ('by_hour', 3600000, 72),
}


def _name(interval, i):
    ms = T0 + i * TREES[interval][1]
    fmt = '%Y-%m-%d.sqlite' if interval == 'day' else '%Y-%m-%d-%H.sqlite'
    return time.strftime(fmt, time.gmtime(ms // 1000))


# (first unit, last unit + 1) of a window, in units from the tree's
# start; the half units put a bound inside a shard's window
WINDOWS = {
    'inside': (3.5, 9.25),
    'one': (7, 8),
    'long': (2, 31),
    'whole': (0, None),
    'astride_start': (-2, 3),
    'astride_end': (-3.5, 2),     # counted from the tree's end
    'outside': (-9, -4),
    'unbounded': None,
}


def _window(interval, which):
    _sub, unit, n = TREES[interval]
    if WINDOWS[which] is None:
        return None, None
    a, b = WINDOWS[which]
    if which == 'astride_end':
        a, b = n + a, n + b
    if b is None:
        b = n
    return int(T0 + a * unit), int(T0 + b * unit)


def _ds(idx):
    return DatasourceFile({
        'ds_backend': 'file',
        'ds_backend_config': {'path': '/dev/null', 'timeField': 'time',
                              'indexPath': idx},
        'ds_filter': None, 'ds_format': 'json'})


def _query(after, before):
    conf = {'breakdowns': [{'name': 'host'}]}
    if after is not None:
        conf['timeAfter'] = after
        conf['timeBefore'] = before
    q = mod_query.query_load(conf)
    assert not isinstance(q, DNError), q
    return q


def _touch(path):
    with open(path, 'w'):
        pass


def _age(root):
    """Put the directory's mtime where a snapshot of it can be proved
    current (index_query_mt._RACY_MARGIN_NS)."""
    old = time.time() - 60
    os.utime(root, (old, old))


def _make_tree(tmp_path, interval, variant='plain'):
    sub, _unit, n = TREES[interval]
    idx = str(tmp_path / 'idx')
    root = os.path.join(idx, sub)
    os.makedirs(root)
    for i in range(n):
        _touch(os.path.join(root, _name(interval, i)))
    if variant == 'generations':
        for i, gens in ((4, (1, 2)), (7, (3,)), (n - 1, (1,)), (20, (12, 2))):
            for g in gens:
                _touch(os.path.join(
                    root, '%s-g%06d' % (_name(interval, i), g)))
    elif variant == 'litter':
        _touch(os.path.join(root, mod_journal.JOURNAL_PREFIX + '77.1'))
        _touch(os.path.join(root, _name(interval, 5) + '.%d.1'
                            % os.getpid()))
        _touch(os.path.join(root, mod_journal.INTEGRITY_NAME))
        _touch(os.path.join(root, 'README'))
        for d in (mod_journal.QUARANTINE_DIR, mod_journal.ROLLUP_DIR,
                  mod_journal.FOLLOW_DIR):
            os.makedirs(os.path.join(root, d))
            _touch(os.path.join(root, d, _name(interval, 6)))
    elif variant == 'deleted':
        os.unlink(os.path.join(root, _name(interval, 8)))
    _age(root)
    return idx, root


@pytest.fixture(autouse=True)
def fresh(monkeypatch):
    # the journal's sweep would clear the litter the trees plant
    monkeypatch.setattr(mod_journal, 'maybe_sweep', lambda indexroot: None)
    mod_iqmt.shard_cache_clear()
    yield
    mod_iqmt.shard_cache_clear()


def _stat_id(st):
    return (st.st_mode, st.st_ino, st.st_size, st.st_mtime_ns)


def _stages(pipeline):
    return [(s.name, dict(s.counters), sorted(s.hidden))
            for s in pipeline.stages]


def _plan(ds, interval, query):
    pipeline = Pipeline()
    root, timeformat, files, snap = ds._index_query_walk(
        query, interval, pipeline)
    assert (root, timeformat, files) == ds.index_query_paths(
        query, interval, Pipeline())
    kept, npruned = ds._prune_index_paths(root, timeformat, files, snap,
                                          query)
    return {'root': root, 'timeformat': timeformat,
            'files': [(p, _stat_id(st)) for p, st in files],
            'kept': kept, 'npruned': npruned,
            'stages': _stages(pipeline)}


def _reference_plan(ds, interval, query):
    """Today's functions, whole."""
    pipeline = Pipeline()
    root, timeformat, after, before = ds.index_find_params(
        interval, query.qc_after, query.qc_before)
    # a builder's tmps and journals are no part of the tree for the
    # walk, in its counters as in its files (a resident server's
    # builds prepare beside its queries)
    files = ds._find(root, timeformat, after, before, pipeline,
                     skip=mod_journal.is_index_litter)
    files = [(p, st) for p, st in files
             if not mod_journal.is_index_litter(p)]
    files = mod_rollup.augment_generation_files(root, files)
    kept, npruned = mod_iqmt.prune_shards(
        [p for p, st in files], timeformat, query.qc_after,
        query.qc_before)
    npruned = max(npruned, mod_iqmt.count_pruned_shards(
        root, timeformat, query.qc_after, query.qc_before))
    return {'root': root, 'timeformat': timeformat,
            'files': [(p, _stat_id(st)) for p, st in files],
            'kept': kept, 'npruned': npruned,
            'stages': _stages(pipeline)}


# -- equality ---------------------------------------------------------------

# which windows the snapshot answers itself on an intact tree; the
# others name a shard the tree does not have and take `_find`
ANSWERED = ('inside', 'one', 'long', 'whole', 'unbounded')


@pytest.mark.parametrize('variant',
                         ['plain', 'generations', 'litter', 'deleted'])
@pytest.mark.parametrize('which', sorted(WINDOWS))
@pytest.mark.parametrize('interval', sorted(TREES))
def test_plan_equals_the_walks(tmp_path, interval, which, variant):
    idx, _root = _make_tree(tmp_path, interval, variant)
    ds = _ds(idx)
    query = _query(*_window(interval, which))
    want = _reference_plan(ds, interval, query)
    cold = _plan(ds, interval, query)
    before = mod_iqmt.find_cache_stats()
    warm = _plan(ds, interval, query)
    after = mod_iqmt.find_cache_stats()
    assert cold == want
    assert warm == want
    # the second call read a kept snapshot (twice: _plan asks
    # index_query_paths too), and nothing was read anew
    assert after['snapshot_hits'] == before['snapshot_hits'] + 2
    assert after['snapshot_rebuilds'] == before['snapshot_rebuilds'] == \
        {'cold': 1}


@pytest.mark.parametrize('which', ANSWERED)
@pytest.mark.parametrize('interval', sorted(TREES))
def test_answered_windows_never_reach_find(tmp_path, monkeypatch, interval,
                                           which):
    """On an intact tree a window inside it is the snapshot's to
    answer: `_find` is not called, cold or warm."""
    idx, _root = _make_tree(tmp_path, interval)
    ds = _ds(idx)
    query = _query(*_window(interval, which))
    want = _reference_plan(ds, interval, query)

    def no_find(*args, **kwargs):
        raise AssertionError('_find called')
    monkeypatch.setattr(ds, '_find', no_find)
    assert _plan(ds, interval, query) == want
    assert _plan(ds, interval, query) == want


def test_warn_func_takes_the_real_walk(tmp_path):
    """A walk that may warn keeps today's `_find`: its warnings carry
    the stat's own error."""
    idx, root = _make_tree(tmp_path, 'day', 'deleted')
    ds = _ds(idx)
    query = _query(*_window('day', 'long'))
    warned = []
    pipeline = Pipeline()
    pipeline.warn_func = lambda stage, kind, error: warned.append(
        (stage.name, kind, str(error)))
    ds.index_query_paths(query, 'day', pipeline)
    assert [w[:2] for w in warned] == [('FindStatter', 'badstat')]
    assert _name('day', 8) in warned[0][2]
    assert mod_iqmt.find_cache_stats()['snapshot_rebuilds'] == {}


# -- the CLI's bytes ----------------------------------------------------------

def _make_data(path, n=1500):
    with open(path, 'w') as f:
        for i in range(n):
            rec = {'host': 'host%d' % (i % 7), 'latency': 1 + i % 900,
                   'time': time.strftime(
                       '%Y-%m-%dT%H:%M:%S.000Z',
                       time.gmtime(T0 // 1000 + i * 600))}
            f.write(json.dumps(rec, separators=(',', ':')) + '\n')


@pytest.fixture()
def built(tmp_path, monkeypatch):
    """A real day tree of 11 shards behind a `dn` configuration."""
    from parity.runner import DnRunner
    monkeypatch.setenv('DN_ENGINE', 'vector')
    datafile = str(tmp_path / 'data.log')
    idx = str(tmp_path / 'idx')
    _make_data(datafile)
    r = DnRunner(tmp_path)
    r.clear_config()
    r.dn('datasource-add', 'input', '--path=' + datafile,
         '--index-path=' + idx, '--time-field=time')
    r.dn('metric-add', 'input', 'met', '-b',
         'timestamp[date,field=time,aggr=lquantize,step=86400],host,'
         'latency[aggr=quantize]')
    r.dn('build', 'input')
    root = os.path.join(idx, 'by_day')
    assert len(os.listdir(root)) == 11
    _age(root)
    mod_iqmt.shard_cache_clear()
    return r, root


@pytest.mark.parametrize('window', [
    [], ['--after', '2014-05-03', '--before', '2014-05-09'],
    ['--after', '2014-05-03T12:00:00Z', '--before', '2014-05-04']])
def test_cli_counters_byte_identical_cold_and_warm(built, monkeypatch,
                                                   window):
    """`dn query --counters` (all of them: DN_COUNTERS_ALL) prints the
    same bytes from `_find`, from a snapshot read for this query and
    from a kept one."""
    r, _root = built
    monkeypatch.setenv('DN_COUNTERS_ALL', '1')
    args = ['query', '-b', 'host', '--counters'] + window + ['input']

    def run():
        out, err, rc = r.run(args)
        assert rc == 0, err
        # the handle cache's hit and miss counters tell a first query
        # from a second whatever the walk
        return out + ''.join(
            line for line in err.splitlines(True)
            if 'index handle cache' not in line)

    with monkeypatch.context() as mp:
        mp.setattr(mod_iqmt, 'tree_snapshot', lambda root: None)
        want = run()
    assert 'FindStatter' in want and 'index shards queried' in want
    mod_iqmt.shard_cache_clear()
    cold = run()
    warm = run()
    stats = mod_iqmt.find_cache_stats()
    assert (stats['snapshot_rebuilds'], stats['snapshot_hits']) == \
        ({'cold': 1}, 1)
    assert cold == want
    assert warm == want


# -- freshness ----------------------------------------------------------------

def _paths(ds, query):
    return [p for p, _st in ds.index_query_paths(query, 'day',
                                                 Pipeline())[2]]


def _rename_in(root, name):
    tmp = os.path.join(os.path.dirname(root), 'incoming')
    _touch(tmp)
    os.rename(tmp, os.path.join(root, name))


@pytest.mark.parametrize('change', ['added', 'removed', 'generation'])
def test_a_change_to_the_directory_is_seen_by_the_next_query(tmp_path,
                                                            change):
    idx, root = _make_tree(tmp_path, 'day',
                           'deleted' if change == 'added' else 'plain')
    ds = _ds(idx)
    query = _query(*_window('day', 'long'))
    first = _paths(ds, query)
    assert _paths(ds, query) == first
    shard = os.path.join(root, _name('day', 8))
    if change == 'added':
        assert shard not in first
        _rename_in(root, _name('day', 8))
        want = sorted(first + [shard])
    elif change == 'removed':
        os.unlink(shard)
        want = [p for p in first if p != shard]
    else:
        _rename_in(root, _name('day', 8) + '-g000001')
        want = list(first)
        want.insert(want.index(shard) + 1, shard + '-g000001')
    assert _paths(ds, query) == want
    assert mod_iqmt.find_cache_stats()['snapshot_rebuilds'].get(
        'identity') == 1


@pytest.mark.parametrize('how', ['shard', 'tree'])
def test_writers_invalidations_drop_the_snapshot(tmp_path, how):
    idx, root = _make_tree(tmp_path, 'day')
    ds = _ds(idx)
    query = _query(*_window('day', 'inside'))
    first = _paths(ds, query)
    assert mod_iqmt.find_cache_stats()['size'] == 1
    if how == 'shard':
        mod_iqmt.shard_cache_invalidate(
            os.path.join(root, _name('day', 30)))
    else:
        mod_iqmt.invalidate_index_tree(idx)
    assert mod_iqmt.find_cache_stats()['size'] == 0
    assert _paths(ds, query) == first
    stats = mod_iqmt.find_cache_stats()
    assert stats['snapshot_rebuilds'] == {'cold': 1, 'invalidated': 1}
    assert stats['size'] == 1


def test_a_rename_in_the_snapshots_own_tick_is_seen(tmp_path):
    """The racy rule: a directory last written in the moment its
    snapshot was read may be written again under the same timestamp, so
    that snapshot answers its own query and is not kept."""
    idx, root = _make_tree(tmp_path, 'day', 'deleted')
    ds = _ds(idx)
    query = _query(*_window('day', 'long'))
    now = time.time_ns()
    os.utime(root, ns=(now, now))
    first = _paths(ds, query)
    assert mod_iqmt.find_cache_stats()['size'] == 0
    # a rename that leaves the directory's identity as it was
    _rename_in(root, _name('day', 8))
    os.utime(root, ns=(now, now))
    assert mod_iqmt._statkey(root)[0] == now
    second = _paths(ds, query)
    assert second == sorted(first + [os.path.join(root, _name('day', 8))])
    assert mod_iqmt.find_cache_stats()['snapshot_rebuilds'] == \
        {'cold': 1, 'racy': 1}
    # and once the directory has aged, its snapshot is kept
    _age(root)
    assert _paths(ds, query) == second
    assert _paths(ds, query) == second
    stats = mod_iqmt.find_cache_stats()
    assert (stats['size'], stats['snapshot_hits']) == (1, 1)


# -- cost ---------------------------------------------------------------------

class _Calls(object):
    """os.stat and os.listdir counted."""

    def __init__(self, monkeypatch):
        self.stat = self.listdir = 0
        real_stat, real_listdir = os.stat, os.listdir

        def stat(*args, **kwargs):
            self.stat += 1
            return real_stat(*args, **kwargs)

        def listdir(*args, **kwargs):
            self.listdir += 1
            return real_listdir(*args, **kwargs)
        monkeypatch.setattr(os, 'stat', stat)
        monkeypatch.setattr(os, 'listdir', listdir)

    def take(self):
        got = (self.stat, self.listdir)
        self.stat = self.listdir = 0
        return got


@pytest.mark.parametrize('interval', sorted(TREES))
def test_a_steady_query_costs_one_stat(tmp_path, monkeypatch, interval):
    idx, _root = _make_tree(tmp_path, interval, 'generations')
    ds = _ds(idx)
    seven = _query(*_window(interval, 'inside'))
    calls = _Calls(monkeypatch)
    _reference_plan(ds, interval, seven)
    todays = calls.take()
    # 7 names and 3 generations statted, the directory listed for the
    # generations and again for the pruned count
    assert todays == (10, 2)
    _plan_once(ds, interval, seven)
    cold = calls.take()
    # the directory's stat more, a listing less
    assert cold == (11, 1)
    assert sum(cold) <= sum(todays)
    _plan_once(ds, interval, seven)
    assert calls.take() == (1, 0)
    # another window of the same tree: its own names' stats, once
    other = _query(*_window(interval, 'long'))
    _plan_once(ds, interval, other)
    # 22 of its 29 names and 2 of its 5 generations are new
    assert calls.take() == (1 + 22 + 2, 0)
    _plan_once(ds, interval, other)
    assert calls.take() == (1, 0)


def _plan_once(ds, interval, query):
    root, timeformat, files, snap = ds._index_query_walk(
        query, interval, Pipeline())
    return ds._prune_index_paths(root, timeformat, files, snap, query)
