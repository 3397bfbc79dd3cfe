"""What the rollup planner keeps between the queries of a resident
process (rollup._manifest, rollup._kept_verdict), and when it has to
let go of it.

The planner's rule is a guarantee: a rollup shard stands in for its
fine shards only while its manifest's sources equal the live files,
and "a stale substitute is worse than a slow fallback".  A resident
process keeps a level's parsed manifest under the file's stat identity
and a rollup's verdict under the proofs the query has already checked:
the fine directory's TreeSnapshot object, the manifest's identity, the
rollup shard's own stat, the walk's names, all for at most
DN_IQ_STAT_TTL_MS.  So:

* every write a query can observe (a rename into the fine directory
  with no hook, a shard added or removed, a follow generation, the
  rollup shard deleted, the in-process hook) makes THE VERY NEXT query
  check the bucket again and answer as a cold planner would;
* a write in place (os.utime: the directory does not move) is seen at
  the next query at TTL 0 and at the first query past the TTL
  otherwise;
* a racy snapshot keeps nothing;
* a steady covered query opens no manifest and stats no fine shard.
"""

import builtins
import json
import os
import random
import shutil
import sys
import threading
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from dragnet_tpu import index_query_mt as mod_iqmt         # noqa: E402
from dragnet_tpu import rollup as mod_rollup               # noqa: E402
from dragnet_tpu.obs import metrics as obs_metrics         # noqa: E402
from dragnet_tpu.vpipe import Pipeline                     # noqa: E402

import test_rollup as tr                                   # noqa: E402

BY_HOST = {'breakdowns': [{'name': 'host'}]}
VICTIM = '2014-02-10-07.sqlite'
LONG_TTL_MS = '600000'
NDAYS = 31                      # 2014-02-01 to 2014-03-03
NHOURS = NDAYS * 24


def _gen_every_hour(path):
    """One to three records in every hour of 2014-02-01 to 2014-03-03
    (all of February, three days of March): a tree with no hole, so
    the directory snapshot answers every bounded window."""
    rng = random.Random(47)
    with open(path, 'w') as f:
        for day in range(NDAYS):
            mon, dom = (2, day + 1) if day < 28 else (3, day - 27)
            for hour in range(24):
                for _ in range(rng.randrange(1, 4)):
                    f.write(json.dumps({
                        'host': 'host%d' % rng.randrange(12),
                        'operation': 'op%d' % rng.randrange(6),
                        'latency': rng.randrange(1, 500),
                        'time': '2014-%02d-%02dT%02d:%02d:00.000Z'
                                % (mon, dom, hour, rng.randrange(60)),
                    }, separators=(',', ':')) + '\n')


@pytest.fixture(scope='module', params=('dnc', 'sqlite'))
def built(request, tmp_path_factory):
    """The hourly tree in one index format, with its 31 day rollups
    and its two month rollups (all of February; March's three
    days)."""
    fmt = request.param
    root = tmp_path_factory.mktemp('rollup_memo_' + fmt)
    datafile, idx = str(root / 'data.json'), str(root / 'idx')
    _gen_every_hour(datafile)
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv('DN_INDEX_FORMAT', fmt)
        tr._make_ds(datafile, idx).build([tr._metric()], 'hour')
        doc = mod_rollup.build_rollups(idx, 'hour')
    assert doc['built'] == NDAYS + 2, doc
    assert len(os.listdir(os.path.join(idx, 'by_hour'))) == NHOURS
    mod_iqmt.shard_cache_clear()
    return {'fmt': fmt, 'datafile': datafile, 'idx': idx}


def _age(idx, manifests=True):
    """Put the tree's directories (and manifests) outside the racy
    margin, as a tree that was not written a moment ago is (the fine
    FILES keep their times: the manifests vouch for them)."""
    old = time.time() - 120
    for d in ('by_hour', 'rollup/by_month', 'rollup/by_day'):
        path = os.path.join(idx, d)
        man = mod_rollup.manifest_path(path)
        if manifests and os.path.exists(man):
            os.utime(man, (old, old))
        os.utime(path, (old, old))


class Tree(object):
    def __init__(self, built, idx):
        self.idx = idx
        self.finedir = os.path.join(idx, 'by_hour')
        self.ds = tr._make_ds(built['datafile'], idx)
        self.nshards = NHOURS
        self.nday = 24

    def fine(self, name=VICTIM):
        return os.path.join(self.finedir, name)

    def answer(self, conf=BY_HOST):
        """(points, the planner's hidden counters) of one query."""
        r = self.ds.query(tr._q(dict(conf)), 'hour')
        h = tr._hidden(r)
        return r.points, (h.get('index shards via rollup', 0),
                          h.get('rollup shards queried', 0))

    def cold_answer(self, conf=BY_HOST):
        """The same from a planner that keeps nothing and trusts
        nothing: the parent's."""
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv('DN_IQ_STAT_TTL_MS', '0')
            mod_rollup.planner_memo_drop()
            return self.answer(conf)


@pytest.fixture
def tree(built, tmp_path, monkeypatch):
    """A private copy of the built tree (copytree keeps the files'
    times, so the manifests still vouch), aged, under a stat TTL long
    enough that only a proof can retire a verdict, with the memo
    warm: the second query was answered from it."""
    monkeypatch.setenv('DN_INDEX_FORMAT', built['fmt'])
    monkeypatch.setenv('DN_IQ_THREADS', '0')
    monkeypatch.setenv('DN_IQ_STAT_TTL_MS', LONG_TTL_MS)
    idx = str(tmp_path / 'idx')
    shutil.copytree(built['idx'], idx, symlinks=True)
    _age(idx)
    mod_iqmt.shard_cache_clear()
    t = Tree(built, idx)
    t.base = t.answer()
    assert t.base[1] == (t.nshards, 2)
    before = verdicts()
    assert t.answer() == t.base
    assert grown(before) == {'kept': 2, 'checked': 0}
    yield t
    mod_iqmt.shard_cache_clear()


def verdicts():
    reg = obs_metrics.global_registry()
    return {r: reg.counter('rollup_plan_verdicts_total', result=r).value
            for r in ('kept', 'checked')}


def loads():
    reg = obs_metrics.global_registry()
    return {r: reg.counter('rollup_manifest_loads_total', result=r).value
            for r in ('kept', 'parsed')}


def grown(before, now=verdicts):
    after = now()
    return {k: after[k] - before[k] for k in after}


# -- every observable write retires the verdict at the very next query -----

def foreign_rename(t):
    """A foreign writer replaces a fine shard: tmp + rename, no hook.
    The same rows under a new identity: February and the victim's day
    are stale, the day's hours are read fine, the month's other 27
    days from their rollups."""
    tmp = t.fine() + '.foreign.tmp'
    shutil.copy(t.fine(), tmp)
    os.rename(tmp, t.fine())
    return (t.nshards - t.nday, 1 + 27)


def shard_added(t):
    """A fine shard nobody vouches for appears in March, on a day of
    its own: March's month rollup is stale, its three day rollups
    stand."""
    shutil.copy(t.fine(), t.fine('2014-03-20-05.sqlite'))
    return (t.nshards, 1 + 3)


def shard_removed(t):
    os.unlink(t.fine())
    return (t.nshards - t.nday, 1 + 27)


def generation_appended(t):
    """`dn follow --append` lands a mini-generation beside a base: the
    bucket's names are not the manifest's any more."""
    shutil.copy(t.fine(), t.fine(VICTIM + '-g000001'))
    return (t.nshards - t.nday, 1 + 27)


def rollup_deleted(t):
    os.unlink(os.path.join(t.idx, 'rollup', 'by_month',
                           '2014-02.sqlite'))
    return (t.nshards, 28 + 1)


WRITES = [foreign_rename, shard_added, shard_removed,
          generation_appended, rollup_deleted]


@pytest.mark.parametrize('write', WRITES, ids=lambda f: f.__name__)
def test_the_next_query_falls_back_for_the_bucket_touched(tree, write):
    """The very next query of the warm process answers what a cold
    planner answers: the touched bucket from its fine shards, the
    fine walk's bytes; `dn rollup` run again brings the rollups back
    at the query after it (a new manifest is a new identity)."""
    expected = write(tree)
    # the write was not a moment ago: the directory's new snapshot is
    # one that is kept, and only its identity tells the old verdicts
    _age(tree.idx, manifests=False)
    points, counters = tree.answer()
    assert counters == expected
    if write in (foreign_rename, rollup_deleted):
        assert points == tree.base[0]
    else:
        # the copy's rows count twice, the removed shard's not at all:
        # a stale rollup would have answered the old sums
        assert points != tree.base[0]
    assert tree.cold_answer() == (points, counters)

    doc = mod_rollup.build_rollups(tree.idx, 'hour')
    assert doc['built'] >= 1, doc
    again, counters = tree.answer()
    assert again == points
    nlogical = len(mod_rollup.logical_groups(
        sorted(os.listdir(tree.finedir))))
    assert counters == (nlogical, 2)
    assert tree.cold_answer() == (again, counters)


def test_the_in_process_hook_drops_the_trees_memo(tree):
    """invalidate_index_tree (what the serving layer calls after a
    build, a compaction or the maintenance timer's rollup pass) takes
    the tree's manifests and verdicts with its snapshots: the next
    query parses and checks everything, and keeps it again."""
    assert mod_rollup._VERDICTS and mod_rollup._MANIFESTS
    mod_iqmt.invalidate_index_tree(tree.idx)
    assert not mod_rollup._VERDICTS and not mod_rollup._MANIFESTS
    v, m = verdicts(), loads()
    assert tree.answer() == tree.base
    assert grown(v) == {'kept': 0, 'checked': 2}
    assert grown(m, loads) == {'kept': 0, 'parsed': 2}
    v, m = verdicts(), loads()
    assert tree.answer() == tree.base
    assert grown(v) == {'kept': 2, 'checked': 0}
    assert grown(m, loads) == {'kept': 2, 'parsed': 0}


def test_another_trees_hook_leaves_this_trees_memo(tree, tmp_path):
    mod_iqmt.invalidate_index_tree(str(tmp_path / 'elsewhere'))
    v = verdicts()
    assert tree.answer() == tree.base
    assert grown(v) == {'kept': 2, 'checked': 0}


# -- each proof on its own --------------------------------------------------

def _replace_by_copy(path):
    """The same bytes under a new inode, a while ago."""
    shutil.copy2(path, path + '.tmp')
    os.rename(path + '.tmp', path)


def test_a_replaced_rollup_shard_is_asked_about_again(tree):
    """The rollup shard's own identity is one of the proofs: replaced
    (the same rows, a new inode), its bucket is checked again, the
    other month's verdict stays."""
    leveldir = os.path.join(tree.idx, 'rollup', 'by_month')
    _replace_by_copy(os.path.join(leveldir, '2014-02.sqlite'))
    v = verdicts()
    assert tree.answer() == tree.base
    assert grown(v) == {'kept': 1, 'checked': 1}


def test_a_new_manifest_retires_its_levels_verdicts(tree):
    """So is the manifest's: `dn rollup` writes a new one by tmp +
    rename, and what the old one vouched for is asked again."""
    leveldir = os.path.join(tree.idx, 'rollup', 'by_month')
    _replace_by_copy(mod_rollup.manifest_path(leveldir))
    v, m = verdicts(), loads()
    assert tree.answer() == tree.base
    assert grown(v) == {'kept': 0, 'checked': 2}
    assert grown(m, loads) == {'kept': 1, 'parsed': 1}


def test_other_names_under_one_snapshot_are_asked_about_again(tree):
    """And the walk's names in the bucket: a walk that met a file less
    (a generation that vanished between the listing and its stat is
    skipped) does not take the verdict of the one that met it."""
    query = tr._q(dict(BY_HOST))
    _r, _t, files, snap = tree.ds._index_query_walk(
        query, 'hour', Pipeline())
    paths = [p for p, _st in files]

    def plan(paths):
        v = verdicts()
        plan = mod_rollup.plan_query(tree.idx, 'hour', paths, query,
                                     snap=snap)
        return (plan['ncovered'], plan['nrollup']), grown(v)
    assert plan(paths) == (tree.base[1], {'kept': 2, 'checked': 0})
    fewer = [p for p in paths if not p.endswith(VICTIM)]
    # February is not what its manifest says, nor is the victim's day
    assert plan(fewer) == ((tree.nshards - tree.nday, 1 + 27),
                           {'kept': 1, 'checked': 1 + 28})
    # and the full walk is not answered by what the short one learned
    assert plan(paths) == (tree.base[1], {'kept': 1, 'checked': 1})


# -- a write in place: the stat TTL is the bound ---------------------------

def _touch_in_place(path):
    """Age a fine shard without renaming anything: the directory's
    identity does not move, only the file's own stat tells."""
    st = os.stat(path)
    os.utime(path, ns=(st.st_atime_ns, st.st_mtime_ns + 1000000))


def test_utime_in_place_at_ttl_zero_is_seen_by_the_next_query(
        tree, monkeypatch):
    monkeypatch.setenv('DN_IQ_STAT_TTL_MS', '0')
    v = verdicts()
    assert tree.answer() == tree.base
    # nothing is taken from the memo at TTL 0: every candidate bucket
    # is checked, every fine source statted
    assert grown(v) == {'kept': 0, 'checked': 2}
    _touch_in_place(tree.fine())
    v = verdicts()
    points, counters = tree.answer()
    assert points == tree.base[0]
    assert counters == (tree.nshards - tree.nday, 1 + 27)
    # the two months, then February's 28 days
    assert grown(v) == {'kept': 0, 'checked': 2 + 28}


def test_utime_in_place_is_seen_by_the_first_query_past_the_ttl(
        tree, monkeypatch):
    ttl_s = 0.4
    monkeypatch.setenv('DN_IQ_STAT_TTL_MS', str(int(ttl_s * 1000)))
    time.sleep(ttl_s + 0.05)
    v = verdicts()
    assert tree.answer() == tree.base
    assert grown(v) == {'kept': 0, 'checked': 2}   # older than the TTL
    _touch_in_place(tree.fine())
    time.sleep(ttl_s + 0.05)
    points, counters = tree.answer()
    assert points == tree.base[0]
    assert counters == (tree.nshards - tree.nday, 1 + 27)
    assert tree.cold_answer() == (points, counters)


def test_no_snapshot_no_memo(tree):
    """A caller with no snapshot (a warn_func consumer, a window the
    snapshot cannot answer) plans as the parent does."""
    paths = sorted(os.path.join(tree.finedir, n)
                   for n in os.listdir(tree.finedir))
    query = tr._q(dict(BY_HOST))
    v = verdicts()
    plan = mod_rollup.plan_query(tree.idx, 'hour', paths, query)
    assert (plan['ncovered'], plan['nrollup']) == tree.base[1]
    assert grown(v) == {'kept': 0, 'checked': 2}


# -- a racy snapshot keeps nothing -----------------------------------------

def test_a_racy_snapshot_keeps_no_verdict(tree):
    """A directory written inside the racy margin cannot prove its
    listing current (index_query_mt._RACY_MARGIN_NS): its snapshot
    serves one query, and so does every verdict taken under it."""
    os.utime(tree.finedir)
    mod_rollup.planner_memo_drop()
    for _ in range(3):
        v = verdicts()
        assert tree.answer() == tree.base
        assert grown(v) == {'kept': 0, 'checked': 2}
        assert not mod_rollup._VERDICTS


def test_a_racy_manifest_is_not_kept(tree):
    """Nor is a manifest younger than the margin, nor a verdict read
    from it."""
    os.utime(mod_rollup.manifest_path(
        os.path.join(tree.idx, 'rollup', 'by_month')))
    for _ in range(2):
        v, m = verdicts(), loads()
        assert tree.answer() == tree.base
        assert grown(v) == {'kept': 0, 'checked': 2}
        # by_day's is old and stays kept
        assert grown(m, loads) == {'kept': 1, 'parsed': 1}
    assert all(not d.endswith('by_month') for d in mod_rollup._MANIFESTS)


@pytest.mark.parametrize('content', [None, b'{"version": 1', b'[]',
                                     b'{"version": 99, "shards": {}}'])
def test_no_valid_manifest_is_no_rollups_and_is_not_kept(tree, content):
    """Absent, unreadable or wrong-shape: the level has no valid
    rollups, the answer is the fine walk's (here: every day by its
    day rollup), and nothing of it is kept."""
    leveldir = os.path.join(tree.idx, 'rollup', 'by_month')
    man = mod_rollup.manifest_path(leveldir)
    os.unlink(man)
    if content is not None:
        with open(man, 'wb') as f:
            f.write(content)
        old = time.time() - 120
        os.utime(man, (old, old))
    assert mod_rollup.load_manifest(leveldir) is None
    assert leveldir not in mod_rollup._MANIFESTS
    points, counters = tree.answer()
    assert points == tree.base[0]
    assert counters == (tree.nshards, NDAYS)


# -- what a steady query costs ---------------------------------------------

def test_a_steady_covered_query_stats_no_fine_shard(tree, monkeypatch):
    """Under index_query.plan, with the memo warm, a covered query of
    the whole tree opens no manifest and stats no fine shard: the
    rollup root, a manifest a level, a rollup shard each."""
    stats, opens = [], []
    real_plan, real_stat, real_open = \
        mod_rollup.plan_query, os.stat, builtins.open

    def plan(*args, **kwargs):
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(os, 'stat', lambda p, *a, **kw: (
                stats.append(str(p)), real_stat(p, *a, **kw))[1])
            mp.setattr(builtins, 'open', lambda p, *a, **kw: (
                opens.append(str(p)), real_open(p, *a, **kw))[1])
            return real_plan(*args, **kwargs)
    monkeypatch.setattr(mod_rollup, 'plan_query', plan)
    conf = dict(BY_HOST, timeAfter='2014-02-01', timeBefore='2014-03-04')
    bounded = tree.answer(conf)
    # February by its month, March's three days by theirs
    assert bounded[1] == (tree.nshards, 1 + 3)
    del stats[:], opens[:]
    assert tree.answer(conf) == bounded
    assert opens == []
    assert not [p for p in stats if p.startswith(tree.finedir)]
    assert len(stats) <= 3 + 4, stats

    # and the planner that keeps nothing stats every source
    monkeypatch.setenv('DN_IQ_STAT_TTL_MS', '0')
    del stats[:], opens[:]
    assert tree.answer(conf) == bounded
    assert opens == []         # the manifest stays kept under its stat
    assert len([p for p in stats if p.startswith(tree.finedir)]) == \
        tree.nshards


# -- the plans themselves --------------------------------------------------

TEMPLATES = [tr.ROLLUP_QUERIES[i][1] for i in (0, 1, 2, 3)]
WINDOWS = [('1d', '2014-02-10', '2014-02-11'),
           ('7d', '2014-02-26', '2014-03-04'),
           ('28d', '2014-02-01', '2014-03-01'),
           ('31d', '2014-02-01', '2014-03-04')]


@pytest.mark.parametrize('window', WINDOWS, ids=lambda w: w[0])
@pytest.mark.parametrize('template', range(len(TEMPLATES)))
def test_a_warm_plan_is_the_cold_plan(tree, monkeypatch, template,
                                      window):
    """Sixteen classes in the shape of the hourly cell's traffic (four
    templates by four whole-day windows): planned from a warm memo and
    by a planner that checks everything, the units are the same."""
    conf = dict(TEMPLATES[template], timeAfter=window[1],
                timeBefore=window[2])
    query = tr._q(conf)

    def plan():
        root, timeformat, files, snap = tree.ds._index_query_walk(
            query, 'hour', Pipeline())
        assert snap is not None
        return mod_rollup.plan_query(tree.idx, 'hour',
                                     [p for p, _st in files], query,
                                     snap=snap)
    plan()
    v = verdicts()
    warm = plan()
    got = grown(v)
    assert got['checked'] == 0 and got['kept'] >= 1
    monkeypatch.setenv('DN_IQ_STAT_TTL_MS', '0')
    v = verdicts()
    cold = plan()
    assert grown(v) == {'kept': 0, 'checked': got['kept']}
    assert warm == cold
    assert warm['ncovered'] == warm['nlogical'] > 0


# -- the memo under the server's slots -------------------------------------

def test_slots_planning_beside_a_writer_never_see_the_stale_rollup(
        tree, monkeypatch):
    """Eight threads plan the whole tree from the shared memo (its cap
    cut to two, so it is cleared under them all the time) while a
    foreign writer replaces a fine shard: no plan whose walk began
    after the rename landed counts the victim's day as covered, and
    nothing raises."""
    monkeypatch.setattr(mod_rollup, '_VERDICT_CAP', 2)
    query = tr._q(dict(BY_HOST))
    stale_free = (tree.nshards - tree.nday, 1 + 27)
    written = []
    plans, errors = [], []
    stop = threading.Event()

    def slot():
        try:
            while not stop.is_set():
                began = time.monotonic()
                _r, _t, files, snap = tree.ds._index_query_walk(
                    query, 'hour', Pipeline())
                plan = mod_rollup.plan_query(
                    tree.idx, 'hour', [p for p, _st in files], query,
                    snap=snap)
                plans.append((began, (plan['ncovered'], plan['nrollup'])))
        except BaseException as e:      # reported below
            errors.append(e)
            raise

    threads = [threading.Thread(target=slot, daemon=True)
               for _ in range(8)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for th in threads:
            th.start()
        time.sleep(0.3)
        foreign_rename(tree)
        written.append(time.monotonic())
        time.sleep(0.3)
    finally:
        stop.set()
        for th in threads:
            th.join(timeout=30)
        sys.setswitchinterval(interval)
    assert not [th for th in threads if th.is_alive()]
    assert not errors
    before = [c for began, c in plans if began < written[0]]
    after = [c for began, c in plans if began >= written[0]]
    assert before and after
    assert set(after) == {stale_free}
    assert set(before) <= {tree.base[1], stale_free}
