"""A build published into a tree that a resident `dn serve` is
answering queries from: the write side of the tree's lock
(serve/admission.TreeLock) is held for the build's COMMIT
(index_build_mt.commit_prepared, through `commit_guard`), not for its
scan; the tree's build mutex keeps two builds of one tree apart; an
acknowledged build is read back, with the result cache on; and the
lock's and the caches' series appear in a scrape.
"""

import json
import os
import sys
import threading
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from dragnet_tpu import cli                                # noqa: E402
from dragnet_tpu import index_build_mt as mod_ibmt         # noqa: E402
from dragnet_tpu import index_query_mt as mod_iqmt         # noqa: E402
from dragnet_tpu.obs import export as obs_export           # noqa: E402
from dragnet_tpu.obs import metrics as obs_metrics         # noqa: E402
from dragnet_tpu.serve import admission as mod_admission   # noqa: E402
from dragnet_tpu.serve import client as mod_client         # noqa: E402
from dragnet_tpu.serve import server as mod_server         # noqa: E402

T0 = 1388534400              # 2014-01-01T00:00:00Z
DAY = 86400
NDAYS = 9                    # days 0 and 1 stand; 2..8 are published
PER_DAY = 40
WAIT = 30.0                  # an event that never comes fails the test


def run_cli(args):
    with mod_server.thread_stdio() as cap:
        rc = cli.main(list(args))
    out, err = cap.finish()
    return rc, out, err


def iso(day):
    return time.strftime('%Y-%m-%d', time.gmtime(T0 + day * DAY))


@pytest.fixture
def tree(tmp_path, monkeypatch):
    """A corpus of NDAYS days in one file with no timeFormat (so a
    bounded build reads it all and keeps its window), one metric, and
    days 0 and 1 built: the standing tree."""
    datafile = str(tmp_path / 'data.log')
    with open(datafile, 'w') as f:
        for i in range(NDAYS * PER_DAY):
            ts = time.strftime(
                '%Y-%m-%dT%H:%M:%S.000Z',
                time.gmtime(T0 + i * (DAY // PER_DAY)))
            f.write(json.dumps({
                'time': ts, 'host': 'host%d' % (i % 3),
                'latency': (i * 7) % 230,
            }, separators=(',', ':')) + '\n')
    rc_path = str(tmp_path / 'dragnetrc.json')
    monkeypatch.setenv('DRAGNET_CONFIG', rc_path)
    monkeypatch.delenv('DN_ENGINE', raising=False)
    for args in (
            ['datasource-add', '--path', datafile, '--index-path',
             str(tmp_path / 'idx'), '--time-field', 'time', 'live'],
            ['metric-add', '-b',
             'timestamp[date,field=time,aggr=lquantize,step=86400],'
             'host,latency[aggr=quantize]', 'live', 'm1'],
            ['build', '--after', iso(0), '--before', iso(2), 'live']):
        rc, out, err = run_cli(args)
        assert rc == 0, err
    return {'rc_path': rc_path, 'root': tmp_path,
            'by_day': str(tmp_path / 'idx' / 'by_day')}


@pytest.fixture
def server(tree, tmp_path):
    conf = {'max_inflight': 4, 'queue_depth': 16, 'deadline_ms': 0,
            'coalesce': True, 'drain_s': 10, 'cache_mb': 8}
    srv = mod_server.DnServer(socket_path=str(tmp_path / 'dn.sock'),
                              conf=conf).start()
    try:
        yield srv
    finally:
        srv.stop()


def ms(day):
    return (T0 + day * DAY) * 1000


def query(srv, tree, after_day=None, before_day=None):
    """`dn query --remote -b host --points` over [after, before) days;
    the reply's points as {host: count}."""
    qc = {'breakdowns': [{'name': 'host', 'field': 'host'}]}
    if after_day is not None:
        qc['timeAfter'], qc['timeBefore'] = ms(after_day), ms(before_day)
    rc, _, out, err = mod_client.request_bytes(
        srv.socket_path,
        {'op': 'query', 'ds': 'live', 'config': tree['rc_path'],
         'interval': 'day', 'queryconfig': qc,
         'opts': {'points': True}}, timeout_s=WAIT)
    assert rc == 0, err
    got = {}
    for line in out.decode().splitlines():
        doc = json.loads(line)
        got[doc['fields']['host']] = doc['value']
    return got


def build(srv, tree, after_day, before_day):
    rc, _, out, err = mod_client.request_bytes(
        srv.socket_path,
        {'op': 'build', 'ds': 'live', 'config': tree['rc_path'],
         'interval': 'day', 'after': ms(after_day),
         'before': ms(before_day), 'index_config': None,
         'idempotency': os.urandom(8).hex(), 'opts': {}},
        timeout_s=WAIT)
    return rc, err


def total(points):
    return sum(points.values())


class Held(object):
    """A seam of the build held open: the patched function sets
    `reached` where the build arrives and goes on when the test sets
    `go`."""

    def __init__(self):
        self.reached, self.go = threading.Event(), threading.Event()
        self.arrivals = 0

    def wait_here(self):
        self.arrivals += 1
        self.reached.set()
        assert self.go.wait(WAIT)


def in_thread(fn, *args):
    box = {}

    def run():
        try:
            box['value'] = fn(*args)
        except BaseException as e:      # reported where it is joined
            box['error'] = e

    t = threading.Thread(target=run)
    t.start()
    return t, box


def joined(t, box):
    t.join(WAIT)
    assert not t.is_alive()
    if 'error' in box:
        raise box['error']
    return box['value']


@pytest.mark.parametrize('nshards', [1, 7])
def test_reader_runs_beside_the_scan_and_waits_for_the_commit(
        server, tree, monkeypatch, nshards):
    """A query that starts while the build is between its scan and its
    commit is answered at once, from the standing tree.  One that
    starts inside the commit (here: after the publish's first rename,
    the rest still tmps) waits, and then sees every shard of the
    publish."""
    scan, commit = Held(), Held()
    real_bucket = mod_ibmt._bucket_blocks
    real_inval = mod_iqmt.shard_cache_invalidate

    def bucket_blocks(*a, **kw):
        scan.wait_here()             # the scan is done; no lock is held
        return real_bucket(*a, **kw)

    def shard_cache_invalidate(path):
        if not commit.reached.is_set():
            commit.wait_here()       # first rename landed, under the lock
        return real_inval(path)

    monkeypatch.setattr(mod_ibmt, '_bucket_blocks', bucket_blocks)
    monkeypatch.setattr(mod_iqmt, 'shard_cache_invalidate',
                        shard_cache_invalidate)
    standing = 2 * PER_DAY
    assert total(query(server, tree)) == standing

    bt, bbox = in_thread(build, server, tree, 2, 2 + nshards)
    assert scan.reached.wait(WAIT)
    t0 = time.monotonic()
    assert total(query(server, tree)) == standing
    assert time.monotonic() - t0 < WAIT / 2
    scan.go.set()

    assert commit.reached.wait(WAIT)
    published = sorted(n for n in os.listdir(tree['by_day'])
                       if n.endswith('.sqlite'))
    assert len(published) == 2 + 1          # the publish is half done
    qt, qbox = in_thread(query, server, tree)
    time.sleep(0.3)
    assert qt.is_alive()                    # held by the write side
    commit.go.set()
    rc, err = joined(bt, bbox)
    assert rc == 0 and b'built' in err, err
    assert total(joined(qt, qbox)) == (2 + nshards) * PER_DAY
    assert commit.arrivals == 1


def test_two_builds_of_one_tree_do_not_overlap(server, tree,
                                               monkeypatch):
    """The tree's build mutex: a second build waits for the whole of
    the first, though no query does."""
    held = Held()
    real_bucket = mod_ibmt._bucket_blocks
    order = []

    def bucket_blocks(*a, **kw):
        order.append('bucket')
        if len(order) == 1:
            held.wait_here()
        return real_bucket(*a, **kw)

    monkeypatch.setattr(mod_ibmt, '_bucket_blocks', bucket_blocks)
    t1, b1 = in_thread(build, server, tree, 2, 3)
    assert held.reached.wait(WAIT)
    t2, b2 = in_thread(build, server, tree, 3, 4)
    time.sleep(0.5)
    assert order == ['bucket']              # the second has not scanned
    assert total(query(server, tree)) == 2 * PER_DAY
    held.go.set()
    for t, b in ((t1, b1), (t2, b2)):
        rc, err = joined(t, b)
        assert rc == 0 and b'built' in err, err
    assert order == ['bucket', 'bucket']
    assert total(query(server, tree)) == 4 * PER_DAY


def test_acknowledged_build_is_read_back_not_the_cached_empty_answer(
        server, tree, monkeypatch):
    """Query a day the tree lacks (empty, and cached: the second ask
    is a hit), build it through the server, query again: the vector
    engine's answer over the raw data, not the cached empty one."""
    day = 4
    assert query(server, tree, day, day + 1) == {}
    hits0 = server.qcache.stats()['hits']
    assert query(server, tree, day, day + 1) == {}
    assert server.qcache.stats()['hits'] == hits0 + 1

    rc, err = build(server, tree, day, day + 1)
    assert rc == 0 and b'built' in err, err
    got = query(server, tree, day, day + 1)

    monkeypatch.setenv('DN_ENGINE', 'vector')
    rc, out, err = run_cli(['scan', '--points', '-b', 'host',
                            '--after', iso(day), '--before',
                            iso(day + 1), 'live'])
    assert rc == 0, err
    want = {}
    for line in out.decode().splitlines():
        doc = json.loads(line)
        want[doc['fields']['host']] = doc['value']
    assert got == want and total(got) == PER_DAY
    # and the fresh answer is what the cache now holds
    hits1 = server.qcache.stats()['hits']
    assert query(server, tree, day, day + 1) == want
    assert server.qcache.stats()['hits'] == hits1 + 1


def scrape(srv):
    rc, _, out, _ = mod_client.request_bytes(
        srv.socket_path, {'op': 'metrics'}, timeout_s=WAIT)
    assert rc == 0
    values = {}
    for line in out.decode().splitlines():
        if line and not line.startswith('#'):
            name, value = line.rsplit(' ', 1)
            values[name] = float(value)
    return values


def test_lock_and_cache_series_in_a_scrape(server, tree):
    """One publish into a tree with a cached answer and open shard
    handles: the lock's histograms with their `side`, the publish's
    counters, and what the epoch's bump retired."""
    assert total(query(server, tree)) == 2 * PER_DAY
    before = scrape(server)
    rc, err = build(server, tree, 2, 5)
    assert rc == 0 and b'built' in err, err
    assert total(query(server, tree)) == 5 * PER_DAY
    after = scrape(server)

    def grew(name):
        return after.get(name, 0.0) - before.get(name, 0.0)

    # nobody waited: both sides' series are there, at what they were
    for side in ('read', 'write'):
        name = 'dn_serve_tree_lock_wait_ms_count{side="%s"}' % side
        assert name in after and grew(name) == 0
    assert grew('dn_serve_tree_lock_held_ms_count{side="write"}') == 1
    held = grew('dn_serve_tree_lock_held_ms_sum{side="write"}')
    commit = grew('dn_stage_ms_sum{stage="index_build.commit"}')
    assert 0 < commit <= held
    # the lock was held for the commit, not for the request
    assert held < grew('dn_serve_op_latency_ms_sum{op="build"}')
    assert grew('dn_index_publishes_total') == 1
    assert grew('dn_index_publish_shards_total') == 3
    assert grew('dn_serve_result_cache_retired_total') == 1
    assert grew('dn_index_shard_handles_retired_total') == 2
    assert 'dn_serve_tree_lock_held_ms_count{side="read"}' not in after


def test_tree_lock_waits_are_observed_under_their_leaf():
    """A reader held by a writer observes its wait under the leaf
    `serve.tree_lock`; an uncontended entry observes nothing and opens
    no leaf."""
    lock = mod_admission.TreeLock()
    reg = obs_metrics.global_registry()

    def hist(name, **labels):
        h = reg.histogram(name, **labels)
        return h.total, h.sum

    def leaf():
        return hist('stage_ms', stage='serve.tree_lock')

    n0, leaf0 = hist('serve_tree_lock_wait_ms', side='read'), leaf()
    with lock.read():
        pass
    n1 = hist('serve_tree_lock_wait_ms', side='read')
    assert n1 == n0 and leaf() == leaf0

    entered = threading.Event()

    def reader():
        with lock.read():
            entered.set()

    with lock.write():
        t = threading.Thread(target=reader)
        t.start()
        time.sleep(0.05)
        assert not entered.is_set()
    t.join(WAIT)
    assert entered.is_set()
    n2 = hist('serve_tree_lock_wait_ms', side='read')
    assert n2[0] == n1[0] + 1 and n2[1] - n1[1] >= 40.0
    assert leaf()[0] == leaf0[0] + 1
    assert 'dn_serve_tree_lock_wait_ms_bucket{side="read",le="1"}' in \
        obs_export.prometheus_text(reg)


def test_commit_guard_is_entered_for_the_commit_alone(tree):
    """The CLI's build sets no guard; the guard a thread sets for its
    builds is entered once, after the prepare, holds the renames and
    the write hooks, and is gone with the block."""
    from dragnet_tpu import config as mod_config
    from dragnet_tpu import datasource_for_name, metrics_for_index
    err, config = mod_config.ConfigBackendLocal().load()
    assert err is None
    ds = datasource_for_name(config, 'live')
    metrics = metrics_for_index(config, 'live')
    events = []
    hook = lambda root, paths: events.append(('hook', len(paths)))

    class Guard(object):
        def __enter__(self):
            tmps = [n for n in os.listdir(tree['by_day'])
                    if not n.endswith('.sqlite')]
            events.append(('enter', len(tmps)))

        def __exit__(self, *exc):
            events.append(('exit', len(
                [n for n in os.listdir(tree['by_day'])
                 if n.endswith('.sqlite')])))

    mod_ibmt.register_index_write_hook(hook)
    try:
        with mod_ibmt.commit_guard(Guard):
            ds.build(metrics, 'day', time_after=ms(2), time_before=ms(5))
        # three prepared tmps on entry; the hook inside; five shards on
        # exit
        assert events == [('enter', 3), ('hook', 3), ('exit', 5)]
        ds.build(metrics, 'day', time_after=ms(5), time_before=ms(6))
        assert events[3:] == [('hook', 1)]
    finally:
        mod_ibmt.unregister_index_write_hook(hook)


def test_bounded_builds_of_other_days_share_one_device_program(
        tree, monkeypatch):
    """Day after day through the forced device engine: a build's time
    bounds are arguments of its program, not constants of it, so the
    second day's build finds the first day's program (a resident
    server would otherwise compile once a publish); and what the
    program kept is each day's own records."""
    from dragnet_tpu import device_scan as mod_ds
    monkeypatch.setenv('DN_ENGINE', 'jax')
    # a batch smaller than BATCH_SIZE is padded from a floor that is
    # otherwise tuned from a measured bandwidth: pinned, so that the
    # padded size is not the measurement's to vary
    monkeypatch.setenv('DN_DEVICE_BATCH_FLOOR', '4096')
    def bounded(pkey):
        # a program of these builds: bounds as arguments, and a
        # metric's columns (another test's lone `-b host` scan, or its
        # background audition, is none of them)
        return pkey[6] == ('arg', 'arg') and len(pkey[1]) > 1

    def programs():
        return ({k for k in mod_ds._STACK_CACHE
                 if all(bounded(pkey) for pkey, _ in k)},
                {k for k in mod_ds._PROGRAM_CACHE if bounded(k)})

    seen = []
    for day in (2, 3, 4):
        rc, out, err = run_cli(['build', '--after', iso(day),
                                '--before', iso(day + 1), 'live'])
        assert rc == 0, err
        seen.append(programs())
    assert len(seen[0][0]) >= 1
    assert seen[1] == seen[0] and seen[2] == seen[0]
    # and the program is a day's, not the file's: the two timestamp
    # columns' windows hold the bounds' one day (the least capacity,
    # 8), where the file's nine days would take 16
    caps = [key[1] for key in seen[0][1]]
    assert caps and all(c[:2] == (8, 8) for c in caps), caps
    monkeypatch.setenv('DN_ENGINE', 'vector')
    for day in (2, 3, 4):
        args = ['-b', 'host', '--after', iso(day), '--before',
                iso(day + 1), 'live']
        rc, got, err = run_cli(['query', '--points'] + args)
        assert rc == 0, err
        rc, want, err = run_cli(['scan', '--points'] + args)
        assert rc == 0, err
        assert sorted(got.splitlines()) == sorted(want.splitlines())
        assert got.count(b'\n') == 3


def test_time_shape_of_bounds_outside_int32():
    """The bounds' static shape: in range they are arguments; past
    int32 they resolve statically, vacuous or nothing-passes."""
    from dragnet_tpu.device_scan import _time_shape, I32MAX, I32MIN
    assert _time_shape((10, 20)) == (('arg', 'arg'), (10, 20))
    assert _time_shape((30, 40))[0] == _time_shape((10, 20))[0]
    assert _time_shape((None, 20)) == ((None, 'arg'), (0, 20))
    assert _time_shape((I32MIN, I32MAX + 1)) == ((None, None), (0, 0))
    assert _time_shape((I32MAX + 1, None))[0] == ('never', None)
    assert _time_shape((0, I32MIN))[0] == ('arg', 'never')


def test_tree_lock_keeps_its_invariants_under_stress():
    """More threads than cores, a short switch interval, two seconds:
    never a reader beside a writer, never two writers, never two
    builds; and every writer that asked got in (writer priority)."""
    lock = mod_admission.TreeLock()
    state = {'readers': 0, 'writers': 0, 'builders': 0}
    seen = {'reads': 0, 'writes': 0, 'bad': []}
    guard = threading.Lock()
    stop = time.monotonic() + 2.0

    def enter(kind, limit_of):
        with guard:
            state[kind] += 1
            for other, limit in limit_of.items():
                if state[other] > limit:
                    seen['bad'].append((kind, dict(state)))

    def leave(kind):
        with guard:
            state[kind] -= 1

    def reader():
        while time.monotonic() < stop:
            with lock.read():
                enter('readers', {'writers': 0})
                leave('readers')
                with guard:
                    seen['reads'] += 1

    def builder():
        while time.monotonic() < stop:
            with lock.building():
                enter('builders', {'builders': 1})
                with lock.write():
                    enter('writers', {'writers': 1, 'readers': 0})
                    leave('writers')
                leave('builders')
                with guard:
                    seen['writes'] += 1

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=reader) for _ in range(12)] + \
            [threading.Thread(target=builder) for _ in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(WAIT)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(interval)
    assert not seen['bad'], seen['bad'][:3]
    assert seen['reads'] > 0 and seen['writes'] > 0
    assert state == {'readers': 0, 'writers': 0, 'builders': 0}
