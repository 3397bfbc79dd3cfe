"""`dn serve` — the resident query server (dragnet_tpu/serve/).

Covers: byte-identity of remote responses vs the sequential local CLI
(including a concurrent soak over both index formats), request
coalescing observable via /stats, queue-full and deadline DNError
paths, remote-unreachable fallback, the request-scoped counter
machinery, lifecycle hygiene (stale pidfile / orphaned socket
reclaim), the SIGTERM drain contract, and `dn serve --validate`.
"""

import json
import os
import signal
import socket as mod_socket
import subprocess
import sys
import threading
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from dragnet_tpu import cli                                # noqa: E402
from dragnet_tpu import vpipe as mod_vpipe                 # noqa: E402
from dragnet_tpu.errors import DNError                     # noqa: E402
from dragnet_tpu.serve import admission as mod_admission   # noqa: E402
from dragnet_tpu.serve import client as mod_client         # noqa: E402
from dragnet_tpu.serve import lifecycle as mod_lifecycle   # noqa: E402
from dragnet_tpu.serve import server as mod_server         # noqa: E402

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_cli(args):
    """One in-process CLI run with its stdout/stderr captured as bytes
    through the serve layer's thread-stdio router — safe to call from
    multiple threads at once (each gets its own buffers), which is
    exactly how the soak drives the remote client."""
    with mod_server.thread_stdio() as cap:
        rc = cli.main(list(args))
    out, err = cap.finish()
    return rc, out, err


def _gen_corpus(path, n=400):
    """Deterministic newline-JSON over 4 days of 2014-01."""
    import datetime
    t0 = 1388534400  # 2014-01-01T00:00:00Z
    with open(path, 'w') as f:
        for i in range(n):
            ts = datetime.datetime.utcfromtimestamp(
                t0 + i * 800).strftime('%Y-%m-%dT%H:%M:%S.000Z')
            f.write(json.dumps({
                'time': ts,
                'host': 'host%d' % (i % 3),
                'operation': ('get', 'put', 'index')[i % 3],
                'req': {'method': ('GET', 'PUT')[i % 2]},
                'latency': (i * 7) % 230,
            }, separators=(',', ':')) + '\n')


@pytest.fixture(scope='module')
def corpus(tmp_path_factory):
    """Two datasources over one corpus — ds_dnc / ds_sq with separate
    index trees built under each DN_INDEX_FORMAT — plus the shared
    DRAGNET_CONFIG file every CLI run and server request uses."""
    root = tmp_path_factory.mktemp('serve_corpus')
    datafile = str(root / 'data.log')
    _gen_corpus(datafile)
    rc_path = str(root / 'dragnetrc.json')
    prior = os.environ.get('DRAGNET_CONFIG')
    os.environ['DRAGNET_CONFIG'] = rc_path
    prior_fmt = os.environ.get('DN_INDEX_FORMAT')
    try:
        for ds, fmt in (('ds_dnc', 'dnc'), ('ds_sq', 'sqlite')):
            idx = str(root / ('idx_' + fmt))
            rc, out, err = run_cli([
                'datasource-add', '--path', datafile,
                '--index-path', idx, '--time-field', 'time', ds])
            assert rc == 0, err
            rc, out, err = run_cli([
                'metric-add', '-b',
                'timestamp[date,field=time,aggr=lquantize,'
                'step=86400],host,latency[aggr=quantize]', ds, 'm1'])
            assert rc == 0, err
            rc, out, err = run_cli([
                'metric-add', '-b', 'operation', '-f',
                '{"eq": ["req.method", "GET"]}', ds, 'm2'])
            assert rc == 0, err
            os.environ['DN_INDEX_FORMAT'] = fmt
            rc, out, err = run_cli(['build', ds])
            assert rc == 0, err
        yield {'root': root, 'rc_path': rc_path,
               'datafile': datafile, 'dss': ['ds_dnc', 'ds_sq']}
    finally:
        if prior_fmt is None:
            os.environ.pop('DN_INDEX_FORMAT', None)
        else:
            os.environ['DN_INDEX_FORMAT'] = prior_fmt
        if prior is None:
            os.environ.pop('DRAGNET_CONFIG', None)
        else:
            os.environ['DRAGNET_CONFIG'] = prior


def _conf(**over):
    base = {'max_inflight': 4, 'queue_depth': 16, 'deadline_ms': 0,
            'coalesce': True, 'drain_s': 10}
    base.update(over)
    return base


@pytest.fixture
def server(corpus, tmp_path):
    sock = str(tmp_path / 'dn.sock')
    srv = mod_server.DnServer(socket_path=sock, conf=_conf()).start()
    try:
        yield srv
    finally:
        srv.stop()


def _req(ds, corpus, breakdowns=('host',), flt=None, interval='day',
         op='query', opts=None):
    bds = []
    for b in breakdowns:
        if b == 'latq':
            bds.append({'name': 'latency', 'field': 'latency',
                        'aggr': 'quantize'})
        else:
            bds.append({'name': b, 'field': b})
    qc = {'breakdowns': bds}
    if flt is not None:
        qc['filter'] = flt
    doc = {'op': op, 'ds': ds, 'config': corpus['rc_path'],
           'queryconfig': qc, 'opts': opts or {}}
    if op == 'query':
        doc['interval'] = interval
    return doc


# -- byte identity: remote == local ----------------------------------------

def _cases(ds):
    return [
        ['query', '-b', 'host', ds],
        ['query', '-b', 'host,latency[aggr=quantize]', '--counters',
         ds],
        ['query', '--points', '-b', 'operation', '-f',
         '{"eq": ["req.method", "GET"]}', ds],
        ['query', '--raw', '-b', 'host,latency[aggr=quantize]',
         '-A', '2014-01-02', '-B', '2014-01-03', ds],
        ['scan', '-b', 'operation', '--raw', ds],
        ['scan', '-b', 'host,latency[aggr=quantize]', '--counters',
         ds],
        ['build', ds],
    ]


def test_remote_byte_identical(server, corpus):
    """Every command shape: `--remote` responses (stdout, stderr, rc)
    match the sequential local CLI byte for byte."""
    sock = server.socket_path
    for ds in corpus['dss']:
        for case in _cases(ds):
            expected = run_cli(case)
            remote = run_cli(case[:1] + ['--remote', sock] + case[1:])
            assert remote == expected, case


def test_concurrent_soak_byte_identical(server, corpus):
    """N client threads x mixed scan/index-query/build against both
    index formats: every response byte-identical to the sequential
    local runs, with coalescing observable via /stats."""
    sock = server.socket_path
    work = []
    for ds in corpus['dss']:
        for case in _cases(ds):
            work.append((case, run_cli(case)))

    errors = []
    start = threading.Barrier(8)

    def client(tid):
        start.wait()
        for rep in range(3):
            for i, (case, expected) in enumerate(work):
                if (i + rep + tid) % 3 == 0:
                    continue     # vary the mix per thread
                got = run_cli(case[:1] + ['--remote', sock] +
                              case[1:])
                if got != expected:
                    errors.append((tid, case, got, expected))

    threads = [threading.Thread(target=client, args=(t,))
               for t in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors[0]

    st = mod_client.stats(sock)
    assert st['requests']['requests'] > 0
    # the soak reuses identical in-flight queries heavily: shared
    # executions must have happened
    assert st['requests']['coalesced'] > 0
    assert st['requests']['errors'] == 0


def test_coalescing_shares_one_execution(corpus, tmp_path,
                                         monkeypatch):
    """With the single execution slot held, identical concurrent
    queries attach to ONE leader: /stats shows followers, and every
    response is byte-identical."""
    monkeypatch.setenv('DN_SERVE_TEST_OPS', '1')
    sock = str(tmp_path / 'dn.sock')
    srv = mod_server.DnServer(
        socket_path=sock,
        conf=_conf(max_inflight=1, queue_depth=8)).start()
    try:
        holder = threading.Thread(
            target=mod_client.request_bytes,
            args=(sock, {'op': '_sleep', 'ms': 500}))
        holder.start()
        time.sleep(0.15)      # the sleeper owns the only slot

        req = _req('ds_dnc', corpus)
        results = []

        def fire():
            results.append(mod_client.request_bytes(sock, req))

        threads = [threading.Thread(target=fire) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        holder.join()

        assert len(set((rc, out, err)
                       for rc, hd, out, err in results)) == 1
        assert results[0][0] == 0
        shared = [hd['stats']['coalesced']
                  for rc, hd, out, err in results]
        assert sum(1 for s in shared if s) == 3
        st = mod_client.stats(sock)
        assert st['requests']['coalesced'] >= 3
        assert st['requests']['executions'] >= 1
    finally:
        srv.stop()


# -- admission + deadline DNError paths ------------------------------------

def test_queue_full_fast_429(corpus, tmp_path, monkeypatch):
    monkeypatch.setenv('DN_SERVE_TEST_OPS', '1')
    sock = str(tmp_path / 'dn.sock')
    srv = mod_server.DnServer(
        socket_path=sock,
        conf=_conf(max_inflight=1, queue_depth=0)).start()
    try:
        holder = threading.Thread(
            target=mod_client.request_bytes,
            args=(sock, {'op': '_sleep', 'ms': 800}))
        holder.start()
        time.sleep(0.2)
        t0 = time.monotonic()
        rc, hd, out, err = mod_client.request_bytes(
            sock, _req('ds_dnc', corpus))
        dt = time.monotonic() - t0
        holder.join()
        assert rc == 1
        assert err.startswith(b'dn: server busy:'), err
        assert b'DN_SERVE_MAX_INFLIGHT=1' in err
        assert dt < 0.5      # fast rejection, not a convoy
        st = mod_client.stats(sock)
        assert st['requests']['busy_rejected'] == 1
    finally:
        srv.stop()


def test_request_deadline_dnerror(corpus, tmp_path, monkeypatch):
    monkeypatch.setenv('DN_SERVE_TEST_OPS', '1')
    sock = str(tmp_path / 'dn.sock')
    srv = mod_server.DnServer(
        socket_path=sock, conf=_conf(deadline_ms=150)).start()
    try:
        t0 = time.monotonic()
        rc, hd, out, err = mod_client.request_bytes(
            sock, {'op': '_sleep', 'ms': 5000})
        dt = time.monotonic() - t0
        assert rc == 1
        assert b'request deadline (150 ms) exceeded' in err
        assert dt < 3.0
        st = mod_client.stats(sock)
        assert st['requests']['deadline_expired'] == 1
    finally:
        srv.stop()


def test_deadline_timeout_frees_admission_slot(corpus, tmp_path,
                                               monkeypatch):
    """An abandoned (deadline-expired) execution must not pin its
    admission slot: with ONE slot and no queue, a request right after
    a timeout succeeds instead of BusyError-ing until restart."""
    monkeypatch.setenv('DN_SERVE_TEST_OPS', '1')
    sock = str(tmp_path / 'dn.sock')
    srv = mod_server.DnServer(
        socket_path=sock,
        conf=_conf(max_inflight=1, queue_depth=0,
                   deadline_ms=200)).start()
    try:
        rc, hd, out, err = mod_client.request_bytes(
            sock, {'op': '_sleep', 'ms': 3000})
        assert rc == 1 and b'deadline' in err
        # the wedged sleep still runs on its abandoned thread, but
        # its slot was freed — the next request executes
        rc, hd, out, err = mod_client.request_bytes(
            sock, _req('ds_dnc', corpus))
        assert rc == 0, err
    finally:
        srv.stop()


def test_coalescer_abandon_retires_dead_execution():
    """After a leader's deadline expires, abandon() wakes followers
    with the deadline error and lets NEW identical requests recompute
    instead of attaching to the dead execution forever."""
    c = mod_admission.Coalescer(True)
    started = threading.Event()
    release = threading.Event()
    lease = {}
    leader_result = {}

    def leader():
        def compute():
            started.set()
            release.wait(10)
            return 'stale'
        leader_result['v'] = c.run('k', compute, lease=lease)

    t = threading.Thread(target=leader)
    t.start()
    assert started.wait(5)

    follower_err = {}

    def follower():
        try:
            c.run('k', lambda: 'unused')
        except mod_admission.DeadlineError as e:
            follower_err['e'] = e

    tf = threading.Thread(target=follower)
    tf.start()
    time.sleep(0.05)
    c.abandon(lease['key'], lease['ex'])
    tf.join(5)
    assert 'e' in follower_err        # follower shares leader's fate
    # a fresh arrival computes fresh (no dead-execution attachment)
    v, shared = c.run('k', lambda: 'fresh')
    assert v == 'fresh' and shared is False
    release.set()
    t.join(5)
    # the abandoned leader completing later is harmless
    assert leader_result['v'] == ('stale', False)


def test_remote_rejects_execution_mode_flags(server, corpus):
    for args in (['query', '--iq-threads', '2'],
                 ['query', '--iq-stack', '0'],
                 ['scan', '--parse', 'host'],
                 ['build', '--build-threads', '2']):
        rc, out, err = run_cli(
            args[:1] + ['--remote', server.socket_path] + args[1:] +
            ['ds_dnc'])
        assert rc == 2, (args, err)
        assert b'cannot be combined with "--remote"' in err, args


def test_per_request_deadline_override(corpus, tmp_path,
                                       monkeypatch):
    monkeypatch.setenv('DN_SERVE_TEST_OPS', '1')
    sock = str(tmp_path / 'dn.sock')
    srv = mod_server.DnServer(socket_path=sock,
                              conf=_conf(deadline_ms=0)).start()
    try:
        rc, hd, out, err = mod_client.request_bytes(
            sock, {'op': '_sleep', 'ms': 5000, 'deadline_ms': 100})
        assert rc == 1 and b'deadline' in err
    finally:
        srv.stop()


# -- fallback + error framing ----------------------------------------------

def test_remote_unreachable_falls_back_local(corpus, tmp_path):
    missing = str(tmp_path / 'nope.sock')
    expected = run_cli(['query', '-b', 'host', 'ds_dnc'])
    rc, out, err = run_cli(['query', '--remote', missing, '-b',
                            'host', 'ds_dnc'])
    assert rc == 0
    assert out == expected[1]
    assert b'unreachable' in err and b'falling back' in err


def test_remote_fatal_error_framing(server, corpus):
    """Server-side fatal errors come back with the CLI's exact
    'dn: <message>' framing and exit code."""
    expected = run_cli(['query', '-b', 'host', 'no_such_ds'])
    remote = run_cli(['query', '--remote', server.socket_path, '-b',
                      'host', 'no_such_ds'])
    assert expected[0] == remote[0] == 1
    assert remote[2] == expected[2]
    assert b'unknown datasource' in remote[2]


def test_remote_rejects_warnings_flag(server, corpus):
    rc, out, err = run_cli(['scan', '--remote', server.socket_path,
                            '--warnings', '-b', 'host', 'ds_dnc'])
    assert rc == 2
    assert b'"--warnings" cannot be combined with "--remote"' in err


def test_unsupported_op(server):
    rc, hd, out, err = mod_client.request_bytes(
        server.socket_path, {'op': 'shrug'})
    assert rc == 1 and b'unsupported request op' in err


# -- request-scoped counters -----------------------------------------------

def test_request_scope_isolates_and_merges():
    mod_vpipe.reset_global_counters()
    seen = {}
    start = threading.Barrier(2)

    def worker(name, n):
        with mod_vpipe.request_scope() as sc:
            start.wait()
            for _ in range(n):
                mod_vpipe.counter_bump('soak counter')
            time.sleep(0.05)
            seen[name] = dict(sc)

    a = threading.Thread(target=worker, args=('a', 3))
    b = threading.Thread(target=worker, args=('b', 7))
    a.start()
    b.start()
    a.join()
    b.join()
    # each request saw exactly its own delta, never the other's
    assert seen['a'] == {'soak counter': 3}
    assert seen['b'] == {'soak counter': 7}
    # and the global store holds the merged total
    assert mod_vpipe.global_counters()['soak counter'] == 10
    # no scope: straight to global (the single-process CLI path)
    mod_vpipe.counter_bump('soak counter')
    assert mod_vpipe.global_counters()['soak counter'] == 11


def test_request_counters_in_response_header(server, corpus):
    """Each response carries only ITS OWN hidden-counter deltas —
    shard fan-out counters attribute per request even under the
    concurrent soak."""
    req = _req('ds_dnc', corpus)
    rc, hd, out, err = mod_client.request_bytes(server.socket_path,
                                                req)
    assert rc == 0
    counters = hd['stats']['counters']
    assert counters.get('index shards queried', 0) > 0


def test_metrics_scrape_carries_the_process_memory(server, corpus):
    """A scrape reads the process's page faults, resident bytes and
    allocator policy when it is asked (obs_metrics.
    refresh_process_gauges); the fault counter only rises."""
    import mmap

    def scrape():
        rc, hd, out, err = mod_client.request_bytes(
            server.socket_path, {'op': 'metrics'})
        assert rc == 0, err
        return {ln.rsplit(' ', 1)[0]: float(ln.rsplit(' ', 1)[1])
                for ln in out.decode().splitlines()
                if not ln.startswith('#')}

    first = scrape()
    rc, hd, out, err = mod_client.request_bytes(
        server.socket_path, _req('ds_dnc', corpus, op='scan'))
    assert rc == 0, err
    with mmap.mmap(-1, 1 << 22) as fresh:       # 1,024 pages, touched
        fresh.write(b'\x01' * (1 << 22))
        second = scrape()
    for doc in (first, second):
        assert doc['dn_process_resident_bytes'] > 0
        assert doc['dn_process_peak_resident_bytes'] >= \
            doc['dn_process_resident_bytes']
        # the module's corpus went through cli.main, the one call site
        held = [k for k in doc if k.startswith('dn_allocator_policy_held{')]
        assert len(held) == 1 and doc[held[0]] in (0.0, 1.0)
    assert second['dn_process_minor_faults_total'] >= \
        first['dn_process_minor_faults_total'] + 1024


# -- the index walk's directory snapshot at a scrape --------------------------

def test_second_query_counts_a_snapshot_hit(server, corpus):
    """A resident server reads a tree's directory once: the first
    bounded query builds its snapshot
    (`index_walk_snapshot_rebuilds_total{reason="cold"}`), the next one
    of any window finds it current by the directory's stat
    (`index_walk_snapshot_hits_total`); `/stats` says the same beside
    `find_memo`."""
    from dragnet_tpu import index_query_mt as mod_iqmt
    hits = 'dn_index_walk_snapshot_hits_total'
    cold = 'dn_index_walk_snapshot_rebuilds_total{reason="cold"}'

    def scrape():
        rc, hd, out, err = mod_client.request_bytes(
            server.socket_path, {'op': 'metrics'})
        assert rc == 0, err
        doc = dict(ln.rsplit(' ', 1) for ln in out.decode().splitlines()
                   if not ln.startswith('#'))
        return {k: float(v) for k, v in doc.items()
                if k.startswith('dn_index_walk_snapshot_')}

    # a snapshot is kept once its directory is older than the racy
    # margin: this tree was built moments ago
    root = os.path.join(str(corpus['root']), 'idx_dnc', 'by_day')
    old = time.time() - 60
    os.utime(root, (old, old))
    mod_iqmt.shard_cache_clear()
    t0 = 1388534400000
    first = scrape()
    for days in (2, 3):
        req = _req('ds_dnc', corpus)
        req['queryconfig'].update(timeAfter=t0,
                                  timeBefore=t0 + days * 86400000)
        rc, hd, out, err = mod_client.request_bytes(server.socket_path,
                                                    req)
        assert rc == 0, err
        assert out
    second = scrape()
    grew = {k: second[k] - first.get(k, 0) for k in second}
    assert {k: v for k, v in grew.items() if v} == {hits: 1, cold: 1}
    memo = mod_client.stats(server.socket_path)['caches']['find_memo']
    assert memo == {'size': 1, 'snapshot_hits': 1,
                    'snapshot_rebuilds': {'cold': 1}}


# -- a columnar result's reply: formatted by column, never as dicts -----------

WIDE_TUPLES = 9000          # past Aggregator.FLAT_COLUMNAR_MIN (8,192)


def add_wide_datasource(root, name='ds_wide', backend=None):
    """A datasource whose scan by `host,seq` answers WIDE_TUPLES tuples
    (every record its own, some values in need of escapes), under the
    DRAGNET_CONFIG of the moment."""
    datafile = os.path.join(str(root), name + '.log')
    with open(datafile, 'w') as f:
        for i in range(WIDE_TUPLES):
            f.write(json.dumps({
                'host': 'h%d "q\\' % (i % 90), 'seq': i // 90,
                'latency': i % 50}, separators=(',', ':')) + '\n')
    args = ['datasource-add', '--path', datafile]
    if backend is not None:
        args.append('--backend=' + backend)
    rc, out, err = run_cli(args + [name])
    assert rc == 0, err
    return name


def scrape_counters(sock):
    """The reply's counters at a scrape (0 where one has not counted)."""
    rc, hd, out, err = mod_client.request_bytes(sock, {'op': 'metrics'})
    assert rc == 0, err
    doc = dict(ln.rsplit(' ', 1) for ln in out.decode().splitlines()
               if not ln.startswith('#'))
    return {k: float(doc.get(k, 0)) for k in (
        'dn_reply_tuples_total{path="block"}',
        'dn_reply_tuples_total{path="tuple"}', 'dn_reply_bytes_total')}


def check_wide_reply(sock, ds):
    """A scan of WIDE_TUPLES tuples through `dn serve` (in this
    process) against the local CLI: the same stdout and stderr; the
    reply went out by column (`reply_tuples_total{path="block"}` grew
    by its lines, `{path="tuple"}` by none) and the dicts were never
    built; `--raw` over such a result still builds them."""
    from dragnet_tpu.aggr import Aggregator
    args = ['--points', '--counters', '-b', 'host,seq', ds]
    expected = run_cli(['scan'] + args)
    assert expected[0] == 0, expected[2]
    lines = expected[1].count(b'\n')
    assert lines == WIDE_TUPLES
    before = scrape_counters(sock)

    def no_dicts(self, as_rows):
        raise AssertionError('the dicts were asked for')
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(Aggregator, '_columnar_points', no_dicts)
        mp.setattr('dragnet_tpu.aggr.PointBlock._make_points', no_dicts)
        remote = run_cli(['scan', '--remote', sock] + args)
    after = scrape_counters(sock)
    assert remote == expected
    grew = {k: after[k] - before[k] for k in before}
    assert grew == {'dn_reply_tuples_total{path="block"}': lines,
                    'dn_reply_tuples_total{path="tuple"}': 0,
                    'dn_reply_bytes_total': len(expected[1])}
    raw = ['--raw', '-b', 'host,seq', ds]
    assert run_cli(['scan', '--remote', sock] + raw) == \
        run_cli(['scan'] + raw)
    assert scrape_counters(sock)[
        'dn_reply_tuples_total{path="block"}'] == after[
        'dn_reply_tuples_total{path="block"}']


def test_columnar_reply_goes_out_by_column(server, corpus):
    check_wide_reply(server.socket_path,
                     add_wide_datasource(corpus['root']))


def test_request_counters_attribute_across_pool_threads(
        server, corpus, monkeypatch):
    """On the per-shard pool path (DN_IQ_STACK=0, DN_IQ_THREADS>0)
    the shard handle cache is hit from ShardQueryExecutor worker
    threads — which adopt the request's counter scope, so cache
    telemetry still lands in the request's own header stats."""
    monkeypatch.setenv('DN_IQ_STACK', '0')
    monkeypatch.setenv('DN_IQ_THREADS', '2')
    req = _req('ds_dnc', corpus,
               breakdowns=('operation',),
               flt={'eq': ['req.method', 'GET']})
    mod_client.request_bytes(server.socket_path, req)  # warm
    rc, hd, out, err = mod_client.request_bytes(server.socket_path,
                                                req)
    assert rc == 0, err
    counters = hd['stats']['counters']
    assert counters.get('index handle cache hits', 0) + \
        counters.get('index handle cache misses', 0) > 0


def test_writer_invalidation_hook(server, corpus):
    """A build THROUGH the server fires the writer-invalidation hook
    (whole-tree retire + counted in /stats) and later queries still
    answer correctly."""
    before = mod_client.stats(server.socket_path)['counters'].get(
        'index writer invalidations', 0)
    rc, hd, out, err = mod_client.request_bytes(
        server.socket_path,
        {'op': 'build', 'ds': 'ds_dnc',
         'config': corpus['rc_path'], 'interval': 'day',
         'opts': {}})
    assert rc == 0 and err == b'indexes for "ds_dnc" built\n'
    after = mod_client.stats(server.socket_path)['counters'].get(
        'index writer invalidations', 0)
    assert after > before
    expected = run_cli(['query', '-b', 'host', 'ds_dnc'])
    got = run_cli(['query', '--remote', server.socket_path, '-b',
                   'host', 'ds_dnc'])
    assert got == expected


# -- retry-hardened remote path --------------------------------------------

def test_remote_dead_after_connect_reports_attempt_count(
        corpus, tmp_path, monkeypatch):
    """A server that accepts the connection but dies before the
    response header: the client retries, then reports a clean
    retryable transport error WITH the attempt count — no socket
    traceback, and no local fallback that could double-run a
    build."""
    monkeypatch.setenv('DN_REMOTE_BACKOFF_MS', '1')
    sock = str(tmp_path / 'dying.sock')
    listener = mod_socket.socket(mod_socket.AF_UNIX,
                                 mod_socket.SOCK_STREAM)
    listener.bind(sock)
    listener.listen(8)
    stop = threading.Event()

    def close_all():
        listener.settimeout(0.1)
        while not stop.is_set():
            try:
                conn, _ = listener.accept()
            except mod_socket.timeout:
                continue
            except OSError:
                break
            # die before any response header, but only once the
            # request is on the wire: a conn that drops before the
            # client has sent was never reached, and the client may
            # then run locally (nothing can double-run)
            conn.settimeout(5.0)
            try:
                conn.recv(1)
            except OSError:
                pass
            conn.close()

    t = threading.Thread(target=close_all, daemon=True)
    t.start()
    try:
        for cmd in (['query', '-b', 'host'],
                    ['scan', '-b', 'host'],
                    ['build']):
            rc, out, err = run_cli(
                [cmd[0], '--remote', sock] + cmd[1:] + ['ds_dnc'])
            text = err.decode()
            assert rc == 1, (cmd, text)
            assert 'dn: remote transport failed after 3 attempt(s)' \
                in text, (cmd, text)
            assert 'retryable' in text
            assert 'Traceback' not in text
            assert b'falling back' not in err     # never runs locally
            assert out == b''
    finally:
        stop.set()
        listener.close()


def test_remote_unreachable_fallback_reports_attempts(
        corpus, tmp_path, monkeypatch):
    monkeypatch.setenv('DN_REMOTE_BACKOFF_MS', '1')
    missing = str(tmp_path / 'nope.sock')
    rc, out, err = run_cli(['query', '--remote', missing, '-b',
                            'host', 'ds_dnc'])
    assert rc == 0
    assert b'unreachable after 3 attempt(s)' in err
    assert b'falling back' in err


def test_retry_recovers_from_transient_busy(corpus, tmp_path,
                                            monkeypatch):
    """A momentarily-saturated server (queue full -> retryable busy
    rejection): the client's backoff loop lands the request once the
    slot frees, byte-identical to local."""
    monkeypatch.setenv('DN_SERVE_TEST_OPS', '1')
    monkeypatch.setenv('DN_REMOTE_RETRIES', '8')
    monkeypatch.setenv('DN_REMOTE_BACKOFF_MS', '60')
    sock = str(tmp_path / 'busy.sock')
    srv = mod_server.DnServer(
        socket_path=sock,
        conf=_conf(max_inflight=1, queue_depth=0)).start()
    try:
        holder = threading.Thread(
            target=mod_client.request_bytes,
            args=(sock, {'op': '_sleep', 'ms': 400}))
        holder.start()
        time.sleep(0.1)           # the sleeper owns the only slot
        expected = run_cli(['query', '-b', 'host', 'ds_dnc'])
        got = run_cli(['query', '--remote', sock, '-b', 'host',
                       'ds_dnc'])
        holder.join()
        assert got == expected
        st = mod_client.stats(sock)
        assert st['requests']['busy_rejected'] >= 1
    finally:
        srv.stop()


def test_drain_rejects_queued_requests_cleanly(corpus, tmp_path,
                                               monkeypatch):
    """SIGTERM/stop mid-load: the in-flight request completes, the
    QUEUED one gets the clean retryable 'draining' error instead of a
    connection reset."""
    monkeypatch.setenv('DN_SERVE_TEST_OPS', '1')
    sock = str(tmp_path / 'drain.sock')
    srv = mod_server.DnServer(
        socket_path=sock,
        conf=_conf(max_inflight=1, queue_depth=8)).start()
    results = {}

    def fire(name, req):
        results[name] = mod_client.request_bytes(sock, req,
                                                 timeout_s=30)

    holder = threading.Thread(
        target=fire, args=('held', {'op': '_sleep', 'ms': 800}))
    holder.start()
    time.sleep(0.2)                      # sleeper owns the only slot
    queued = threading.Thread(
        target=fire,
        args=('queued', _req('ds_dnc', corpus)))
    queued.start()
    time.sleep(0.2)                      # queued request is waiting
    srv.request_stop()
    holder.join(timeout=30)
    queued.join(timeout=30)
    srv.stop()
    assert results['held'][0] == 0       # in-flight COMPLETED
    rc, hd, out, err = results['queued']
    assert rc == 1
    assert b'draining' in err
    assert hd['retryable'] is True


def test_health_op(server, corpus):
    doc = mod_client.health(server.socket_path)
    assert doc['ok'] is True
    assert doc['draining'] is False
    assert doc['pid'] == os.getpid()
    assert 'inflight' in doc and 'uptime_s' in doc


def test_health_on_dead_endpoint(tmp_path):
    doc = mod_client.health(str(tmp_path / 'gone.sock'))
    assert doc['ok'] is False
    assert 'error' in doc


def test_build_idempotency_key_replays_not_reruns(server, corpus):
    """A retried build (same idempotency key) returns the RECORDED
    response instead of running the build again."""
    req = {'op': 'build', 'ds': 'ds_dnc',
           'config': corpus['rc_path'], 'interval': 'day',
           'opts': {}, 'idempotency': 'soak-key-1'}
    first = mod_client.request_bytes(server.socket_path, dict(req))
    assert first[0] == 0, first[3]
    before = mod_client.stats(server.socket_path)
    second = mod_client.request_bytes(server.socket_path, dict(req))
    after = mod_client.stats(server.socket_path)
    assert second[0] == 0
    assert second[2] == first[2] and second[3] == first[3]
    assert second[1]['stats'].get('idempotent_replay') is True
    assert after['requests']['build_idem_replays'] == \
        before['requests']['build_idem_replays'] + 1
    # the replay did not execute a second build: the writer
    # invalidation count is unchanged
    assert after['counters'].get('index writer invalidations', 0) == \
        before['counters'].get('index writer invalidations', 0)


def test_injected_transport_faults_recovered_by_retry(
        corpus, tmp_path, monkeypatch):
    """The marquee chaos property: with error faults armed on the
    client transport seams, the retry loop still lands every request
    byte-identical to local execution."""
    import dragnet_tpu.faults as mod_faults
    sock = str(tmp_path / 'chaos.sock')
    srv = mod_server.DnServer(socket_path=sock, conf=_conf()).start()
    expected = run_cli(['query', '-b', 'host', 'ds_dnc'])
    monkeypatch.setenv('DN_REMOTE_RETRIES', '6')
    monkeypatch.setenv('DN_REMOTE_BACKOFF_MS', '1')
    monkeypatch.setenv(
        'DN_FAULTS',
        'client.connect:error:0.3:5,client.send:error:0.2:6,'
        'client.recv:error:0.3:7')
    mod_faults.reset()
    try:
        for _ in range(6):
            got = run_cli(['query', '--remote', sock, '-b', 'host',
                           'ds_dnc'])
            assert got == expected
        assert mod_faults.total_fired() > 0
    finally:
        monkeypatch.delenv('DN_FAULTS')
        mod_faults.reset()
        srv.stop()


def test_stats_reports_faults_and_recovery(server, corpus):
    st = mod_client.stats(server.socket_path)
    assert 'faults' in st
    assert set(st['recovery']) == {'index recovery rollbacks',
                                   'index recovery rollforwards',
                                   'index tmps quarantined',
                                   'quarantine_files',
                                   'quarantine_bytes'}
    assert st['draining'] is False
    # the shard-integrity section (integrity.py, serve/scrub.py)
    integ = st['integrity']
    assert integ['verify'] in ('off', 'open', 'full')
    assert isinstance(integ['repair'], dict)
    assert {'scheduled', 'completed', 'failed'} <= set(
        integ['repair'])


# -- lifecycle hygiene -----------------------------------------------------

def test_stale_pidfile_and_orphan_socket_reclaim(tmp_path):
    sock = str(tmp_path / 'stale.sock')
    pidfile = sock + '.pid'
    # an orphaned socket: bound once, never unlinked (a crash)
    s = mod_socket.socket(mod_socket.AF_UNIX,
                          mod_socket.SOCK_STREAM)
    s.bind(sock)
    s.close()
    with open(pidfile, 'w') as f:
        f.write('999999999\n')
    notes = []
    mod_lifecycle.claim(socket_path=sock, pidfile=pidfile,
                        warn=notes.append)
    assert any('stale pidfile' in m for m in notes)
    assert any('orphaned socket' in m for m in notes)
    assert not os.path.exists(sock)
    with open(pidfile) as f:
        assert int(f.read()) == os.getpid()
    # a fresh server can now bind the reclaimed path
    srv = mod_server.DnServer(socket_path=sock, conf=_conf(),
                              pidfile=pidfile).start()
    try:
        assert mod_lifecycle.probe(socket_path=sock)
    finally:
        srv.stop()
    assert not os.path.exists(sock)
    assert not os.path.exists(pidfile)


def test_claim_refuses_live_server(tmp_path):
    sock = str(tmp_path / 'live.sock')
    srv = mod_server.DnServer(socket_path=sock, conf=_conf()).start()
    try:
        with pytest.raises(DNError) as ei:
            mod_lifecycle.claim(socket_path=sock)
        assert 'already running' in str(ei.value)
    finally:
        srv.stop()


def test_sigterm_drain_completes_inflight(tmp_path):
    """The daemon: SIGTERM mid-request stops accepting, FINISHES the
    in-flight request, unlinks the socket, and exits 0."""
    sock = str(tmp_path / 'daemon.sock')
    env = dict(os.environ, DN_SERVE_TEST_OPS='1')
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO_ROOT, 'bin', 'dn.py'),
         'serve', '--socket', sock],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE)
    try:
        deadline = time.monotonic() + 60
        while not mod_lifecycle.probe(socket_path=sock):
            assert proc.poll() is None, proc.stderr.read()
            assert time.monotonic() < deadline
            time.sleep(0.1)

        result = {}

        def inflight():
            result['r'] = mod_client.request_bytes(
                sock, {'op': '_sleep', 'ms': 1200}, timeout_s=30)

        t = threading.Thread(target=inflight)
        t.start()
        time.sleep(0.3)                  # request is in flight
        proc.send_signal(signal.SIGTERM)
        t.join(timeout=30)
        assert 'r' in result, 'in-flight request was dropped'
        assert result['r'][0] == 0       # it COMPLETED
        assert proc.wait(timeout=30) == 0
        assert not os.path.exists(sock)
        assert not os.path.exists(sock + '.pid')
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()


# -- dn serve --validate ---------------------------------------------------

def test_serve_validate_ok(monkeypatch):
    monkeypatch.setenv('DN_SERVE_MAX_INFLIGHT', '3')
    monkeypatch.setenv('DN_SERVE_DEADLINE_MS', '2500')
    monkeypatch.delenv('DN_FAULTS', raising=False)
    # pin the device-lane line: host-only rig, audition cache off
    monkeypatch.setenv('JAX_PLATFORMS', 'cpu')
    monkeypatch.delenv('DN_ENGINE', raising=False)
    monkeypatch.setenv('DN_AUDITION_CACHE', '0')
    # pin the scan-pipeline line (auto values are machine-dependent)
    monkeypatch.setenv('DN_SCAN_PARTITIONS', '4')
    monkeypatch.setenv('DN_SCAN_THREADS', '2')
    monkeypatch.delenv('DN_DEVICE_PIPELINE_DEPTH', raising=False)
    monkeypatch.delenv('DN_DEVICE_BATCH_FLOOR', raising=False)
    monkeypatch.delenv('DN_INDEX_DEVICE', raising=False)
    rc, out, err = run_cli(['serve', '--validate', '--socket',
                            '/tmp/never-bound.sock'])
    assert rc == 0
    assert out == (b'serve config ok: max_inflight=3 queue_depth=16 '
                   b'deadline_ms=2500 coalesce=1 drain_s=30\n'
                   b'serve front-end ok: read_deadline_ms=10000 '
                   b'write_deadline_ms=60000 idle_ms=300000\n'
                   b'serve tenancy ok: quota=0 default_weight=1 '
                   b'weights=none\n'
                   b'remote config ok: retries=2 backoff_ms=50 '
                   b'connect_timeout_s=5 deadline_ms=0\n'
                   b'obs config ok: trace=off slow_ms=off '
                   b'buckets=14\n'
                   b'fleet obs ok: history_s=0 events=0 '
                   b'events_file=off top_interval_ms=1000 '
                   b'fleet_timeout_s=5\n'
                   b'subscribe config ok: max=64 coalesce_ms=250 '
                   b'queue_depth=4 delta_pct=50\n'
                   b'router config ok: probe_ms=500 failures=3 '
                   b'cooldown_ms=2000 hedge_ms=0 fetch_timeout_s=60 '
                   b'partial=error\n'
                   b'topo config ok: poll_ms=0 '
                   b'handoff_timeout_s=120 handoff_retries=2 '
                   b'max_moves=2\n'
                   b'integrity config ok: verify=off '
                   b'scrub_interval_s=0 scrub_rate_mb_s=64 '
                   b'quarantine_max_mb=0\n'
                   b'resources config ok: disk_low_pct=10 '
                   b'disk_critical_pct=5 poll_ms=2000 '
                   b'mem_budget_mb=0 fd_headroom=64 '
                   b'events_file_max_mb=64\n'
                   b'device lane ok: engine=auto backend=host-only '
                   b'residency_mb=0 prewarm=1 probe_timeout_s=420 '
                   b'audition_cache=off entries=0 wins=0\n'
                   b'index device lane ok: mode=auto\n'
                   b'scan pipeline ok: pipeline_depth=2 '
                   b'batch_floor=auto partitions=4 scan_threads=2\n')


def test_serve_validate_reports_armed_faults(monkeypatch):
    monkeypatch.setenv('DN_FAULTS',
                       'sink.flush:error:0.5:7,client.recv:delay:1.0')
    rc, out, err = run_cli(['serve', '--validate', '--socket',
                            '/tmp/never-bound.sock'])
    assert rc == 0
    assert (b'faults armed: client.recv:delay:1:0 '
            b'sink.flush:error:0.5:7\n') in out


def test_serve_validate_rejects_bad_faults(monkeypatch):
    monkeypatch.setenv('DN_FAULTS', 'nope.where:error:0.5')
    rc, out, err = run_cli(['serve', '--validate', '--socket',
                            '/tmp/never-bound.sock'])
    assert rc == 1
    assert b'DN_FAULTS: unknown site "nope.where"' in err


def test_serve_validate_rejects_bad_remote_knob(monkeypatch):
    monkeypatch.setenv('DN_REMOTE_RETRIES', 'many')
    rc, out, err = run_cli(['serve', '--validate', '--socket',
                            '/tmp/never-bound.sock'])
    assert rc == 1
    assert err == (b'dn: DN_REMOTE_RETRIES: expected an integer '
                   b'>= 0, got "many"\n')


def test_serve_validate_bad_knob_fails_fast(monkeypatch):
    monkeypatch.setenv('DN_SERVE_MAX_INFLIGHT', 'lots')
    rc, out, err = run_cli(['serve', '--validate', '--socket',
                            '/tmp/never-bound.sock'])
    assert rc == 1
    assert err == (b'dn: DN_SERVE_MAX_INFLIGHT: expected an integer '
                   b'>= 1, got "lots"\n')


def test_serve_requires_exactly_one_endpoint():
    rc, out, err = run_cli(['serve'])
    assert rc == 2
    assert b'exactly one of "--socket" and "--port"' in err
    rc, out, err = run_cli(['serve', '--socket', '/tmp/x.sock',
                            '--port', '123'])
    assert rc == 2


def test_serve_bad_port():
    rc, out, err = run_cli(['serve', '--port', 'zzz'])
    assert rc == 2
    assert b'bad value for "port"' in err


def test_tcp_endpoint_roundtrip(corpus):
    srv = mod_server.DnServer(port=0, conf=_conf()).start()
    try:
        addr = '127.0.0.1:%d' % srv.bound_port
        expected = run_cli(['query', '-b', 'host', 'ds_dnc'])
        got = run_cli(['query', '--remote', addr, '-b', 'host',
                       'ds_dnc'])
        assert got == expected
    finally:
        srv.stop()
