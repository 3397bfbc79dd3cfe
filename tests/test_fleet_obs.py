"""Fleet observability (obs/history.py, obs/events.py,
serve/fleet.py, serve/top.py, the `events`/`fleet_stats` serve ops,
and `dn stats --cluster` / `dn events` / `dn top`).

Covers: history-ring windowed rates (honest Nones, counter-reset
clamp, bounded capacity), the event journal (ring bounds, trace-id
joining, JSONL spill, burst coalescing, zero-op when disabled), the
Prometheus exposition completeness gate (every typed metric named in
the source renders), the merged fleet document against a live
3-member cluster (aggregate quantiles from merged histograms, epoch
table, per-member rows, a dead member reported unreachable — never a
hang or a partial doc presented as complete), trace propagation
through the pooled v2 partial path (one joined span tree covering
router + members), byte-identity of the query path with the journal
and history armed, and the `dn top --once` frame."""

import json
import os
import re
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from dragnet_tpu import cli                                # noqa: E402
from dragnet_tpu.errors import DNError                     # noqa: E402
from dragnet_tpu.obs import events as obs_events           # noqa: E402
from dragnet_tpu.obs import export as obs_export           # noqa: E402
from dragnet_tpu.obs import history as obs_history         # noqa: E402
from dragnet_tpu.obs import metrics as obs_metrics         # noqa: E402
from dragnet_tpu.obs import trace as obs_trace             # noqa: E402
from dragnet_tpu.serve import client as mod_client         # noqa: E402
from dragnet_tpu.serve import fleet as mod_fleet           # noqa: E402
from dragnet_tpu.serve import router as mod_router         # noqa: E402
from dragnet_tpu.serve import server as mod_server         # noqa: E402
from dragnet_tpu.serve import top as mod_top               # noqa: E402
from dragnet_tpu.serve import topology as mod_topology     # noqa: E402

REPO_ROOT = os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))


def run_cli(args):
    with mod_server.thread_stdio() as cap:
        rc = cli.main(list(args))
    out, err = cap.finish()
    return rc, out, err


@pytest.fixture(autouse=True)
def _journal_isolation():
    """The journal is process-global (like DN_TRACE): every test in
    this file starts and ends without one installed."""
    obs_events.uninstall()
    yield
    obs_events.uninstall()


# -- history rings ----------------------------------------------------------

def test_history_counter_rates_and_gauge_avgs():
    h = obs_history.MetricHistory(1)
    t0 = time.monotonic() - 120.0
    for i in range(121):           # one sample/s for two minutes
        h.record('reqs', obs_history.COUNTER_KIND, i * 10,
                 t=t0 + i)
        h.record('depth', obs_history.GAUGE_KIND, 4.0, t=t0 + i)
    doc = h.series_doc()
    # 10/s across every window that has coverage
    assert abs(doc['reqs']['rate_1m'] - 10.0) < 0.5
    assert doc['reqs']['last'] == 1200.0
    assert abs(doc['depth']['avg_1m'] - 4.0) < 1e-6
    # the 15m window only has ~2m of samples: the rate is computed
    # over the covered span, still ~10/s
    assert abs(doc['reqs']['rate_15m'] - 10.0) < 0.5


def test_history_too_few_samples_is_none_not_fabricated():
    h = obs_history.MetricHistory(1)
    h.record('reqs', obs_history.COUNTER_KIND, 100)
    doc = h.series_doc()
    assert doc['reqs']['last'] == 100.0
    assert doc['reqs']['rate_1m'] is None
    assert h.rate('reqs') is None
    assert h.rate('nope') is None


def test_history_counter_reset_clamps_to_zero():
    h = obs_history.MetricHistory(1)
    now = time.monotonic()
    h.record('reqs', obs_history.COUNTER_KIND, 5000, t=now - 30)
    h.record('reqs', obs_history.COUNTER_KIND, 10, t=now)
    assert h.series_doc()['reqs']['rate_1m'] == 0.0


def test_history_capacity_bounded():
    h = obs_history.MetricHistory(60)
    assert h.capacity == int(900 // 60) + 2
    for i in range(1000):
        h.record('x', obs_history.COUNTER_KIND, i)
    with h._lock:
        assert len(h._series['x'][1]) == h.capacity


def test_history_snapshotter_samples_registry_and_provider():
    reg = obs_metrics.Registry()
    reg.inc('widgets_total', 3)
    reg.observe('op_ms', 12.0)
    snap = obs_history.HistorySnapshotter(
        1, registry=reg, provider=lambda: {
            'serve.requests': (obs_history.COUNTER_KIND, 7),
            'absent': (obs_history.GAUGE_KIND, None)})
    snap.sample_once()
    doc = snap.history.doc()
    assert doc['enabled'] and doc['samples'] == 1
    series = doc['series']
    assert series['widgets_total']['last'] == 3.0
    assert series['op_ms:count']['last'] == 1.0
    assert 'op_ms:p50' in series
    assert series['serve.requests']['last'] == 7.0
    assert 'absent' not in series        # None values never recorded


# -- the event journal ------------------------------------------------------

def test_journal_ring_bounds_seq_and_tail():
    j = obs_events.EventJournal(3, member='a')
    for i in range(5):
        j.record('t.ev', n=i)
    assert j.seq == 5 and j.dropped == 2
    tail = j.tail()
    assert [e['n'] for e in tail] == [2, 3, 4]
    assert [e['seq'] for e in tail] == [3, 4, 5]
    assert all(e['member'] == 'a' for e in tail)
    assert [e['n'] for e in j.tail(since=4)] == [4]
    assert [e['n'] for e in j.tail(limit=1)] == [4]
    doc = j.doc()
    assert doc['enabled'] and doc['seq'] == 5 and doc['dropped'] == 2


def test_journal_joins_active_trace_id():
    j = obs_events.install(capacity=8)
    with obs_trace.request('op', force=True, emit=False) as obs:
        obs_events.emit('router.failover', partition=1, to='b')
        want = obs.trace.trace_id
    obs_events.emit('breaker.open', member='b')
    ev = j.tail()
    assert ev[0]['trace'] == want
    assert ev[1]['trace'] is None


def test_journal_spill_is_jsonl(tmp_path):
    path = str(tmp_path / 'ev.jsonl')
    j = obs_events.EventJournal(8, path=path)
    j.record('a.b', x=1)
    j.record('c.d')
    lines = open(path).read().splitlines()
    docs = [json.loads(ln) for ln in lines]
    assert [d['type'] for d in docs] == ['a.b', 'c.d']
    assert docs[0]['x'] == 1 and docs[0]['seq'] == 1


def test_journal_spill_failure_disables_spill_not_ring(tmp_path):
    j = obs_events.EventJournal(8, path=str(tmp_path / 'no' / 'ev'))
    j.record('a.b')
    j.record('c.d')
    assert j.spill_errors == 1          # counted once, then dark
    assert len(j.tail()) == 2           # the ring never suffered


def test_burst_coalescing_bounds_storms():
    j = obs_events.install(capacity=64)
    for _ in range(50):
        obs_events.emit_burst('serve.shed', key='overload',
                              reason='overload', tenant='t1')
    assert len(j.tail()) == 1           # one entry per window
    # a DIFFERENT key gets its own window — an 'expired' shed is
    # never folded into an 'overload' count
    obs_events.emit_burst('serve.shed', key='expired',
                          reason='expired')
    assert len(j.tail()) == 2
    # when the window expires, the next same-keyed emission flushes
    # the suppressed occurrences as one aggregated entry
    with j._lock:
        j._bursts[('serve.shed', 'overload')][0] -= \
            obs_events.BURST_WINDOW_S + 1
    obs_events.emit_burst('serve.shed', key='overload',
                          reason='overload', tenant='t2')
    tail = j.tail()
    flushed = [e for e in tail if e.get('coalesced')]
    assert len(flushed) == 1 and flushed[0]['coalesced'] == 49
    assert flushed[0]['reason'] == 'overload'


def test_burst_tail_flushes_expired_window_on_read():
    """A storm that ENDS must still report its full size: the journal
    read flushes expired windows' suppressed counts even when no
    later event arrives."""
    j = obs_events.install(capacity=64)
    for _ in range(10):
        obs_events.emit_burst('serve.shed', key='overload',
                              reason='overload')
    with j._lock:
        j._bursts[('serve.shed', 'overload')][0] -= \
            obs_events.BURST_WINDOW_S + 1
    tail = j.tail()
    assert len(tail) == 2
    assert tail[1]['coalesced'] == 9


def test_events_spill_is_filtered_tree_metadata():
    """A DN_EVENTS_FILE named `.dn_events*` inside an index tree is
    filtered from shard walks and exempt from the soaks' litter
    checks — like the integrity catalog."""
    from dragnet_tpu import index_journal as mod_journal
    assert mod_journal.is_index_litter('/idx/.dn_events.jsonl')
    assert mod_journal.is_durable_metadata('.dn_events.jsonl')
    assert not mod_journal.is_index_litter('/idx/all')


def test_emit_without_journal_is_noop():
    assert obs_events.journal() is None
    assert obs_events.emit('x.y', a=1) is None
    assert obs_events.emit_burst('x.y') is None
    assert not obs_events.enabled()


def test_disabled_docs_are_shape_stable():
    assert set(obs_events.disabled_doc()) == \
        set(obs_events.EventJournal(1).doc())
    h = obs_history.MetricHistory(1)
    assert set(obs_history.disabled_doc()) == set(h.doc())


# -- Prometheus exposition completeness gate --------------------------------

# helper calls whose first literal argument is a typed metric name
_METRIC_CALL = re.compile(
    r"\b(?:obs_metrics|mod_metrics|metrics|reg)\."
    r"(inc|set_gauge|observe|counter|gauge|histogram)\(\s*"
    r"(?:name\s*=\s*)?'([^']+)'")
_TIMED_STAGE = re.compile(r"metric\s*=\s*'([^']+)'")
_KIND_OF = {'inc': 'counter', 'counter': 'counter',
            'set_gauge': 'gauge', 'gauge': 'gauge',
            'observe': 'histogram', 'histogram': 'histogram'}
_WELL_FORMED = re.compile(r'^[a-zA-Z_][a-zA-Z0-9_.]*$')


def _registered_metric_names():
    """Every typed metric name the source registers, found by walking
    the helper-call sites (plus the router's dynamic counter family
    and the device gauges wired through refresh_device_gauges).  A
    new counter added anywhere lands here automatically — and must
    then render in prometheus_text."""
    names = {}
    pkg = os.path.join(REPO_ROOT, 'dragnet_tpu')
    for dirpath, dirnames, filenames in os.walk(pkg):
        dirnames[:] = [d for d in dirnames if d != '__pycache__']
        for fn in filenames:
            if not fn.endswith('.py'):
                continue
            src = open(os.path.join(dirpath, fn)).read()
            for m in _METRIC_CALL.finditer(src):
                if '%' in m.group(2):
                    # a dynamic family ('router_%s_total' % name):
                    # enumerated explicitly below, never silently
                    # skipped — assert the only one we know about
                    assert m.group(2) == 'router_%s_total', \
                        ('new dynamic metric family %r: enumerate '
                         'its names in _registered_metric_names'
                         % m.group(2))
                    continue
                names.setdefault(m.group(2), _KIND_OF[m.group(1)])
            for m in _TIMED_STAGE.finditer(src):
                names.setdefault(m.group(1), 'histogram')
    for cname in mod_router.COUNTER_NAMES:
        names['router_%s_total' % cname] = 'counter'
    for _, gname in obs_metrics._DEVICE_COUNTER_GAUGES:
        names[gname] = 'gauge'
    return names


def test_prometheus_exposition_completeness():
    """The gate: every typed metric registered anywhere in the
    process appears in prometheus_text() with a well-formed name —
    including the topo_* and integrity_* families — so a new counter
    can never silently miss the exposition."""
    names = _registered_metric_names()
    # sanity: the walk actually found the families the satellites
    # call out (a broken regex must not pass vacuously)
    for expected in ('topo_epoch_transitions_total',
                     'topo_epoch_mismatch_total',
                     'integrity_repairs_total',
                     'integrity_corrupt_shards_total',
                     'router_failovers_total', 'serve_shed_total',
                     'router_partial_items_total',
                     'router_partial_bytes_total',
                     'cluster_partials_total',
                     'handoff_shards_streamed_total',
                     'follow_ingest_lag_ms', 'device_residency_pct'):
        assert expected in names, expected
    assert len(names) > 25
    reg = obs_metrics.Registry()
    for name, kind in sorted(names.items()):
        assert _WELL_FORMED.match(name), \
            'metric name %r will not expose cleanly' % name
        if kind == 'counter':
            reg.inc(name)
        elif kind == 'gauge':
            reg.set_gauge(name, 1.0)
        else:
            reg.observe(name, 1.0)
    text = obs_export.prometheus_text(reg)
    prom_line = re.compile(
        r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? [0-9eE.+-]+$')
    for line in text.splitlines():
        if not line.startswith('#'):
            assert prom_line.match(line), line
    for name, kind in names.items():
        pname = 'dn_' + name.replace('.', '_')
        if kind == 'histogram':
            assert ('%s_bucket' % pname) in text, name
            assert ('%s_count' % pname) in text, name
        else:
            assert re.search(r'^%s(\{| )' % re.escape(pname), text,
                             re.M), name


# -- corpus + cluster fixtures ----------------------------------------------

def _gen_corpus(path, n=300):
    import datetime
    t0 = 1388534400
    with open(path, 'w') as f:
        for i in range(n):
            ts = datetime.datetime.utcfromtimestamp(
                t0 + i * 1100).strftime('%Y-%m-%dT%H:%M:%S.000Z')
            f.write(json.dumps({
                'time': ts, 'host': 'host%d' % (i % 3),
                'latency': (i * 7) % 230,
            }, separators=(',', ':')) + '\n')


@pytest.fixture(scope='module')
def corpus(tmp_path_factory):
    root = tmp_path_factory.mktemp('fleet_corpus')
    datafile = str(root / 'data.log')
    _gen_corpus(datafile)
    rc_path = str(root / 'dragnetrc.json')
    prior = os.environ.get('DRAGNET_CONFIG')
    os.environ['DRAGNET_CONFIG'] = rc_path
    try:
        idx = str(root / 'idx')
        rc, out, err = run_cli([
            'datasource-add', '--path', datafile,
            '--index-path', idx, '--time-field', 'time', 'fleetds'])
        assert rc == 0, err
        rc, out, err = run_cli(['metric-add', '-b', 'host',
                                'fleetds', 'm1'])
        assert rc == 0, err
        rc, out, err = run_cli(['build', 'fleetds'])
        assert rc == 0, err
        yield {'rc_path': rc_path, 'ds': 'fleetds'}
    finally:
        if prior is None:
            os.environ.pop('DRAGNET_CONFIG', None)
        else:
            os.environ['DRAGNET_CONFIG'] = prior


def _conf(**over):
    base = {'max_inflight': 4, 'queue_depth': 16, 'deadline_ms': 0,
            'coalesce': True, 'drain_s': 10, 'fleet_timeout_s': 3}
    base.update(over)
    return base


@pytest.fixture
def cluster(corpus, tmp_path, monkeypatch):
    """Three in-process members, journal + history armed (the fleet
    tests exist to see them), fast-failing client knobs so a dead
    member costs milliseconds."""
    monkeypatch.setenv('DN_ROUTER_PROBE_MS', '60000')
    monkeypatch.setenv('DN_REMOTE_RETRIES', '0')
    monkeypatch.setenv('DN_REMOTE_BACKOFF_MS', '1')
    monkeypatch.setenv('DN_REMOTE_CONNECT_TIMEOUT_S', '1')
    monkeypatch.setenv('DN_EVENTS', '256')
    monkeypatch.setenv('DN_METRICS_HISTORY_S', '1')
    socks = {m: str(tmp_path / ('dn-%s.sock' % m)) for m in 'abc'}
    topo_path = str(tmp_path / 'topo.json')
    with open(topo_path, 'w') as f:
        json.dump({
            'epoch': 1, 'assign': 'hash',
            'members': {m: {'endpoint': socks[m]} for m in socks},
            'partitions': [
                {'id': 0, 'replicas': ['a', 'b']},
                {'id': 1, 'replicas': ['b', 'c']},
                {'id': 2, 'replicas': ['c', 'a']},
            ],
        }, f)
    servers = {}
    for m in 'abc':
        topo = mod_topology.load_topology(topo_path, member=m)
        servers[m] = mod_server.DnServer(
            socket_path=socks[m], conf=_conf(), cluster=topo,
            member=m).start()
    try:
        yield {'servers': servers, 'socks': socks,
               'topo_path': topo_path}
    finally:
        for srv in servers.values():
            srv.stop()


def _routed_query(corpus, sock):
    req = {'op': 'query', 'ds': corpus['ds'], 'interval': 'day',
           'config': corpus['rc_path'],
           'queryconfig': {'breakdowns': [
               {'name': 'host', 'field': 'host'}]},
           'opts': {}}
    return mod_client.request_bytes(sock, req, timeout_s=120.0)


# -- the fleet document -----------------------------------------------------

def test_fleet_doc_three_members_merged(cluster, corpus):
    rc, hd, out, err = _routed_query(corpus, cluster['socks']['a'])
    assert rc == 0, err
    rc, hd, out, err = mod_client.request_bytes(
        cluster['socks']['a'], {'op': 'fleet_stats'}, timeout_s=60.0)
    assert rc == 0, err
    doc = json.loads(out.decode('utf-8'))
    assert doc['version'] == mod_fleet.FLEET_VERSION
    assert doc['members_total'] == 3 and doc['members_up'] == 3
    assert doc['complete'] and doc['unreachable'] == []
    assert doc['epoch'] == 1 and doc['epoch_skew'] == 0
    assert set(doc['members']) == {'a', 'b', 'c'}
    for name, row in doc['members'].items():
        assert row['ok'] and row['epoch'] == 1, name
        assert row['history'] and row['events'], name
    # the epoch-skew table covers every member
    assert set(doc['epochs']) == {'a', 'b', 'c'}
    # aggregate latency quantiles come from merged histograms: the
    # fleet count is the SUM of per-member observation counts
    agg = doc['aggregate']
    assert agg['latency'] is not None
    member_counts = 0
    for m in 'abc':
        st = mod_client.stats(cluster['socks'][m])
        hists = st['metrics']['histograms']
        for jname, ent in hists.items():
            if jname.startswith('serve_op_latency_ms'):
                member_counts += ent['count']
    assert agg['latency']['count'] == member_counts
    assert agg['requests'] >= 3      # router + two member partials
    # the aggregating member's breaker view covers the fleet
    assert set(doc['breakers']) == {'a', 'b', 'c'}


def test_fleet_doc_dead_member_unreachable_not_hang(cluster, corpus):
    rc, hd, out, err = _routed_query(corpus, cluster['socks']['a'])
    assert rc == 0, err
    cluster['servers']['b'].stop()
    t0 = time.monotonic()
    rc, hd, out, err = mod_client.request_bytes(
        cluster['socks']['a'], {'op': 'fleet_stats'}, timeout_s=60.0)
    elapsed = time.monotonic() - t0
    assert rc == 0, err
    doc = json.loads(out.decode('utf-8'))
    assert elapsed < _conf()['fleet_timeout_s'] + 10
    assert doc['members_up'] == 2
    assert doc['unreachable'] == ['b']
    assert not doc['complete']       # never a partial doc as complete
    row = doc['members']['b']
    assert row == {'ok': False, 'unreachable': True,
                   'error': row['error']}
    assert row['error']
    # the live members still merged
    assert doc['members']['a']['ok'] and doc['members']['c']['ok']
    assert doc['aggregate']['latency'] is not None


def test_fleet_events_merged_and_deduped(cluster, corpus):
    obs_events.emit('router.failover', partition=9, to='c')
    rc, hd, out, err = mod_client.request_bytes(
        cluster['socks']['a'], {'op': 'fleet_stats', 'events': 20},
        timeout_s=60.0)
    assert rc == 0, err
    doc = json.loads(out.decode('utf-8'))
    evs = [e for e in doc['events'] if e['type'] == 'router.failover'
           and e.get('partition') == 9]
    # three in-process members share one journal: the merge dedupes
    # by (member, seq) so the entry appears exactly once
    assert len(evs) == 1
    assert evs[0]['member'] == 'a'   # first server to bind installed


def test_dn_stats_cluster_cli_and_prom(cluster, corpus):
    rc, out, err = run_cli(['stats', '--cluster', '--remote',
                            cluster['socks']['b']])
    assert rc == 0, err
    doc = json.loads(out.decode('utf-8'))
    assert doc['members_total'] == 3
    assert doc['aggregated_by'] == 'b'
    rc, out, err = run_cli(['stats', '--cluster', '--prom',
                            '--remote', cluster['socks']['b']])
    assert rc == 0, err
    text = out.decode('utf-8')
    assert 'dn_fleet_members_up 3' in text
    assert 'dn_fleet_member_up{member="a"} 1' in text
    rc, out, err = run_cli(['stats', '--cluster'])
    assert rc == 1
    assert b'requires "--remote"' in err


def test_fleet_single_process_degrade(corpus, tmp_path):
    sock = str(tmp_path / 'solo.sock')
    srv = mod_server.DnServer(socket_path=sock,
                              conf=_conf()).start()
    try:
        rc, hd, out, err = mod_client.request_bytes(
            sock, {'op': 'fleet_stats'}, timeout_s=30.0)
        assert rc == 0, err
        doc = json.loads(out.decode('utf-8'))
        assert doc['members_total'] == 1 and doc['members_up'] == 1
        assert doc['complete'] and doc['epoch'] is None
        assert list(doc['members']) == ['local']
        frame = mod_top.render_frame(doc, ansi=False)
        assert 'members 1/1 up' in frame
    finally:
        srv.stop()


# -- dn top / dn events -----------------------------------------------------

def test_dn_top_once_renders_fleet_frame(cluster, corpus):
    rc, hd, out, err = _routed_query(corpus, cluster['socks']['a'])
    assert rc == 0, err
    obs_events.emit('topo.commit', epoch=1)
    rc, out, err = run_cli(['top', '--remote', cluster['socks']['a'],
                            '--once'])
    assert rc == 0, err
    text = out.decode('utf-8')
    assert '\x1b[' not in text          # --once: no ANSI codes
    assert 'members 3/3 up' in text
    assert re.search(r'^a +up', text, re.M)
    assert 'topo.commit' in text
    rc, out, err = run_cli(['top'])
    assert rc == 2                      # --remote required


def test_dn_top_once_unreachable_is_clean(tmp_path):
    rc, out, err = run_cli(['top', '--remote',
                            str(tmp_path / 'nope.sock'), '--once'])
    assert rc == 1
    assert b'Traceback' not in err
    assert b'fleet fetch failed' in err


def test_dn_events_remote_and_follow_shape(cluster, corpus):
    obs_events.emit('repair.completed', shard='x/y.dnc', ds='fleetds')
    rc, out, err = run_cli(['events', '--remote',
                            cluster['socks']['a']])
    assert rc == 0, err
    docs = [json.loads(ln) for ln in out.decode().splitlines()]
    assert any(d['type'] == 'repair.completed' and
               d['shard'] == 'x/y.dnc' for d in docs)
    assert all('seq' in d and 'ts' in d for d in docs)


def test_dn_events_disabled_server_is_clean_error(corpus, tmp_path):
    sock = str(tmp_path / 'noev.sock')
    srv = mod_server.DnServer(socket_path=sock,
                              conf=_conf()).start()
    try:
        rc, out, err = run_cli(['events', '--remote', sock])
        assert rc == 1
        assert b'journal disabled' in err
    finally:
        srv.stop()


# -- trace propagation through the pooled v2 partial path -------------------

def test_traced_routed_query_one_joined_tree(corpus, tmp_path,
                                             monkeypatch):
    """The satellite regression: a traced routed query produces ONE
    joined span tree covering the router and both remote members'
    partials over the pooled v2 path.  The topology puts two
    partitions on b/c only, so router a MUST dial both remotely
    (replica ranking self-prefers; the shared fixture's layout gives
    every router two local partitions)."""
    monkeypatch.setenv('DN_ROUTER_PROBE_MS', '60000')
    monkeypatch.setenv('DN_REMOTE_RETRIES', '0')
    socks = {m: str(tmp_path / ('tr-%s.sock' % m)) for m in 'abc'}
    topo_path = str(tmp_path / 'tr-topo.json')
    with open(topo_path, 'w') as f:
        json.dump({
            'epoch': 1, 'assign': 'hash',
            'members': {m: {'endpoint': socks[m]} for m in socks},
            'partitions': [
                {'id': 0, 'replicas': ['b', 'c']},
                {'id': 1, 'replicas': ['c', 'b']},
                {'id': 2, 'replicas': ['a', 'b']},
            ],
        }, f)
    servers = {}
    for m in 'abc':
        topo = mod_topology.load_topology(topo_path, member=m)
        servers[m] = mod_server.DnServer(
            socket_path=socks[m], conf=_conf(), cluster=topo,
            member=m).start()
    sink = str(tmp_path / 'routed.jsonl')
    monkeypatch.setenv('DN_TRACE', sink)
    try:
        rc, out, err = run_cli(['query', '-b', 'host', '--remote',
                                socks['a'], corpus['ds']])
    finally:
        monkeypatch.delenv('DN_TRACE')
        for srv in servers.values():
            srv.stop()
    assert rc == 0, err
    docs = [json.loads(ln) for ln in open(sink).read().splitlines()]
    client_docs = [d for d in docs if d['op'] == 'query']
    assert len(client_docs) == 1
    doc = client_docs[0]

    grafted = []

    def walk(span, path):
        if span.get('name') == 'router.partial':
            member = (span.get('attrs') or {}).get('member')
            for c in span.get('children') or []:
                if c.get('name') == 'serve.query_partial':
                    grafted.append(member)
        for c in span.get('children') or []:
            walk(c, path + [span.get('name')])

    walk(doc['spans'], [])
    # member a's own partial runs locally (its spans attribute
    # directly); b and c answered over the POOLED path and their
    # subtrees grafted under the router.partial spans — the joined
    # tree covers the router plus (at least) two members
    assert len(set(grafted)) >= 2, doc['spans']
    assert set(grafted) <= {'b', 'c'}
    # every member-side trace line shares the client's id
    partials = [d for d in docs if d['op'] == 'serve.query_partial']
    assert partials and all(d['trace'] == doc['trace']
                            for d in partials)


def test_query_bytes_identical_with_fleet_obs_armed(corpus, tmp_path,
                                                    monkeypatch):
    """The acceptance gate: with history + events DISABLED (default)
    and ENABLED, a served query's payload bytes are identical."""
    def serve_once():
        sock = str(tmp_path / ('bi-%d.sock' % time.monotonic_ns()))
        srv = mod_server.DnServer(socket_path=sock,
                                  conf=_conf()).start()
        try:
            req = {'op': 'query', 'ds': corpus['ds'],
                   'interval': 'day', 'config': corpus['rc_path'],
                   'queryconfig': {'breakdowns': [
                       {'name': 'host', 'field': 'host'}]},
                   'opts': {}}
            rc, hd, out, err = mod_client.request_bytes(
                sock, req, timeout_s=60.0)
            assert rc == 0, err
            return out
        finally:
            srv.stop()

    monkeypatch.delenv('DN_EVENTS', raising=False)
    monkeypatch.delenv('DN_METRICS_HISTORY_S', raising=False)
    baseline = serve_once()
    obs_events.uninstall()
    monkeypatch.setenv('DN_EVENTS', '128')
    monkeypatch.setenv('DN_METRICS_HISTORY_S', '1')
    armed = serve_once()
    assert armed == baseline


# -- merge unit (canned inputs) ---------------------------------------------

def test_merge_fleet_histogram_math():
    """Aggregate quantiles come from bucket-merged histograms, not
    averaged member quantiles."""
    def member_stats(latencies):
        reg = obs_metrics.Registry()
        for v in latencies:
            reg.observe('serve_op_latency_ms', v, op='query')
        return {'requests': {'requests': len(latencies), 'errors': 0,
                             'shed_overloaded': 0,
                             'busy_rejected': 0},
                'inflight': {'active': 0, 'queued': 0},
                'metrics': obs_export.stats_section(reg)}

    class FakeServer(object):
        cluster = None
        router = None
        member = 'a'

    stats = {'a': member_stats([1.5] * 90),
             'b': member_stats([700.0] * 10)}
    doc = mod_fleet.merge_fleet(FakeServer(), ['a', 'b'], stats, {},
                                {})
    lat = doc['aggregate']['latency']
    assert lat['count'] == 100
    # 90% of mass at ~1.5ms: the merged p50 sits in the low buckets,
    # p99 in the high ones — impossible from averaging (350ms)
    assert lat['p50'] < 10
    assert lat['p99'] >= 500
    assert doc['aggregate']['requests'] == 100
