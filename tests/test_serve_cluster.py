"""Scatter-gather cluster serving (serve/topology.py, serve/router.py).

Covers: topology-file validation (bad JSON, overlapping partitions,
unknown members, time-range rules), deterministic shard->partition
assignment, routed-query byte-identity vs the single-process
index_query_stack output across both index formats, replica failover
on a dead member, per-member circuit-breaker transitions
(closed/open/half-open) both as a unit and under injected
member.health faults, hedged-read accounting, draining-member
demotion, the clean degraded-response contract in both
DN_ROUTER_PARTIAL modes, topology-epoch mismatch rejection, the
duplicate-shard merge guard, and `dn serve --validate` cluster
reporting.
"""

import json
import os
import sys
import threading

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from dragnet_tpu import cli                                # noqa: E402
from dragnet_tpu import faults as mod_faults               # noqa: E402
from dragnet_tpu.errors import DNError                     # noqa: E402
from dragnet_tpu.serve import client as mod_client         # noqa: E402
from dragnet_tpu.serve import router as mod_router         # noqa: E402
from dragnet_tpu.serve import server as mod_server         # noqa: E402
from dragnet_tpu.serve import topology as mod_topology     # noqa: E402


def run_cli(args):
    with mod_server.thread_stdio() as cap:
        rc = cli.main(list(args))
    out, err = cap.finish()
    return rc, out, err


def _gen_corpus(path, n=400):
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
    root = tmp_path_factory.mktemp('cluster_corpus')
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
               'dss': ['ds_dnc', 'ds_sq']}
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


def _topo_doc(socks, epoch=1, assign='hash'):
    return {
        'epoch': epoch,
        'assign': assign,
        'members': {m: {'endpoint': socks[m]} for m in socks},
        'partitions': [
            {'id': 0, 'replicas': ['a', 'b']},
            {'id': 1, 'replicas': ['b', 'c']},
            {'id': 2, 'replicas': ['c', 'a']},
        ],
    }


@pytest.fixture
def cluster(corpus, tmp_path, monkeypatch):
    """Three in-process members over one index tree.  The background
    prober is quiesced (probe_once() drives member state when a test
    needs it) and client backoff is minimal so dead-member dials fail
    fast."""
    monkeypatch.setenv('DN_ROUTER_PROBE_MS', '60000')
    monkeypatch.setenv('DN_REMOTE_RETRIES', '0')
    monkeypatch.setenv('DN_REMOTE_BACKOFF_MS', '1')
    monkeypatch.setenv('DN_REMOTE_CONNECT_TIMEOUT_S', '1')
    socks = {m: str(tmp_path / ('dn-%s.sock' % m)) for m in 'abc'}
    topo_path = str(tmp_path / 'topo.json')
    with open(topo_path, 'w') as f:
        json.dump(_topo_doc(socks), f)
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


def _query_req(ds, corpus, epoch=None, partitions=None,
               op='query'):
    doc = {'op': op, 'ds': ds, 'config': corpus['rc_path'],
           'queryconfig': {'breakdowns': [
               {'name': 'host', 'field': 'host'}]},
           'interval': 'day', 'opts': {}}
    if epoch is not None:
        doc['epoch'] = epoch
    if partitions is not None:
        doc['partitions'] = partitions
    return doc


# -- topology validation ----------------------------------------------------

def _write_topo(tmp_path, doc):
    path = str(tmp_path / 'topo.json')
    with open(path, 'w') as f:
        if isinstance(doc, str):
            f.write(doc)
        else:
            json.dump(doc, f)
    return path


def test_topology_loads_and_summarizes(tmp_path):
    socks = {m: str(tmp_path / (m + '.sock')) for m in 'abc'}
    path = _write_topo(tmp_path, _topo_doc(socks))
    topo = mod_topology.load_topology(path, member='b')
    assert topo.epoch == 1
    assert topo.partition_ids() == [0, 1, 2]
    assert topo.replicas(1) == ['b', 'c']
    assert topo.partitions_of('b') == [0, 1]
    assert topo.summary()['assign'] == 'hash'


@pytest.mark.parametrize('mutate,needle', [
    (lambda d: d.update(epoch=0), 'epoch'),
    (lambda d: d.update(epoch='one'), 'epoch'),
    (lambda d: d.update(assign='roundrobin'), 'assign'),
    (lambda d: d.update(members={}), 'members'),
    (lambda d: d['members'].update(a={'endpoint': ''}), 'endpoint'),
    (lambda d: d.update(partitions=[]), 'partitions'),
    (lambda d: d['partitions'].append(
        {'id': 0, 'replicas': ['a']}), 'overlapping'),
    (lambda d: d['partitions'][0].update(replicas=[]), 'replicas'),
    (lambda d: d['partitions'][0].update(replicas=['a', 'a']),
     'duplicate replica'),
    (lambda d: d['partitions'][0].update(replicas=['nope']),
     'unknown member'),
    (lambda d: d.update(partitions=[
        {'id': 0, 'replicas': ['b', 'c']}]), 'owns no partition'),
])
def test_topology_rejects_bad_docs(tmp_path, mutate, needle):
    socks = {m: str(tmp_path / (m + '.sock')) for m in 'abc'}
    doc = _topo_doc(socks)
    mutate(doc)
    path = _write_topo(tmp_path, doc)
    with pytest.raises(DNError) as ei:
        mod_topology.load_topology(path)
    assert needle in ei.value.message


def test_topology_rejects_bad_json_and_unknown_member(tmp_path):
    path = _write_topo(tmp_path, '{nope')
    with pytest.raises(DNError) as ei:
        mod_topology.load_topology(path)
    assert 'invalid JSON' in ei.value.message
    socks = {m: str(tmp_path / (m + '.sock')) for m in 'abc'}
    with pytest.raises(DNError) as ei:
        mod_topology.load_topology(
            _write_topo(tmp_path, _topo_doc(socks)), member='zed')
    assert '"zed" is not a member' in ei.value.message
    with pytest.raises(DNError):
        mod_topology.load_topology(str(tmp_path / 'missing.json'))


def test_topology_rejects_overlapping_time_ranges(tmp_path):
    socks = {m: str(tmp_path / (m + '.sock')) for m in 'abc'}
    doc = _topo_doc(socks, assign='time-range')
    doc['partitions'][0].update(after='2014-01-01',
                                before='2014-01-03')
    doc['partitions'][1].update(after='2014-01-02',
                                before='2014-01-04')
    with pytest.raises(DNError) as ei:
        mod_topology.load_topology(_write_topo(tmp_path, doc))
    assert 'overlapping time ranges' in ei.value.message
    doc['partitions'][1].update(after='2014-01-05',
                                before='2014-01-04')
    with pytest.raises(DNError) as ei:
        mod_topology.load_topology(_write_topo(tmp_path, doc))
    assert '"before" must be after "after"' in ei.value.message
    doc['partitions'][1].update(after='not-a-date',
                                before='2014-01-08')
    with pytest.raises(DNError) as ei:
        mod_topology.load_topology(_write_topo(tmp_path, doc))
    assert 'not a valid date' in ei.value.message


def test_partition_assignment_deterministic(tmp_path):
    """The hash rule is crc32-stable: two independently loaded
    topologies assign every shard name identically (the router and
    every member must agree without coordination)."""
    socks = {m: {'endpoint': str(tmp_path / m)} for m in 'abc'}
    doc = {'epoch': 1, 'members': socks,
           'partitions': [{'id': i, 'replicas': [m]}
                          for i, m in enumerate('abc')]}
    t1 = mod_topology.Topology(json.loads(json.dumps(doc)))
    t2 = mod_topology.Topology(json.loads(json.dumps(doc)))
    names = ['2014-01-%02d.sqlite' % d for d in range(1, 29)]
    assign1 = [t1.partition_of(n) for n in names]
    assert assign1 == [t2.partition_of(n) for n in names]
    assert len(set(assign1)) > 1      # spreads across partitions
    # full paths assign by basename only
    assert t1.partition_of('/idx/a/' + names[0]) == assign1[0]


def test_partition_of_time_range(tmp_path):
    socks = {m: {'endpoint': str(tmp_path / m)} for m in 'ab'}
    doc = {'epoch': 1, 'assign': 'time-range', 'members': socks,
           'partitions': [
               {'id': 0, 'replicas': ['a'], 'after': '2014-01-01',
                'before': '2014-01-03', '_after_ms': None,
                '_before_ms': None},
               {'id': 1, 'replicas': ['b']},
           ]}
    err = mod_topology.validate_doc(doc)
    assert err is None
    topo = mod_topology.Topology(doc)
    fmt = '%Y-%m-%d.sqlite'
    assert topo.partition_of('2014-01-01.sqlite', fmt) == 0
    assert topo.partition_of('2014-01-02.sqlite', fmt) == 0
    # outside the window (and unparseable names): the hash fallback
    out = topo.partition_of('2014-01-05.sqlite', fmt)
    assert out == topo._hash_partition('2014-01-05.sqlite')
    weird = topo.partition_of('all.sqlite', fmt)
    assert weird == topo._hash_partition('all.sqlite')


def test_cluster_plan_reports_serve_topology(tmp_path, monkeypatch):
    """The cluster backend's execution plan reports the serve-cluster
    layout when DN_SERVE_TOPOLOGY names a map — and a broken map
    reports in-plan instead of failing the dry run."""
    from dragnet_tpu.parallel import cluster as mod_cluster
    socks = {m: str(tmp_path / (m + '.sock')) for m in 'abc'}
    topo_path = _write_topo(tmp_path, _topo_doc(socks))
    ds = mod_cluster.DatasourceCluster({
        'ds_backend': 'cluster',
        'ds_backend_config': {'path': str(tmp_path)},
        'ds_filter': None, 'ds_format': 'json',
    })
    monkeypatch.delenv('DN_SERVE_TOPOLOGY', raising=False)
    assert 'serve_topology' not in ds.execution_plan([])
    monkeypatch.setenv('DN_SERVE_TOPOLOGY', topo_path)
    topo = ds.execution_plan([])['serve_topology']
    assert topo['epoch'] == 1 and topo['assign'] == 'hash'
    assert [p['id'] for p in topo['partitions']] == [0, 1, 2]
    assert topo['members']['a'] == socks['a']
    monkeypatch.setenv('DN_SERVE_TOPOLOGY',
                       str(tmp_path / 'missing.json'))
    broken = ds.execution_plan([])['serve_topology']
    assert 'error' in broken


# -- routed byte-identity ---------------------------------------------------

def _cases(ds):
    return [
        ['query', '-b', 'host', ds],
        ['query', '-b', 'host,latency[aggr=quantize]', ds],
        ['query', '--points', '-b', 'operation', '-f',
         '{"eq": ["req.method", "GET"]}', ds],
        ['query', '--raw', '-b', 'host,latency[aggr=quantize]',
         '-A', '2014-01-02', '-B', '2014-01-03', ds],
        ['query', '-b',
         'timestamp[date,field=time,aggr=lquantize,step=86400],host',
         ds],
    ]


def test_routed_queries_byte_identical(cluster, corpus):
    """Every query shape x both index formats x every member as
    router: routed bytes == the single-process index_query_stack
    run's bytes."""
    for ds in corpus['dss']:
        for case in _cases(ds):
            expected = run_cli(case)
            assert expected[0] == 0
            for m in 'abc':
                got = run_cli(case[:1] +
                              ['--remote', cluster['socks'][m]] +
                              case[1:])
                assert got == expected, (m, case)


def test_cluster_stats_section(cluster, corpus):
    sock = cluster['socks']['a']
    case = _cases(corpus['dss'][0])[0]
    assert run_cli(case[:1] + ['--remote', sock] + case[1:])[0] == 0
    doc = mod_client.stats(sock)
    cl = doc['cluster']
    assert cl['member'] == 'a'
    assert cl['epoch'] == 1
    assert cl['partitions'] == 3
    assert cl['partitions_owned'] == [0, 2]
    assert cl['counters']['scatters'] >= 1
    assert cl['counters']['partials_local'] >= 1
    for m in 'abc':
        assert cl['members'][m]['state'] == 'closed'
    # health op names the member and epoch in cluster mode
    h = mod_client.health(sock)
    assert h['member'] == 'a' and h['epoch'] == 1


def test_failover_dead_member_byte_identical(cluster, corpus):
    """Partition 1's primary (b) dies without the prober noticing
    (it is quiesced): the scatter dials b, fails, and fails over to
    c — bytes still identical, failover counted."""
    cluster['servers']['b'].stop()
    case = _cases(corpus['dss'][0])[0]
    expected = run_cli(case)
    sock = cluster['socks']['a']
    got = run_cli(case[:1] + ['--remote', sock] + case[1:])
    assert got == expected
    cl = mod_client.stats(sock)['cluster']
    assert cl['counters']['failovers'] >= 1
    assert cl['counters']['degraded'] == 0


def test_degraded_error_mode(cluster, corpus):
    """Every replica of partition 1 (b, c) dead under the default
    DN_ROUTER_PARTIAL=error: a clean retryable rc=1 response naming
    the missing partition — no hang, no traceback, no bytes."""
    cluster['servers']['b'].stop()
    cluster['servers']['c'].stop()
    rc, header, out, err = mod_client.request_bytes(
        cluster['socks']['a'],
        _query_req(corpus['dss'][0], corpus), timeout_s=120.0)
    assert rc == 1
    assert header['retryable'] is True
    assert header['stats']['missing_partitions'] == [1]
    assert out == b''
    text = err.decode()
    assert text.startswith('dn: ')
    assert 'partition(s) unavailable: 1' in text
    assert 'Traceback' not in text
    cl = mod_client.stats(cluster['socks']['a'])['cluster']
    assert cl['counters']['degraded'] >= 1


def test_degraded_allow_mode(corpus, tmp_path, monkeypatch):
    """DN_ROUTER_PARTIAL=allow: the live partitions merge, rc=0, the
    header carries partial=true + the missing ids, and stderr warns."""
    monkeypatch.setenv('DN_ROUTER_PARTIAL', 'allow')
    monkeypatch.setenv('DN_ROUTER_PROBE_MS', '60000')
    monkeypatch.setenv('DN_REMOTE_RETRIES', '0')
    monkeypatch.setenv('DN_REMOTE_CONNECT_TIMEOUT_S', '1')
    socks = {m: str(tmp_path / ('dn-%s.sock' % m)) for m in 'abc'}
    topo_path = _write_topo(tmp_path, _topo_doc(socks))
    topo = mod_topology.load_topology(topo_path, member='a')
    srv = mod_server.DnServer(socket_path=socks['a'], conf=_conf(),
                              cluster=topo, member='a').start()
    try:
        rc, header, out, err = mod_client.request_bytes(
            socks['a'], _query_req(corpus['dss'][0], corpus),
            timeout_s=120.0)
        assert rc == 0
        assert header['stats']['partial'] is True
        assert header['stats']['missing_partitions'] == [1]
        assert b'VALUE' in out            # the live partitions merged
        assert 'partition(s) 1 unavailable' in err.decode()
    finally:
        srv.stop()


def test_epoch_mismatch_is_clean_retryable(cluster, corpus):
    rc, header, out, err = mod_client.request_bytes(
        cluster['socks']['b'],
        _query_req(corpus['dss'][0], corpus, epoch=999,
                   partitions=[1], op='query_partial'),
        timeout_s=60.0)
    assert rc == 1
    assert header['retryable'] is True
    assert 'epoch mismatch' in err.decode()


def test_query_partial_shape_and_validation(cluster, corpus):
    rc, header, out, err = mod_client.request_bytes(
        cluster['socks']['b'],
        _query_req(corpus['dss'][0], corpus, epoch=1,
                   partitions=[1], op='query_partial'),
        timeout_s=60.0)
    assert rc == 0, err
    doc = json.loads(out.decode())
    assert doc['member'] == 'b' and doc['epoch'] == 1
    assert isinstance(doc['shards'], list)
    for relpath, items in doc['shards']:
        assert not os.path.isabs(relpath)
        for keys, weight in items:
            assert isinstance(keys, list)
    # unknown partition ids are rejected cleanly
    rc, header, out, err = mod_client.request_bytes(
        cluster['socks']['b'],
        _query_req(corpus['dss'][0], corpus, epoch=1,
                   partitions=[7], op='query_partial'),
        timeout_s=60.0)
    assert rc == 1
    assert 'bad "partitions"' in err.decode()


# -- circuit breaker --------------------------------------------------------

def test_breaker_transitions_unit():
    clock = [0.0]
    b = mod_router.Breaker(3, 1000, clock=lambda: clock[0])
    assert b.state == b.CLOSED
    for _ in range(2):
        b.record_failure()
    assert b.state == b.CLOSED and b.allow()
    b.record_failure()                    # third consecutive: open
    assert b.state == b.OPEN
    assert not b.allow()                  # cooldown not elapsed
    clock[0] += 1.0
    assert b.allow()                      # half-open trial
    assert b.state == b.HALF_OPEN
    assert not b.allow()                  # one trial at a time
    b.record_failure()                    # trial failed: re-open
    assert b.state == b.OPEN
    clock[0] += 1.0
    assert b.allow()
    b.record_success()                    # trial succeeded: closed
    assert b.state == b.CLOSED
    assert b.allow()
    snap = b.snapshot()
    assert snap['transitions'][b.OPEN] == 2
    assert snap['transitions'][b.HALF_OPEN] == 2
    assert snap['transitions'][b.CLOSED] == 1


def test_breaker_opens_under_injected_health_faults(
        cluster, monkeypatch):
    """member.health armed at rate 1.0: probe sweeps fail for every
    remote member, the breakers open after DN_ROUTER_FAILURES
    verdicts, and /stats shows it; disarming lets the half-open
    trial close them again."""
    router = cluster['servers']['a'].router
    monkeypatch.setenv('DN_FAULTS', 'member.health:error:1.0')
    try:
        for _ in range(3):
            router.probe_once()
        for m in 'bc':
            assert router.states[m].breaker.state == \
                mod_router.Breaker.OPEN
        assert router.states['a'].breaker.state == \
            mod_router.Breaker.CLOSED       # self never probed remotely
    finally:
        monkeypatch.delenv('DN_FAULTS')
        mod_faults.reset()
    # cooldown (default 2000 ms) must elapse before the trial
    for st in router.states.values():
        st.breaker._opened_at = -10.0
    router.probe_once()
    for m in 'bc':
        assert router.states[m].breaker.state == \
            mod_router.Breaker.CLOSED


# -- hedged reads -----------------------------------------------------------

def _bare_router(tmp_path, hedge_ms=0, failures=3):
    socks = {m: {'endpoint': str(tmp_path / m)} for m in 'ab'}
    doc = {'epoch': 1, 'members': socks,
           'partitions': [{'id': 0, 'replicas': ['a', 'b']}]}
    err = mod_topology.validate_doc(doc)
    assert err is None
    topo = mod_topology.Topology(doc)
    conf = {'probe_ms': 60000, 'failures': failures,
            'cooldown_ms': 1000, 'hedge_ms': hedge_ms,
            'fetch_timeout_s': 30, 'partial': 'error'}
    return mod_router.Router(topo, 'router-under-test', conf=conf)


def test_hedge_fires_and_accounts_win(tmp_path, monkeypatch):
    """The primary is slower than the hedge delay: a duplicate fires
    at the next replica, the fast replica wins, and the abandoned
    primary's eventual result is discarded (hedges_won)."""
    router = _bare_router(tmp_path, hedge_ms=30)
    release = threading.Event()

    def fake_fetch(name, pid, req, timeout_s, force=False):
        if name == 'a':
            release.wait(10.0)            # the slow primary
            return [['slow', []]]
        return [['fast', []]]

    monkeypatch.setattr(router, '_fetch_one', fake_fetch)
    shards = router._fetch_partition(0, {'partitions': [0]}, None,
                                     router.topo)
    release.set()
    assert shards == [['fast', []]]
    with router._lock:
        counters = dict(router._counters)
    assert counters['hedges_fired'] == 1
    assert counters['hedges_won'] == 1
    assert counters['hedges_wasted'] == 0


def test_hedge_wasted_when_primary_wins(tmp_path, monkeypatch):
    """The primary answers after the hedge fired but before the
    hedge does: the duplicate was wasted, and the primary's result
    is kept."""
    router = _bare_router(tmp_path, hedge_ms=20)
    hedge_started = threading.Event()
    release_hedge = threading.Event()

    def fake_fetch(name, pid, req, timeout_s, force=False):
        if name == 'a':
            hedge_started.wait(10.0)      # outlast the hedge delay
            return [['primary', []]]
        hedge_started.set()
        release_hedge.wait(10.0)          # hedge never beats it
        return [['hedge', []]]

    monkeypatch.setattr(router, '_fetch_one', fake_fetch)
    shards = router._fetch_partition(0, {'partitions': [0]}, None,
                                     router.topo)
    release_hedge.set()
    assert shards == [['primary', []]]
    with router._lock:
        counters = dict(router._counters)
    assert counters['hedges_fired'] == 1
    assert counters['hedges_wasted'] == 1
    assert counters['hedges_won'] == 0


def test_hedge_disabled_by_default(tmp_path, monkeypatch):
    router = _bare_router(tmp_path, hedge_ms=0)
    assert router._hedge_delay_s() is None


def test_failover_exhaustion_is_clean_error(tmp_path, monkeypatch):
    router = _bare_router(tmp_path)

    def fake_fetch(name, pid, req, timeout_s, force=False):
        raise DNError('member "%s": connection refused' % name)

    monkeypatch.setattr(router, '_fetch_one', fake_fetch)
    with pytest.raises(DNError) as ei:
        router._fetch_partition(0, {'partitions': [0]}, None,
                                router.topo)
    assert 'all replicas failed' in ei.value.message
    assert 'tried a,b' in ei.value.message
    with router._lock:
        assert router._counters['failovers'] == 1


# -- replica ranking --------------------------------------------------------

def test_draining_member_demoted(tmp_path):
    """A draining member is demoted below a healthy one BEFORE its
    socket dies, and an open-breaker member ranks last-resort — but
    both stay in the list (last-resort beats degraded)."""
    router = _bare_router(tmp_path)
    assert router._rank(['a', 'b']) == ['a', 'b']
    router.states['a'].note_health({'ok': True, 'draining': True})
    assert router._rank(['a', 'b']) == ['b', 'a']
    # breaker-open outranks draining for last place
    for _ in range(3):
        router.states['b'].breaker.record_failure()
    assert router.states['b'].breaker.state == mod_router.Breaker.OPEN
    assert router._rank(['a', 'b']) == ['a', 'b']


def test_draining_member_demoted_integration(cluster, corpus):
    """Member b reports draining through the health op: after a probe
    sweep the router prefers c for partition 1, while bytes stay
    identical."""
    cluster['servers']['b'].draining = True
    router = cluster['servers']['a'].router
    router.probe_once()
    assert router.states['b'].draining is True
    assert router._rank(['b', 'c']) == ['c', 'b']
    case = _cases(corpus['dss'][0])[0]
    expected = run_cli(case)
    got = run_cli(case[:1] + ['--remote', cluster['socks']['a']] +
                  case[1:])
    assert got == expected
    cl = mod_client.stats(cluster['socks']['a'])['cluster']
    assert cl['members']['b']['draining'] is True


# -- merge guards -----------------------------------------------------------

def test_merge_rejects_duplicate_shard(tmp_path, monkeypatch,
                                       corpus):
    """One shard reported by two partitions (mismatched topologies
    that slipped the epoch gate) must refuse to double-count."""
    router = _bare_router(tmp_path)
    router.topo.partitions.append(
        {'id': 1, 'replicas': ['b'], 'after_ms': None,
         'before_ms': None})
    router.topo._by_id[1] = router.topo.partitions[1]

    def fake_fetch_partition(pid, req, scope, topo):
        return [['2014-01-01.sqlite', [[['host0'], 3]]]]

    monkeypatch.setattr(router, '_fetch_partition',
                        fake_fetch_partition)
    opts = mod_server._opts_shim(_query_req(corpus['dss'][0], corpus))
    query = cli.dn_query_config(opts)
    with pytest.raises(DNError) as ei:
        router.scatter(None, corpus['dss'][0], query, 'day',
                       _query_req(corpus['dss'][0], corpus))
    assert 'reported by two partitions' in ei.value.message


# -- fault seams ------------------------------------------------------------

def test_router_dispatch_fault_degrades_cleanly(cluster, corpus,
                                                monkeypatch):
    """router.dispatch armed at rate 1.0: every partition dispatch
    fails by injection, and the response is the clean degraded error
    — the chaos soak's router-path contract."""
    monkeypatch.setenv('DN_FAULTS', 'router.dispatch:error:1.0')
    try:
        rc, header, out, err = mod_client.request_bytes(
            cluster['socks']['a'],
            _query_req(corpus['dss'][0], corpus), timeout_s=120.0)
        assert rc == 1
        assert header['retryable'] is True
        assert header['stats']['missing_partitions'] == [0, 1, 2]
        assert 'Traceback' not in err.decode()
    finally:
        monkeypatch.delenv('DN_FAULTS')
        mod_faults.reset()


def test_router_merge_fault_is_clean_error(cluster, corpus,
                                           monkeypatch):
    monkeypatch.setenv('DN_FAULTS', 'router.merge:error:1.0')
    try:
        rc, header, out, err = mod_client.request_bytes(
            cluster['socks']['a'],
            _query_req(corpus['dss'][0], corpus), timeout_s=120.0)
        assert rc == 1
        text = err.decode()
        assert text.startswith('dn: ')
        assert 'Traceback' not in text
    finally:
        monkeypatch.delenv('DN_FAULTS')
        mod_faults.reset()


# -- validate / CLI surface -------------------------------------------------

def test_serve_validate_reports_cluster(tmp_path, monkeypatch):
    socks = {m: str(tmp_path / (m + '.sock')) for m in 'abc'}
    topo_path = _write_topo(tmp_path, _topo_doc(socks))
    rc, out, err = run_cli(['serve', '--validate', '--socket',
                            str(tmp_path / 's.sock'),
                            '--cluster', topo_path, '--member', 'a'])
    assert rc == 0, err
    text = out.decode()
    assert 'router config ok:' in text
    assert 'cluster topology ok: member=a epoch=1' in text
    assert 'owns: 0,2' in text


def test_serve_validate_rejects_bad_topology(tmp_path):
    path = _write_topo(tmp_path, '{nope')
    rc, out, err = run_cli(['serve', '--validate', '--socket',
                            str(tmp_path / 's.sock'),
                            '--cluster', path, '--member', 'a'])
    assert rc != 0
    assert b'invalid JSON' in err


def test_serve_cluster_requires_member(tmp_path):
    rc, out, err = run_cli(['serve', '--socket',
                            str(tmp_path / 's.sock'),
                            '--cluster', str(tmp_path / 't.json')])
    assert rc != 0
    assert b'together' in err


def test_non_member_rejects_query_partial(corpus, tmp_path):
    srv = mod_server.DnServer(socket_path=str(tmp_path / 'x.sock'),
                              conf=_conf()).start()
    try:
        rc, header, out, err = mod_client.request_bytes(
            srv.socket_path,
            _query_req(corpus['dss'][0], corpus, epoch=1,
                       partitions=[0], op='query_partial'),
            timeout_s=60.0)
        assert rc == 1
        assert 'not a cluster member' in err.decode()
    finally:
        srv.stop()


# -- a member's partial on every lane ---------------------------------------
#
# The tree below makes the order matter: day d's shard brings a host
# whose name sorts BEFORE every host seen so far, so the reply's order
# of hosts (first occurrence in find order) is the reverse of any
# sort, and the days' shards land in different partitions.

LANE_ENV = {
    'shard': {'DN_IQ_STACK': '0', 'DN_INDEX_DEVICE': '0'},
    'stacked': {'DN_IQ_STACK': '1', 'DN_INDEX_DEVICE': '0'},
    'device': {'DN_IQ_STACK': '1', 'DN_INDEX_DEVICE': '1'},
}
ORDER_DAYS = 9
ORDER_QUERY = ['query', '--points', '-b', 'host,latency[aggr=quantize]']
VALUE_QUERY = ['query', '--points', '-b', 'value,host']


def _gen_order_corpus(path):
    import datetime
    t0 = 1388534400  # 2014-01-01T00:00:00Z
    with open(path, 'w') as f:
        for day in range(ORDER_DAYS):
            # hosts of this day: a new one that sorts first, and the
            # two before it (so tuples repeat across shards)
            hosts = ['h%02d' % (50 - d) for d in range(max(0, day - 2),
                                                       day + 1)]
            for i in range(24):
                ts = datetime.datetime.fromtimestamp(
                    t0 + day * 86400 + i * 3000,
                    datetime.timezone.utc).strftime(
                        '%Y-%m-%dT%H:%M:%S.000Z')
                f.write(json.dumps({
                    'time': ts, 'host': hosts[i % len(hosts)],
                    'value': 'v%d' % ((i + day) % 4),
                    'latency': (i * 37 + day * 11) % 900,
                }, separators=(',', ':')) + '\n')


@pytest.fixture(scope='module')
def order_corpus(tmp_path_factory):
    """Two trees over the same nine days: `ord` (the default format)
    and `ordf` (SQLite, one stored weight made non-integral
    afterwards: the exactness gate must refuse it)."""
    import sqlite3
    root = tmp_path_factory.mktemp('order_corpus')
    datafile = str(root / 'data.log')
    _gen_order_corpus(datafile)
    rc_path = str(root / 'dragnetrc.json')
    prior = {k: os.environ.get(k)
             for k in ('DRAGNET_CONFIG', 'DN_INDEX_FORMAT')}
    os.environ['DRAGNET_CONFIG'] = rc_path
    try:
        for ds, fmt in (('ord', None), ('ordf', 'sqlite')):
            if fmt is None:
                os.environ.pop('DN_INDEX_FORMAT', None)
            else:
                os.environ['DN_INDEX_FORMAT'] = fmt
            for args in (
                    ['datasource-add', '--path', datafile, '--index-path',
                     str(root / ('idx_' + ds)), '--time-field', 'time', ds],
                    ['metric-add', '-b',
                     'timestamp[date,field=time,aggr=lquantize,'
                     'step=86400],host,latency[aggr=quantize]', ds, 'm1'],
                    # (SQLite cannot hold a column named `value` twice)
                    ['metric-add', '-b', 'value,host', ds, 'mv'],
                    ['build', ds]):
                if fmt == 'sqlite' and args[-1] == 'mv':
                    continue
                rc, out, err = run_cli(args)
                assert rc == 0, err
        shard = sorted((root / 'idx_ordf' / 'by_day').iterdir())[3]
        db = sqlite3.connect(str(shard))
        table = [r[0] for r in db.execute(
            "select name from sqlite_master where type='table' and "
            "name like 'dragnet_index_%'")][0]
        db.execute('update %s set value = 2.5 where rowid = '
                   '(select min(rowid) from %s)' % (table, table))
        db.commit()
        db.close()
        yield {'root': root, 'rc_path': rc_path}
    finally:
        for k, v in prior.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _order_topo(socks, assign):
    doc = _topo_doc(socks, assign=assign)
    if assign == 'time-range':
        # two windows and a partition that takes the rest by hash
        doc['partitions'][0].update(after='2014-01-02',
                                    before='2014-01-04')
        doc['partitions'][1].update(after='2014-01-06',
                                    before='2014-01-08')
    return doc


@pytest.fixture
def lane_cluster(order_corpus, tmp_path, monkeypatch, request):
    """Three in-process members over the order trees, on the lane and
    under the assignment rule the test's parameters name."""
    from dragnet_tpu import device_index as mod_di
    from dragnet_tpu import index_query_mt as mod_iqmt
    lane = request.getfixturevalue('lane')
    assign = request.getfixturevalue('assign')
    for k, v in LANE_ENV[lane].items():
        monkeypatch.setenv(k, v)
    monkeypatch.setenv('DRAGNET_CONFIG', order_corpus['rc_path'])
    monkeypatch.setenv('DN_ROUTER_PROBE_MS', '60000')
    monkeypatch.setenv('DN_REMOTE_RETRIES', '0')
    mod_di._reset_device_state()
    mod_iqmt.shard_cache_clear()
    socks = {m: str(tmp_path / ('dn-%s.sock' % m)) for m in 'abc'}
    topo_path = str(tmp_path / 'topo.json')
    with open(topo_path, 'w') as f:
        json.dump(_order_topo(socks, assign), f)
    servers = {}
    for m in 'abc':
        topo = mod_topology.load_topology(topo_path, member=m)
        servers[m] = mod_server.DnServer(
            socket_path=socks[m], conf=_conf(), cluster=topo,
            member=m).start()
    try:
        yield {'socks': socks, 'rc_path': order_corpus['rc_path'],
               'topo': mod_topology.load_topology(topo_path)}
    finally:
        for srv in servers.values():
            srv.stop()
        mod_di._reset_device_state()


def _order_query(case, rc_path):
    """(datasource, query) of a `dn query` command line."""
    from dragnet_tpu import config as mod_config
    from dragnet_tpu import datasource_for_name
    from dragnet_tpu import query as mod_query
    err, config = mod_config.ConfigBackendLocal(rc_path).load()
    assert err is None
    bds = []
    specs = case[case.index('-b') + 1].split(',') if '-b' in case else []
    for spec in specs:
        name = spec.split('[')[0]
        b = {'name': name, 'field': name}
        if 'quantize' in spec:
            b['aggr'] = 'quantize'
        bds.append(b)
    return (datasource_for_name(config, case[-1]),
            mod_query.query_load({'breakdowns': bds}))


def _find_order_paths(ds, query):
    from dragnet_tpu import index_query_mt as mod_iqmt
    from dragnet_tpu.vpipe import Pipeline
    root, timeformat, files = ds.index_query_paths(query, 'day',
                                                   Pipeline())
    paths, _ = mod_iqmt.prune_shards([p for p, st in files], timeformat,
                                     query.qc_after, query.qc_before)
    return paths, timeformat


def _points_of(query, shard_items):
    """The points of a replay of per-shard key items, in the order
    given: the straightforward merge."""
    from dragnet_tpu.aggr import Aggregator
    aggr = Aggregator(query)
    for items in shard_items:
        aggr.merge_key_items([(tuple(k), w) for k, w in items])
    return aggr.points()


def _sequential(ds, query):
    """(points, {tuple: weight}) of the plain sequential loop: every
    shard opened, queried and merged in find order."""
    from dragnet_tpu import index_query_mt as mod_iqmt
    paths, _ = _find_order_paths(ds, query)
    per_shard = [mod_iqmt.query_shard_once(p, query) for p in paths]
    flat = {}
    for items in per_shard:
        for k, w in items:
            flat[tuple(k)] = flat.get(tuple(k), 0) + w
    return _points_of(query, per_shard), flat


def _partials(lane_cluster, case):
    """{partition: [[relpath, items], ...]} asked of each partition's
    first replica."""
    topo = lane_cluster['topo']
    doc = {'op': 'query_partial', 'ds': case[-1],
           'config': lane_cluster['rc_path'], 'interval': 'day',
           'opts': {}, 'epoch': 1, 'queryconfig': {'breakdowns': [
               dict(b) for b in _order_query(
                   case, lane_cluster['rc_path'])[1].qc_breakdowns]}}
    out = {}
    for pid in topo.partition_ids():
        rc, header, body, err = mod_client.request_bytes(
            lane_cluster['socks'][topo.replicas(pid)[0]],
            dict(doc, partitions=[pid]), timeout_s=60.0)
        assert rc == 0, err
        out[pid] = json.loads(body.decode())['shards']
    return out


def _routed(lane_cluster, case):
    return [run_cli(case[:1] + ['--remote', lane_cluster['socks'][m]] +
                    case[1:]) for m in 'abc']


def _points_text(case, points):
    """Points as the output layer prints them for this command."""
    from dragnet_tpu.datasource_file import ScanResult
    from dragnet_tpu.vpipe import Pipeline
    opts = mod_server._opts_shim({'opts': {'points': True}})
    with mod_server.thread_stdio() as cap:
        cli.dn_output(None, opts, ScanResult(Pipeline(), points=points),
                      case[-1])
    return cap.finish()[0]


def _check_bytes(lc, lane, assign):
    case = ORDER_QUERY + ['ord']
    ds, query = _order_query(case, lc['rc_path'])
    expected = run_cli(case)
    assert expected[0] == 0, expected[2]
    seq_points, _ = _sequential(ds, query)
    assert _points_text(case, seq_points) == expected[1]
    from dragnet_tpu import device_index as mod_di
    folds = mod_di.stats_doc()['dispatches']
    assert _routed(lc, case) == [expected] * 3
    # the members' partials reached the device fold on its lane alone
    assert (mod_di.stats_doc()['dispatches'] > folds) == \
        (lane == 'device')
    # the order is not a sort's: hosts come newest first
    hosts = [f['host'] for f, w in seq_points]
    assert hosts != sorted(hosts)
    if assign == 'hash':
        # and not a partition's either: the partials replayed one
        # partition after the other put the hosts in another order
        parts = _partials(lc, case)
        assert len({pid for pid in parts if parts[pid]}) > 1
        wrong = _points_of(query, [items for pid in sorted(parts)
                                   for rel, items in parts[pid]])
        assert [f['host'] for f, w in wrong] != hosts


def _check_once(lc, lane, assign):
    from dragnet_tpu import index_query_mt as mod_iqmt
    case = ORDER_QUERY + ['ord']
    ds, query = _order_query(case, lc['rc_path'])
    paths, timeformat = _find_order_paths(ds, query)
    by_rel = {os.path.relpath(p, ds.ds_indexpath): p for p in paths}
    parts = _partials(lc, case)
    listed = [rel for pid in sorted(parts) for rel, items in parts[pid]]
    # every shard of the tree listed once, by the partition that owns it
    assert sorted(listed) == sorted(by_rel)
    for pid, shards in parts.items():
        assert all(lc['topo'].partition_of(by_rel[rel], timeformat) == pid
                   for rel, items in shards)
        tuples = [tuple(k) for rel, items in shards for k, w in items]
        if lane == 'shard':
            # the per-shard wire: each shard's own aggregate
            for rel, items in shards:
                assert [[list(k), w] for k, w in
                        mod_iqmt.query_shard_once(by_rel[rel], query)] \
                    == items
        else:
            assert len(tuples) == len(set(tuples))
    if lane != 'shard':
        # fewer key items than the per-shard wire carries
        nshard = sum(len(mod_iqmt.query_shard_once(p, query))
                     for p in paths)
        assert sum(len(items) for s in parts.values()
                   for rel, items in s) < nshard


def _assert_partials_add_up(lc, case, ds, query):
    """The partitions' weights for a tuple add up to the whole tree's;
    returns the tree's {tuple: weight}."""
    _, flat = _sequential(ds, query)
    got = {}
    for shards in _partials(lc, case).values():
        for rel, items in shards:
            for k, w in items:
                got[tuple(k)] = got.get(tuple(k), 0) + w
    assert got == flat
    return flat


def _check_weights(lc, lane, assign):
    case = ORDER_QUERY + ['ord']
    assert _assert_partials_add_up(
        lc, case, *_order_query(case, lc['rc_path']))


def _check_value_breakdown(lc, lane, assign):
    """A breakdown named `value` is not the stack's: the per-shard
    loop answers on every lane, with the single process's bytes."""
    from dragnet_tpu import index_query_mt as mod_iqmt
    case = VALUE_QUERY + ['ord']
    ds, query = _order_query(case, lc['rc_path'])
    expected = run_cli(case)
    assert expected[0] == 0, expected[2]
    assert _points_text(case, _sequential(ds, query)[0]) == expected[1]
    assert _routed(lc, case) == [expected] * 3
    paths, _ = _find_order_paths(ds, query)
    by_rel = {os.path.relpath(p, ds.ds_indexpath): p for p in paths}
    for shards in _partials(lc, case).values():
        for rel, items in shards:
            assert [[list(k), w] for k, w in mod_iqmt.query_shard_once(
                by_rel[rel], query)] == items


def _check_float_weight(lc, lane, assign):
    """A stored weight of 2.5 fails the exactness gate: the per-shard
    loop answers, and the bytes are the single process's."""
    case = ORDER_QUERY + ['ordf']
    ds, query = _order_query(case, lc['rc_path'])
    expected = run_cli(case)
    assert expected[0] == 0, expected[2]
    assert b'.5' in expected[1]
    assert _points_text(case, _sequential(ds, query)[0]) == expected[1]
    assert _routed(lc, case) == [expected] * 3
    _assert_partials_add_up(lc, case, ds, query)


def _check_lane_cannot_run(lc, lane, assign, monkeypatch):
    """No jax in the process: a forced device lane is an error on a
    member as in a single process, never a quiet host answer; a lane
    that needs no device answers as ever."""
    from dragnet_tpu import ops as mod_ops
    case = ORDER_QUERY + ['ord']
    monkeypatch.setattr(mod_ops, 'get_jax', lambda: None)
    expected = run_cli(case)
    routed = _routed(lc, case)
    if lane != 'device':
        assert expected[0] == 0
        assert routed == [expected] * 3
        return
    needle = b'device index-query lane unavailable ('
    assert expected[0] != 0 and needle in expected[2]
    assert b'(jax unavailable)' in expected[2]
    # the verdict sticks to the process (these members share it with
    # the single process above): every later request is refused too
    for rc, out, err in routed:
        assert rc != 0 and out == b'' and needle in err, err


def _check_no_breakdown(lc, lane, assign):
    """The grand total: one key item a partition from the stacked
    lanes, one a shard from the per-shard loop, the same bytes."""
    case = ['query', '--points', 'ord']
    expected = run_cli(case)
    assert expected[0] == 0, expected[2]
    assert _routed(lc, case) == [expected] * 3
    total = 0
    for shards in _partials(lc, case).values():
        items = [it for rel, its in shards for it in its]
        assert all(keys == [] for keys, w in items)
        assert len(items) == (len(shards) if lane == 'shard'
                              else min(1, len(shards)))
        total += sum(w for keys, w in items)
    assert total == ORDER_DAYS * 24
    assert ('"value":%d' % total).encode() in expected[1]


CHECKS = {'bytes': _check_bytes, 'once': _check_once,
          'no_breakdown': _check_no_breakdown,
          'weights': _check_weights,
          'value_breakdown': _check_value_breakdown,
          'float_weight': _check_float_weight,
          'lane_cannot_run': _check_lane_cannot_run}


@pytest.mark.parametrize('check', sorted(CHECKS))
@pytest.mark.parametrize('assign', ['hash', 'time-range'])
@pytest.mark.parametrize('lane', sorted(LANE_ENV))
def test_partial_lanes(lane_cluster, lane, assign, check, monkeypatch):
    """A member's partial on each lane (the per-shard loop, the stack
    on the host, the stack on the device fold) under both assignment
    rules: the routed reply is byte-equal to the single process's and
    to the plain sequential loop's."""
    if lane == 'device':
        from dragnet_tpu.ops import get_jax
        if get_jax() is None:
            pytest.skip('jax unavailable')
    if check == 'lane_cannot_run':
        CHECKS[check](lane_cluster, lane, assign, monkeypatch)
    else:
        CHECKS[check](lane_cluster, lane, assign)
