"""`dn subscribe` — standing queries with pushed result frames
(dragnet_tpu/serve/subscribe.py).

Covers: the byte-identity contract (a pushed frame at epoch E is
byte-identical to a poll at epoch E — seed, post-publish push, and
delta-reconstructed frames, on both index formats), the one-merge
fan-out economics (N subscribers on one group cost ONE incremental
recompute per publish, counter-asserted), backpressure (a stalled
subscriber sheds and degrades without delaying healthy subscribers,
then catches up with one coalesced full frame on ack), resume tokens,
the fleet watch, lifecycle (unsubscribe, server drain pushing 'end'
frames, disabled/limit rejections), the `dn subscribe` JSONL CLI,
`dn top --subscribe` riding the push path with polling fallback, and
the /stats + fleet-merge observability surface."""

import json
import os
import socket as mod_socket
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from dragnet_tpu.errors import DNError                     # noqa: E402
from dragnet_tpu.serve import client as mod_client         # noqa: E402
from dragnet_tpu.serve import protocol as mod_protocol     # noqa: E402
from dragnet_tpu.serve import server as mod_server         # noqa: E402

from test_serve import run_cli                             # noqa: E402

T0 = 1388534400  # 2014-01-01T00:00:00Z


def _append(datafile, n, start):
    """Append n deterministic records continuing the corpus clock."""
    import datetime
    with open(datafile, 'a') as f:
        for i in range(start, start + n):
            ts = datetime.datetime.utcfromtimestamp(
                T0 + i * 800).strftime('%Y-%m-%dT%H:%M:%S.000Z')
            f.write(json.dumps({
                'time': ts,
                'host': 'host%d' % (i % 3),
                'operation': ('get', 'put', 'index')[i % 3],
                'latency': (i * 7) % 230,
            }, separators=(',', ':')) + '\n')


@pytest.fixture(scope='module')
def corpus(tmp_path_factory):
    """A GROWING corpus (unlike test_serve's): publish tests append
    records and rebuild, so each datasource owns its own datafile."""
    root = tmp_path_factory.mktemp('sub_corpus')
    rc_path = str(root / 'dragnetrc.json')
    prior = os.environ.get('DRAGNET_CONFIG')
    os.environ['DRAGNET_CONFIG'] = rc_path
    prior_fmt = os.environ.get('DN_INDEX_FORMAT')
    state = {'root': root, 'rc_path': rc_path, 'n': {},
             'fmt': {'ds_dnc': 'dnc', 'ds_sq': 'sqlite'},
             'datafile': {}}
    try:
        for ds, fmt in (('ds_dnc', 'dnc'), ('ds_sq', 'sqlite')):
            datafile = str(root / ('data_%s.log' % fmt))
            _append(datafile, 400, 0)
            state['datafile'][ds] = datafile
            state['n'][ds] = 400
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
            os.environ['DN_INDEX_FORMAT'] = fmt
            rc, out, err = run_cli(['build', ds])
            assert rc == 0, err
        yield state
    finally:
        if prior_fmt is None:
            os.environ.pop('DN_INDEX_FORMAT', None)
        else:
            os.environ['DN_INDEX_FORMAT'] = prior_fmt
        if prior is None:
            os.environ.pop('DRAGNET_CONFIG', None)
        else:
            os.environ['DRAGNET_CONFIG'] = prior


def _publish(server, corpus, ds, n=60):
    """One `dn follow`-equivalent publish: append + an incremental
    rebuild bounded to the appended records' days (untouched day
    shards keep their idents, like a follow merge-publish).  The
    build's publish fires the in-process index write hook the
    manager folds.

    The build runs with every standing group's compute lock held: a
    sweep that wakes while the publish is half landed (shards renamed
    in, the hooks that mark the group dirty and bump the epoch not
    yet fired) waits for the whole of it, so the next frame is the
    publish's one frame whatever the machine's load, not the first of
    several steps."""
    import contextlib
    import datetime
    start = corpus['n'][ds]
    _append(corpus['datafile'][ds], n, start)
    corpus['n'][ds] += n
    fmt = '%Y-%m-%dT%H:%M:%S.000Z'
    day0 = ((T0 + start * 800) // 86400) * 86400
    day9 = ((T0 + corpus['n'][ds] * 800) // 86400 + 1) * 86400
    after = datetime.datetime.utcfromtimestamp(day0).strftime(fmt)
    before = datetime.datetime.utcfromtimestamp(day9).strftime(fmt)
    os.environ['DN_INDEX_FORMAT'] = corpus['fmt'][ds]
    with server.subman._lock:
        groups = list(server.subman._groups.values())
    with contextlib.ExitStack() as held:
        for group in groups:
            held.enter_context(group.compute_lock)
        rc, out, err = run_cli(['build', '--after', after,
                                '--before', before, ds])
    assert rc == 0, err


def _settled(server, timeout=10.0):
    """Wait until no standing group is dirty.  A group is created
    dirty and its seed does not clear that, so the pusher's first
    sweep after the seed recomputes it once more (unchanged): a test
    that counts recomputes lets that sweep pass first."""
    limit = time.monotonic() + timeout
    while time.monotonic() < limit:
        with server.subman._lock:
            groups = list(server.subman._groups.values())
        if not any(g.dirty for g in groups):
            # the sweep clears the flag before it recomputes, under
            # the group's lock: its count is in when the lock is free
            for g in groups:
                with g.compute_lock:
                    pass
            return
        time.sleep(0.01)
    raise AssertionError('a subscription group stayed dirty')


def _conf(**over):
    base = {'max_inflight': 4, 'queue_depth': 16, 'deadline_ms': 0,
            'coalesce': True, 'drain_s': 10}
    base.update(over)
    return base


@pytest.fixture
def server(corpus, tmp_path, monkeypatch):
    """A push-ready server with a fast sweep cadence (the manager
    reads DN_SUB_* at construction)."""
    monkeypatch.setenv('DN_SUB_COALESCE_MS', '30')
    sock = str(tmp_path / 'dn.sock')
    srv = mod_server.DnServer(socket_path=sock, conf=_conf()).start()
    try:
        yield srv
    finally:
        srv.stop()


def _sub_req(corpus, ds, breakdowns='host'):
    qdoc = {'breakdowns': [{'name': b, 'field': b}
                           for b in breakdowns.split(',')]}
    return {'op': 'subscribe', 'ds': ds, 'config': corpus['rc_path'],
            'interval': 'day', 'queryconfig': qdoc, 'opts': {}}


def _poll(corpus, sock, ds, breakdowns='host'):
    rc, out, err = run_cli(['query', '--remote', sock,
                            '-b', breakdowns, ds])
    assert rc == 0, err
    return out


# -- byte identity: seed / push / delta, both formats -----------------------

@pytest.mark.parametrize('ds', ['ds_dnc', 'ds_sq'])
def test_push_byte_identical_to_poll(server, corpus, ds):
    """The pinned contract: the seed frame and every pushed frame
    carry EXACTLY the bytes a `dn query --remote` poll returns at the
    same epoch."""
    stream = mod_client.subscribe_stream(server.socket_path,
                                         _sub_req(corpus, ds))
    try:
        seed = next(stream)
        assert seed['kind'] == 'full' and seed['seq'] == 1
        assert seed['payload'] == _poll(corpus, server.socket_path,
                                        ds)
        _publish(server, corpus, ds)
        pushed = next(stream)
        assert pushed['seq'] == 2
        assert pushed['epoch'] > seed['epoch']
        assert pushed['payload'] == _poll(corpus,
                                          server.socket_path, ds)
    finally:
        stream.close()


def test_delta_frame_reconstructs_identical_bytes(corpus, tmp_path,
                                                  monkeypatch):
    """DN_SUB_DELTA_PCT=100: the post-publish frame ships as a byte
    delta, and the client-side splice reconstructs bytes identical to
    a fresh poll."""
    ds = 'ds_dnc'
    monkeypatch.setenv('DN_SUB_COALESCE_MS', '30')
    monkeypatch.setenv('DN_SUB_DELTA_PCT', '100')
    sock = str(tmp_path / 'delta.sock')
    srv = mod_server.DnServer(socket_path=sock, conf=_conf()).start()
    try:
        stream = mod_client.subscribe_stream(
            sock, _sub_req(corpus, ds, breakdowns='timestamp,host'))
        try:
            seed = next(stream)
            assert seed['kind'] == 'full'
            _publish(srv, corpus, ds)
            pushed = next(stream)
            assert pushed['kind'] == 'delta'
            assert pushed['payload'] == _poll(
                corpus, sock, ds, breakdowns='timestamp,host')
            st = mod_client.stats(sock)
            assert st['subscriptions']['counters'][
                'frames_delta'] >= 1
        finally:
            stream.close()
    finally:
        srv.stop()


def test_resume_token_skips_reseed(server, corpus):
    """Reconnecting with the last frame's token against unchanged
    state: a 'current' frame (no payload on the wire), then deltas
    continue from the held base."""
    ds = 'ds_sq'
    req = _sub_req(corpus, ds)
    stream = mod_client.subscribe_stream(server.socket_path,
                                         dict(req))
    seed = next(stream)
    stream.close()
    stream2 = mod_client.subscribe_stream(
        server.socket_path, dict(req),
        resume=(seed['token'], seed['payload']))
    try:
        fr = next(stream2)
        assert fr['kind'] == 'current'
        assert fr['payload'] == seed['payload']
        st = mod_client.stats(server.socket_path)
        assert st['subscriptions']['counters']['resumed'] >= 1
    finally:
        stream2.close()


# -- fan-out economics: one merge per publish, not N ------------------------

def test_one_recompute_serves_all_subscribers(server, corpus):
    """Three subscribers on one standing query, one publish: the
    group recomputes ONCE (one incremental merge) and all three get
    the frame — per-publish cost is O(1) in subscriber count."""
    ds = 'ds_dnc'
    streams = [mod_client.subscribe_stream(server.socket_path,
                                           _sub_req(corpus, ds))
               for _ in range(3)]
    try:
        seeds = [next(s) for s in streams]
        assert len({fr['payload'] for fr in seeds}) == 1
        _settled(server)
        before = mod_client.stats(
            server.socket_path)['subscriptions']['counters']
        _publish(server, corpus, ds)
        pushed = [next(s) for s in streams]
        assert len({fr['payload'] for fr in pushed}) == 1
        after = mod_client.stats(
            server.socket_path)['subscriptions']['counters']
        assert after['recomputes'] - before['recomputes'] == 1
        assert after['pushes'] - before['pushes'] == 3
    finally:
        for s in streams:
            s.close()


def test_incremental_fold_reuses_unchanged_shards(server, corpus):
    """A publish that touches one day's shards re-queries only the
    CHANGED shards; the rest replay from the group memo."""
    ds = 'ds_sq'
    stream = mod_client.subscribe_stream(server.socket_path,
                                         _sub_req(corpus, ds))
    try:
        next(stream)
        before = mod_client.stats(
            server.socket_path)['subscriptions']['counters']
        _publish(server, corpus, ds)
        next(stream)
        after = mod_client.stats(
            server.socket_path)['subscriptions']['counters']
        assert after['shards_reused'] > before['shards_reused']
    finally:
        stream.close()


# -- backpressure: a stalled subscriber never delays healthy ones -----------

def _raw_subscribe(sock_path, req):
    """A hand-rolled v2 subscriber that NEVER acks: (socket, file,
    registration header, seed push header)."""
    s = mod_socket.socket(mod_socket.AF_UNIX, mod_socket.SOCK_STREAM)
    s.settimeout(30.0)
    s.connect(sock_path)
    s.sendall(mod_protocol.encode_request(dict(req), 1))
    f = s.makefile('rb')

    def read_frame():
        line = f.readline(mod_protocol.MAX_FRAME_BYTES)
        assert line, 'unexpected EOF'
        header = json.loads(line.decode('utf-8'))
        need = (int(header.get('nout', 0)) +
                int(header.get('nerr', 0)))
        payload = b''
        while len(payload) < need:
            chunk = f.read(need - len(payload))
            assert chunk, 'short frame'
            payload += chunk
        return header, payload

    reg, body = read_frame()
    assert reg['rc'] == 0, body
    seed, _ = read_frame()
    assert seed.get('kind') == 'full'
    return s, f, read_frame, json.loads(body.decode())['sub']


def test_stalled_subscriber_sheds_healthy_delivers(
        corpus, tmp_path, monkeypatch):
    """DN_SUB_QUEUE_DEPTH=1: a subscriber that never acks has its
    post-seed pushes SHED (degraded, counted) while a healthy
    subscriber on the same group receives every frame; the stalled
    one's first ack buys a single coalesced catch-up FULL frame."""
    ds = 'ds_dnc'
    monkeypatch.setenv('DN_SUB_COALESCE_MS', '30')
    monkeypatch.setenv('DN_SUB_QUEUE_DEPTH', '1')
    sock = str(tmp_path / 'stall.sock')
    srv = mod_server.DnServer(socket_path=sock, conf=_conf()).start()
    try:
        req = _sub_req(corpus, ds)
        s, f, read_frame, sid = _raw_subscribe(sock, req)
        healthy = mod_client.subscribe_stream(sock, dict(req))
        try:
            next(healthy)
            _publish(srv, corpus, ds)
            fresh = next(healthy)          # healthy gets the frame...
            assert fresh['seq'] == 2
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                st = mod_client.stats(sock)['subscriptions']
                if st['counters']['lagging_sheds'] >= 1:
                    break
                time.sleep(0.05)
            # ...while the staller was shed, not wedged, not pushed
            assert st['counters']['lagging_sheds'] >= 1
            row = [d for d in st['subscribers']
                   if d['sub'] == sid][0]
            assert row['lagging'] is True and row['seq'] == 1
            # the ack reopens the window: ONE catch-up full frame
            # carrying the CURRENT bytes
            s.sendall(mod_protocol.encode_request(
                {'op': 'sub_ack', 'sub': sid, 'seq': 1}, 2))
            got = []
            while len(got) < 2:
                header, payload = read_frame()
                got.append((header, payload))
            kinds = [h.get('kind') for h, _ in got
                     if h.get('sub') is not None]
            assert kinds == ['full']
            catch_up = [p for h, p in got
                        if h.get('kind') == 'full'][0]
            assert catch_up == fresh['payload']
        finally:
            healthy.close()
            s.close()
    finally:
        srv.stop()


# -- lifecycle: unsubscribe, drain, disabled, limits ------------------------

def test_unsubscribe_idempotent(server, corpus):
    stream = mod_client.subscribe_stream(server.socket_path,
                                         _sub_req(corpus, 'ds_dnc'))
    try:
        sid = next(stream)['sub']
    finally:
        pass
    rc, hd, out, err = mod_client.request_bytes(
        server.socket_path, {'op': 'unsubscribe', 'sub': sid})
    assert rc == 0, err
    rc, hd, out, err = mod_client.request_bytes(
        server.socket_path, {'op': 'unsubscribe', 'sub': sid})
    assert rc == 1
    assert b'unknown subscription' in err
    stream.close()
    st = mod_client.stats(server.socket_path)['subscriptions']
    assert st['active'] == 0 and st['counters']['dropped'] >= 1


def test_drain_sends_end_frame(corpus, tmp_path, monkeypatch):
    """A stopping server tells every subscriber with an 'end' frame —
    a clean goodbye the client distinguishes from a cut stream."""
    monkeypatch.setenv('DN_SUB_COALESCE_MS', '30')
    sock = str(tmp_path / 'drain.sock')
    srv = mod_server.DnServer(socket_path=sock, conf=_conf()).start()
    stream = mod_client.subscribe_stream(sock,
                                         _sub_req(corpus, 'ds_dnc'))
    try:
        next(stream)
        srv.stop()
        # a clean 'end' exhausts the generator (no transport error)
        assert list(stream) == []
    finally:
        stream.close()


def test_disabled_and_limit_rejections(corpus, tmp_path,
                                       monkeypatch):
    monkeypatch.setenv('DN_SUB_COALESCE_MS', '30')
    monkeypatch.setenv('DN_SUB_MAX', '0')
    sock = str(tmp_path / 'off.sock')
    srv = mod_server.DnServer(socket_path=sock, conf=_conf()).start()
    try:
        with pytest.raises(mod_client.SubscribeUnsupported):
            next(mod_client.subscribe_stream(
                sock, _sub_req(corpus, 'ds_dnc')))
    finally:
        srv.stop()
    monkeypatch.setenv('DN_SUB_MAX', '1')
    sock2 = str(tmp_path / 'one.sock')
    srv = mod_server.DnServer(socket_path=sock2,
                              conf=_conf()).start()
    try:
        stream = mod_client.subscribe_stream(
            sock2, _sub_req(corpus, 'ds_dnc'))
        next(stream)
        with pytest.raises(DNError) as ei:
            next(mod_client.subscribe_stream(
                sock2, _sub_req(corpus, 'ds_sq')))
        assert 'subscription limit' in ei.value.message
        assert getattr(ei.value, 'retryable', False) is True
        stream.close()
    finally:
        srv.stop()


def test_rejected_registrations(server, corpus):
    """Bad standing queries answer a clean error, not a stream."""
    cases = [
        (dict(_sub_req(corpus, 'ds_dnc'), ds=None), 'missing "ds"'),
        (dict(_sub_req(corpus, 'nope')), 'unknown datasource'),
        (dict(_sub_req(corpus, 'ds_dnc'),
              opts={'counters': True}),
         'cannot ride a standing query'),
    ]
    for req, needle in cases:
        with pytest.raises(DNError) as ei:
            next(mod_client.subscribe_stream(server.socket_path,
                                             req))
        assert needle in ei.value.message, (needle, ei.value.message)


# -- the fleet watch ---------------------------------------------------------

def test_fleet_watch_pushes_fleet_doc(server, corpus):
    """watch=fleet frames carry the same document the fleet_stats op
    renders, on the subscriber's cadence with no re-registration."""
    stream = mod_client.subscribe_stream(
        server.socket_path,
        {'op': 'subscribe', 'watch': 'fleet', 'interval_ms': 150})
    try:
        first = next(stream)
        doc = json.loads(first['payload'].decode('utf-8'))
        assert doc['members_total'] >= 1
        assert 'aggregate' in doc and 'members' in doc
        second = next(stream)               # cadence, not a publish
        assert second['seq'] == first['seq'] + 1
        assert json.loads(second['payload'].decode('utf-8'))[
            'members_total'] == doc['members_total']
    finally:
        stream.close()


# -- observability: /stats shape + fleet merge ------------------------------

def test_stats_doc_shape(server, corpus):
    stream = mod_client.subscribe_stream(server.socket_path,
                                         _sub_req(corpus, 'ds_dnc'))
    try:
        next(stream)
        st = mod_client.stats(server.socket_path)['subscriptions']
        assert st['enabled'] is True and st['active'] == 1
        assert st['max'] >= 1 and st['queue_depth'] >= 1
        assert st['groups'][0]['watch'] == 'query'
        assert st['groups'][0]['subscribers'] == 1
        assert st['groups'][0]['memo_shards'] >= 1
        assert st['subscribers'][0]['seq'] >= 1
        for key in ('registered', 'pushes', 'recomputes',
                    'shards_folded', 'shards_reused',
                    'lagging_sheds', 'duplicate_acks'):
            assert key in st['counters'], key
    finally:
        stream.close()


def test_fleet_merge_carries_subscriptions(server, corpus):
    """The fleet doc's member rows and aggregate roll subscription
    counts up (honest absence preserved for non-push members)."""
    from dragnet_tpu.serve import fleet as mod_fleet
    stream = mod_client.subscribe_stream(server.socket_path,
                                         _sub_req(corpus, 'ds_dnc'))
    try:
        next(stream)
        st = mod_client.stats(server.socket_path)
        doc = mod_fleet.merge_fleet(
            server, ['a', 'b'], {'a': st, 'b': {}}, {}, {})
        assert doc['members']['a']['subscriptions'] == 1
        assert 'subscriptions' not in doc['members']['b']
        assert doc['aggregate']['subscriptions'] == 1
        assert doc['aggregate']['subscription_pushes'] >= 1
        text = mod_fleet.fleet_prometheus_text(doc)
        assert 'fleet_subscriptions 1' in text
    finally:
        stream.close()


# -- the CLI surface: dn subscribe JSONL + dn top --subscribe ---------------

def test_dn_subscribe_cli_streams_jsonl(server, corpus):
    """`dn subscribe --frames=1`: one JSON line whose payload is the
    polled bytes, plus a resume token."""
    ds = 'ds_sq'
    rc, out, err = run_cli(['subscribe', '--remote',
                            server.socket_path, '--frames', '1',
                            '-b', 'host', ds])
    assert rc == 0, err
    lines = out.decode('utf-8').splitlines()
    assert len(lines) == 1
    frame = json.loads(lines[0])
    assert frame['kind'] == 'full' and frame['seq'] == 1
    assert frame['token']['k']
    polled = _poll(corpus, server.socket_path, ds)
    assert frame['payload'].encode('utf-8') == polled


def test_dn_subscribe_cli_requires_remote_and_validates(corpus):
    rc, out, err = run_cli(['subscribe', 'ds_dnc'])
    assert rc == 2
    assert b'--remote' in err
    rc, out, err = run_cli(['subscribe', '--remote', '/nope.sock',
                            '--frames', 'x', 'ds_dnc'])
    assert rc == 1
    assert b'--frames' in err


def test_dn_top_subscribe_rides_push_path(server, corpus):
    """`dn top --subscribe --once` renders a frame fed by a pushed
    fleet subscription, not a fleet_stats poll."""
    import io
    from dragnet_tpu.serve import top as mod_top
    buf = io.StringIO()
    rc = mod_top.top_main(server.socket_path, 200, once=True,
                          out=buf, subscribe=True)
    assert rc == 0
    assert 'dn top' in buf.getvalue()
    st = mod_client.stats(server.socket_path)['subscriptions']
    assert st['counters']['registered'] >= 1


def test_dn_top_subscribe_falls_back_to_polling(corpus, tmp_path,
                                                monkeypatch):
    """Against a server with subscriptions disabled, --subscribe
    degrades to the fleet_stats polling loop with a notice."""
    import io
    from dragnet_tpu.serve import top as mod_top
    monkeypatch.setenv('DN_SUB_MAX', '0')
    sock = str(tmp_path / 'nopush.sock')
    srv = mod_server.DnServer(socket_path=sock, conf=_conf()).start()
    try:
        buf = io.StringIO()
        with mod_server.thread_stdio() as cap:
            rc = mod_top.top_main(sock, 200, once=True, out=buf,
                                  subscribe=True)
        _out, err = cap.finish()
        assert rc == 0
        assert 'dn top' in buf.getvalue()
        assert b'falling back to polling' in err
    finally:
        srv.stop()


# -- routed reconvergence: the confirming scatter ---------------------------

def test_routed_group_reconfirms_and_stays_quiet(corpus, tmp_path,
                                                 monkeypatch):
    """Cluster mode: a routed group re-scatters ONCE after the peer
    stat-TTL window expires (a peer process that never saw the write
    hook can answer a scatter with a view up to one TTL stale; the
    confirming scatter either observes the settled bytes and stops,
    or pushes the newer state).  Pinned: the confirm fires after
    quiescence, and a confirm that finds identical bytes pushes NO
    spurious frame."""
    from dragnet_tpu.serve import topology as mod_topology
    ds = 'ds_dnc'
    monkeypatch.setenv('DN_SUB_COALESCE_MS', '30')
    monkeypatch.setenv('DN_IQ_STAT_TTL_MS', '120')
    sock = str(tmp_path / 'routed.sock')
    topo_path = str(tmp_path / 'topo.json')
    with open(topo_path, 'w') as f:
        json.dump({'epoch': 1, 'assign': 'hash',
                   'members': {'a': {'endpoint': sock}},
                   'partitions': [{'id': 0, 'replicas': ['a']},
                                  {'id': 1, 'replicas': ['a']},
                                  {'id': 2, 'replicas': ['a']}]}, f)
    topo = mod_topology.load_topology(topo_path, member='a')
    srv = mod_server.DnServer(socket_path=sock, conf=_conf(),
                              cluster=topo, member='a').start()
    try:
        stream = mod_client.subscribe_stream(sock,
                                             _sub_req(corpus, ds))
        try:
            seed = next(stream)
            assert seed['kind'] == 'full' and seed['seq'] == 1

            def reconfirms():
                st = mod_client.stats(sock)['subscriptions']
                return st['counters']['reconfirms']

            # the seed arms a confirm; quiescence lets it fire
            deadline = time.monotonic() + 10.0
            while reconfirms() < 1 and time.monotonic() < deadline:
                time.sleep(0.05)
            assert reconfirms() >= 1
            # identical bytes: converged, no frame pushed, disarmed
            time.sleep(0.5)
            st = mod_client.stats(sock)['subscriptions']
            assert st['subscribers'][0]['seq'] == 1
            assert st['groups'][0]['version'] == 1

            # a publish pushes once, then its confirm stays quiet too
            before = reconfirms()
            _publish(srv, corpus, ds)
            pushed = next(stream)
            assert pushed['seq'] == 2
            assert pushed['payload'] == _poll(corpus, sock, ds)
            deadline = time.monotonic() + 10.0
            while reconfirms() <= before and \
                    time.monotonic() < deadline:
                time.sleep(0.05)
            assert reconfirms() > before
            time.sleep(0.5)
            st = mod_client.stats(sock)['subscriptions']
            assert st['subscribers'][0]['seq'] == 2
        finally:
            stream.close()
    finally:
        srv.stop()
