"""Observability (dragnet_tpu/obs/): typed metrics registry, span
tracing, trace-id propagation through `--remote`, the /stats schema
gold shape, and the Prometheus exposition.

The /stats golden-shape test is the dashboard contract: section names
and types must not drift silently — additive changes are fine,
renames/retypes must bump STATS_METRICS_VERSION and this test.
"""

import json
import os
import re
import sys
import threading

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from dragnet_tpu import cli                                # noqa: E402
from dragnet_tpu import vpipe as mod_vpipe                 # noqa: E402
from dragnet_tpu.obs import export as obs_export           # noqa: E402
from dragnet_tpu.obs import metrics as obs_metrics         # noqa: E402
from dragnet_tpu.obs import trace as obs_trace             # noqa: E402
from dragnet_tpu.serve import client as mod_client         # noqa: E402
from dragnet_tpu.serve import server as mod_server         # noqa: E402


def run_cli(args):
    with mod_server.thread_stdio() as cap:
        rc = cli.main(list(args))
    out, err = cap.finish()
    return rc, out, err


# -- metrics units ----------------------------------------------------------

def test_histogram_observe_and_quantiles():
    h = obs_metrics.Histogram(bounds=(1.0, 10.0, 100.0))
    for v in (0.5, 0.7, 5.0, 50.0):
        h.observe(v)
    assert h.total == 4
    assert h.counts == [2, 1, 1, 0]
    assert h.sum == pytest.approx(56.2)
    # p50 falls in the first bucket (2 of 4 observations <= 1.0)
    assert 0.0 < h.quantile(0.5) <= 1.0
    assert 10.0 < h.quantile(0.99) <= 100.0
    assert obs_metrics.Histogram(bounds=(1.0,)).quantile(0.5) is None


def test_histogram_overflow_bucket():
    h = obs_metrics.Histogram(bounds=(1.0, 2.0))
    h.observe(99.0)
    assert h.counts == [0, 0, 1]
    assert h.quantile(0.5) == 2.0     # capped at the top bound


def test_histogram_merge_same_bounds():
    a = obs_metrics.Histogram(bounds=(1.0, 10.0))
    b = obs_metrics.Histogram(bounds=(1.0, 10.0))
    a.observe(0.5)
    b.observe(5.0)
    b.observe(500.0)
    a.merge(b)
    assert a.counts == [1, 1, 1]
    assert a.total == 3
    assert a.sum == pytest.approx(505.5)


def test_histogram_merge_mismatched_bounds_rebins():
    a = obs_metrics.Histogram(bounds=(1.0, 10.0))
    b = obs_metrics.Histogram(bounds=(3.0,))
    b.observe(2.0)      # lands in b's <=3 bucket
    b.observe(50.0)     # lands in b's +Inf bucket
    a.merge(b)
    # mass re-binned at b's bucket bounds: 3.0 -> a's <=10, 3.0
    # (overflow re-bin uses the top bound) -> a's <=10
    assert a.total == 2
    assert sum(a.counts) == 2
    assert a.sum == pytest.approx(52.0)


def test_registry_merge_and_kinds():
    a = obs_metrics.Registry()
    b = obs_metrics.Registry()
    a.inc('reqs_total', 2)
    b.inc('reqs_total', 3)
    b.set_gauge('g', 7.0)
    b.observe('lat_ms', 5.0, op='query')
    a.merge(b)
    snap = {(n, lb): m for n, lb, m in a.snapshot()}
    assert snap[('reqs_total', ())].value == 5
    assert snap[('g', ())].value == 7.0
    assert snap[('lat_ms', (('op', 'query'),))].total == 1


def test_scoped_metrics_merge_on_request_end():
    obs_metrics.reset_global_registry()
    with obs_trace.request('test-op') as obs:
        obs_metrics.inc('scoped_total', 4)
        # lands in the request registry, not the global one yet
        assert not [m for n, _, m in
                    obs_metrics.global_registry().snapshot()
                    if n == 'scoped_total']
        assert obs.registry is not None
    snap = {n: m for n, _, m in
            obs_metrics.global_registry().snapshot()}
    assert snap['scoped_total'].value == 4


def test_bucket_bounds_env(monkeypatch):
    monkeypatch.setenv('DN_METRICS_BUCKETS', '5,50,500')
    assert obs_metrics.bucket_bounds() == (5.0, 50.0, 500.0)
    monkeypatch.setenv('DN_METRICS_BUCKETS', 'garbage')
    assert obs_metrics.bucket_bounds() == \
        obs_metrics.DEFAULT_BUCKETS_MS


def test_device_gauges_honest_zeros():
    reg = obs_metrics.Registry()
    obs_metrics.refresh_device_gauges({}, reg)
    g = {n: m.value for n, _, m in reg.snapshot()
         if m.kind == obs_metrics.GAUGE}
    assert g['device_engaged'] == 0.0
    assert 'device_mfu_pct' not in g
    assert g['device_residency_pct'] == 0.0


def test_device_gauges_engaged():
    reg = obs_metrics.Registry()
    obs_metrics.refresh_device_gauges(
        {'ndevicebatches': 3, 'nhostbatches': 1,
         'index device sums': 2}, reg)
    g = {n: m.value for n, _, m in reg.snapshot()
         if m.kind == obs_metrics.GAUGE}
    assert g['device_engaged'] == 1.0
    assert g['device_batches'] == 3.0
    assert g['device_index_sums'] == 2.0
    assert g['device_residency_pct'] == pytest.approx(75.0)


# -- prometheus exposition --------------------------------------------------

_PROM_LINE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? -?[0-9.eE+-]+$')


def test_prometheus_text_parseable():
    reg = obs_metrics.Registry()
    reg.inc('reqs_total', 2)
    reg.set_gauge('weird name-1', 1.5)
    reg.observe('lat_ms', 3.0, op='query')
    reg.observe('lat_ms', 700.0, op='query')
    text = obs_export.prometheus_text(reg)
    assert text.endswith('\n')
    buckets = []
    for line in text.splitlines():
        if line.startswith('#'):
            assert re.match(r'^# TYPE dn_\w+ '
                            r'(counter|gauge|histogram)$', line)
            continue
        assert _PROM_LINE.match(line), line
        if line.startswith('dn_lat_ms_bucket'):
            buckets.append(int(line.rsplit(' ', 1)[1]))
    # cumulative bucket counts are monotone and end at the total
    assert buckets == sorted(buckets)
    assert buckets[-1] == 2
    assert 'dn_lat_ms_sum{op="query"} 703' in text
    assert 'dn_lat_ms_count{op="query"} 2' in text
    assert 'dn_weird_name_1 1.5' in text


def test_stats_section_shape_and_quantiles():
    reg = obs_metrics.Registry()
    for v in (1.0, 5.0, 9.0, 80.0):
        reg.observe('lat_ms', v)
    doc = obs_export.stats_section(reg)
    assert doc['version'] == obs_export.STATS_METRICS_VERSION
    ent = doc['histograms']['lat_ms']
    assert ent['count'] == 4
    assert ent['sum'] == pytest.approx(95.0)
    for q in ('p50', 'p90', 'p99'):
        assert isinstance(ent[q], float)
    assert ent['buckets']['+Inf'] == 4


# -- tracing units ----------------------------------------------------------

def test_span_noop_without_context():
    # no context: span/event are no-ops, not errors
    with obs_trace.span('nothing', attr=1) as sp:
        sp.set(more=2)
    obs_trace.event('nothing-happened')
    assert obs_trace.current_trace() is None


def test_span_tree_nesting_and_threads(tmp_path, monkeypatch):
    sink = str(tmp_path / 'trace.jsonl')
    monkeypatch.setenv('DN_TRACE', sink)
    with obs_trace.request('unit-op') as obs:
        scope = mod_vpipe.current_scope()
        with obs_trace.span('outer', k='v'):
            with obs_trace.span('inner'):
                obs_trace.event('tick', n=1)

        def pool_work():
            # a worker pool adopting the submitter's scope attributes
            # its spans to the same request, tagged with its thread
            with mod_vpipe.adopt_scope(scope):
                with obs_trace.span('pool-span'):
                    pass
        t = threading.Thread(target=pool_work, name='w0')
        t.start()
        t.join()
        trace_id = obs.trace.trace_id
    lines = open(sink).read().splitlines()
    assert len(lines) == 1
    doc = json.loads(lines[0])
    assert doc['trace'] == trace_id
    assert doc['op'] == 'unit-op'
    assert doc['dur_ms'] >= 0
    root = doc['spans']
    names = [c['name'] for c in root['children']]
    assert 'outer' in names
    outer = root['children'][names.index('outer')]
    assert outer['attrs'] == {'k': 'v'}
    assert outer['children'][0]['name'] == 'inner'
    assert outer['children'][0]['events'] == [
        {'name': 'tick', 'n': 1}]
    pool = root['children'][names.index('pool-span')]
    assert pool['thread'] == 'w0'


def test_slow_log_marks_outliers(tmp_path, monkeypatch):
    sink = str(tmp_path / 'trace.jsonl')
    monkeypatch.setenv('DN_TRACE', sink)
    monkeypatch.setenv('DN_SLOW_MS', '0')     # everything is slow
    with obs_trace.request('slow-op'):
        pass
    doc = json.loads(open(sink).read().splitlines()[0])
    assert doc['slow'] is True
    monkeypatch.setenv('DN_SLOW_MS', '600000')
    with obs_trace.request('fast-op'):
        pass
    doc = json.loads(open(sink).read().splitlines()[1])
    assert 'slow' not in doc


def test_fault_firing_lands_as_span_event(monkeypatch):
    from dragnet_tpu import faults as mod_faults
    monkeypatch.setenv('DN_FAULTS', 'iq.shard_read:delay:1.0')
    monkeypatch.setenv('DN_FAULT_DELAY_MS', '0')
    mod_faults.reset()
    try:
        with obs_trace.request('chaos-op', force=True,
                               emit=False) as obs:
            mod_faults.fire('iq.shard_read')
            root = obs.trace.root
            assert root.events and \
                root.events[0]['name'] == 'fault.injected'
            assert root.events[0]['site'] == 'iq.shard_read'
    finally:
        mod_faults.reset()


# -- end-to-end: corpus + server -------------------------------------------

def _gen_corpus(path, n=200):
    import datetime
    t0 = 1388534400
    with open(path, 'w') as f:
        for i in range(n):
            ts = datetime.datetime.utcfromtimestamp(
                t0 + i * 1600).strftime('%Y-%m-%dT%H:%M:%S.000Z')
            f.write(json.dumps({
                'time': ts, 'host': 'host%d' % (i % 3),
                'latency': (i * 7) % 230,
            }, separators=(',', ':')) + '\n')


@pytest.fixture(scope='module')
def corpus(tmp_path_factory):
    root = tmp_path_factory.mktemp('obs_corpus')
    datafile = str(root / 'data.log')
    _gen_corpus(datafile)
    rc_path = str(root / 'dragnetrc.json')
    prior = os.environ.get('DRAGNET_CONFIG')
    os.environ['DRAGNET_CONFIG'] = rc_path
    try:
        idx = str(root / 'idx')
        rc, out, err = run_cli([
            'datasource-add', '--path', datafile,
            '--index-path', idx, '--time-field', 'time', 'obsds'])
        assert rc == 0, err
        rc, out, err = run_cli(['metric-add', '-b', 'host',
                                'obsds', 'm1'])
        assert rc == 0, err
        rc, out, err = run_cli(['build', 'obsds'])
        assert rc == 0, err
        yield {'rc_path': rc_path, 'ds': 'obsds'}
    finally:
        if prior is None:
            os.environ.pop('DRAGNET_CONFIG', None)
        else:
            os.environ['DRAGNET_CONFIG'] = prior


@pytest.fixture
def server(corpus, tmp_path):
    sock = str(tmp_path / 'obs.sock')
    conf = {'max_inflight': 4, 'queue_depth': 16, 'deadline_ms': 0,
            'coalesce': True, 'drain_s': 10}
    srv = mod_server.DnServer(socket_path=sock, conf=conf).start()
    try:
        yield srv
    finally:
        srv.stop()


# the dashboard contract: /stats section names and value types.
# Additive changes are fine; renames/retypes must bump
# STATS_METRICS_VERSION and this golden.
_STATS_SHAPE = {
    'pid': int, 'uptime_s': float, 'started_at': float,
    'draining': bool, 'requests': dict, 'inflight': dict,
    'caches': dict, 'counters': dict, 'device': dict,
    'faults': dict, 'recovery': dict, 'metrics': dict,
    'history': dict, 'events': dict, 'resources': dict,
}


def test_stats_schema_golden_shape(server, corpus):
    # run one query through the server so latency histograms exist
    req = {'op': 'query', 'ds': corpus['ds'], 'interval': 'day',
           'config': corpus['rc_path'],
           'queryconfig': {'breakdowns': [{'name': 'host',
                                           'field': 'host'}]},
           'opts': {}}
    rc, hd, out, err = mod_client.request_bytes(server.socket_path,
                                                req)
    assert rc == 0, err
    st = mod_client.stats(server.socket_path)
    for name, typ in _STATS_SHAPE.items():
        assert name in st, 'missing /stats section %r' % name
        if typ is float:
            assert isinstance(st[name], (int, float)), name
        else:
            assert isinstance(st[name], typ), name
    # uptime is monotonic-based and sane
    assert 0 <= st['uptime_s'] < 3600
    m = st['metrics']
    assert m['version'] == obs_export.STATS_METRICS_VERSION
    assert set(m) == {'version', 'counters', 'gauges', 'histograms'}
    # fleet-observability sections (versioned like `metrics`):
    # disabled-by-default stubs keep the shape stable for dashboards
    from dragnet_tpu.obs import history as obs_history
    from dragnet_tpu.obs import events as obs_events_mod
    h = st['history']
    assert h['version'] == obs_history.HISTORY_VERSION
    assert set(h) == {'version', 'enabled', 'interval_s', 'capacity',
                      'samples', 'nseries', 'series'}
    ev = st['events']
    assert ev['version'] == obs_events_mod.EVENTS_VERSION
    assert set(ev) == {'version', 'enabled', 'capacity', 'seq',
                       'buffered', 'dropped', 'file',
                       'file_max_bytes', 'rotations', 'spill_errors'}
    lat = m['histograms'].get('serve_op_latency_ms{op=query}')
    assert lat is not None
    assert lat['count'] >= 1
    assert isinstance(lat['p50'], float)
    assert isinstance(lat['p99'], float)
    qw = m['histograms'].get('serve_queue_wait_ms')
    assert qw is not None and qw['count'] >= 1
    for g in ('device_engaged', 'device_residency_pct'):
        assert g in m['gauges']
    assert st['device']['engaged'] in (False, True)


def test_metrics_op_prometheus(server, corpus):
    req = {'op': 'query', 'ds': corpus['ds'], 'interval': 'day',
           'config': corpus['rc_path'],
           'queryconfig': {'breakdowns': [{'name': 'host',
                                           'field': 'host'}]},
           'opts': {}}
    rc, hd, out, err = mod_client.request_bytes(server.socket_path,
                                                req)
    assert rc == 0, err
    rc, hd, out, err = mod_client.request_bytes(
        server.socket_path, {'op': 'metrics'})
    assert rc == 0
    text = out.decode('utf-8')
    assert '# TYPE dn_serve_op_latency_ms histogram' in text
    for line in text.splitlines():
        if not line.startswith('#'):
            assert _PROM_LINE.match(line), line
    assert 'dn_device_residency_pct' in text


def test_trace_id_propagates_and_joins(server, corpus, tmp_path,
                                       monkeypatch):
    """`dn query --remote` under DN_TRACE: the client generates the
    trace id, the server's span subtree joins it, and ONE line holds
    client + server + stage spans."""
    sink = str(tmp_path / 'joined.jsonl')
    monkeypatch.setenv('DN_TRACE', sink)
    rc, out, err = run_cli(['query', '-b', 'host', '--remote',
                            server.socket_path, corpus['ds']])
    assert rc == 0, err
    docs = [json.loads(ln) for ln in open(sink).read().splitlines()]
    client_docs = [d for d in docs if d['op'] == 'query']
    assert len(client_docs) == 1
    doc = client_docs[0]
    # the server side (same process here) emitted its own line under
    # the SAME client-generated id — a server-side trace joins its
    # client
    server_docs = [d for d in docs if d['op'] == 'serve.query']
    assert server_docs and \
        server_docs[0]['trace'] == doc['trace']

    def names(span, acc):
        acc.add(span['name'])
        for c in span.get('children') or []:
            names(c, acc)
        return acc

    got = names(doc['spans'], set())
    assert 'remote.exchange' in got
    assert 'serve.query' in got        # the grafted server subtree
    assert 'serve.execute' in got
    # pool-thread stage spans attributed into the same joined tree
    assert ('index_query_mt.shard' in got or
            'index_query_stack.load' in got)


def test_trace_off_leaves_output_byte_identical(server, corpus,
                                                tmp_path,
                                                monkeypatch):
    args = ['query', '-b', 'host', corpus['ds']]
    monkeypatch.delenv('DN_TRACE', raising=False)
    monkeypatch.delenv('DN_SLOW_MS', raising=False)
    rc0, out0, err0 = run_cli(args)
    sink = str(tmp_path / 't.jsonl')
    monkeypatch.setenv('DN_TRACE', sink)
    rc1, out1, err1 = run_cli(args)
    assert (rc0, out0, err0) == (rc1, out1, err1)
    assert os.path.exists(sink)       # the trace went to the sink


def test_trace_flag_emits_to_stderr(corpus, capfd, monkeypatch):
    """`dn query --trace` == DN_TRACE=stderr for one run: the span
    tree lands on the PROCESS stderr (not the captured CLI output),
    and the CLI output itself is unchanged."""
    monkeypatch.delenv('DN_TRACE', raising=False)
    rc0, out0, err0 = run_cli(['query', '-b', 'host', corpus['ds']])
    capfd.readouterr()
    rc, out, err = run_cli(['query', '-b', 'host', '--trace',
                            corpus['ds']])
    assert rc == 0, err
    assert (rc, out, err) == (rc0, out0, err0)
    traced = capfd.readouterr().err
    doc = json.loads(traced.splitlines()[-1])
    assert doc['op'] == 'query'
    assert doc['spans']['name'] == 'query'


def test_dn_stats_local_and_remote(server, corpus):
    rc, out, err = run_cli(['stats'])
    assert rc == 0, err
    doc = json.loads(out.decode())
    assert doc['version'] == obs_export.STATS_METRICS_VERSION
    rc, out, err = run_cli(['stats', '--prom'])
    assert rc == 0
    rc, out, err = run_cli(['stats', '--remote', server.socket_path])
    assert rc == 0, err
    doc = json.loads(out.decode())
    assert 'metrics' in doc and 'uptime_s' in doc
    rc, out, err = run_cli(['stats', '--remote', server.socket_path,
                            '--prom'])
    assert rc == 0
    for line in out.decode().splitlines():
        if line and not line.startswith('#'):
            assert _PROM_LINE.match(line), line


def test_dn_stats_unreachable_is_clean_error(tmp_path):
    rc, out, err = run_cli(['stats', '--remote',
                            str(tmp_path / 'nope.sock')])
    assert rc == 1
    assert err.startswith(b'dn: serve endpoint')
    assert b'Traceback' not in err
