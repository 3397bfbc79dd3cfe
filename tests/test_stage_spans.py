"""The leaf stages of the scan, build and index-fold host path
(obs/metrics.leaf_stage): always-on `stage_ms{stage}`, spans in the
request's tree, `jax.profiler` annotations on the device trace's
clock; the counters at the same boundaries; the compile counters of
`jax.monitoring`.

One forced-device scan, build and index query over a small corpus run
once per module (`runs`), in small batches so that every stage of the
table occurs; the tests read what they left behind.
"""

import hashlib
import importlib.util
import json
import os
import subprocess
import sys
import threading
import time

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

from dragnet_tpu import cli                                # noqa: E402
from dragnet_tpu.obs import metrics as obs_metrics         # noqa: E402
from dragnet_tpu.serve import server as mod_server         # noqa: E402

# the parser's thread (datasource_file._RunAhead, 'dn-parse-ahead')
# runs one batch ahead of the request's: its two leaves lie beside the
# request thread's, not among them
PRODUCER_STAGES = ('scan.read', 'scan.parse')
PRODUCER_THREAD = 'dn-parse-ahead'
SCAN_STAGES = PRODUCER_STAGES + (
    'scan.parse_wait', 'scan.stage', 'scan.upload', 'scan.dispatch',
    'scan.device_wait', 'scan.fetch', 'scan.emit')
FOLD_STAGES = ('index_fold.stage', 'index_fold.dispatch',
               'index_fold.device_wait', 'index_fold.fetch')
QUERY_STAGES = ('index_query_stack.load',
                'index_query_stack.sort') + FOLD_STAGES
# the request thread's last two: the aggregate's emission (the order,
# the decode) and the formatting of the reply
REPLY_STAGES = ('scan.order', 'reply.format')
# PR 42, the request between its device phases, once a request each:
# a scan's set-up and its deferred merge, the index write's routing
# (its two phases were `timed_stage` before), the query's plan and the
# stack's two ends
BETWEEN = {
    'scan': ('scan.init', 'scan.finish'),
    'build': ('scan.init', 'scan.finish', 'index_build.bucket',
              'index_build.prepare', 'index_build.commit'),
    'query': ('index_query.paths', 'index_query.prune',
              'index_query.plan', 'index_query_stack.stack',
              'index_query_stack.commit'),
}
# a resident server's own: the request before its execution (on its
# thread), and the reply's frame (after the request's accounting)
SERVED = ('serve.resolve', 'reply.frame')
# which request must have met which leaves
LEAVES = {'scan': SCAN_STAGES + REPLY_STAGES + BETWEEN['scan'],
          'build': SCAN_STAGES + BETWEEN['build'],
          'query': QUERY_STAGES + REPLY_STAGES + BETWEEN['query']}
# `serve.tree_lock` is a leaf only where a request waited for its
# tree's lock (PR 50: tests/test_live_publish.py); none here does
ALL_LEAVES = set(SCAN_STAGES + QUERY_STAGES + REPLY_STAGES + SERVED +
                 sum(BETWEEN.values(), ())) | {'serve.tree_lock'}
# in `stage_ms` and no leaf: they enclose leaves
ENCLOSING = ('index_query_stack.aggregate', 'device_scan.probe')

NRECORDS = 3000
SMALL_BATCH = 512
SCAN_ARGS = ['scan', '-b', 'host,req.method,latency[aggr=quantize]',
             '-f', '{"ne":["latency",7]}']


def gen_corpus(path, n=NRECORDS):
    """`n` records by formula over three days: the same bytes every
    time, so that outputs can be pinned."""
    t0 = 1388534400
    with open(path, 'w') as f:
        for i in range(n):
            ts = time.strftime('%Y-%m-%dT%H:%M:%S.000Z',
                               time.gmtime(t0 + i * 80))
            f.write(json.dumps({
                'time': ts, 'host': 'host%d' % (i % 5),
                'req': {'method': ('GET', 'PUT', 'HEAD')[i % 3]},
                'latency': (i * 7) % 230,
            }, separators=(',', ':')) + '\n')


def run_cli(args):
    with mod_server.thread_stdio() as cap:
        rc = cli.main(list(args))
    out, err = cap.finish()
    return rc, out, err


def stage_table():
    """{stage: (count, summed ms)} of `stage_ms`, and {counter: value},
    of the global registry."""
    stages, counters = {}, {}
    for name, labels, m in obs_metrics.global_registry().snapshot():
        if name == 'stage_ms':
            stages[dict(labels)['stage']] = (m.total, m.sum)
        elif m.kind == obs_metrics.COUNTER:
            counters[name] = m.value
    return stages, counters


def add_datasource(root, name='stageds'):
    datafile = os.path.join(root, 'data.log')
    gen_corpus(datafile)
    os.environ['DRAGNET_CONFIG'] = os.path.join(root, 'dragnetrc.json')
    for args in (
            ['datasource-add', '--path', datafile, '--index-path',
             os.path.join(root, 'idx'), '--time-field', 'time', name],
            ['metric-add', '-b',
             'timestamp[field=time,date,aggr=lquantize,step=86400]',
             '-b', 'host', '-b', 'latency[aggr=quantize]', name, 'm1'],
            ['metric-add', '-b',
             'timestamp[field=time,date,aggr=lquantize,step=86400]',
             '-b', 'req.method', name, 'm2']):
        rc, out, err = run_cli(args)
        assert rc == 0, err
    return datafile


@pytest.fixture(scope='module')
def runs(tmp_path_factory):
    """{op: {'stages', 'counters', 'doc' (the DN_TRACE line), 'out',
    'err'}} of one forced-device scan, build and (second, so that the
    first device contact's deadline thread is behind it) index query."""
    from dragnet_tpu import device_scan as mod_ds
    from dragnet_tpu import engine as mod_engine
    root = str(tmp_path_factory.mktemp('stage_spans'))
    sink = os.path.join(root, 'trace.jsonl')
    got = {}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(mod_engine, 'BATCH_SIZE', SMALL_BATCH)
        mp.setattr(mod_ds, 'BATCH_SIZE', SMALL_BATCH)
        for k, v in (('DN_READ_SIZE', '16384'), ('DN_ENGINE', 'jax'),
                     ('DN_INDEX_DEVICE', '1'), ('DN_PARSE_THREADS', '1'),
                     ('DN_DEVICE_PIPELINE_DEPTH', '1'),
                     ('DN_TRACE', sink),
                     ('DRAGNET_CONFIG', '')):
            mp.setenv(k, v)
        mp.delenv('DN_SLOW_MS', raising=False)
        datafile = add_datasource(root)
        got['corpus_bytes'] = os.path.getsize(datafile)
        for op, args in (('scan', SCAN_ARGS + ['--counters', 'stageds']),
                         ('build', ['build', 'stageds']),
                         ('warm', ['query', '-b', 'host', 'stageds']),
                         ('query', ['query', '-b', 'host', '--counters',
                                    'stageds'])):
            obs_metrics.reset_global_registry()
            open(sink, 'w').close()
            rc, out, err = run_cli(args)
            assert rc == 0, err
            stages, counters = stage_table()
            with open(sink) as f:
                docs = [json.loads(ln) for ln in f]
            assert len(docs) == 1
            got[op] = {'stages': stages, 'counters': counters,
                       'doc': docs[0], 'out': out, 'err': err}
    return got


# -- (1) always-on stage_ms and the counters beside it ----------------------

@pytest.mark.parametrize('op,stage', [
    (op, s) for op in ('scan', 'build', 'query') for s in LEAVES[op]])
def test_every_stage_is_observed(runs, op, stage):
    count, ms = runs[op]['stages'].get(stage, (0, 0.0))
    assert count > 0 and ms >= 0.0


@pytest.mark.parametrize('op', ['scan', 'build'])
def test_parse_counters_equal_the_corpus(runs, op):
    c = runs[op]['counters']
    assert c['scan_parse_bytes'] == runs['corpus_bytes']
    assert c['scan_parse_records'] == NRECORDS


def test_index_fold_counts_its_staged_shards(runs):
    # three days of records, one shard a day
    assert runs['query']['counters']['index_fold_shards_staged'] == 3


def test_index_fold_counts_its_rows_and_its_padding(runs):
    """`index_fold_rows` is the stacked batch's rows (m1's host x
    latency-bucket tuples of the three shards: the corpus is made by
    formula), `index_fold_padded_rows` the ladder's count for them:
    their ratio is what the ladder costs the device."""
    from dragnet_tpu import device_index
    c = runs['query']['counters']
    assert c['index_fold_rows'] == 99
    assert c['index_fold_padded_rows'] == device_index.pad_rows(99)


@pytest.mark.parametrize('op', ['scan', 'build', 'query'])
def test_leaf_stages_sum_within_the_request(runs, op):
    """No double counting: no leaf's `stage_ms` of the request's thread
    holds another leaf's time, so together (the wait for the parser's
    thread among them, that thread's own leaves not) they fit into the
    request's root span."""
    r = runs[op]
    leaves = sum(ms for s, (_n, ms) in r['stages'].items()
                 if s in ALL_LEAVES and s not in PRODUCER_STAGES)
    assert 0 < leaves <= r['doc']['dur_ms']
    if op != 'query':
        assert r['stages']['scan.parse_wait'][1] <= leaves


@pytest.mark.parametrize('op', ['scan', 'build'])
def test_batches_handed_equal_the_batches_of_the_scan(runs, op):
    """Every batch crosses the hand-off once (one upload a batch), and
    the request's thread waits once a batch and once for the end."""
    r = runs[op]
    handed = r['counters']['scan_batches_handed']
    assert handed == r['stages']['scan.upload'][0] == 6
    assert 0 <= r['counters'].get('scan_batches_ready', 0) <= handed
    assert r['stages']['scan.parse_wait'][0] in (handed + 1, handed + 2)


def test_nested_leaf_suspends_the_outer_one():
    """An epoch flush in the middle of staging: the outer leaf's
    stage_ms is its self time, and the two sum to the wall time."""
    obs_metrics.reset_global_registry()
    t0 = time.perf_counter()
    with obs_metrics.leaf_stage('t.outer'):
        time.sleep(0.02)
        with obs_metrics.leaf_stage('t.inner'):
            time.sleep(0.03)
        time.sleep(0.01)
    wall_ms = (time.perf_counter() - t0) * 1000.0
    stages, _ = stage_table()
    (n_out, outer), (n_in, inner) = stages['t.outer'], stages['t.inner']
    assert (n_out, n_in) == (1, 1)
    assert inner >= 30.0 and 30.0 <= outer < wall_ms - inner + 1.0
    assert outer + inner <= wall_ms
    assert getattr(obs_metrics._LEAF, 'top', None) is None


# -- (2) the same stages in the request's span tree -------------------------

def _walk(span, fn, parent=None):
    fn(span, parent)
    for c in span.get('children') or []:
        _walk(c, fn, span)


@pytest.mark.parametrize('op', ['scan', 'build', 'query'])
def test_stages_in_the_span_tree(runs, op):
    doc = runs[op]['doc']
    assert doc['op'] == op and len(doc['trace']) == 32
    names = set()

    def check(span, parent):
        names.add(span['name'])
        if parent is not None and span.get('thread') is None:
            # children lie inside their parents' intervals (0.01 ms
            # of rounding in the document)
            assert span['t0_ms'] >= parent['t0_ms'] - 0.01
            assert span['t0_ms'] + span['dur_ms'] <= \
                parent['t0_ms'] + parent['dur_ms'] + 0.01
    _walk(doc['spans'], check)
    assert set(LEAVES[op]) <= names
    assert 'device_scan.d2h' not in names


@pytest.mark.parametrize('op', ['scan', 'build'])
def test_producer_stages_are_tagged_with_their_thread(runs, op):
    """`scan.read` and `scan.parse` come from the parser's thread and
    land in the request's tree all the same, each tagged with that
    thread; the request thread's leaves carry no tag."""
    threads = {}

    def note(span, parent):
        if span['name'].startswith('scan.'):
            threads.setdefault(span['name'], set()).add(
                span.get('thread'))
    _walk(runs[op]['doc']['spans'], note)
    for stage in PRODUCER_STAGES:
        assert threads[stage] == {PRODUCER_THREAD}
    for stage in set(SCAN_STAGES) - set(PRODUCER_STAGES):
        assert threads[stage] == {None}


@pytest.mark.parametrize('op', ['scan', 'query'])
@pytest.mark.parametrize('stage', REPLY_STAGES)
def test_reply_stages_are_the_request_threads(runs, op, stage):
    """`scan.order` and `reply.format` are once in the request's tree,
    on its own thread (no tag), inside the root span, the order
    before the format."""
    root = runs[op]['doc']['spans']
    found = []
    _walk(root, lambda span, parent: found.append(span)
          if span['name'] in REPLY_STAGES else None)
    assert [sp['name'] for sp in found] == list(REPLY_STAGES)
    span = found[REPLY_STAGES.index(stage)]
    assert span.get('thread') is None
    assert span['t0_ms'] >= root['t0_ms'] - 0.01
    assert span['t0_ms'] + span['dur_ms'] <= \
        root['t0_ms'] + root['dur_ms'] + 0.01
    assert runs[op]['stages'][stage][0] == 1


# -- (2b) a resident server's requests: the leaves between the phases ------

def hist_table():
    """{(name, labels): (count, sum)} of the global registry's
    histograms other than `stage_ms`."""
    return {(name, labels): (m.total, m.sum)
            for name, labels, m in obs_metrics.global_registry().snapshot()
            if m.kind == obs_metrics.HISTOGRAM and name != 'stage_ms'}


@pytest.fixture(scope='module')
def served(tmp_path_factory):
    """{op: {'stages', 'hists'}} of one scan, one build and one index
    query through a resident server of this process, each the second
    of its kind (the first device contact runs on a deadline thread of
    its own, whose leaves are not the request thread's)."""
    from dragnet_tpu import device_scan as mod_ds
    from dragnet_tpu import engine as mod_engine
    from dragnet_tpu.serve import client as mod_client
    root = str(tmp_path_factory.mktemp('stage_served'))
    got = {}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(mod_engine, 'BATCH_SIZE', SMALL_BATCH)
        mp.setattr(mod_ds, 'BATCH_SIZE', SMALL_BATCH)
        for k, v in (('DN_READ_SIZE', '16384'), ('DN_ENGINE', 'jax'),
                     ('DN_INDEX_DEVICE', '1'), ('DN_PARSE_THREADS', '1'),
                     ('DRAGNET_CONFIG', '')):
            mp.setenv(k, v)
        for k in ('DN_TRACE', 'DN_SLOW_MS', 'DN_SERVE_CACHE_MB'):
            mp.delenv(k, raising=False)
        add_datasource(root)
        srv = mod_server.DnServer(
            socket_path=os.path.join(root, 's.sock'),
            conf={'max_inflight': 2, 'queue_depth': 4, 'deadline_ms': 0,
                  'coalesce': True, 'drain_s': 10}).start()
        base = {'ds': 'stageds', 'config': os.environ['DRAGNET_CONFIG']}
        by_host = {'breakdowns': [{'name': 'host', 'field': 'host'}]}
        reqs = {'scan': dict(base, op='scan', queryconfig=by_host,
                             opts={'points': True}),
                'build': dict(base, op='build', interval='day', opts={}),
                'query': dict(base, op='query', interval='day',
                              queryconfig=by_host, opts={'points': True})}
        try:
            for op in ('scan', 'build', 'query'):
                for attempt in (0, 1):
                    obs_metrics.reset_global_registry()
                    rc, _, out, err = mod_client.request_bytes(
                        srv.socket_path, reqs[op])
                    assert rc == 0, err
                    # the reply's frame and its drain are observed
                    # after the client has its bytes: by the worker,
                    # by the I/O loop
                    limit = time.monotonic() + 10.0
                    while time.monotonic() < limit:
                        stages, hists = stage_table()[0], hist_table()
                        if 'reply.frame' in stages and \
                                ('serve_reply_drain_ms', ()) in hists:
                            break
                        time.sleep(0.01)
                got[op] = {'stages': stages, 'hists': hists, 'out': out}
        finally:
            srv.stop()
        rc, got['cli_query_out'], err = run_cli(
            ['query', '-b', 'host', '--points', 'stageds'])
        assert rc == 0, err
    return got


@pytest.mark.parametrize('op,stage', [
    (op, s) for op in ('scan', 'build', 'query')
    for s in BETWEEN[op] + SERVED])
def test_a_served_request_meets_each_new_leaf_once(served, op, stage):
    assert served[op]['stages'].get(stage, (0, 0.0))[0] == 1


@pytest.mark.parametrize('op', ['scan', 'build', 'query'])
def test_leaf_ms_is_the_request_threads_leaves(served, op):
    """`serve_leaf_ms{op}` is counted where the leaves end, by no list
    of names: it equals the `stage_ms` of every leaf but the parser
    thread's two and the reply's frame (which follows the request's
    accounting), and fits into the request's latency."""
    r = served[op]
    assert not set(r['stages']) - ALL_LEAVES - set(ENCLOSING)
    own = sum(ms for s, (_n, ms) in r['stages'].items()
              if s in ALL_LEAVES and s not in PRODUCER_STAGES and
              s != 'reply.frame')
    n, leaf_ms = r['hists'][('serve_leaf_ms', (('op', op),))]
    n_lat, latency = r['hists'][('serve_op_latency_ms', (('op', op),))]
    assert (n, n_lat) == (1, 1)
    assert leaf_ms == pytest.approx(own, rel=0.01)
    assert 0 < own <= latency


@pytest.mark.parametrize('op', ['scan', 'build', 'query'])
def test_the_replys_drain_is_observed_once_a_request(served, op):
    n, ms = served[op]['hists'][('serve_reply_drain_ms', ())]
    assert n == 1 and ms >= 0.0


def test_a_served_query_answers_as_the_cli_does(served):
    """The same tree, the same question: the server's reply under its
    leaves is the CLI's answer, byte for byte."""
    assert served['query']['out']
    assert served['query']['out'] == served['cli_query_out']


# -- (2b') the rollup planner's kept reads at a scrape and in /stats -------

VERDICTS = 'dn_rollup_plan_verdicts_total{result="%s"}'
MANIFESTS = 'dn_rollup_manifest_loads_total{result="%s"}'


def test_the_planners_kept_reads_are_counted(tmp_path, monkeypatch):
    """`rollup_plan_verdicts_total{result}` and
    `rollup_manifest_loads_total{result}` at a scrape and under
    `/stats` `rollup`: a query's `kept + checked` grows by its
    candidate buckets (a rollup shard of the manifest whose window
    lies inside the query's), `kept + parsed` by the levels of the
    interval; the first query of a tree checks and parses, the next
    ones are answered from what it kept."""
    from dragnet_tpu import index_query_mt as mod_iqmt
    from dragnet_tpu import rollup as mod_rollup
    from dragnet_tpu.serve import client as mod_client
    root = str(tmp_path)
    for k, v in (('DN_IQ_STAT_TTL_MS', '600000'), ('DN_INDEX_DEVICE', '0'),
                 ('DRAGNET_CONFIG', '')):
        monkeypatch.setenv(k, v)
    for k in ('DN_TRACE', 'DN_SLOW_MS', 'DN_SERVE_CACHE_MB', 'DN_ENGINE'):
        monkeypatch.delenv(k, raising=False)
    add_datasource(root)
    # four days of records over the end of January: two months' shards
    datafile = os.path.join(root, 'data.log')
    t0 = 1391040000                     # 2014-01-30T00:00:00Z
    with open(datafile, 'w') as f:
        for i in range(400):
            f.write(json.dumps({
                'time': time.strftime('%Y-%m-%dT%H:%M:%S.000Z',
                                      time.gmtime(t0 + i * 860)),
                'host': 'host%d' % (i % 5), 'req': {'method': 'GET'},
                'latency': i % 230}, separators=(',', ':')) + '\n')
    rc, out, err = run_cli(['build', 'stageds'])
    assert rc == 0, err
    idx = os.path.join(root, 'idx')
    assert sorted(os.listdir(os.path.join(idx, 'by_day'))) == [
        '2014-01-30.sqlite', '2014-01-31.sqlite', '2014-02-01.sqlite',
        '2014-02-02.sqlite']
    assert mod_rollup.build_rollups(idx, 'day')['built'] == 2
    # a tree that was not written a moment ago (the racy margin)
    old = time.time() - 120
    leveldir = os.path.join(idx, 'rollup', 'by_month')
    for path in (mod_rollup.manifest_path(leveldir), leveldir,
                 os.path.join(idx, 'by_day')):
        os.utime(path, (old, old))
    mod_iqmt.shard_cache_clear()

    srv = mod_server.DnServer(
        socket_path=os.path.join(root, 's.sock'),
        conf={'max_inflight': 2, 'queue_depth': 4, 'deadline_ms': 0,
              'coalesce': False, 'drain_s': 10}).start()
    names = [VERDICTS % 'kept', VERDICTS % 'checked',
             MANIFESTS % 'kept', MANIFESTS % 'parsed']

    def scrape():
        rc, hd, out, err = mod_client.request_bytes(
            srv.socket_path, {'op': 'metrics'})
        assert rc == 0, err
        doc = dict(ln.rsplit(' ', 1) for ln in out.decode().splitlines()
                   if not ln.startswith('#'))
        return [float(doc.get(k, 0)) for k in names]

    def query(after=None, before=None):
        """One query; how the four counters grew by it."""
        first = scrape()
        qc = {'breakdowns': [{'name': 'host', 'field': 'host'}]}
        if after is not None:
            qc.update(timeAfter=after, timeBefore=before)
        rc, hd, out, err = mod_client.request_bytes(srv.socket_path, {
            'op': 'query', 'ds': 'stageds', 'interval': 'day',
            'config': os.environ['DRAGNET_CONFIG'], 'queryconfig': qc,
            'opts': {'points': True}})
        assert rc == 0, err
        # a request's registry is merged after the client has its bytes
        limit = time.monotonic() + 10.0
        while time.monotonic() < limit:
            grew = [b - a for a, b in zip(first, scrape())]
            if sum(grew[2:]):
                break
            time.sleep(0.01)
        return out, grew

    try:
        stats0 = mod_client.stats(srv.socket_path)['rollup']
        cold, grew = query()
        assert grew == [0, 2, 0, 1]        # both months asked, one level
        warm, grew = query()
        assert grew == [2, 0, 1, 0] and warm == cold
        # February alone, a window whose days the tree does not all
        # hold: the filesystem answers that walk, not the snapshot, and
        # with no snapshot the one candidate is checked
        out, grew = query('2014-02-01', '2014-03-01')
        assert grew == [0, 1, 1, 0]
        # a window inside a month has no candidate bucket
        out, grew = query('2014-01-30', '2014-01-31')
        assert grew == [0, 0, 1, 0]
        stats = mod_client.stats(srv.socket_path)['rollup']
        assert {k: {r: stats[k][r] - stats0[k][r] for r in stats[k]}
                for k in ('plan_verdicts', 'manifest_loads')} == {
            'plan_verdicts': {'kept': 2, 'checked': 3},
            'manifest_loads': {'kept': 3, 'parsed': 1}}
    finally:
        srv.stop()
        mod_iqmt.shard_cache_clear()


# -- (2b'') which sort ordered a columnar aggregate ------------------------

ORDER = 'dn_aggr_order_total{path="%s"}'


def scrape_paths(series, paths):
    """{path: value} of a `{path}`-labelled counter at a scrape, 0
    where the series has not been bumped yet."""
    from dragnet_tpu.obs import export as obs_export
    doc = dict(ln.rsplit(' ', 1) for ln in
               obs_export.prometheus_text().splitlines()
               if not ln.startswith('#'))
    return {p: float(doc.get(series % p, 0)) for p in paths}


@pytest.mark.parametrize('path,ords', [
    ('fused', (0, 40)),
    # three bucketized levels spanning 2^31 ordinals each: the fused
    # key would pass 2^62, so the lexsort over the same columns
    ('lexsort', (-2 ** 30, 2 ** 30))])
def test_a_columnar_order_counts_its_sort(path, ords):
    """`aggr_order_total{path}` at a scrape: one bump for each
    emission of a columnar aggregate that holds a tuple, under the
    sort that ordered it; none for an empty one."""
    import numpy as np
    from dragnet_tpu import aggr as mod_aggr
    from dragnet_tpu import query as mod_query
    query = mod_query.query_load({'breakdowns': [
        {'name': 'host'},
        {'name': 'a', 'aggr': 'lquantize', 'step': 10},
        {'name': 'b', 'aggr': 'lquantize', 'step': 10},
        {'name': 'c', 'aggr': 'lquantize', 'step': 10}]})

    def aggregate(ntuples):
        rng = np.random.default_rng(ntuples)
        aggr = mod_aggr.Aggregator(query)
        lo, hi = ords
        aggr.set_columnar(
            [rng.integers(0, 3, ntuples)] +
            [np.concatenate([[lo, hi], rng.integers(lo, hi, ntuples)]
                            )[:ntuples] for _ in range(3)],
            np.ones(ntuples), [('str', ['x', '7', 'y'])] +
            [('ord', None)] * 3)
        return aggr

    def scrape():
        return scrape_paths(ORDER, ('fused', 'lexsort'))

    before = scrape()
    block = aggregate(50).point_block()
    assert len(block) == 50
    assert aggregate(0).point_block().points() == []
    after = scrape()
    other = 'lexsort' if path == 'fused' else 'fused'
    assert after[path] - before[path] == 1
    assert after[other] == before[other]
    assert counter_table()[
        ('aggr_order_total', (('path', path),))] == after[path]


# -- (2b''') which path translated a numeric key column --------------------

TRANSLATE = 'dn_scan_key_translate_total{path="%s"}'


@pytest.mark.parametrize('batches,want', [
    # every value new, then every value seen
    ([[4, 9, 4], [9, 4]], [('values', 1), ('table', 1)]),
    # one value the table has not seen is the whole call's `values`
    ([[4, 9], [9, 5], [5, 4, 9]],
     [('values', 1), ('values', 1), ('table', 1)]),
    # outside the table's domain every time; strings count nothing
    ([[2.5, 1], [2.5, 1], ['a', 'b'], [1, 'a']],
     [('values', 1), ('values', 1), None, ('table', 1)])])
def test_a_numeric_translation_counts_its_path(batches, want):
    """`scan_key_translate_total{path}` at a scrape: exactly one bump
    for each `NativeColumns.string_codes` call that had numbers to
    translate, `table` when the kept table answered every one of
    them, `values` when at least one went through the per-value path;
    a call over no number bumps neither."""
    from dragnet_tpu import batch as mod_batch
    from dragnet_tpu import engine as mod_engine
    from dragnet_tpu import native as mod_native
    if mod_native.get_lib() is None:
        pytest.skip('native parser not built')

    def scrape():
        return scrape_paths(TRANSLATE, ('table', 'values'))

    parser = mod_native.NativeParser(['k'], [False])
    column = mod_batch.StringColumn()
    for values, grew in zip(batches, want):
        parser.parse(''.join(json.dumps({'k': v}) + '\n'
                             for v in values).encode())
        before = scrape()
        codes = mod_engine.NativeColumns(parser).string_codes('k', column)
        parser.reset_batch()
        after = scrape()
        assert [column.dict.values[c] for c in codes] == [
            v if isinstance(v, str) else '%g' % v for v in values]
        assert {p: after[p] - before[p] for p in after} == {
            p: (1 if grew and grew[0] == p else 0) for p in after}
    assert counter_table()[
        ('scan_key_translate_total', (('path', 'table'),))] == \
        scrape()['table']


def test_end_open_ends_the_leaf_once_and_counts_once():
    """A leaf ended where its part ends (`end_open`), inside its own
    `with`: one observation, the thread's total grows by its self time,
    and nothing stays open."""
    obs_metrics.reset_global_registry()
    before = obs_metrics.leaf_stage.thread_ms()
    with obs_metrics.leaf_stage('t.early'):
        time.sleep(0.01)
        obs_metrics.leaf_stage.end_open('t.early')
        assert getattr(obs_metrics._LEAF, 'top', None) is None
        obs_metrics.leaf_stage.end_open('t.early')
        with obs_metrics.leaf_stage('t.after'):
            time.sleep(0.01)
    stages, _ = stage_table()
    assert stages['t.early'][0] == 1 and stages['t.after'][0] == 1
    assert 10.0 <= stages['t.early'][1] < 20.0
    grown = obs_metrics.leaf_stage.thread_ms() - before
    assert grown == pytest.approx(
        stages['t.early'][1] + stages['t.after'][1])
    assert getattr(obs_metrics._LEAF, 'top', None) is None


@pytest.mark.parametrize('inner', [None, 't.inner'])
def test_end_open_ends_only_the_threads_open_leaf(inner):
    """Asked for another name than the open leaf's, or for a leaf
    under which another is still open, `end_open` does nothing: the
    leaves end innermost first, each once, by their `with`."""
    obs_metrics.reset_global_registry()
    with obs_metrics.leaf_stage('t.outer') as outer:
        if inner is None:
            obs_metrics.leaf_stage.end_open('t.other')
        else:
            with obs_metrics.leaf_stage(inner) as leaf:
                obs_metrics.leaf_stage.end_open('t.outer')
                assert obs_metrics._LEAF.top is leaf
                # nor does an exit out of order end the outer leaf
                outer.__exit__(None, None, None)
                assert obs_metrics._LEAF.top is leaf
        assert obs_metrics._LEAF.top is outer
        assert 't.outer' not in stage_table()[0]
    stages, _ = stage_table()
    assert stages['t.outer'][0] == 1
    assert stages.get(inner, (1,))[0] == 1
    assert getattr(obs_metrics._LEAF, 'top', None) is None


def test_a_leaf_of_another_thread_is_not_ended_here():
    """A leaf belongs to the thread that opened it: its exit on
    another thread leaves both threads' open leaves as they were."""
    import threading
    obs_metrics.reset_global_registry()
    with obs_metrics.leaf_stage('t.mine') as leaf:
        t = threading.Thread(target=leaf.__exit__,
                             args=(None, None, None))
        t.start()
        t.join()
        assert obs_metrics._LEAF.top is leaf
        assert 't.mine' not in stage_table()[0]
    assert stage_table()[0]['t.mine'][0] == 1


# -- (3) the profiler leg ----------------------------------------------------

PROFILED = r'''
import glob, json, os, sys
sys.path.insert(0, %(root)r)
sys.path.insert(0, os.path.join(%(root)r, 'tests'))
import test_stage_spans as t
from dragnet_tpu.serve import client as mod_client
from dragnet_tpu.serve import server as mod_server
work = sys.argv[1]
os.environ['DN_TRACE'] = os.path.join(work, 'trace.jsonl')
os.environ['DN_ENGINE'] = 'jax'
t.add_datasource(work)
srv = mod_server.DnServer(
    socket_path=os.path.join(work, 's.sock'),
    conf={'max_inflight': 2, 'queue_depth': 4, 'deadline_ms': 0,
          'coalesce': True, 'drain_s': 10}).start()
req = {'op': 'scan', 'ds': 'stageds',
       'config': os.environ['DRAGNET_CONFIG'],
       'queryconfig': {'breakdowns': [{'name': 'host', 'field': 'host'}]},
       'opts': {'points': True}}
try:
    rc, _, out, err = mod_client.request_bytes(srv.socket_path, req)
    assert rc == 0, err                   # jax imported, backend up
    import jax
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(os.path.join(work, 'prof'),
                             profiler_options=opts)
    open(os.environ['DN_TRACE'], 'w').close()
    rc, _, out, err = mod_client.request_bytes(srv.socket_path, req)
    assert rc == 0, err
    rc, _, out, err = mod_client.request_bytes(
        srv.socket_path, {'op': 'build', 'ds': 'stageds',
                          'config': os.environ['DRAGNET_CONFIG'],
                          'opts': {}})
    jax.profiler.stop_trace()
    assert rc == 0, err
finally:
    srv.stop()
path = glob.glob(os.path.join(work, 'prof', 'plugins', 'profile', '*',
                              '*.xplane.pb'))[0]
events = {}
for plane in ProfileData.from_file(path).planes:
    if plane.name != '/host:CPU':
        continue
    for line in plane.lines:
        for e in line.events:
            events.setdefault(e.name, set()).update(
                str(v) for k, v in e.stats if k == 'trace')
with open(os.environ['DN_TRACE']) as f:
    ids = sorted(set(json.loads(ln)['trace'] for ln in f))
print(json.dumps({'events': {k: sorted(v) for k, v in events.items()},
                  'trace_ids': ids}))
'''


def _run_script(text, *argv, env=None, timeout=180):
    """A child process with a time limit of its own; its last stdout
    line is a JSON document."""
    e = dict(os.environ, JAX_PLATFORMS='cpu')
    for k in list(e):
        if k.startswith('DN_') or k == 'DRAGNET_CONFIG':
            del e[k]
    e['DN_AUDITION_CACHE'] = '0'
    e.update(env or {})
    p = subprocess.run([sys.executable, '-c', text] + list(argv), env=e,
                       cwd=REPO_ROOT, stdout=subprocess.PIPE,
                       stderr=subprocess.PIPE, timeout=timeout)
    assert p.returncode == 0, p.stderr.decode()[-3000:]
    return json.loads(p.stdout.decode().splitlines()[-1])


def test_profiler_host_plane_holds_the_leaves_only(tmp_path):
    """Under a jax.profiler trace the leaves are events of the host
    plane, on the device planes' clock; the enclosing spans are not;
    and an event carries its request's trace id."""
    doc = _run_script(PROFILED % {'root': REPO_ROOT}, str(tmp_path))
    events = doc['events']
    for stage in ('scan.parse', 'scan.stage', 'scan.fetch'):
        assert stage in events, sorted(events)
    assert 'serve.execute' not in events
    # of the server's own spans only the leaves: the request before its
    # execution, not the execution, not the wait for a slot
    assert [n for n in events if n.startswith('serve.')] == \
        ['serve.resolve']
    assert len(doc['trace_ids']) == 2      # the scan, the build
    assert events['scan.parse'] == doc['trace_ids']
    # the index write's two were `timed_stage` until PR 42 and never
    # reached the profiler; the same names, leaves now
    for stage in ('index_build.prepare', 'index_build.commit',
                  'scan.init', 'scan.finish', 'reply.frame'):
        assert stage in events, sorted(events)
    assert events['index_build.prepare'] == events['index_build.commit']
    assert len(events['index_build.commit']) == 1


# -- (4) what the benchmark's reducer makes of an annotation ----------------

def test_reducer_names_a_gap_by_the_enclosing_annotation():
    # by path: `benchmarks/` is no package, and nothing of it belongs
    # on this process's sys.path
    spec = importlib.util.spec_from_file_location(
        'bench_trace_reduce',
        os.path.join(REPO_ROOT, 'benchmarks', 'trace', 'reduce.py'))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    reduce_events = mod.reduce_events
    ms = 1000000
    doc = {'planes': [
        {'name': '/device:TPU:0', 'lines': [
            {'name': 'XLA Ops', 'events': [
                ['fusion.1', 0, 2 * ms], ['fusion.1', 80 * ms, 2 * ms]]}]},
        {'name': '/host:CPU', 'lines': [
            {'name': 'dn-serve-job', 'events': [
                ['scan.dispatch', 0, 1 * ms],
                ['scan.fetch', 3 * ms, 76 * ms],
                ['np.asarray(jax.Array)', 4 * ms, 74 * ms]]}]}]}
    gaps = reduce_events(doc)['breakdown']['idle_gaps']
    assert gaps[0][0] == 'scan.fetch (dn-serve-job)'
    assert gaps[0][1] == pytest.approx(0.078)


# -- (5) the compile counters -------------------------------------------------

def _xla_compiles():
    return stage_table()[1].get('xla_compiles_total', 0)


def test_xla_compiles_total_counts_real_compiles_once():
    from dragnet_tpu.ops import get_jax
    jax, jnp = get_jax()                   # registers the listeners

    @jax.jit
    def fresh(x):
        return (x * 3 + 1).sum()
    x = jnp.arange(1237, dtype=jnp.int32)  # its own input: made before
    x.block_until_ready()
    # a server of this module may still be compiling its pre-warm
    # ladder (six programs, to 2^20 rows) on its background thread:
    # its compiles are not this test's
    for t in threading.enumerate():
        if t.name == 'dn-prewarm':
            t.join()
    c0 = _xla_compiles()
    fresh(x).block_until_ready()
    c1 = _xla_compiles()
    fresh(x).block_until_ready()
    c2 = _xla_compiles()
    assert (c1 - c0, c2 - c1) == (1, 0)
    stages = {n: m for n, _lb, m in
              obs_metrics.global_registry().snapshot()}
    assert stages['xla_compile_ms'].total >= 1


CACHED = r'''
import json, sys
sys.path.insert(0, %(root)r)
from dragnet_tpu.ops import get_jax
from dragnet_tpu.obs import metrics as obs_metrics
jax, jnp = get_jax()
# write every program, however quick its compile
jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)
x = jnp.arange(1237, dtype=jnp.int32)
x.block_until_ready()
def count():
    c = {n: m.value for n, _lb, m in
         obs_metrics.global_registry().snapshot()
         if m.kind == obs_metrics.COUNTER}
    return c['xla_compiles_total'], c['xla_cache_loads_total']
c0 = count()
jax.jit(lambda v: (v * 5 + 2).sum())(x).block_until_ready()
c1 = count()
print(json.dumps({'compiles': c1[0] - c0[0], 'loads': c1[1] - c0[1]}))
'''


def test_xla_cache_loads_total_counts_the_persistent_cache(tmp_path):
    """The same program in two processes that share a cache directory:
    the first compiles it, the second loads it and compiles nothing."""
    env = {'JAX_COMPILATION_CACHE_DIR': str(tmp_path / 'xla')}
    text = CACHED % {'root': REPO_ROOT}
    assert _run_script(text, env=env) == {'compiles': 1, 'loads': 0}
    assert _run_script(text, env=env) == {'compiles': 0, 'loads': 1}


# -- (6) a host-engine process stays off jax --------------------------------

HOST_SCAN = r'''
import json, os, sys
sys.path.insert(0, %(root)r)
sys.path.insert(0, os.path.join(%(root)r, 'tests'))
import test_stage_spans as t
t.add_datasource(sys.argv[1])
rc, out, err = t.run_cli(t.SCAN_ARGS + ['stageds'])
assert rc == 0, err
stages, counters = t.stage_table()
print(json.dumps({'jax': 'jax' in sys.modules, 'stages': sorted(stages),
                  'records': counters['scan_parse_records']}))
'''


def test_host_engine_scan_never_imports_jax(tmp_path):
    doc = _run_script(HOST_SCAN % {'root': REPO_ROOT}, str(tmp_path),
                      env={'DN_ENGINE': 'vector'})
    assert doc['jax'] is False
    assert {'scan.read', 'scan.parse', 'scan.stage'} <= set(doc['stages'])
    assert doc['records'] == NRECORDS


# -- (7) outputs, byte for byte -----------------------------------------------

# sha256 of stdout + stderr at the parent commit (d23e433, PR 25), same
# corpus, same commands, DN_ENGINE=jax and DN_INDEX_DEVICE=1
GOLDEN = {
    'scan': '64082b5a79301d496a6e08a94e015917a06340538e67d88a'
            '6451279b88d94335',
    'query': 'e4c67f078f75fb2d76da1eeba74702098681f5c3fb9e728e'
             'f96e4ef5b2c2348d',
}


def _digest(run):
    return hashlib.sha256(run['out'] + run['err']).hexdigest()


@pytest.mark.parametrize('op', sorted(GOLDEN))
def test_outputs_are_byte_for_byte_the_parents(runs, op):
    """`--counters` dumps and answers with every stage, span and
    annotation live: what the parent commit wrote."""
    assert _digest(runs[op]) == GOLDEN[op]


def test_points_equal_the_host_engines(runs, tmp_path, monkeypatch):
    monkeypatch.setenv('DRAGNET_CONFIG', '')
    add_datasource(str(tmp_path))
    outs = {}
    for engine in ('jax', 'vector'):
        monkeypatch.setenv('DN_ENGINE', engine)
        rc, out, err = run_cli(SCAN_ARGS + ['--points', 'stageds'])
        assert rc == 0, err
        outs[engine] = out
    assert outs['jax'] == outs['vector'] and outs['jax']


# -- (2c) a routed query: the router's leaves and the member's export ------

ROUTED_LEAVES = ('router.scatter', 'router.merge')
EXPORT = 'index_query_stack.export'
NPARTITIONS = 2


def counter_table():
    """{(name, labels): value} of the global registry's counters."""
    return {(name, labels): m.value
            for name, labels, m in obs_metrics.global_registry().snapshot()
            if m.kind == obs_metrics.COUNTER}


@pytest.fixture(scope='module')
def routed(tmp_path_factory):
    """{'stages', 'hists', 'counters', 'out'} of one index query through
    a two-member cluster of this process (member a routes: one
    partition is its own, one is b's), the device lane forced; the
    second of its kind, as in `served`."""
    from dragnet_tpu.serve import client as mod_client
    from dragnet_tpu.serve import topology as mod_topology
    root = str(tmp_path_factory.mktemp('stage_routed'))
    got = {}
    with pytest.MonkeyPatch.context() as mp:
        for k, v in (('DN_INDEX_DEVICE', '1'), ('DRAGNET_CONFIG', ''),
                     ('DN_ROUTER_PROBE_MS', '60000')):
            mp.setenv(k, v)
        for k in ('DN_TRACE', 'DN_SLOW_MS', 'DN_SERVE_CACHE_MB',
                  'DN_ENGINE'):
            mp.delenv(k, raising=False)
        add_datasource(root)
        rc, out, err = run_cli(['build', 'stageds'])
        assert rc == 0, err
        socks = {m: os.path.join(root, m + '.sock') for m in 'ab'}
        topo_path = os.path.join(root, 'topo.json')
        with open(topo_path, 'w') as f:
            json.dump({'epoch': 1, 'assign': 'time-range',
                       'members': {m: {'endpoint': socks[m]}
                                   for m in socks},
                       'partitions': [
                           {'id': 0, 'replicas': ['a'],
                            'after': '2014-01-01', 'before': '2014-01-02'},
                           {'id': 1, 'replicas': ['b'],
                            'after': '2014-01-02', 'before': '2014-01-05'},
                       ]}, f)
        servers = [mod_server.DnServer(
            socket_path=socks[m],
            conf={'max_inflight': 2, 'queue_depth': 4, 'deadline_ms': 0,
                  'coalesce': True, 'drain_s': 10},
            cluster=mod_topology.load_topology(topo_path, member=m),
            member=m).start() for m in 'ab']
        req = {'ds': 'stageds', 'config': os.environ['DRAGNET_CONFIG'],
               'op': 'query', 'interval': 'day', 'opts': {'points': True},
               'queryconfig': {'breakdowns': [
                   {'name': 'host', 'field': 'host'}]}}
        try:
            for attempt in (0, 1):
                obs_metrics.reset_global_registry()
                rc, _, out, err = mod_client.request_bytes(socks['a'], req)
                assert rc == 0, err
                limit = time.monotonic() + 10.0
                while time.monotonic() < limit:
                    stages, hists = stage_table()[0], hist_table()
                    if stages.get('reply.frame', (0,))[0] == 2 and \
                            hists.get(('serve_reply_drain_ms', ()),
                                      (0,))[0] == 2:
                        break
                    time.sleep(0.01)
            got.update(stages=stages, hists=hists, out=out,
                       counters=counter_table())
        finally:
            for srv in servers:
                srv.stop()
        rc, got['cli_query_out'], err = run_cli(
            ['query', '-b', 'host', '--points', 'stageds'])
        assert rc == 0, err
    return got


@pytest.mark.parametrize('stage,count', [
    ('router.scatter', 1), ('router.merge', 1), (EXPORT, NPARTITIONS),
    ('index_query.paths', NPARTITIONS), ('index_query.prune', NPARTITIONS),
    ('index_query_stack.load', NPARTITIONS), ('index_fold.stage',
                                              NPARTITIONS)])
def test_a_routed_query_meets_the_routers_leaves(routed, stage, count):
    """The router's two leaves once a routed query; the member's export
    and the stack's and the fold's leaves once a partial."""
    assert routed['stages'].get(stage, (0, 0.0))[0] == count


@pytest.mark.parametrize('series', ['serve_op_latency_ms',
                                    'serve_leaf_ms'])
def test_a_partial_is_accounted_under_its_own_op(routed, series):
    """One observation a partial under `op="query_partial"`, the one
    that ran in the router's process and the one that came by the
    socket alike; one under `op="query"` for the routed query."""
    n, ms = routed['hists'][(series, (('op', 'query_partial'),))]
    assert n == NPARTITIONS and ms > 0
    assert routed['hists'][(series, (('op', 'query'),))][0] == 1


def test_a_routed_querys_leaf_ms_is_its_own_threads(routed):
    """`serve_leaf_ms{op="query"}`: the resolution, the wait for the
    partials, the merge and the reply's two, and none of the partials'
    leaves (they ran on other threads)."""
    own = sum(routed['stages'][s][1] for s in
              ('router.scatter', 'router.merge') + REPLY_STAGES)
    n, leaf_ms = routed['hists'][('serve_leaf_ms', (('op', 'query'),))]
    resolve = routed['stages']['serve.resolve'][1]
    assert own <= leaf_ms <= own + resolve + 0.01
    _, latency = routed['hists'][('serve_op_latency_ms',
                                  (('op', 'query'),))]
    assert leaf_ms <= latency


@pytest.mark.parametrize('name,labels,least', [
    ('router_partial_items_total', (), 5),
    ('router_partial_bytes_total', (), 40),
    ('cluster_partials_total', (('lane', 'device'),), NPARTITIONS)])
def test_a_routed_query_counts_what_its_partials_carried(routed, name,
                                                         labels, least):
    """Five hosts over three days, two partitions: each partition's
    partial lists its hosts once (at most ten key items where the
    per-shard wire carried fifteen), b's came over the socket, and
    both took the device lane."""
    value = routed['counters'][(name, labels)]
    assert value >= least
    if name == 'router_partial_items_total':
        assert value <= 5 * NPARTITIONS
    if name == 'cluster_partials_total':
        assert value == NPARTITIONS
        assert not [k for k in routed['counters']
                    if k[0] == name and k != (name, labels)]


def test_a_routed_query_answers_as_the_cli_does(routed):
    assert routed['out'] and routed['out'] == routed['cli_query_out']
