"""A rollup plan's units through the stacked batch and the device fold
(datasource_file.query -> index_query_stack.run_index_query ->
run_stacked -> _load_units).

The tree: hourly shards of the benchmark's three metrics over 34 days
(2014-01-29 to 2014-03-04: a few days either side of one whole
month), day and month rollups on top.  The corpus is seeded numpy
columns in the shape of the benchmark generator's, written out as
JSON, so that the plain reference (benchmarks/reference/groupby.py,
loaded by path) answers over the same records.

The contracts:

* BYTE IDENTITY, three ways: the rollup-planned stacked reply and its
  fan-in counters equal the fine walk's (the same tree before it had
  rollups) and rollup.execute_plan's (DN_IQ_STACK=0, the per-shard
  lane a plan keeps for what the stack refuses), with windows on and
  off the rollups' edges.
* A FORCED LANE ENGAGES: under DN_INDEX_DEVICE=1 such a query is
  summed by the device fold (`index device sums` grows beside `index
  shards via rollup`).
* THE REFERENCE AGREES: replies equal expected_lines(..., part='day').
* A STALE ROLLUP FALLS BACK: a fine shard rewritten after the rollup
  was built takes its bucket back to the fine shards, inside the same
  stacked batch, and the answer does not change.
"""

import importlib.util
import io
import json
import os
import shutil
import sys

import numpy as np
import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

from dragnet_tpu import device_index as mod_di             # noqa: E402
from dragnet_tpu import index_query_mt as mod_iqmt         # noqa: E402
from dragnet_tpu import index_query_stack as mod_iqs       # noqa: E402
from dragnet_tpu import query as mod_query                 # noqa: E402
from dragnet_tpu import rollup as mod_rollup               # noqa: E402
from dragnet_tpu.datasource_file import DatasourceFile     # noqa: E402
from dragnet_tpu.errors import DNError                     # noqa: E402
from dragnet_tpu.obs import metrics as obs_metrics         # noqa: E402
from dragnet_tpu.serve import residency                    # noqa: E402

HOSTS = ['ralph', 'janey', 'kearney', 'sherri', 'wendell']
METHODS = ['HEAD', 'GET', 'PUT', 'DELETE']
OPERATIONS = ['headstorage', 'headpublicstorage', 'getjoberrors',
              'getpublicstorage', 'getstorage', 'putdirectory',
              'putpublicobject', 'putobject', 'deletestorage',
              'deletepublicstorage']
STATUS = [200, 204, 400, 404, 500, 503, 507]

DAY_MS = 86400000
T0_MS = 1390953600000           # 2014-01-29T00:00:00Z
NDAYS = 34                      # ... to 2014-03-04: all of February
NRECORDS = 20000
NHOURS = NDAYS * 24


def _day(n):
    """Epoch ms of the corpus's day `n`."""
    return T0_MS + n * DAY_MS


def _columns(seed=46):
    """The generator's columns (benchmarks/gen/corpus.COLUMNS, the
    ones the reference reads), seeded; timestamps rise linearly."""
    rng = np.random.RandomState(seed)
    n = NRECORDS
    return {
        'host': rng.randint(0, len(HOSTS), n).astype(np.uint8),
        'method': rng.randint(0, len(METHODS), n).astype(np.uint8),
        'op': rng.randint(0, len(OPERATIONS), n).astype(np.uint8),
        'status': np.asarray(STATUS, dtype=np.int16)[
            rng.randint(0, len(STATUS), n)],
        'latency': rng.randint(1, 4096, n).astype(np.int32),
        'ts_ms': T0_MS + (np.arange(n, dtype=np.int64)
                          * (NDAYS * DAY_MS)) // n,
    }


def _write_corpus(cols, path):
    from datetime import datetime, timezone
    with open(path, 'w') as f:
        for i in range(len(cols['ts_ms'])):
            ms = int(cols['ts_ms'][i])
            t = datetime.fromtimestamp(ms // 1000, timezone.utc)
            f.write(json.dumps({
                'time': t.strftime('%Y-%m-%dT%H:%M:%S.') + '%03dZ'
                % (ms % 1000),
                'host': HOSTS[cols['host'][i]],
                'req': {'method': METHODS[cols['method'][i]]},
                'operation': OPERATIONS[cols['op'][i]],
                'res': {'statusCode': int(cols['status'][i])},
                'latency': int(cols['latency'][i]),
            }, separators=(',', ':')) + '\n')


def _ts():
    return {'name': 'timestamp', 'field': 'time', 'date': '',
            'aggr': 'lquantize', 'step': 3600}


# benchmarks/configs/muskie-90d-hourly.json's three
METRICS = [
    {'name': 'm1', 'breakdowns': [
        _ts(), {'name': 'host', 'field': 'host'},
        {'name': 'req.method', 'field': 'req.method'},
        {'name': 'operation', 'field': 'operation'},
        {'name': 'latency', 'field': 'latency', 'aggr': 'quantize'}]},
    {'name': 'm2', 'breakdowns': [
        _ts(), {'name': 'host', 'field': 'host'},
        {'name': 'res.statusCode', 'field': 'res.statusCode'}]},
    {'name': 'm3', 'filter': {'ne': ['res.statusCode', 500]},
     'breakdowns': [
        _ts(), {'name': 'operation', 'field': 'operation'},
        {'name': 'latency', 'field': 'latency', 'aggr': 'lquantize',
         'step': 100}]},
]

NE500 = {'ne': ['res.statusCode', 500]}
# the cell's four templates (benchmarks/workloads/
# muskie-90d-hourly.query-rollup.json), then a bare SUM and an hourly
# timestamp breakdown (which the reference does not answer)
QUERIES = [
    ('m1', {'breakdowns': [
        {'name': 'host'}, {'name': 'req.method'}, {'name': 'operation'},
        {'name': 'latency', 'aggr': 'quantize'}]}),
    ('m2', {'breakdowns': [{'name': 'host'},
                           {'name': 'res.statusCode'}],
            'filter': NE500}),
    ('m3', {'breakdowns': [
        {'name': 'operation'},
        {'name': 'latency', 'aggr': 'lquantize', 'step': 100}],
        'filter': NE500}),
    ('m1-host-latency-get', {'breakdowns': [
        {'name': 'host'}, {'name': 'latency', 'aggr': 'quantize'}],
        'filter': {'eq': ['req.method', 'GET']}}),
    ('bare', {}),
    ('hourly', {'breakdowns': [
        {'name': 'timestamp', 'aggr': 'lquantize', 'step': 3600},
        {'name': 'host'}]}),
]
TEMPLATES = [n for n, _ in QUERIES[:4]]

# (after day, before day) of the corpus, None for no bounds; whole
# days unless a fraction says otherwise
WINDOWS = [
    ('unbounded', None),
    ('february', (3, 31)),             # the month rollup, exactly
    ('days-month-days', (1, 33)),      # day rollups, the month, days
    ('one-day', (2, 3)),               # one day rollup
    ('week-over-edge', (0, 7)),        # day rollups either side of
                                       # the month's start
    ('mid-day', (1.25, 4.5)),          # fine hours, days, fine hours
]


def _conf(qconf, window):
    conf = json.loads(json.dumps(qconf))
    if window is not None:
        conf['timeAfter'] = int(_day(window[0]))
        conf['timeBefore'] = int(_day(window[1]))
    return conf


def _q(conf):
    q = mod_query.query_load(dict(conf))
    assert not isinstance(q, DNError), q
    return q


# what no lane may move: the visible fan-in counters and the walk's
# hidden ones; what only a plan writes, equal between its two lanes
FAN_IN = ('ninputs', 'noutputs', 'index shards queried',
          'index shards pruned')
PLANNED = ('index shards via rollup', 'rollup shards queried')


def _answer(ds, conf):
    r = ds.query(_q(conf), 'hour')
    counters = {}
    for s in r.pipeline.stages:
        for c, v in s.counters.items():
            if c in FAN_IN + PLANNED + (mod_iqs.DEVICE_SUMS,):
                counters[(s.name, c)] = v
    return r.points, counters


def _only(counters, names):
    return {k: v for k, v in counters.items() if k[1] in names}


@pytest.fixture(autouse=True)
def _lanes(monkeypatch):
    monkeypatch.setenv('DN_IQ_STACK', 'auto')
    monkeypatch.setenv('DN_IQ_THREADS', 'auto')
    monkeypatch.setenv('DN_IQ_STAT_TTL_MS', '0')
    monkeypatch.delenv('DN_ENGINE', raising=False)
    # the host bincount unless a test forces the fold: `auto` would
    # audition the device once an earlier test has probed it
    monkeypatch.setenv('DN_INDEX_DEVICE', '0')
    mod_di._reset_device_state()
    mod_di._reset_engagement()
    residency.deconfigure()
    yield
    mod_iqmt.shard_cache_clear()
    mod_di._reset_device_state()
    mod_di._reset_engagement()


@pytest.fixture(scope='module', params=('dnc', 'sqlite'))
def tree(request, tmp_path_factory):
    """The hourly tree in one index format, every (query, window)
    answered by the fine walk BEFORE any rollup exists, then the day
    and month rollups built."""
    fmt = request.param
    root = tmp_path_factory.mktemp('rollup_stack_' + fmt)
    datafile, idx = str(root / 'data.log'), str(root / 'idx')
    cols = _columns()
    _write_corpus(cols, datafile)
    ds = DatasourceFile({
        'ds_backend': 'file',
        'ds_backend_config': {'path': datafile, 'timeField': 'time',
                              'indexPath': idx},
        'ds_filter': None, 'ds_format': 'json'})
    old = os.environ.get('DN_INDEX_FORMAT')
    os.environ['DN_INDEX_FORMAT'] = fmt
    try:
        ds.build([mod_query.metric_deserialize(dict(m, datasource=None))
                  for m in METRICS], 'hour')
        fine = {(qn, wn): _answer(ds, _conf(qc, w))
                for qn, qc in QUERIES for wn, w in WINDOWS}
        doc = mod_rollup.build_rollups(idx, 'hour')
    finally:
        if old is None:
            del os.environ['DN_INDEX_FORMAT']
        else:
            os.environ['DN_INDEX_FORMAT'] = old
    # 34 days and three months' worth of rollup shards (January's and
    # March's hold the corpus's few days of them)
    assert doc['built'] == NDAYS + 3, doc
    mod_iqmt.shard_cache_clear()
    return {'ds': ds, 'idx': idx, 'cols': cols, 'fine': fine,
            'fmt': fmt}


@pytest.mark.parametrize('wname,window', WINDOWS)
@pytest.mark.parametrize('qname,qconf', QUERIES)
def test_planned_stack_equals_fine_walk_and_execute_plan(
        tree, monkeypatch, qname, qconf, wname, window):
    """(a) one query three ways: the fine walk (no rollups), the plan
    through the stack, the plan through execute_plan."""
    conf = _conf(qconf, window)
    fine_points, fine_counters = tree['fine'][(qname, wname)]
    calls = []
    real = mod_iqs.run_stacked

    def spy(*a, **kw):
        calls.append((kw.get('plan'), real(*a, **kw)))
        return calls[-1][1]
    monkeypatch.setattr(mod_iqs, 'run_stacked', spy)
    points, counters = _answer(tree['ds'], conf)
    # the plan reached the stack, and the stack kept it
    assert len(calls) == 1 and calls[0][1] is True
    assert calls[0][0]['nrollup'] >= 1
    assert points == fine_points
    assert _only(counters, FAN_IN) == _only(fine_counters, FAN_IN)
    assert not _only(fine_counters, PLANNED)

    monkeypatch.setenv('DN_IQ_STACK', '0')
    ex_points, ex_counters = _answer(tree['ds'], conf)
    assert len(calls) == 1
    assert ex_points == points
    assert ex_counters == counters
    via = counters[('Index List', 'index shards via rollup')]
    assert 0 < via <= counters[('Index List', 'index shards queried')]
    if wname in ('unbounded', 'february', 'days-month-days',
                 'one-day', 'week-over-edge'):
        # whole days: every shard of the window is behind a rollup
        assert via == counters[('Index List', 'index shards queried')]


@pytest.mark.parametrize('qname', TEMPLATES + ['bare'])
def test_forced_device_lane_folds_a_planned_query(tree, monkeypatch,
                                                  qname):
    """(b) DN_INDEX_DEVICE=1 over a rollup plan: the device fold sums
    it (never a quiet host answer), with the same bytes."""
    from dragnet_tpu.ops import get_jax
    if get_jax() is None:
        pytest.skip('jax unavailable')
    conf = _conf(dict(QUERIES)[qname], (1, 33))
    host_points, host_counters = _answer(tree['ds'], conf)
    monkeypatch.setenv('DN_INDEX_DEVICE', '1')
    points, counters = _answer(tree['ds'], conf)
    if mod_di._DEVICE_STATE['ready'] is False:
        pytest.skip('device lane unavailable on this rig')
    assert points == host_points
    assert counters[('Index List', 'index shards via rollup')] > 0
    if qname == 'bare':
        # no tuples to sum: the stack answers without the fold
        assert ('Index List', mod_iqs.DEVICE_SUMS) not in counters
        return
    assert counters[('Index List', mod_iqs.DEVICE_SUMS)] == 1
    assert mod_di.stats_doc()['last_lane'] == 'device'
    # the fold counted the plan's logical shards, not its files
    assert mod_di.stats_doc()['shards'] == \
        counters[('Index List', 'index shards queried')]
    counters.pop(('Index List', mod_iqs.DEVICE_SUMS))
    assert counters == host_counters


@pytest.fixture(scope='module')
def reference():
    # by path: `benchmarks/` is no package
    spec = importlib.util.spec_from_file_location(
        'bench_reference_groupby',
        os.path.join(REPO_ROOT, 'benchmarks', 'reference', 'groupby.py'))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize('wname,window',
                         [w for w in WINDOWS if w[0] != 'mid-day'])
@pytest.mark.parametrize('qname', TEMPLATES)
def test_planned_reply_equals_the_plain_reference(
        tree, reference, qname, wname, window):
    """(c) the cell's comparison at a small size: the `--points` lines
    of a rollup-planned reply against the plain reference's, whole-day
    bounds."""
    from dragnet_tpu import output as mod_output
    conf = _conf(dict(QUERIES)[qname],
                 window if window is not None else (0, NDAYS))
    ref = reference.Reference(
        tree['cols'], {'host': HOSTS, 'method': METHODS,
                       'op': OPERATIONS})
    doc = {'breakdowns': [dict(b, field=b['name'])
                          for b in conf['breakdowns']],
           'filter': conf.get('filter'),
           'timeAfter': conf['timeAfter'],
           'timeBefore': conf['timeBefore']}
    expected = ref.expected_lines(doc, part='day')
    r = tree['ds'].query(_q(conf), 'hour')
    out = io.StringIO()
    mod_output.print_points(r.block if r.block is not None
                            else r.points, out)
    assert expected
    assert reference.compare(out.getvalue().encode(), expected) == (0, 0)


def test_a_stale_rollup_falls_back_inside_the_stack(tree, tmp_path,
                                                    monkeypatch):
    """(d) one fine shard rewritten after its rollups were built: its
    day and its month go back to fine shards and day rollups, the
    rest of the window stays on rollups, all in one stacked batch,
    and the bytes hold."""
    idx = str(tmp_path / 'idx')
    shutil.copytree(tree['idx'], idx, symlinks=True)
    # copytree keeps mtimes (copy2), so the manifests still vouch
    ds = DatasourceFile({
        'ds_backend': 'file',
        'ds_backend_config': {'path': '/nonexistent', 'timeField':
                              'time', 'indexPath': idx},
        'ds_filter': None, 'ds_format': 'json'})
    qname, wname = 'm1', 'unbounded'
    conf = _conf(dict(QUERIES)[qname], None)
    fine_points, fine_counters = tree['fine'][(qname, wname)]
    points, counters = _answer(ds, conf)
    assert points == fine_points
    assert counters[('Index List', 'index shards via rollup')] == NHOURS

    finedir = os.path.join(idx, 'by_hour')
    victim = os.path.join(finedir, '2014-02-10-07.sqlite')
    assert os.path.exists(victim)
    st = os.stat(victim)
    os.utime(victim, ns=(st.st_atime_ns, st.st_mtime_ns + 1000000))
    mod_iqmt.shard_cache_clear()
    plans = []
    real = mod_iqs.run_stacked

    def spy(*a, **kw):
        plans.append(kw.get('plan'))
        return real(*a, **kw)
    monkeypatch.setattr(mod_iqs, 'run_stacked', spy)
    leaves = {}

    real_leaf = obs_metrics.leaf_stage

    class Leaf(real_leaf):
        __slots__ = ()

        def __init__(self, name, **attrs):
            leaves[name] = attrs
            real_leaf.__init__(self, name, **attrs)
    monkeypatch.setattr(obs_metrics, 'leaf_stage', Leaf)
    points, counters = _answer(ds, conf)
    assert points == fine_points
    assert _only(counters, FAN_IN) == _only(fine_counters, FAN_IN)
    # the planner under its own leaf, the load's span with the units
    assert 'index_query.plan' in leaves
    assert leaves['index_query_stack.load'] == {
        'nshards': NHOURS, 'nrollup': 29, 'nfine': 24}
    # the stale day's 24 hours are read fine; February is read from
    # its 27 other day rollups, January and March from their months
    assert counters[('Index List', 'index shards via rollup')] == \
        NHOURS - 24
    assert counters[('Index List', 'rollup shards queried')] == 2 + 27
    kinds = [u[0] for u in plans[0]['units']]
    assert kinds.count('single') == 24 and kinds.count('rollup') == 29


def test_a_rollup_shard_without_integer_buckets_keeps_execute_plan(
        tree, monkeypatch):
    """What the stack refuses stays execute_plan's: a rollup block
    whose `__dn_ts` column is no integer column hands the whole plan
    back, and the answer is the same."""
    conf = _conf(dict(QUERIES)['m2'], (3, 31))
    points, counters = _answer(tree['ds'], conf)
    real = mod_iqs._split_rollup_blocks
    refused = []

    def refuse(sh, *a):
        nrows, cols, values, isint = sh
        refused.append(cols[0][0])
        return real((nrows, [('dict',) + tuple(cols[0][1:])]
                     + list(cols[1:]), values, isint), *a)
    monkeypatch.setattr(mod_iqs, '_split_rollup_blocks', refuse)
    seen = []
    real_exec = mod_rollup.execute_plan

    def spy(plan, *a, **kw):
        seen.append(plan['nrollup'])
        return real_exec(plan, *a, **kw)
    monkeypatch.setattr(mod_rollup, 'execute_plan', spy)
    again, counters2 = _answer(tree['ds'], conf)
    assert refused and seen == [1]
    assert again == points and counters2 == counters


# -- group units: a base and its follow generations -------------------------

GROUP_QUERIES = [
    {},
    {'breakdowns': [{'name': 'host'}]},
    {'filter': {'eq': ['operation', 'get']},
     'breakdowns': [{'name': 'host'},
                    {'name': 'latency', 'aggr': 'quantize'}]},
    {'breakdowns': [{'name': 'host'}],
     'timeAfter': '2014-01-01T12:00:00',
     'timeBefore': '2014-01-03T06:00:00'},
]


@pytest.fixture
def generations(tmp_path, monkeypatch, request):
    """test_rollup's tree of `dn follow --append` generations (a base
    and two mini-generations a touched day), one format a case, beside
    a from-scratch build of the same records."""
    import test_follow as tf
    import test_rollup as tr
    fmt = request.param
    monkeypatch.setenv('DN_INDEX_FORMAT', fmt)
    ctx = tf._corpus(tmp_path, monkeypatch, n=200)
    assert tf._follow_once(fmt, env={'DN_FOLLOW_APPEND': '1'})[0] == 0
    n = 200
    for _ in range(2):
        tf._gen(ctx['datafile'], 40, start=n)
        n += 40
        assert tf._follow_once(
            fmt, env={'DN_FOLLOW_APPEND': '1'})[0] == 0
    ctx['n'] = n
    assert mod_rollup.compaction_backlog(ctx['idx'][fmt], 'day') > 0
    tf._rebuild_ref(ctx, fmt)
    return tr._ds_for('f_' + fmt), tr._ds_for('r_' + fmt)


def _day_answer(ds, conf):
    r = ds.query(_q(conf), 'day')
    return r.points, {(s.name, c): v for s in r.pipeline.stages
                      for c, v in s.counters.items()
                      if c in FAN_IN + PLANNED}


@pytest.mark.parametrize('conf', GROUP_QUERIES)
@pytest.mark.parametrize('generations', ('dnc', 'sqlite'), indirect=True)
def test_a_generation_group_shares_one_shard_id(generations, monkeypatch,
                                                conf):
    """`group` units through the stack: a base and its generations
    under one shard id give the compacted shard's batch, so the reply
    and the fan-in counters equal execute_plan's (DN_IQ_STACK=0) and
    a from-scratch build's."""
    followed, rebuilt = generations
    calls = []
    real = mod_iqs.run_stacked

    def spy(*a, **kw):
        calls.append((kw.get('plan'), real(*a, **kw)))
        return calls[-1][1]
    monkeypatch.setattr(mod_iqs, 'run_stacked', spy)
    points, counters = _day_answer(followed, conf)
    plan, kept = calls[0]
    assert kept is True
    assert any(u[0] == 'group' for u in plan['units'])
    ref_points, ref_counters = _day_answer(rebuilt, conf)
    assert calls[1][0] is None          # plain shards: no plan
    assert points == ref_points
    # the walk counts files (a generation is one); the fan-in counts
    # logical shards' key items, as over the from-scratch tree
    fan_in = lambda c: {k: v for k, v in c.items()
                        if k[0].startswith('Index ') and
                        k[1] in ('ninputs', 'noutputs')}
    assert fan_in(counters) and fan_in(counters) == fan_in(ref_counters)
    monkeypatch.setenv('DN_IQ_STACK', '0')
    assert _day_answer(followed, conf) == (points, counters)
    assert len(calls) == 2


@pytest.mark.parametrize('generations', ('dnc',), indirect=True)
def test_a_group_of_two_kinds_keeps_execute_plan(generations,
                                                 monkeypatch):
    """A generation that stores a breakdown in another kind than its
    base (their sort keys share no scale): the stack hands the plan
    back and execute_plan answers."""
    followed, rebuilt = generations
    conf = GROUP_QUERIES[1]
    want = _day_answer(rebuilt, conf)[0]
    real = mod_iqmt._load_shard_blocks_cached

    def other_kind(path, query, memo):
        nrows, cols, values, isint = real(path, query, memo)
        if mod_rollup.split_generation(path)[1] is not None:
            cols = [('obj', [None] * nrows)] + list(cols[1:])
        return nrows, cols, values, isint
    monkeypatch.setattr(mod_iqmt, '_load_shard_blocks_cached',
                        other_kind)
    ran = []
    real_exec = mod_rollup.execute_plan

    def spy(plan, *a, **kw):
        ran.append(plan)
        return real_exec(plan, *a, **kw)
    monkeypatch.setattr(mod_rollup, 'execute_plan', spy)
    assert _day_answer(followed, conf)[0] == want
    assert len(ran) == 1
