"""The live cell's own tests (CPU; `python -m pytest benchmarks/tests -q`):
`muskie-365d-index-live.query-publish`, its driver `drivers/serve_live.py`
and its five metric files (PR 50).

* the publisher's schedule for `seconds` 30 and 12, and the day and the
  bounds of publish k;
* each metric file reads a number from the recorded scrape pair of the
  change, nothing (and raises nothing) from the same pair without the
  series PR 50 adds, which is what the program before it exposes, and
  nothing from nothing; the arithmetic of the five;
* the rule that keeps the publisher's queries out of
  `query_completed_per_s`, and its builds out of a rate over `query`;
* the cell's files against the daily cell's and ISSUE 50's traffic;
* one 20,000-record rehearsal of the cell through `serve_live` on
  XLA:CPU, traced; one with the lock's scope of the program before
  PR 50 put back (every answer still right: what differs is a time);
  one with the stale cached answer served after a build (`correct`
  false by the read-back); one whose requests outlast `timeout_s`
  (the run ends in set-up).

The scrapes are `data/live_publish_scrapes.json`: a 20,000-record CPU
rehearsal, so their numbers stand for nothing but their names.
"""

import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, HERE)

from loader import load_module                            # noqa: E402
import test_benchmark as tb                               # noqa: E402
from test_benchmark import throwaway                      # noqa: E402,F401

run = load_module('.', 'run')
serve_live = load_module('drivers', 'serve_live')

CELL = 'muskie-365d-index-live.query-publish'
DAILY = 'muskie-365d-index.query-windows'
NEW_METRICS = ('tree_lock_wait_ms.query', 'tree_lock_held_ms.build',
               'publish_latency_ms.build', 'cache_retired_per_publish.query',
               'walk_snapshot_rebuilds_per_publish.query')
# series the program before PR 50 does not expose
NEW_SERIES = ('dn_serve_tree_lock_', 'dn_index_publish',
              'dn_serve_result_cache_retired_total',
              'dn_device_residency_retired_total',
              'dn_index_shard_handles_retired_total',
              'dn_stage_ms_sum{stage="serve.tree_lock"}',
              'dn_stage_ms_count{stage="serve.tree_lock"}')
# what reads one of them, and so reads nothing there
NEW_IN_THE_PROGRAM = tuple(m for m in NEW_METRICS
                           if m != 'publish_latency_ms.build')
DAY_MS = 86400000


def _recorded():
    with open(os.path.join(HERE, 'data', 'live_publish_scrapes.json')) as f:
        return json.load(f)['change']


def _outcome(op):
    return types.SimpleNamespace(
        ok=True, err=None, latency_s=0.05,
        req=types.SimpleNamespace(template={'op': op, 'name': op},
                                  due_s=None))


def _reading(before, after, done, stats_before=None, stats_after=None):
    ctx = types.SimpleNamespace(
        config={'corpus': {'records': 20000, 'days': 370}},
        workload={'name': CELL}, say=lambda msg: None)
    res = {'prom_before': before, 'prom_after': after,
           'stats_before': stats_before or {},
           'stats_after': stats_after or {}, 'window_stderr': '',
           'outcomes': [_outcome(op) for op, n in sorted(done.items())
                        for _ in range(n)],
           'window_s': 6.0,
           'device': {'kind': 'cpu', 'platform': 'cpu', 'count': 1}}
    return run.Reading(ctx, res, None)


def _change():
    rec = _recorded()
    return _reading(rec['before'], rec['after'], rec['done'])


def _before_pr50():
    rec = _recorded()
    strip = lambda text: ''.join(
        ln + '\n' for ln in text.splitlines()
        if not ln.startswith(NEW_SERIES))
    return _reading(strip(rec['before']), strip(rec['after']), rec['done'])


# -- the publisher's plan ---------------------------------------------------

def test_publish_schedule():
    assert serve_live.publish_due(30, 4) == [3.75, 11.25, 18.75, 26.25]
    assert serve_live.publish_due(12, 4) == [1.5, 4.5, 7.5, 10.5]
    # ISSUE 50's six a window
    assert serve_live.publish_due(30, 6) == [2.5, 7.5, 12.5, 17.5, 22.5,
                                             27.5]
    assert serve_live.publish_due(12, 6) == [1.0, 3.0, 5.0, 7.0, 9.0, 11.0]
    assert serve_live.publish_due(8, 0) == []       # the ramp-up


@pytest.mark.parametrize('k', range(4))
def test_day_and_bounds_of_publish_k(k):
    """Publish k of the window (four of them) writes day 366 + k (the
    standing tree is days 0 to 364, the warm-up wrote 365), [00:00,
    24:00) UTC."""
    cfg = tb._load('configs', 'muskie-365d-index-live')
    day = serve_live.publish_day(cfg, k)
    assert day == 366 + k < cfg['corpus']['days']
    after, before = serve_live.day_bounds_ms(cfg, day)
    assert after == cfg['corpus']['mindate_ms'] + day * DAY_MS
    assert before - after == DAY_MS and after % DAY_MS == 0
    assert serve_live.iso_day(after) == '2015-01-%02d' % (2 + k)


def test_the_standing_tree_ends_where_the_publishes_begin():
    cfg = tb._load('configs', 'muskie-365d-index-live')
    after, _ = serve_live.day_bounds_ms(cfg, 0)
    before, _ = serve_live.day_bounds_ms(cfg, cfg['corpus']['standing_days'])
    assert (serve_live.iso_day(after), serve_live.iso_day(before)) == \
        ('2014-01-01', '2015-01-01')


# -- the five metric files --------------------------------------------------

@pytest.mark.parametrize('metric', NEW_METRICS)
def test_metric_reads_a_number(metric):
    mod = load_module('metrics', metric)
    value = mod.read(_change())
    assert isinstance(value, float) and value > 0.0
    assert mod.META['moves'] == 'query_completed_per_s'
    assert set(mod.META) == {'layer', 'source', 'unit', 'better', 'moves'}
    assert mod.META['better'] == 'lower'


@pytest.mark.parametrize('metric', NEW_METRICS)
def test_metric_on_the_program_before_pr50(metric):
    """The same files over a program that has no such series: the four
    that read one read nothing and raise nothing; a build's latency at
    the server was observed before too."""
    value = load_module('metrics', metric).read(_before_pr50())
    if metric in NEW_IN_THE_PROGRAM:
        assert value is None
    else:
        assert value > 0


@pytest.mark.parametrize('metric', NEW_METRICS)
def test_metric_reads_nothing_from_nothing(metric):
    assert load_module('metrics', metric).read(
        _reading('', '', {'query': 3})) is None


def test_the_recorded_window():
    """Six publishes of one shard each between the two scrapes (the
    recording was made with six a window); the
    lock was held for the commits, a small part of the builds."""
    r = _change()
    assert r.delta('index_publishes_total') == 6
    assert r.delta('index_publish_shards_total') == 6
    assert r.delta('serve_tree_lock_held_ms_count', side='write') == 6
    held = load_module('metrics', 'tree_lock_held_ms.build').read(r)
    build = load_module('metrics', 'publish_latency_ms.build').read(r)
    assert held < build / 10


def test_the_five_files_arithmetic():
    before = ('dn_index_publishes_total 2\n'
              'dn_serve_tree_lock_wait_ms_sum{side="read"} 100.0\n'
              'dn_serve_tree_lock_held_ms_sum{side="write"} 10.0\n'
              'dn_serve_op_latency_ms_sum{op="build"} 1000.0\n'
              'dn_serve_op_latency_ms_count{op="build"} 2\n'
              'dn_index_walk_snapshot_rebuilds_total{reason="cold"} 1\n'
              'dn_index_walk_snapshot_rebuilds_total{reason="racy"} 10\n'
              'dn_index_shard_handles_retired_total 365\n')
    after = ('dn_index_publishes_total 8\n'
             'dn_serve_tree_lock_wait_ms_sum{side="read"} 400.0\n'
             'dn_serve_tree_lock_wait_ms_sum{side="write"} 900.0\n'
             'dn_serve_tree_lock_held_ms_sum{side="write"} 70.0\n'
             'dn_serve_op_latency_ms_sum{op="build"} 7000.0\n'
             'dn_serve_op_latency_ms_count{op="build"} 8\n'
             'dn_index_walk_snapshot_rebuilds_total{reason="cold"} 1\n'
             'dn_index_walk_snapshot_rebuilds_total{reason="racy"} 40\n'
             'dn_index_walk_snapshot_rebuilds_total{reason="invalidated"} 6\n'
             'dn_index_shard_handles_retired_total 2555\n'
             'dn_serve_result_cache_retired_total 600\n')
    r = _reading(before, after, {'query': 100, 'build': 6})
    read = lambda name: load_module('metrics', name).read(r)
    assert read('tree_lock_wait_ms.query') == 3.0       # 300 ms, 100 queries
    assert read('tree_lock_held_ms.build') == 10.0      # 60 ms, 6 publishes
    assert read('publish_latency_ms.build') == 1000.0
    # 2,190 handles and 600 entries (no pin was ever dropped), 6 publishes
    assert read('cache_retired_per_publish.query') == 465.0
    assert read('walk_snapshot_rebuilds_per_publish.query') == 6.0


# -- what a rate is taken over ----------------------------------------------

def test_the_publishers_queries_are_in_no_rate_of_the_readers():
    """`outcomes` holds the readers' queries and the builds, and
    `completed_per_s` over `op` `query` counts the first alone; the
    publisher's queries (two a publish) are held apart."""
    wl = tb._load('workloads', CELL)
    spec = wl['end_to_end']['query_completed_per_s']
    assert spec == {'stat': 'completed_per_s', 'op': 'query',
                    'unit': 'requests/s'}
    publisher = [_outcome('query') for _ in range(12)]
    res = {'outcomes': [_outcome('query') for _ in range(90)] +
           [_outcome('build') for _ in range(6)],
           'publisher_queries': publisher, 'window_s': 30.0}
    ctx = types.SimpleNamespace(config={'corpus': {'records': 1}})
    assert run.end_to_end(spec, res, ctx, 1.0) == 3.0
    assert not set(map(id, publisher)) & set(map(id, res['outcomes']))
    r = _reading('', '', {'query': 90, 'build': 6})
    assert (len(r.done('query')), len(r.done('build'))) == (90, 6)
    assert 'build_records_per_s' not in wl['end_to_end']


# -- the cell's files -------------------------------------------------------

def test_the_cells_files():
    """The daily cell's traffic letter for letter for the readers, the
    publisher and the checks' inputs as ISSUE 50 gives them; the
    configuration is the daily one's but for what it says differs."""
    ours, theirs = tb._load('workloads', CELL), tb._load('workloads', DAILY)
    for k in ('templates', 'windows', 'end_to_end', 'loop', 'mix_seed',
              'rampup_s', 'clients', 'cycle'):
        assert ours[k] == theirs[k], k
    assert 'prebuilt_index' not in ours      # the driver builds 365 of 370
    assert (ours['driver'], ours['timeout_s'], ours['control']) == \
        ('serve_live', 120, 'bfloat16')
    assert ours['publishes'] == 4 and ours['templates'][0]['name'] == 'm1'
    # two whole publishes inside the trace
    assert ours['trace'] == {'after_s': 1.0, 'seconds': 15.0,
                             'python_tracer': 0}
    assert ours['engagement']['counters'] == ['index device sums',
                                              'nstackedbatches']
    assert [t['name'] for t in ours['verify']] == ['m1', 'm2', 'm3']
    assert set(NEW_METRICS) <= set(ours['per_layer'])
    assert not [m for m in ours['per_layer'] if 'roofline' in m]
    # every accepted `.query` file the daily cell lists
    assert set(ours['per_layer']) - set(NEW_METRICS) == \
        set(theirs['per_layer'])
    cfg, base = (tb._load('configs', c) for c in
                 ('muskie-365d-index-live', 'muskie-365d-index'))
    for k in ('datasource', 'setup_build_environment', 'record_shape',
              'chips', 'metrics', 'index_interval'):
        assert cfg[k] == base[k], k
    assert cfg['reduced'] == ['records', 'publish_cadence']
    assert cfg['guarantees'][1:3] == base['guarantees'][1:]
    assert len(cfg['guarantees']) == 5
    assert dict(cfg['environment'], DN_ENGINE=None) == \
        dict(base['environment'], DN_ENGINE=None)
    assert cfg['environment']['DN_ENGINE'] == 'jax'
    c = cfg['corpus']
    assert (c['days'], c['standing_days'], c['mindate_ms']) == \
        (370, 365, base['corpus']['mindate_ms'])
    # the daily cell's records a day
    assert c['records'] == round(
        base['corpus']['records'] * c['days'] / base['corpus']['days'])
    # the standing tree, the warm-up's day, the window's
    assert c['standing_days'] + 1 + ours['publishes'] == c['days']
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        bench = json.load(f)
    entries = [e for k in ('configs', 'workloads') for e in bench[k]
               if e['name'] in ('muskie-365d-index-live', CELL)]
    assert len(entries) == 2
    for e in entries:
        for k in ('why', 'source'):
            assert 1 <= len(e.get(k, 'x')) <= 200 and \
                e.get(k, 'x').isprintable(), (e['name'], k)
    listed = {m['name']: m for m in bench['per_layer']}
    for name in ours['per_layer']:
        assert CELL in listed[name]['workloads'], name
        assert listed[name]['moves'] == 'query_completed_per_s', name
    for name in NEW_METRICS:
        assert listed[name]['workloads'] == [CELL]
        meta = load_module('metrics', name).META
        assert {k: listed[name][k] for k in meta} == meta
    rate = [m for m in bench['end_to_end']
            if m['name'] == 'query_completed_per_s'][0]
    assert rate['workloads'][-1] == CELL


# -- rehearsals -------------------------------------------------------------

# a batch smaller than BATCH_SIZE is padded from a floor tuned from a
# measured bandwidth; pinned, so that 20,000 records make one program
SMALL = {'DN_DEVICE_BATCH_FLOOR': '65536'}
# run.py's three comparisons, and the steps the window's two are the
# worst of, each printed by its name
CHECKS = {'warmup.mismatched_tuples', 'window.mismatched_tuples',
          'window.count_difference'}
STEPS = {'window.mismatched_tuples', 'window.count_difference',
         'publish.prepublish_tuples', 'publish.readback_mismatched_tuples',
         'publish.readback_count_difference', 'publish.not_built',
         'tree.mismatched_tuples', 'tree.count_difference'}


def _rehearsal(name, trace=0, seconds=6):
    rc, lines = tb._rehearse(name, trace=trace, seconds=seconds)
    assert rc != 0 and lines[-1].startswith('rehearsal '), lines[-5:]
    return json.loads(lines[-1][len('rehearsal '):]), lines


def _steps(lines):
    """{step: value} of the driver's `step <name> = <n> (limit 0)`."""
    steps = {}
    for ln in lines:
        if ln.startswith('step '):
            name, _, value = ln[len('step '):].partition(' = ')
            assert value.endswith(' (limit 0)'), ln
            steps[name] = int(value.split()[0])
    return steps


def test_rehearsal_through_serve_live(throwaway):
    """20,000 records, traced: 365 standing shards, the warm-up's
    publish, four publishes in the window, every step and every check 0
    beside its limit, both engagement counters grown, nothing compiled
    inside the window, every listed metric a CPU can read read."""
    name, _ = tb._small_copy(throwaway, CELL, environment=SMALL)
    doc, lines = _rehearsal(name, trace=1)
    text = '\n'.join(lines)
    assert 'set-up standing tree: 365 daily shards' in text
    assert [ln.split(' due')[0] for ln in lines
            if ln.startswith('publish of day ')] == [
        'publish of day %d' % d for d in range(365, 370)]
    steps = _steps(lines)
    assert set(steps) == STEPS and not any(steps.values()), steps
    assert set(doc['numbers_compared']) == CHECKS
    assert all(c == {'value': 0, 'limit': 0}
               for c in doc['numbers_compared'].values()), lines
    assert doc['failed'] == 0 and doc['attempted'] > 6
    grew = [ln for ln in lines if ln.startswith('engagement: counter')]
    assert len(grew) == 2 and not any(' grew by 0 ' in ln for ln in grew)
    assert [ln for ln in lines if ln.startswith('problem: ')] == [
        'problem: no operation ran on the device in the traced window']
    wl = tb._load('workloads', CELL)
    assert set(wl['per_layer']) - {'device_idle_share.query'} <= \
        set(doc['metrics'])
    for m in ('window_compiles.query', 'xla_compiles.query'):
        assert doc['metrics'][m]['value'] == 0.0
    assert 0 < doc['metrics']['tree_lock_held_ms.build']['value'] < \
        doc['metrics']['publish_latency_ms.build']['value'] / 10


WIDE_LOCK = '''"""The normal launcher with the lock's scope of the program before
PR 50: the write side of a tree's lock around the whole build."""
import contextlib
import sys
sys.path.insert(0, %(root)r)
from dragnet_tpu.serve import admission
admission.TreeLock.building = admission.TreeLock.write
admission.TreeLock.write = lambda self: contextlib.nullcontext()
sys.argv[0] = %(launcher)r
exec(compile(open(%(launcher)r).read(), %(launcher)r, 'exec'))
'''


def test_the_old_locks_scope_is_correct_and_holds_the_build(throwaway):
    """What PR 50 changed is a time, not an answer: with the write side
    around the whole build every check still reads 0, and the lock is
    held for most of a build's latency."""
    launcher = throwaway('tests', 't_wide_lock.py', WIDE_LOCK % {
        'root': ROOT,
        'launcher': os.path.join(BENCH, 'drivers', 'launch_serve.py')})
    name, _ = tb._small_copy(throwaway, CELL, launcher=launcher,
                             environment=SMALL)
    doc, lines = _rehearsal(name, trace=1)
    assert all(c['value'] == 0 for c in doc['numbers_compared'].values())
    assert doc['failed'] == 0
    assert doc['metrics']['tree_lock_held_ms.build']['value'] > \
        doc['metrics']['publish_latency_ms.build']['value'] / 2


# the result cache left deaf to a write: the epoch it stamps and asks
# with never moves, and the tree's stat identities always agree
STALE_CACHE = '''"""The normal launcher with the result cache never retired."""
import sys
sys.path.insert(0, %(root)r)
from dragnet_tpu import index_query_mt
from dragnet_tpu.serve import qcache
index_query_mt.cache_epoch = lambda: 0
qcache._validators_ok = lambda validators: True
sys.argv[0] = %(launcher)r
exec(compile(open(%(launcher)r).read(), %(launcher)r, 'exec'))
'''


def test_a_stale_cached_answer_is_not_correct(throwaway):
    """The guarantee the read-back holds: with the cache kept over a
    publish, the query after the build is the cached empty answer, and
    `correct` is false by the read-back's steps, which the window's
    two comparisons carry."""
    launcher = throwaway('tests', 't_stale_cache.py', STALE_CACHE % {
        'root': ROOT,
        'launcher': os.path.join(BENCH, 'drivers', 'launch_serve.py')})
    name, _ = tb._small_copy(throwaway, CELL, launcher=launcher,
                             environment=SMALL)
    doc, lines = _rehearsal(name)
    assert doc['correct'] is False
    steps = _steps(lines)
    assert steps['publish.readback_mismatched_tuples'] > 0
    assert steps['publish.readback_count_difference'] > 0
    # the tree itself is whole: the stale answers are the cache's
    for step in ('publish.prepublish_tuples', 'publish.not_built',
                 'window.mismatched_tuples', 'window.count_difference'):
        assert steps[step] == 0, step
    got = doc['numbers_compared']
    assert got['window.mismatched_tuples']['value'] >= \
        steps['publish.readback_mismatched_tuples']
    assert got['window.count_difference']['value'] >= \
        steps['publish.readback_count_difference']
    assert any('window.mismatched_tuples' in ln and
               'over its limit' in ln for ln in lines), lines


def test_a_build_that_outlasts_the_timeout_ends_the_run_in_setup(throwaway):
    """Every request has the cell's `timeout_s`, the warm-up's build
    too: a program that cannot answer within it (one that compiles for
    minutes a window of bounds, as the program before PR 50 does on the
    chip) ends its run in set-up, with an error, no result line and the
    server stopped."""
    name, _ = tb._small_copy(throwaway, CELL, environment=SMALL,
                             timeout_s=0.001)
    p = tb._run_cell(name)
    assert p.returncode not in (0, None)
    out = p.stdout.decode()
    assert 'set-up standing tree: 365 daily shards' in out
    assert 'rehearsal ' not in out and '"correct"' not in out
    assert 'warm-up publish failed' in p.stderr.decode() + out
