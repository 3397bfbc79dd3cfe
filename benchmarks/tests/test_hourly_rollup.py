"""The hourly cell's own tests (CPU; `python -m pytest benchmarks/tests -q`):
`muskie-90d-hourly.query-rollup`, its driver `drivers/serve_rollup.py`
and its four metric files (PR 46).

* each metric file reads a number from the recorded scrape pair of the
  change; from the parent's, which plans inside `index_query.prune` and
  never folds a planned query, the two that read the new leaf and the
  fold's rows read nothing (and raise nothing), the two that read the
  planner's own counters read what the planner did there too;
* the arithmetic of the four;
* the driver's count of the rollup shards a corpus's window holds;
* the cell's traffic is the daily cell's templates letter for letter;
* one 20,000-record rehearsal of the cell through `serve_rollup` on
  XLA:CPU; one with an answer altered where the server produces it
  (`correct` false by the comparison); one with the plan kept off the
  stack (`DN_IQ_STACK=0`: rollup.execute_plan answers, every answer
  right, and `correct` false by the engagement check).

The scrapes are `data/hourly_rollup_scrapes.json`: 20,000-record CPU
rehearsals, so their numbers stand for nothing but their names.
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
serve_rollup = load_module('drivers', 'serve_rollup')

CELL = 'muskie-90d-hourly.query-rollup'
NEW_METRICS = ('rollup_coverage_share.query', 'rollup_files_per_query.query',
               'rollup_plan_ms.query', 'index_fold_rows_per_query.query')
# what the program before PR 46 gives no reading for
NEW_IN_THE_PROGRAM = ('rollup_plan_ms.query',
                      'index_fold_rows_per_query.query')


def _scrapes():
    with open(os.path.join(HERE, 'data',
                           'hourly_rollup_scrapes.json')) as f:
        return json.load(f)


def _reading(before, after, done, stats_before=None, stats_after=None):
    outcome = types.SimpleNamespace(
        ok=True, err=None,
        req=types.SimpleNamespace(template={'op': 'query', 'name': 'm1'}))
    ctx = types.SimpleNamespace(
        config={'corpus': {'records': 20000, 'days': 90}},
        workload={'name': CELL}, say=lambda msg: None)
    res = {'prom_before': before, 'prom_after': after,
           'stats_before': stats_before or {},
           'stats_after': stats_after or {}, 'window_stderr': '',
           'outcomes': [outcome] * done, 'window_s': 2.0,
           'device': {'kind': 'cpu', 'platform': 'cpu', 'count': 1}}
    return run.Reading(ctx, res, None)


def _recorded(side):
    rec = _scrapes()[side]
    return _reading(rec['before'], rec['after'], rec['done']['query'],
                    rec['stats_before'], rec['stats_after'])


# -- the four metric files --------------------------------------------------

@pytest.mark.parametrize('metric', NEW_METRICS)
def test_metric_reads_a_number(metric):
    mod = load_module('metrics', metric)
    value = mod.read(_recorded('change'))
    assert isinstance(value, float)
    assert 0.0 < value <= (100.0 if mod.META['unit'] == '%'
                           else float('inf'))
    assert mod.META['moves'] == 'query_completed_per_s'
    assert set(mod.META) == {'layer', 'source', 'unit', 'better', 'moves'}


@pytest.mark.parametrize('metric', NEW_METRICS)
def test_metric_on_the_parents_scrape(metric):
    """The program before PR 46 under the same files: no
    `index_query.plan` leaf and no fold of a planned query, so those
    two files read nothing; the planner's counters were there already
    (every whole-day window is behind rollups on either side)."""
    value = load_module('metrics', metric).read(_recorded('parent'))
    if metric in NEW_IN_THE_PROGRAM:
        assert value is None
    else:
        assert value > 0


def test_the_recorded_window_was_read_from_rollups_alone():
    r = _recorded('change')
    assert load_module('metrics', 'rollup_coverage_share.query').read(r) \
        == 100.0
    # 1, 7, 28.7 and 3 files for the four windows, in their shares
    assert 5.0 < load_module('metrics',
                             'rollup_files_per_query.query').read(r) < 15.0


@pytest.mark.parametrize('metric', NEW_METRICS)
def test_metric_reads_nothing_from_nothing(metric):
    assert load_module('metrics', metric).read(_reading('', '', 3)) is None


def test_the_four_files_arithmetic():
    before = {'counters': {'index shards queried': 1000,
                           'index shards via rollup': 900,
                           'rollup shards queried': 40}}
    after = {'counters': {'index shards queried': 3000,
                          'index shards via rollup': 2500,
                          'rollup shards queried': 90,
                          'index device sums': 7}}
    text = ('dn_stage_ms_sum{stage="index_query.plan"} 250.0\n'
            'dn_stage_ms_count{stage="index_query.plan"} 10.0\n'
            'dn_index_fold_rows 40000.0\n'
            'dn_index_fold_padded_rows 65536.0\n'
            'dn_serve_result_cache_hits_total 2.0\n')
    r = _reading('', text, 10, before, after)
    read = lambda name: load_module('metrics', name).read(r)
    assert read('rollup_coverage_share.query') == 80.0
    # 50 rollup files and 2,000 - 1,600 uncovered fine shards, 10 queries
    assert read('rollup_files_per_query.query') == 45.0
    assert read('rollup_plan_ms.query') == 25.0
    # eight of the ten queries reached the device
    assert read('index_fold_rows_per_query.query') == 5000.0


# -- the driver and the cell's files ----------------------------------------

@pytest.mark.parametrize('mindate_ms,days,want', [
    (1388534400000, 90, 93),        # 2014-01-01 .. 04-01: three months
    (1388534400000, 365, 377),
    (1390953600000, 34, 37),        # 01-29 .. 03-04: parts of three
    (1388534400000, 31, 32), (1388534400000, 1, 2)])
def test_rollup_shards_of_a_window(mindate_ms, days, want):
    assert serve_rollup.rollup_shards(
        {'mindate_ms': mindate_ms, 'days': days}) == want


def test_the_cells_files():
    """The daily cell's four templates letter for letter, the windows
    and the engagement ISSUE 46 gives; the configuration is the daily
    one's but for what an hourly tree under rollups changes."""
    ours, theirs = (tb._load('workloads', c) for c in
                    (CELL, 'muskie-365d-index.query-windows'))
    for k in ('templates', 'end_to_end', 'loop', 'mix_seed', 'rampup_s',
              'clients', 'cycle', 'prebuilt_index', 'trace'):
        assert ours[k] == theirs[k], k
    assert ours['windows'] == [{'days': d, 'share': s} for d, s in
                               ((1, 40), (7, 30), (30, 20), (90, 10))]
    assert ours['engagement']['counters'] == [
        'index device sums', 'index shards via rollup']
    assert (ours['driver'], ours['timeout_s'], ours['control']) == \
        ('serve_rollup', 120, 'bfloat16')
    assert 'qcache_hit_share.query' not in ours['per_layer']
    cfg, base = (tb._load('configs', c) for c in
                 ('muskie-90d-hourly', 'muskie-365d-index'))
    for k in ('datasource', 'setup_build_environment', 'reduced',
              'record_shape', 'chips'):
        assert cfg[k] == base[k], k
    assert cfg['guarantees'][:3] == base['guarantees']
    assert len(cfg['guarantees']) == 4
    assert cfg['index_interval'] == 'hour' and cfg['corpus']['days'] == 90
    assert 'DN_SERVE_CACHE_MB' not in cfg['environment']
    assert cfg['environment']['DN_INDEX_DEVICE'] == '1'
    strip = lambda ms: [dict(m, breakdowns=[
        {k: v for k, v in b.items() if k != 'step' or
         b['name'] != 'timestamp'} for b in m['breakdowns']]) for m in ms]
    assert strip(cfg['metrics']) == strip(base['metrics'])
    assert all(m['breakdowns'][0]['step'] == 3600 for m in cfg['metrics'])
    assert serve_rollup.rollup_shards(cfg['corpus']) == 93
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        bench = json.load(f)
    entries = [e for k in ('configs', 'workloads') for e in bench[k]
               if e['name'] in ('muskie-90d-hourly', CELL)]
    assert len(entries) == 2
    for e in entries:
        for k in ('why', 'source'):
            assert 1 <= len(e.get(k, 'x')) <= 200 and \
                e.get(k, 'x').isprintable(), (e['name'], k)


# -- rehearsals -------------------------------------------------------------

def _rehearsal(name, trace=0):
    rc, lines = tb._rehearse(name, trace=trace)
    assert rc != 0 and lines[-1].startswith('rehearsal '), lines[-5:]
    return json.loads(lines[-1][len('rehearsal '):]), lines


def test_rehearsal_through_serve_rollup(throwaway):
    """20,000 records, traced: the rollups are built in set-up (93
    shards), every answer equals the reference, both engagement
    counters grow, every listed metric a CPU can read is read, and the
    windows are read from rollups alone."""
    name, _ = tb._small_copy(throwaway, CELL)
    doc, lines = _rehearsal(name, trace=1)
    assert 'set-up rollup build: 93 shards' in ' '.join(lines)
    assert all(c == {'value': 0, 'limit': 0}
               for c in doc['numbers_compared'].values()), lines
    assert doc['failed'] == 0 and doc['attempted'] > 0
    grew = [ln for ln in lines if ln.startswith('engagement: counter')]
    assert len(grew) == 2 and not any(' grew by 0 ' in ln for ln in grew)
    # correct but for the chip: the one problem is the traced window's
    # empty device plane
    assert [ln for ln in lines if ln.startswith('problem: ')] == [
        'problem: no operation ran on the device in the traced window']
    wl = tb._load('workloads', CELL)
    assert set(wl['per_layer']) - {'device_idle_share.query'} <= \
        set(doc['metrics'])
    assert doc['metrics']['rollup_coverage_share.query']['value'] == 100.0
    assert doc['metrics']['rollup_plan_ms.query']['value'] > 0
    assert doc['metrics']['window_compiles.query']['value'] == 0.0


# the stacked aggregate's first sum one too high, where the stack
# installs it (test_benchmark's BROKEN_LAUNCHER swaps sys.stdout around
# the reply, which eight concurrent clients cannot share)
BROKEN_STACK = '''"""The normal launcher with the stacked aggregate altered underneath."""
import sys
sys.path.insert(0, %(root)r)
from dragnet_tpu import aggr
_real = aggr.Aggregator.set_columnar


def _broken(self, cols, weights, decoders):
    weights = list(weights)
    weights[0] += 1
    return _real(self, cols, weights, decoders)


aggr.Aggregator.set_columnar = _broken
sys.argv[0] = %(launcher)r
exec(compile(open(%(launcher)r).read(), %(launcher)r, 'exec'))
'''


def test_altered_answer_is_not_correct(throwaway):
    launcher = throwaway('tests', 't_broken_stack.py', BROKEN_STACK % {
        'root': ROOT,
        'launcher': os.path.join(BENCH, 'drivers', 'launch_serve.py')})
    name, _ = tb._small_copy(throwaway, CELL, launcher=launcher)
    doc, lines = _rehearsal(name)
    assert doc['correct'] is False
    assert any('count_difference' in ln and 'over its limit' in ln
               for ln in lines), lines
    assert doc['numbers_compared']['window.count_difference']['value'] > 0


def test_plan_off_the_stack_is_not_correct(throwaway):
    """The plan routed to rollup.execute_plan (what the program before
    PR 46 did with every plan): every answer equals the reference, and
    `correct` is false all the same, by the forced lane's counter."""
    name, _ = tb._small_copy(throwaway, CELL,
                             environment={'DN_IQ_STACK': '0'})
    doc, lines = _rehearsal(name)
    assert doc['correct'] is False
    assert all(c['value'] == 0 for c in doc['numbers_compared'].values())
    assert doc['failed'] == 0
    assert any('did not engage: counter "index device sums"' in ln
               for ln in lines), lines
    assert any('counter "index shards via rollup" grew by' in ln and
               ' grew by 0 ' not in ln for ln in lines), lines
