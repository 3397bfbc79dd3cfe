"""The per-layer metrics over the request between its device phases
(PR 42), files of `benchmarks/metrics/` that no cell lists yet (CPU):

* each reads a number from a recorded scrape pair of its family and a
  reduced trace, and a share lies between 0 and 100;
* each reads nothing, and raises nothing, from the scrape pair of a
  program that writes none of the new series (the parent, PR 41);
* `span_coverage_share.*` is the program's own count over the request's
  latency, whatever the leaves are called;
* `idle_gaps_named_share.*` reads 0 and 100 on two hand-made lists of
  gaps, and the share by seconds in between.

The scrapes are `data/request_spans_scrapes.json`: 20,000-record CPU
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

from loader import load_module                            # noqa: E402

run = load_module('.', 'run')
trace_reduce = load_module('trace', 'reduce')

FAMILY_METRICS = {
    'scan': ('span_coverage_share.scan', 'idle_gaps_named_share.scan',
             'request_init_ms.scan', 'finish_ms.scan',
             'reply_drain_ms.scan'),
    'build': ('span_coverage_share.build', 'idle_gaps_named_share.build',
              'request_init_ms.build', 'finish_ms.build'),
    'query': ('span_coverage_share.query', 'idle_gaps_named_share.query',
              'plan_ms.query'),
}
MOVES = {'scan': 'scan_records_per_s', 'build': 'build_records_per_s',
         'query': 'query_completed_per_s'}
CASES = [(op, m) for op in sorted(FAMILY_METRICS)
         for m in FAMILY_METRICS[op]]


def _scrapes():
    with open(os.path.join(HERE, 'data',
                           'request_spans_scrapes.json')) as f:
        return json.load(f)


def _recorded_trace():
    """The reduction of the trace recorded on the chip (PR 25)."""
    with open(os.path.join(HERE, 'data', 'recorded_events.json')) as f:
        return trace_reduce.reduce_events(json.load(f))


def _reading(rec, op, trace):
    """`run.Reading` over a recorded scrape pair: `done[op]` finished
    requests of the family, and nothing else in the window."""
    template = {'op': op, 'name': op}
    outcome = types.SimpleNamespace(
        ok=True, err=None, req=types.SimpleNamespace(template=template))
    ctx = types.SimpleNamespace(
        config={'corpus': {'records': rec['records'], 'days': rec['days']}},
        workload={'name': rec['cell']}, say=lambda msg: None)
    res = {'prom_before': rec['before'], 'prom_after': rec['after'],
           'stats_before': {}, 'stats_after': {}, 'window_stderr': '',
           'outcomes': [outcome] * rec['done'][op], 'window_s': 2.0,
           'device': {'kind': 'cpu', 'platform': 'cpu', 'count': 1}}
    return run.Reading(ctx, res, trace)


@pytest.mark.parametrize('op,metric', CASES)
def test_metric_reads_a_number(op, metric):
    mod = load_module('metrics', metric)
    r = _reading(_scrapes()['change'][op], op, _recorded_trace())
    value = mod.read(r)
    assert isinstance(value, float)
    assert 0.0 <= value <= (100.0 if mod.META['unit'] == '%'
                            else float('inf'))
    assert mod.META['moves'] == MOVES[op]
    assert set(mod.META) == {'layer', 'source', 'unit', 'better', 'moves'}


@pytest.mark.parametrize('op,metric', [
    c for c in CASES if not c[1].startswith('idle_gaps_named_share')])
def test_metric_reads_nothing_from_the_parents_scrape(op, metric):
    """A program without the leaves and counters of PR 42: nothing to
    read, nothing raised, with a trace or without one."""
    mod = load_module('metrics', metric)
    rec = _scrapes()['parent'][op]
    assert mod.read(_reading(rec, op, _recorded_trace())) is None
    assert mod.read(_reading(rec, op, None)) is None


@pytest.mark.parametrize('op', sorted(FAMILY_METRICS))
def test_coverage_is_the_programs_own_count(op):
    """100 x (the leaves' total that the program counted + the wait for
    a slot) / the request's latency, and close to all of a request on
    the recorded scrapes (each family's new leaves are in the total
    though no list here names them)."""
    r = _reading(_scrapes()['change'][op], op, None)
    want = 100.0 * (r.delta('serve_leaf_ms_sum', op=op) +
                    (r.delta('serve_queue_wait_ms_sum') or 0.0)) / \
        r.delta('serve_op_latency_ms_sum', op=op)
    got = load_module('metrics', 'span_coverage_share.' + op).read(r)
    assert got == pytest.approx(want)
    assert 85.0 < got <= 100.0


@pytest.mark.parametrize('op', sorted(FAMILY_METRICS))
def test_idle_gaps_named_share_on_hand_made_gaps(op):
    mod = load_module('metrics', 'idle_gaps_named_share.' + op)

    def read(gaps):
        trace = None if gaps is None else \
            {'breakdown': {'idle_gaps': gaps, 'device_ops': []}}
        return mod.read(_reading(_scrapes()['parent'][op], op, trace))
    unnamed = [['host: nothing traced', 0.2],
               ['host: nothing traced for most of it (scan.parse_wait '
                '(dn-serve-job) covers 28%)', 0.3]]
    named = [['scan.init (dn-serve-job)', 0.1],
             ['index_build.prepare 35% + serve.resolve 20%', 0.3]]
    assert read(unnamed) == 0.0
    assert read(named) == 100.0
    assert read(unnamed + named) == pytest.approx(100.0 * 0.4 / 0.9)
    # no trace, or one without a gap: nothing to read
    assert read(None) is None and read([]) is None

