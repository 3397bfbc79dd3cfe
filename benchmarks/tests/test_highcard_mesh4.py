"""`muskie-30d-highcard-mesh4.scan-highcard` (PR 35), the
high-cardinality scan on the cluster backend, held to BENCHMARK.json,
to its one-chip twin and to the program (CPU, four virtual devices):

* the configuration and the cell say what the twin says (query, corpus,
  guarantees, control) and differ in what the mesh brings;
* a rehearsal at 20,000 records ends `correct: true` with every
  compared number 0; with a reply altered where the server produces it,
  and with the mesh's sparse lane shut so that the host's lane answers,
  it ends `correct: false`;
* `key32` differs from the exact reference on the cell's query;
* the four per-layer metrics the cell brings read numbers from a traced
  rehearsal's scrape (and the roofline share from a trace stub), and
  nothing from a program that never wrote their counters.
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import test_benchmark as tb                               # noqa: E402
from test_benchmark import medium, throwaway              # noqa: E402,F401
from loader import load_module                            # noqa: E402
from reference.groupby import compare                     # noqa: E402

CELL = 'muskie-30d-highcard-mesh4.scan-highcard'
TWIN = 'muskie-30d-highcard.scan-highcard'
NEW_METRICS = ('sparse_merge_ms.mesh', 'sparse_merge_roofline.mesh',
               'sparse_set_fill.mesh', 'sparse_merge_rows_per_tuple.mesh')
MESH4 = {'XLA_FLAGS': '--xla_force_host_platform_device_count=4'}

# the mesh's sparse lane shut as it was before PR 35: the guard sends
# every high-cardinality batch to the host's sparse merge
GATE_BACK = '''
from dragnet_tpu.parallel import cluster


def _shut(self, n):
    self._disabled = True
    return False


cluster.MeshDeviceScan._sparse_guard = _shut
'''

GATED_LAUNCHER = '''"""The normal launcher over a program whose mesh has no sparse lane."""
import sys
sys.path.insert(0, %(root)r)
%(patch)s
sys.argv[0] = %(launcher)r
exec(compile(open(%(launcher)r).read(), %(launcher)r, 'exec'))
'''


def _doc(lines):
    return json.loads(lines[-1][len('rehearsal '):])


def test_the_cell_says_what_its_twin_says():
    wl, twin = tb._load('workloads', CELL), tb._load('workloads', TWIN)
    cfg = tb._load('configs', wl['config'])
    tcfg = tb._load('configs', twin['config'])
    same = ('templates', 'control', 'loop', 'clients', 'driver',
            'end_to_end', 'trace')
    assert {k: wl[k] for k in same} == {k: twin[k] for k in same}
    assert wl['control'] == 'key32' and wl['timeout_s'] == 600
    for k in ('corpus', 'guarantees', 'environment', 'record_shape',
              'shapes_kept', 'metrics', 'index_interval', 'reduced'):
        assert cfg[k] == tcfg[k], k
    assert len(cfg['guarantees']) == 4 and cfg['metrics'] == []
    mesh = tb._load('configs', 'muskie-30d-mesh4')
    assert cfg['datasource'] == mesh['datasource']
    assert cfg['datasource']['backend'] == 'cluster'
    assert (cfg['chips'], tcfg['chips']) == (4, 1)
    assert cfg['reduced_why'] == mesh['reduced_why']
    assert wl['engagement'] == {
        'counters': ['ndevicebatches'],
        'kernel_log': {'kernel': 'sparse-sort-merge', 'mesh_devices': 4,
                       'merge': 'allgather+sparse-fold'}}
    assert wl['per_layer'] == twin['per_layer'] + \
        ['collective_share.mesh'] + list(NEW_METRICS)


def test_benchmark_json_holds_the_cell():
    """The cell, its configuration and its four metrics, found by name:
    a later PR appends to every list."""
    with open(os.path.join(tb.ROOT, 'BENCHMARK.json')) as f:
        doc = json.load(f)
    (cell,) = [w for w in doc['workloads'] if w['name'] == CELL]
    assert (cell['chips'], cell['traffic']) == (4, 'scan-highcard')
    (config,) = [c for c in doc['configs'] if c['name'] == cell['config']]
    assert config['reduced'] == ['records']
    assert len(config['source']) <= 200
    four = [w['name'] for w in doc['workloads'] if w['chips'] == 4]
    assert CELL in four and 2 * len(four) <= len(doc['workloads'])
    layers = {m['name']: m for m in doc['per_layer']}
    for name in NEW_METRICS:
        assert layers[name]['workloads'] == [CELL]
        assert layers[name]['moves'] == 'scan_records_per_s'
    (rates,) = [m for m in doc['end_to_end']
                if m['name'] == 'scan_records_per_s']
    assert CELL in rates['workloads'] and rates['bound'] == 0.06


def test_key32_fails_on_the_cells_query(medium):         # noqa: F811
    (template,) = tb._load('workloads', CELL)['templates']
    exact = medium.expected_lines(template['query'], part='batch')
    low = medium.expected_lines(template['query'], part='batch',
                                accumulate='key32')
    ntuples, delta = compare(b'\n'.join(low), exact)
    assert ntuples > 0 and delta > 0


@pytest.fixture(scope='module')
def traced_doc():
    """One --trace 1 rehearsal of the cell at 20,000 records."""
    with tb._throwaway_files() as add:
        name, _ = tb._small_copy(add, CELL)
        rc, lines = tb._rehearse(name, MESH4, trace=1)
    assert rc != 0
    return _doc(lines), lines


def test_rehearsal_is_correct_on_the_mesh(throwaway):     # noqa: F811
    name, cfg = tb._small_copy(throwaway, CELL)
    rc, lines = tb._rehearse(name, MESH4)
    assert rc != 0 and lines[-1].startswith('rehearsal ')
    doc = _doc(lines)
    assert doc['correct'] is True, lines
    assert doc['failed'] == 0 and doc['attempted'] > 0
    assert doc['device']['count'] == 4
    assert all(c == {'value': 0, 'limit': 0}
               for c in doc['numbers_compared'].values())
    assert any('kernel records in the window' in ln and
               '"merge": "allgather+sparse-fold"' in ln for ln in lines)


@pytest.mark.parametrize('fault', ['truncate', 'gate-back'])
def test_a_fault_is_not_correct(fault, throwaway):        # noqa: F811
    """A reply cut short where the server produces it fails by the
    comparison; the host's lane answering in the device's place gives
    every tuple right and fails all the same, by the lane counter and
    the kernel records."""
    launcher = os.path.join(tb.BENCH, 'drivers', 'launch_serve.py')
    if fault == 'truncate':
        text = tb.BROKEN_LAUNCHER % {
            'root': tb.ROOT, 'fault': tb.FAULTS[fault],
            'launcher': launcher}
    else:
        text = GATED_LAUNCHER % {'root': tb.ROOT, 'patch': GATE_BACK,
                                 'launcher': launcher}
    name, _ = tb._small_copy(
        throwaway, CELL,
        launcher=throwaway('tests', 't_mesh4_launcher.py', text))
    rc, lines = tb._rehearse(name, MESH4)
    assert rc != 0
    doc = _doc(lines)
    assert doc['correct'] is False
    compared = doc['numbers_compared']
    if fault == 'truncate':
        assert compared['window.mismatched_tuples']['value'] > 0
    else:
        assert all(c['value'] == 0 for c in compared.values())
        assert any('did not engage' in ln for ln in lines), lines
        assert any('kernel records of the window do not all say' in ln
                   for ln in lines), lines


def test_new_metrics_read_the_rehearsals_scrape(traced_doc):
    doc, lines = traced_doc
    got = doc['metrics']
    # (a CPU has no device plane: the roofline share has nothing to
    # read there and the line leaves it out)
    assert 'sparse_merge_roofline.mesh' not in got
    assert got['sparse_merge_ms.mesh']['value'] > 0
    assert 0 < got['sparse_set_fill.mesh']['value'] <= 100
    assert 1 <= got['sparse_merge_rows_per_tuple.mesh']['value'] <= 4
    assert got['sparse_fold_batches.scan']['value'] >= 1


class _Stub(object):
    """What the roofline's reader reads, from fixed numbers."""
    config = {'chips': 4}
    device = {'kind': 'TPU v5 lite'}

    def __init__(self, counters, trace):
        self.counters, self.trace = counters, trace

    def delta(self, name, **labels):
        return self.counters.get((name,) + tuple(sorted(labels.items())))


def test_roofline_share_from_a_trace_stub():
    mod = load_module('metrics', 'sparse_merge_roofline.mesh')
    costs = load_module('trace', 'costs_sparse_merge')
    counters = {('device_sparse_merge_rows',): 800000.0,
                ('stage_ms_count', ('stage', 'scan.sparse_merge')): 4.0}
    trace = {'chips': [
        {'modules': {'jit_sparse_merge': [2, 0.008], 'jit_run': [9, 1.0]}},
        {'modules': {'jit_sparse_merge': [2, 0.010]}},
        {'modules': {}}]}
    peak = {'ici_bytes_per_s': 200e9, 'hbm_bytes_per_s': 819e9}
    need = costs.least_seconds(200000.0, 4, peak)
    # 200,000 rows a merge: 3.6 MB arrive over ICI (18 us), 9.6 MB
    # cross HBM (11.7 us); the slower bounds; 5 ms a run on the most
    # loaded chip
    assert need == pytest.approx(24 * 200000 * 0.75 / 200e9)
    assert mod.read(_Stub(counters, trace)) == \
        pytest.approx(100.0 * need / 0.005)
    assert mod.read(_Stub(counters, trace)) < 100.0
    # a program without the counters, or a trace without the module
    assert mod.read(_Stub({}, trace)) is None
    assert mod.read(_Stub(counters, {'chips': [{'modules': {}}]})) is None
    assert mod.read(_Stub(counters, None)) is None


@pytest.mark.parametrize('name', NEW_METRICS)
def test_new_metrics_read_nothing_from_an_older_program(name):
    """On the parent commit no counter and no stage of the merge is
    written: every new reader returns None and does not raise."""
    class Older(_Stub):
        outcomes = []

        def done(self, op):
            return [object()]

    assert load_module('metrics', name).read(
        Older({}, {'chips': [{'modules': {'jit_run': [5, 0.2]}}]})) is None
