"""`muskie-30d-mesh4.build-daily` (PR 38), the daily build on the
cluster backend, held to BENCHMARK.json, to its one-chip twin and to the
program (CPU, four virtual devices):

* the run document (`run.run_document`) of every committed
  configuration: the file-backed ones' is byte for byte what the rule
  before PR 38 wrote, and every datasource has its index path and its
  metrics, whatever its backend;
* the cell says what `muskie-30d.build-daily` says (templates, verify,
  loop, trees, statistics) and differs in what the mesh brings;
* a traced rehearsal at 20,000 records ends with every compared number
  0, the kernel records all on four devices, every host-side metric
  read, three dispatches a batch where the twin's stack makes one;
* with the exchange between the chips left out (every psum takes chip
  0's part alone) and with a count altered where the index is written,
  it ends `correct: false` by the comparison;
* bfloat16 differs from the exact reference on the tree's three queries.
"""

import glob
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

run = load_module('.', 'run')

CELL = 'muskie-30d-mesh4.build-daily'
TWIN = 'muskie-30d.build-daily'
NEW_METRICS = ('dispatches_per_batch.build', 'collective_share.build',
               'sparse_merge_ms.build')
MESH4 = {'XLA_FLAGS': '--xla_force_host_platform_device_count=4'}

# the faults the cell can have, planted in the program underneath the
# normal launcher
FAULTS = {
    # the exchange between chips left out: every psum adds up chip 0's
    # part and nothing of the others' (the replication stays provable,
    # so shard_map still takes the program)
    'no-exchange': '''
import jax
import jax.numpy as jnp
_psum = jax.lax.psum


def _chip0_only(x, axis_name, **kw):
    first = jax.lax.axis_index(axis_name) == 0
    return _psum(jax.tree.map(lambda v: jnp.where(first, v, 0), x),
                 axis_name, **kw)


jax.lax.psum = _chip0_only
''',
    # an answer altered where it is produced: the first tuple of the
    # first metric goes into the index with its count one too high
    'count': '''
from dragnet_tpu import index_build_mt
_write = index_build_mt.write_index_blocks


def _one_too_high(metrics, interval, indexpath, blocks):
    names, cols, weights = blocks[0]
    weights = list(weights)
    weights[0] += 1
    return _write(metrics, interval, indexpath,
                  [(names, cols, weights)] + list(blocks[1:]))


index_build_mt.write_index_blocks = _one_too_high
'''}

FAULTY_LAUNCHER = '''"""The normal launcher over a program with a fault planted."""
import sys
sys.path.insert(0, %(root)r)
%(fault)s
sys.argv[0] = %(launcher)r
exec(compile(open(%(launcher)r).read(), %(launcher)r, 'exec'))
'''


def _doc(lines):
    return json.loads(lines[-1][len('rehearsal '):])


def _configs():
    return sorted(os.path.basename(p)[:-len('.json')] for p in glob.glob(
        os.path.join(tb.BENCH, 'configs', '*.json')))


def _document_before_pr38(config, workload, run_dir, corpus_path):
    """The rule `run.make_corpus` held until PR 38, written out: only a
    file-backed datasource got an index path and metrics."""
    dsconf = config['datasource']
    indexed = dsconf['backend'] == 'file'
    names = ['muskie'] + ['muskie_b%d' % i
                          for i in range(workload.get('build_trees', 0))]
    sources = []
    for n in names:
        bc = {'path': corpus_path, 'timeField': dsconf['timeField']}
        if indexed:
            bc['indexPath'] = os.path.join(run_dir, 'idx', n)
        sources.append({'name': n, 'backend': dsconf['backend'],
                        'backend_config': bc, 'filter': None,
                        'dataFormat': dsconf['dataFormat']})
    return {'vmaj': 0, 'vmin': 0, 'datasources': sources,
            'metrics': [dict(m, datasource=n) for n in names
                        for m in config['metrics']] if indexed else []}


@pytest.mark.parametrize('trees', [0, 3])
@pytest.mark.parametrize('name', _configs())
def test_run_document(name, trees):
    config = tb._load('configs', name)
    doc = run.run_document(config, {'build_trees': trees}, '/r/un',
                           '/r/un/muskie.log')
    names = [d['name'] for d in doc['datasources']]
    assert names == ['muskie'] + ['muskie_b%d' % i for i in range(trees)]
    for d in doc['datasources']:
        assert d['backend'] == config['datasource']['backend']
        assert d['backend_config']['indexPath'] == '/r/un/idx/' + d['name']
        assert d['backend_config']['path'] == '/r/un/muskie.log'
    assert len({d['backend_config']['indexPath']
                for d in doc['datasources']}) == len(names)
    # its metrics once a tree
    for n in names:
        assert [dict(m, datasource=n) for m in config['metrics']] == \
            [m for m in doc['metrics'] if m['datasource'] == n]
    assert len(doc['metrics']) == len(names) * len(config['metrics'])
    old = _document_before_pr38(config, {'build_trees': trees}, '/r/un',
                                '/r/un/muskie.log')
    if config['datasource']['backend'] == 'file':
        assert json.dumps(doc) == json.dumps(old)
    else:
        # a cluster datasource gains what it lacked and loses nothing
        for d, o in zip(doc['datasources'], old['datasources']):
            assert d['backend_config'].pop('indexPath') and d == o


def test_the_cell_says_what_its_twin_says():
    wl, twin = tb._load('workloads', CELL), tb._load('workloads', TWIN)
    same = ('templates', 'verify', 'loop', 'clients', 'driver',
            'build_trees', 'end_to_end', 'trace', 'traffic')
    assert {k: wl[k] for k in same} == {k: twin[k] for k in same}
    assert wl.get('control', 'bfloat16') == twin.get('control', 'bfloat16') \
        == 'bfloat16'
    assert set(wl) == set(twin)
    assert (wl['timeout_s'], twin['timeout_s']) == (1200, 600)
    assert wl['engagement'] == {'counters': ['ndevicebatches'],
                                'kernel_log': {'mesh_devices': 4}}
    assert twin['engagement'] == {'counters': ['nstackedbatches']}
    assert wl['per_layer'] == twin['per_layer'] + \
        ['collective_share.build', 'sparse_merge_ms.build']
    assert 'dispatches_per_batch.build' in twin['per_layer']
    # the configuration is mesh4 scan-dense's as it stands, and that is
    # the twin's on the cluster backend
    assert wl['config'] == tb._load(
        'workloads', 'muskie-30d-mesh4.scan-dense')['config']
    cfg, tcfg = tb._load('configs', wl['config']), \
        tb._load('configs', twin['config'])
    for k in ('corpus', 'metrics', 'guarantees', 'index_interval',
              'record_shape', 'reduced'):
        assert cfg[k] == tcfg[k], k
    assert cfg['datasource']['backend'] == 'cluster'
    assert (cfg['chips'], tcfg['chips']) == (4, 1)
    assert len(cfg['metrics']) == 3


def test_benchmark_json_holds_the_cell():
    with open(os.path.join(tb.ROOT, 'BENCHMARK.json')) as f:
        doc = json.load(f)
    (cell,) = [w for w in doc['workloads'] if w['name'] == CELL]
    assert (cell['chips'], cell['traffic'], cell['config']) == \
        (4, 'build-daily', 'muskie-30d-mesh4')
    assert len(cell['why']) <= 200
    four = [w['name'] for w in doc['workloads'] if w['chips'] == 4]
    assert CELL in four and 2 * len(four) <= len(doc['workloads'])
    layers = {m['name']: m for m in doc['per_layer']}
    for name in NEW_METRICS:
        assert layers[name]['moves'] == 'build_records_per_s'
        assert CELL in layers[name]['workloads']
    assert layers['dispatches_per_batch.build']['workloads'] == [TWIN, CELL]
    assert layers['reply_share.scan']['moves'] == 'scan_records_per_s'
    (rates,) = [m for m in doc['end_to_end']
                if m['name'] == 'build_records_per_s']
    assert rates['workloads'] == [TWIN, CELL] and rates['bound'] == 0.06


def test_bfloat16_fails_on_the_trees_queries(medium):    # noqa: F811
    for t in tb._load('workloads', CELL)['verify']:
        exact = medium.expected_lines(t['query'], part=t['part'])
        low = medium.expected_lines(t['query'], part=t['part'],
                                    accumulate='bfloat16')
        ntuples, delta = compare(b'\n'.join(low), exact)
        assert ntuples > 0 and delta > 0, t['name']


@pytest.fixture(scope='module')
def traced_docs():
    """One --trace 1 rehearsal at 20,000 records of the cell and one of
    its twin: cell -> (the line's doc, the run's lines)."""
    docs = {}
    for cell, env in ((CELL, MESH4), (TWIN, {})):
        with tb._throwaway_files() as add:
            name, _ = tb._small_copy(add, cell)
            rc, lines = tb._rehearse(name, env, trace=1)
        assert rc != 0
        docs[cell] = (_doc(lines), lines)
    return docs


def test_rehearsal_builds_on_the_mesh(traced_docs):
    doc, lines = traced_docs[CELL]
    assert doc['failed'] == 0 and doc['attempted'] > 0
    assert doc['device']['count'] == 4
    assert all(c == {'value': 0, 'limit': 0}
               for c in doc['numbers_compared'].values())
    # a CPU has no device plane, and says so: nothing else is amiss
    problems = [ln for ln in lines if ln.startswith('problem: ')]
    assert problems == ['problem: no operation ran on the device in the '
                        'traced window'], problems
    assert any('kernel records in the window, want {"mesh_devices": 4}'
               in ln and not ln.startswith('engagement: 0 ')
               for ln in lines), lines
    assert any('counter "ndevicebatches" grew by' in ln for ln in lines)


def test_every_host_side_metric_reads_a_number(traced_docs):
    with open(os.path.join(tb.ROOT, 'BENCHMARK.json')) as f:
        layers = {m['name']: m for m in json.load(f)['per_layer']}
    got = traced_docs[CELL][0]['metrics']
    for name in tb._load('workloads', CELL)['per_layer']:
        if layers[name]['source'] == 'device_trace':
            assert name not in got      # nothing under a device's name
        else:
            assert isinstance(got[name]['value'], float), name
            assert got[name]['value'] >= 0.0, name
    assert got['sparse_merge_ms.build']['value'] > 0


def test_dispatches_per_batch_counts_the_loop_and_the_stack(traced_docs):
    """Three metrics: three dispatches a batch on the mesh's per-scan
    loop, one on the twin's stack."""
    mesh, one = (traced_docs[c][0]['metrics'] for c in (CELL, TWIN))
    assert mesh['dispatches_per_batch.build']['value'] == 3.0
    assert one['dispatches_per_batch.build']['value'] == 1.0


@pytest.mark.parametrize('fault', sorted(FAULTS))
def test_a_fault_is_not_correct(fault, throwaway):        # noqa: F811
    launcher = throwaway('tests', 't_mesh4_build_launcher.py',
                         FAULTY_LAUNCHER % {
                             'root': tb.ROOT, 'fault': FAULTS[fault],
                             'launcher': os.path.join(
                                 tb.BENCH, 'drivers', 'launch_serve.py')})
    name, _ = tb._small_copy(throwaway, CELL, launcher=launcher)
    rc, lines = tb._rehearse(name, MESH4)
    assert rc != 0
    doc = _doc(lines)
    assert doc['correct'] is False
    assert doc['failed'] == 0 and doc['attempted'] > 0
    compared = doc['numbers_compared']
    assert compared['warmup.mismatched_tuples']['value'] > 0
    assert compared['window.mismatched_tuples']['value'] > 0
    assert compared['window.count_difference']['value'] > 0
    assert any('over its limit 0' in ln for ln in lines), lines


@pytest.mark.parametrize('name', NEW_METRICS + ('reply_share.scan',))
def test_new_metrics_read_nothing_from_a_silent_program(name):
    """A program that never wrote the counters and stages (or a run
    without a trace): every new reader returns None and does not
    raise."""
    class Silent(object):
        trace, outcomes = None, []

        def delta(self, name, **labels):
            return None

        def done(self, op):
            return [object()]

    assert load_module('metrics', name).read(Silent()) is None
