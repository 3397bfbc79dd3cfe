"""The benchmark's own tests (CPU): `python -m pytest benchmarks/tests -q`.

* the plain reference against dragnet_tpu/scan.py's StreamScan, once per
  query shape, and against the lines its dense body gave before PR 34
  made the table sparse;
* the control each cell names (bfloat16 accumulation, or the key folded
  to 32 bits) must NOT agree, and `key32` is refused where it could not
  fail;
* the trace reduction on a small recorded trace;
* the generator's determinism per seed;
* BENCHMARK.json against the data files;
* one 20,000-record rehearsal of each cell end to end (final line's
  keys, non-zero exit off the chip), with a throw-away cell and metric
  added as files, one with the timed path broken underneath for each
  fault (a count altered, a reply truncated), and one with the
  high-cardinality cell answered by another lane than the sparse one;
* every per-layer metric that needs no device plane, read in a traced
  rehearsal of each cell that lists it.
"""

import contextlib
import glob
import hashlib
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)

from gen import corpus                                    # noqa: E402
from reference.groupby import Reference, compare         # noqa: E402
from loader import load_module                            # noqa: E402
import traffic                                            # noqa: E402

trace_reduce = load_module('trace', 'reduce')

MINDATE = 1388534400000
DAY = 86400000


def _load(kind, name):
    with open(os.path.join(BENCH, kind, name + '.json')) as f:
        return json.load(f)


def _cells():
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        return [w['name'] for w in json.load(f)['workloads']]


@pytest.fixture(scope='module')
def small(tmp_path_factory):
    """6,000 records over 30 days: (path, columns, reference)."""
    d = tmp_path_factory.mktemp('corpus')
    lib = corpus.build_library(str(d))
    path = str(d / 'muskie.log')
    cols, _ = corpus.generate(lib, path, 6000, MINDATE,
                              MINDATE + 30 * DAY, 2147483999)
    ref = Reference(cols, {'host': corpus.HOSTS, 'method': corpus.METHODS,
                           'op': corpus.OPERATIONS})
    return path, cols, ref


def _shapes(with_control=False):
    """Every query shape a committed workload sends or verifies with
    (and, asked for, the control its cell names)."""
    seen, out = set(), []
    for path in sorted(glob.glob(os.path.join(BENCH, 'workloads', '*.json'))):
        with open(path) as f:
            wl = json.load(f)
        for t in (wl.get('templates') or []) + (wl.get('verify') or []):
            if 'query' not in t:
                continue
            key = json.dumps(t['query'], sort_keys=True)
            if key not in seen:
                seen.add(key)
                args = (t['query'], wl.get('control', 'bfloat16')) \
                    if with_control else (t['query'],)
                out.append(pytest.param(*args, id=t['name']))
    return out


def _stream_scan_points(path, query):
    """The program's per-record host path over the file, as sorted
    `--points` lines."""
    from dragnet_tpu import query as mod_query
    from dragnet_tpu.scan import StreamScan
    from dragnet_tpu.vpipe import Pipeline
    qc = mod_query.query_load({
        'breakdowns': [dict(b, field=b.get('field') or b['name'])
                       for b in query['breakdowns']],
        **({'filter': query['filter']} if query.get('filter') else {})})
    from dragnet_tpu import output as mod_output
    import io
    scan = StreamScan(qc, 'time', Pipeline())
    with open(path) as f:
        for line in f:
            scan.write(json.loads(line), 1)
    out = io.StringIO()
    mod_output.print_points(scan.aggr.points(), out)
    return sorted(out.getvalue().encode().splitlines())


@pytest.mark.parametrize('query', _shapes())
def test_reference_equals_stream_scan(small, query):
    path, _, ref = small
    got = _stream_scan_points(path, query)
    assert ref.expected_lines(query, part='batch') == got
    assert ref.expected_lines(query, part='day') == got


@pytest.fixture(scope='module')
def medium():
    """400,000 records over 30 days, where counts pass 256: the
    reference."""
    lib = corpus.build_library(os.path.join(ROOT, '.cache', 'bench', 'gen'))
    path = os.path.join(ROOT, '.cache', 'bench', 'gen', 'control.log')
    cols, _ = corpus.generate(lib, path, 400000, MINDATE,
                              MINDATE + 30 * DAY, 5)
    os.unlink(path)
    return Reference(cols, {'host': corpus.HOSTS, 'method': corpus.METHODS,
                            'op': corpus.OPERATIONS})


RECORDED_WINDOWS = [None, (0, 7), (3, 30), (10, 11), (40, 50)]

with open(os.path.join(HERE, 'data', 'reference_dense_body.json')) as _f:
    RECORDED = json.load(_f)


def _recorded_cases(ref, query):
    """{case: lines' digest} of one shape: both parts, exact and
    bfloat16, whole and by windows of days."""
    got = {}
    for part in ('batch', 'day'):
        for acc in ('exact', 'bfloat16'):
            for win in RECORDED_WINDOWS if part == 'day' else [None]:
                q = dict(query)
                if win:
                    q['timeAfter'] = MINDATE + win[0] * DAY
                    q['timeBefore'] = MINDATE + win[1] * DAY
                lines = ref.expected_lines(q, part=part, accumulate=acc)
                got['%s/%s/%s' % (part, acc, win)] = '%d:%s' % (
                    len(lines),
                    hashlib.sha256(b'\n'.join(lines)).hexdigest()[:16])
    return got


@pytest.mark.parametrize('query', [
    p for p in _shapes()
    if json.dumps(p.values[0], sort_keys=True) in RECORDED])
def test_reference_equals_its_dense_body(small, medium, query):
    """New against old: `data/reference_dense_body.json` holds what the
    reference's dense `counts[part, key]` body (until PR 34) answered
    for every shape it could hold, over the `small` corpus and over
    the `medium` one, where bfloat16 loses counts; the sparse body
    gives the same lines byte for byte, the control's too."""
    want = RECORDED[json.dumps(query, sort_keys=True)]
    assert _recorded_cases(small[2], query) == want['small']
    got = _recorded_cases(medium, query)
    assert got == want['medium']
    assert got['batch/bfloat16/None'] != got['batch/exact/None']


@pytest.mark.parametrize('query,control', _shapes(with_control=True))
def test_control_fails(medium, query, control):
    """The control the shape's cell names must differ from the exact
    reference: the comparison can fail.  bfloat16 at a size where
    counts pass 256; key32 wherever tuples differ above bit 31, where
    counts are too small for bfloat16 to lose anything."""
    for part in ('batch', 'day'):
        exact = medium.expected_lines(query, part=part)
        low = medium.expected_lines(query, part=part, accumulate=control)
        ntuples, delta = compare(b'\n'.join(low), exact)
        assert ntuples > 0 and delta > 0
        assert compare(b'\n'.join(exact), exact) == (0, 0)
        if control != 'bfloat16':
            # why the cell names another: bfloat16 cannot fail here
            assert medium.expected_lines(query, part=part,
                                         accumulate='bfloat16') == exact


@pytest.mark.parametrize('query', [
    pytest.param(p.values[0], id=p.id)
    for p in _shapes(with_control=True) if p.values[1] != 'key32'])
def test_key32_refused_where_the_key_fits_32_bits(small, query):
    """A cell whose bit-packed key fits 32 bits cannot name `key32`:
    the fold would lose nothing, so the control could not fail."""
    _, _, ref = small
    with pytest.raises(ValueError, match='cannot fail'):
        ref.expected_lines(query, accumulate='key32')


def test_compare_counts_differences():
    exp = sorted([b'{"fields":{"a":"x"},"value":3}',
                  b'{"fields":{"a":"y"},"value":5}'])
    assert compare(b'\n'.join(exp) + b'\n', exp) == (0, 0)
    assert compare(exp[0] + b'\n', exp) == (1, 5)
    assert compare(b'{"fields":{"a":"x"},"value":4}\n' + exp[1], exp) \
        == (1, 1)


def test_generator_is_deterministic_per_seed(tmp_path):
    lib = corpus.build_library(str(tmp_path))
    outs = []
    for seed in (2147483999, 2147483999, 7):
        p = str(tmp_path / ('c%d.log' % len(outs)))
        cols, n = corpus.generate(lib, p, 3000, MINDATE, MINDATE + 30 * DAY,
                                  seed)
        with open(p, 'rb') as f:
            outs.append((f.read(), cols))
    assert outs[0][0] == outs[1][0]
    assert outs[0][0] != outs[2][0]
    # the columns are the file's records
    rec = json.loads(outs[0][0].splitlines()[17])
    cols = outs[0][1]
    assert rec['host'] == corpus.HOSTS[cols['host'][17]]
    assert rec['operation'] == corpus.OPERATIONS[cols['op'][17]]
    assert rec['req']['method'] == corpus.METHODS[cols['method'][17]]
    assert rec['latency'] == cols['latency'][17]
    assert rec['res']['statusCode'] == cols['status'][17]


def test_generator_equals_the_programs(tmp_path):
    """benchgen.cc is a copy: same bytes as native/dngen.cc."""
    import bench
    theirs = str(tmp_path / 'theirs.log')
    bench.gen_to_file(2000, theirs, mindate_ms=MINDATE,
                      maxdate_ms=MINDATE + 30 * DAY, seed=99)
    lib = corpus.build_library(str(tmp_path))
    mine = str(tmp_path / 'mine.log')
    corpus.generate(lib, mine, 2000, MINDATE, MINDATE + 30 * DAY, 99)
    with open(theirs, 'rb') as a, open(mine, 'rb') as b:
        assert a.read() == b.read()


def test_traffic_same_work_every_seed():
    """The seed draws the start days; the classes, their order and the
    arrivals are the workload's own."""
    wl = dict(_load('workloads', 'muskie-365d-index.query-windows'),
              loop='open', rate_per_s=12, mix_seed=1)
    a = traffic.open_loop(wl, 1, 20.0, 365)
    b = traffic.open_loop(wl, 2147483999, 20.0, 365)
    assert len(a) == len(b) == 240
    arrivals = lambda rs: [(r.due_s, r.template['name'], r.days)
                           for r in rs]
    assert arrivals(a) == arrivals(b)
    assert [r.start_day for r in a] != [r.start_day for r in b]
    assert all(0 <= r.start_day <= 365 - r.days for r in a)
    assert a == traffic.open_loop(wl, 1, 20.0, 365)
    assert traffic.apportion([40, 35, 15, 10], 7) == [3, 2, 1, 1]
    import itertools
    wl = _load('workloads', 'muskie-365d-index.query-windows')
    c = list(itertools.islice(traffic.closed_loop(wl, 1, 365), 800))
    d = list(itertools.islice(traffic.closed_loop(wl, 2, 365), 800))
    kinds = lambda rs: [(r.template['name'], r.days) for r in rs]
    assert kinds(c) == kinds(d) and kinds(c[:400]) == kinds(c[400:])
    assert sum(1 for r in c[:400] if r.days == 7) == 200
    assert sum(1 for r in c[:400] if r.days == 365) == 40
    assert [r.start_day for r in c] != [r.start_day for r in d]


def test_trace_reduction_on_recorded_trace():
    """A trace recorded on the chip (TPU v5 lite, PR 25), cut down to a
    few hundred events: the reduction's numbers are pinned."""
    with open(os.path.join(HERE, 'data', 'recorded_events.json')) as f:
        doc = json.load(f)
    with open(os.path.join(HERE, 'data', 'recorded_expected.json')) as f:
        want = json.load(f)
    got = trace_reduce.reduce_events(doc)
    assert got['window_s'] == pytest.approx(want['window_s'])
    assert got['busy_s'] == pytest.approx(want['busy_s'])
    assert 0 < got['busy_s'] < got['window_s']
    assert len(got['chips']) == want['nchips']
    for c, w in zip(got['chips'], want['chips']):
        assert c['idle_share'] == pytest.approx(w['idle_share'])
        assert c['collective_s'] == pytest.approx(w['collective_s'])
    assert [n for n, _ in got['breakdown']['device_ops']] == \
        want['top_ops']


def test_trace_reduction_arithmetic():
    ev = lambda n, s, d: [n, s, d]
    doc = {'planes': [
        {'name': '/device:TPU:0', 'lines': [
            {'name': 'XLA Ops', 'events': [
                ev('fusion.1', 0, 100), ev('all-reduce.2', 50, 100),
                ev('fusion.1', 400, 100)]},
            {'name': 'XLA Modules', 'events': [
                ev('jit_run(123)', 0, 150), ev('jit_run(123)', 400, 100)]}]},
        {'name': '/host:CPU', 'lines': [
            {'name': 'worker', 'events': [ev('parse', 140, 270)]}]}]}
    got = trace_reduce.reduce_events(doc)
    assert got['window_s'] == pytest.approx(500e-9)
    chip = got['chips'][0]
    assert chip['busy_s'] == pytest.approx(250e-9)
    assert chip['idle_share'] == pytest.approx(0.5)
    assert chip['collective_s'] == pytest.approx(100e-9)
    assert chip['modules']['jit_run'] == [2, pytest.approx(250e-9)]
    assert got['breakdown']['device_ops'][0] == ['fusion.1',
                                                 pytest.approx(200e-9)]
    assert got['breakdown']['idle_gaps'][0] == ['parse (worker)',
                                                pytest.approx(250e-9)]
    # the launcher's marker stretches the window to the trace's whole
    # length and names no gap
    doc['planes'][1]['lines'].append({'name': 'bench-control', 'events': [
        ev(trace_reduce.WINDOW_MARK, -100, 1100)]})
    got = trace_reduce.reduce_events(doc)
    assert got['window_s'] == pytest.approx(1100e-9)
    assert got['chips'][0]['busy_s'] == pytest.approx(250e-9)
    assert got['chips'][0]['idle_share'] == pytest.approx(1 - 250 / 1100)
    assert got['breakdown']['idle_gaps'][:2] == [
        ['host: nothing traced', pytest.approx(500e-9)],
        ['parse (worker)', pytest.approx(250e-9)]]
    # a span that only touches a gap does not name it
    doc['planes'][1]['lines'][0]['events'].append(ev('emit', 480, 60))
    got = trace_reduce.reduce_events(doc)
    assert got['breakdown']['idle_gaps'][0] == [
        'host: nothing traced for most of it (emit (worker) covers 8%)',
        pytest.approx(500e-9)]
    # two leaves of a third each (a reply's order and its formatting
    # between two scans) name the gap together, the larger first; a
    # third that would not be needed to reach half is not listed
    lines = doc['planes'][1]['lines']
    lines[0]['events'] += [ev('scan.order', 520, 180),
                           ev('scan.order', 980, 5),
                           ev('reply.format', 705, 195),
                           ev('socket', 900, 60)]
    got = trace_reduce.reduce_events(doc)
    assert got['breakdown']['idle_gaps'][0] == [
        'reply.format 39% + scan.order 37%', pytest.approx(500e-9)]
    # without the larger leaf the rest still reach half, the spans at
    # the gap's edges with them: every one is listed
    lines[0]['events'] = [e for e in lines[0]['events']
                          if e[0] != 'reply.format']
    got = trace_reduce.reduce_events(doc)
    assert got['breakdown']['idle_gaps'][0] == [
        'scan.order 37% + socket 12% + emit 8%', pytest.approx(500e-9)]
    # and where all of them together do not reach half, as before
    lines[0]['events'] = [e for e in lines[0]['events'] if e[0] != 'socket']
    got = trace_reduce.reduce_events(doc)
    assert got['breakdown']['idle_gaps'][0] == [
        'host: nothing traced for most of it (scan.order (worker) covers '
        '36%)', pytest.approx(500e-9)]


def test_benchmark_json_matches_the_files():
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        doc = json.load(f)
    assert doc['paths'] == ['benchmarks']
    e2e = {m['name']: m for m in doc['end_to_end']}
    layers = {m['name']: m for m in doc['per_layer']}
    for cell in doc['workloads']:
        wl = _load('workloads', cell['name'])
        cfg = _load('configs', wl['config'])
        assert (wl['config'], wl['traffic'], wl['why'], cfg['chips']) == \
            (cell['config'], cell['traffic'], cell['why'], cell['chips'])
        for name, spec in wl['end_to_end'].items():
            assert e2e[name]['unit'] == spec['unit']
            assert cell['name'] in e2e[name].get('workloads',
                                                 [cell['name']])
        for name in wl['per_layer']:
            assert cell['name'] in layers[name]['workloads']
    for c in doc['configs']:
        cfg = _load('configs', c['name'])
        assert (cfg['source'], cfg['reduced']) == (c['source'], c['reduced'])
    for name, m in layers.items():
        meta = load_module('metrics', name).META
        assert {k: m[k] for k in meta} == meta
        for cell in m['workloads']:
            assert name in _load('workloads', cell)['per_layer']
            assert m['moves'] in _load('workloads', cell)['end_to_end']


# -- rehearsals -------------------------------------------------------------

THROWAWAY_METRIC = '''"""A throw-away per-layer metric, added by the test as a file."""
META = {'layer': 'test', 'source': 'program_counter', 'unit': 'count',
        'better': 'higher', 'moves': 'setup_s'}


def read(r):
    return float(len(r.outcomes))
'''

# an answer altered where it is produced: its first count one too high,
# or its last tuple left out (a truncated reply)
FAULTS = {
    'count': '''
    head, sep, tail = text.partition('"value":')
    if sep:
        digits = ''
        while tail and tail[0].isdigit():
            digits, tail = digits + tail[0], tail[1:]
        text = head + sep + str(int(digits) + 1) + tail
''',
    'truncate': '''
    text = text[:text.rstrip('\\n').rfind('\\n') + 1]
'''}

BROKEN_LAUNCHER = '''"""The normal launcher with the timed path broken underneath: every
answer is altered where it is produced."""
import os, sys
sys.path.insert(0, %(root)r)
from dragnet_tpu import cli
_real = cli.dn_output


def _broken(query, opts, result, dsname):
    import io
    out, sys.stdout = sys.stdout, io.StringIO()
    try:
        _real(query, opts, result, dsname)
        text = sys.stdout.getvalue()
    finally:
        sys.stdout = out
%(fault)s
    sys.stdout.write(text)


cli.dn_output = _broken
sys.argv[0] = %(launcher)r
exec(compile(open(%(launcher)r).read(), %(launcher)r, 'exec'))
'''


@contextlib.contextmanager
def _throwaway_files():
    """Files a test adds under benchmarks/ and takes away again."""
    made = []

    def add(kind, name, text):
        path = os.path.join(BENCH, kind, name)
        with open(path, 'w') as f:
            f.write(text)
        made.append(path)
        return path
    try:
        yield add
    finally:
        for p in made:
            os.unlink(p)


@pytest.fixture
def throwaway():
    with _throwaway_files() as add:
        yield add


def _run_cell(cell, extra_env=None, trace=0, seconds=2):
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    env.pop('XLA_FLAGS', None)
    env.update(extra_env or {})
    return subprocess.run(
        [sys.executable, os.path.join(BENCH, 'run.py'), '--workload', cell,
         '--seed', '2147483999', '--seconds', str(seconds), '--trace',
         str(trace)], env=env, cwd=ROOT, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, timeout=600)


def _rehearse(cell, extra_env=None, trace=0, seconds=2):
    p = _run_cell(cell, extra_env, trace, seconds)
    lines = p.stdout.decode().splitlines()
    assert lines, p.stderr.decode()[-3000:]
    return p.returncode, lines


def _mesh_env(cfg):
    return {'XLA_FLAGS': '--xla_force_host_platform_device_count=4'} \
        if cfg['chips'] == 4 else {}


def _small_copy(add, cell, launcher=None, per_layer=None, environment=None,
                **changed):
    """The cell with its configuration cut to 20,000 records, as
    throw-away files; returns the copy's name."""
    wl = dict(_load('workloads', cell), **changed)
    cfg = _load('configs', wl['config'])
    cfg['name'] = 't-' + cfg['name']
    cfg['corpus']['records'] = 20000
    cfg['environment'].update(environment or {})
    wl.update(name='t-' + cell, config=cfg['name'])
    if launcher:
        wl['launcher'] = launcher
    if per_layer:
        wl['per_layer'] = wl['per_layer'] + per_layer
    add('configs', cfg['name'] + '.json', json.dumps(cfg))
    add('workloads', wl['name'] + '.json', json.dumps(wl))
    return wl['name'], cfg


@pytest.mark.parametrize('cell', _cells())
def test_rehearsal_end_to_end(cell, throwaway):
    """Each cell at 20,000 records on the CPU: runs to its end, every
    answer equal to the reference, the result's keys in place, no result
    line and a non-zero exit because this is not the chip."""
    name, cfg = _small_copy(throwaway, cell)
    p = _run_cell(name, _mesh_env(cfg))
    rc, lines = p.returncode, p.stdout.decode().splitlines()
    assert rc != 0 and lines, p.stderr.decode()[-3000:]
    assert lines[-1].startswith('rehearsal ')
    with pytest.raises(ValueError):
        json.loads(lines[-1])            # not a result line
    doc = json.loads(lines[-1][len('rehearsal '):])
    assert set(doc) == {'correct', 'attempted', 'failed', 'metrics',
                        'device', 'numbers_compared'}
    # every number compared beside its limit, last in the line
    assert lines[-1].rindex('"numbers_compared"') > \
        lines[-1].rindex('"metrics"')
    assert set(doc['numbers_compared']) == {
        'warmup.mismatched_tuples', 'window.mismatched_tuples',
        'window.count_difference'}
    assert all(c == {'value': 0, 'limit': 0}
               for c in doc['numbers_compared'].values())
    assert p.stderr.decode().splitlines()[-3:] == [
        'compared warmup.mismatched_tuples = 0 (limit 0)',
        'compared window.mismatched_tuples = 0 (limit 0)',
        'compared window.count_difference = 0 (limit 0)']
    assert doc['device']['platform'] == 'cpu'
    assert doc['device']['count'] == cfg['chips']
    assert doc['correct'] is True, lines
    assert doc['failed'] == 0 and doc['attempted'] > 0
    wl = _load('workloads', cell)
    assert set(doc['metrics']) == set(wl['end_to_end'])
    for m in doc['metrics'].values():
        assert m['value'] > 0 and m['unit']


def test_rehearsal_takes_a_new_cell_and_metric_as_files(throwaway):
    """A cell, a configuration and a per-layer metric that run.py has
    never heard of, added as files only."""
    throwaway('metrics', 't_requests_seen.py', THROWAWAY_METRIC)
    name, _ = _small_copy(throwaway, 'muskie-30d.scan-dense',
                          per_layer=['t_requests_seen'])
    rc, lines = _rehearse(name, trace=1)
    assert rc != 0
    doc = json.loads(lines[-1][len('rehearsal '):])
    assert doc['metrics']['t_requests_seen']['value'] == doc['attempted']
    assert 'window_compiles.scan' in doc['metrics']
    assert {'busy_s', 'window_s'} <= set(doc['device'])
    # a CPU has no device plane: nothing is written under a device
    # metric's name, and the run says so
    assert 'device_idle_share.scan' not in doc['metrics']
    assert doc['correct'] is False


@pytest.mark.parametrize('cell,fault', [
    ('muskie-30d.scan-dense', 'count'),
    ('muskie-30d-highcard.scan-highcard', 'truncate')])
def test_broken_timed_path_is_not_correct(cell, fault, throwaway):
    """The rest of a run with an answer altered where it is produced:
    `correct` comes out false, by the comparison and nothing else."""
    launcher = throwaway('tests', 't_broken_launcher.py', BROKEN_LAUNCHER % {
        'root': ROOT, 'fault': FAULTS[fault],
        'launcher': os.path.join(BENCH, 'drivers', 'launch_serve.py')})
    name, _ = _small_copy(throwaway, cell, launcher=launcher)
    rc, lines = _rehearse(name)
    assert rc != 0
    doc = json.loads(lines[-1][len('rehearsal '):])
    assert doc['correct'] is False
    assert any('mismatched_tuples' in ln and 'over its limit' in ln
               for ln in lines), lines
    assert doc['numbers_compared']['window.mismatched_tuples']['value'] > 0


def test_scan_off_the_sparse_lane_is_not_correct(throwaway):
    """The high-cardinality cell answered by another lane (here the
    host's): every answer equals the reference, and `correct` is false
    all the same, by the lane counter and the kernel records."""
    name, _ = _small_copy(throwaway, 'muskie-30d-highcard.scan-highcard',
                          environment={'DN_ENGINE': 'vector'})
    rc, lines = _rehearse(name)
    assert rc != 0
    doc = json.loads(lines[-1][len('rehearsal '):])
    assert doc['correct'] is False
    assert all(c['value'] == 0 for c in doc['numbers_compared'].values())
    assert any('did not engage' in ln for ln in lines), lines
    assert any('kernel records of the window do not all say' in ln
               for ln in lines), lines


@pytest.fixture(scope='module')
def traced():
    """One --trace 1 rehearsal of a cell at 20,000 records, made when
    first asked for and kept for the module: cell -> the line's doc."""
    docs = {}

    def get(cell):
        if cell not in docs:
            with _throwaway_files() as add:
                name, cfg = _small_copy(add, cell)
                _, lines = _rehearse(name, _mesh_env(cfg), trace=1)
            docs[cell] = json.loads(lines[-1][len('rehearsal '):])
        return docs[cell]
    return get


def _host_side_metrics():
    """Every (per-layer metric, cell that lists it) of BENCHMARK.json
    that a CPU can read: all but those taken from the device's trace."""
    with open(os.path.join(ROOT, 'BENCHMARK.json')) as f:
        doc = json.load(f)
    return [pytest.param(m, cell, id='%s-%s' % (m['name'], cell))
            for m in doc['per_layer'] if m['source'] != 'device_trace'
            for cell in m['workloads']]


@pytest.mark.parametrize('metric,cell', _host_side_metrics())
def test_per_layer_metric_reads_a_number(metric, cell, traced):
    """In every cell that lists it the metric's file finds something to
    read (a line that lacks it is refused on the chip), a share lies
    between 0 and 100, and nothing is negative."""
    got = traced(cell)['metrics']
    assert metric['name'] in got, sorted(got)
    value = got[metric['name']]
    assert value['unit'] == metric['unit']
    assert isinstance(value['value'], float)
    assert 0.0 <= value['value'] <= (100.0 if metric['unit'] == '%'
                                     else float('inf'))


def test_spare_trees_reads_zero_when_the_trees_run_out(throwaway):
    """A warm-up tree and one more: the client's loop ends for want of
    a tree after one build, whatever the window's seconds, and the
    metric says so; with the committed count trees are left."""
    cell = 'muskie-30d.build-daily'
    name, _ = _small_copy(throwaway, cell, build_trees=2)
    _, lines = _rehearse(name, trace=1, seconds=20)
    doc = json.loads(lines[-1][len('rehearsal '):])
    assert doc['attempted'] == 1 and doc['failed'] == 0
    assert doc['metrics']['spare_trees.build']['value'] == 0.0
    assert _load('workloads', cell)['build_trees'] >= 100


def test_no_program_no_result(tmp_path):
    """In a directory that holds only BENCHMARK.json and benchmarks/."""
    import shutil
    shutil.copy(os.path.join(ROOT, 'BENCHMARK.json'), str(tmp_path))
    shutil.copytree(BENCH, str(tmp_path / 'benchmarks'),
                    ignore=shutil.ignore_patterns('__pycache__', 't-*'))
    p = subprocess.run(
        [sys.executable, 'benchmarks/run.py', '--workload', _cells()[0],
         '--seed', '1', '--seconds', '1', '--trace', '0'],
        cwd=str(tmp_path), stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        env=dict(os.environ, JAX_PLATFORMS='cpu'))
    assert p.returncode != 0
    assert not p.stdout.strip()
