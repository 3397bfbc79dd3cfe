"""The reply of a columnar result: output.print_points over an
aggr.PointBlock writes, by column, the bytes its per-point loop
writes for block.points(), and block.points() is the list
Aggregator.points() returns.

The per-point loop is the definition; every case here formats one
block both ways, through the server's capture (utf-8 with the CLI's
surrogate policy), and compares the bytes.
"""

import json
import os
import sys

import numpy as np
import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

from dragnet_tpu import aggr as mod_aggr                   # noqa: E402
from dragnet_tpu import cli                                # noqa: E402
from dragnet_tpu import jsvalues as jsv                    # noqa: E402
from dragnet_tpu import output as mod_output               # noqa: E402
from dragnet_tpu import query as mod_query                 # noqa: E402
from dragnet_tpu.obs import metrics as obs_metrics         # noqa: E402
from dragnet_tpu.serve import qcache as mod_qcache         # noqa: E402
from dragnet_tpu.serve import server as mod_server         # noqa: E402
from dragnet_tpu.vpipe import Pipeline                     # noqa: E402

NROWS = 400


def written(points):
    """(the path print_points took, the bytes the capture holds)."""
    with mod_server.thread_stdio() as cap:
        path = mod_output.print_points(points, sys.stdout)
    return path, cap.finish()[0]


def query_of(breakdowns):
    return mod_query.query_load({'breakdowns': breakdowns})


def columnar(query, tables, weights, seed=7, nrows=NROWS):
    """An Aggregator with a stage, set columnar over `nrows` distinct
    random tuples: per decomposition `tables` has the dictionary's
    values (a plain column) or a range of ordinals (a bucketized one)."""
    rng = np.random.default_rng(seed)
    cols, decoders = [], []
    for name, table in zip([b['name'] for b in query.qc_breakdowns],
                           tables):
        if name in query.qc_bucketizers:
            lo, hi = table
            cols.append(rng.integers(lo, hi, nrows))
            decoders.append(('ord', None))
        else:
            cols.append(rng.integers(0, len(table), nrows))
            decoders.append(('str', list(table)))
    if cols:
        _, first = np.unique(np.stack(cols, axis=1), axis=0,
                             return_index=True)
        first.sort()
        cols = [c[first] for c in cols]
        n = len(first)
    else:
        n = nrows
    if callable(weights):
        weights = weights(rng, n)
    ag = mod_aggr.Aggregator(query, stage=Pipeline().stage('agg'))
    ag.set_columnar(cols, weights, decoders)
    return ag


def check_block(make):
    """Format one aggregate's block both ways; the block's points are
    the aggregate's, and so are its counters.  Returns the path."""
    ag, twin = make(), make()
    block = ag.point_block()
    points = twin.points()
    # by repr: 10 is not 10.0 here, and a NaN is a NaN
    assert repr(block.points()) == repr(points)
    assert len(block) == len(points)
    assert ag.stage.counters == twin.stage.counters
    path, got = written(block)
    assert got == written(points)[1]
    assert got.count(b'\n') == len(points)
    return path


def int_weights(rng, n):
    return rng.integers(1, 26, n).astype('float64')


STRINGS = {
    'quotes': ['say "hi"', "it's", '""', '"'],
    'backslashes': ['a\\b', '\\', '\\\\n', 'c:\\dir\\"x"'],
    'controls': ['tab\there', 'nl\nhere', 'cr\rhere', '\b\f',
                 '\x00\x01\x1f', '\x7f'],
    'non_bmp': ['\U0001f600', 'a\U00010000b', '\u00e9\u4e2d\uffff'],
    'lone_surrogates': ['\ud800', 'x\udfffy', '\udc00\ud800'],
    'array_index_like': ['10', '010', '-1', '4294967295',
                         '4294967294', '0', '007', '1e3', ''],
    'numbers': [10, -3, 2.5, -0.5, 1e21, 1e-7, -0.0, 0, 10.0,
                2 ** 53 + 2, 2.0 ** 70, 1.5e300],
    'null_and_nan': [None, float('nan'), float('inf'), 'null', 'NaN'],
}


@pytest.mark.parametrize('kind', sorted(STRINGS))
def test_plain_columns(kind):
    q = query_of([{'name': 'key "\\\n\U0001f600'}, {'name': 'b'}])
    path = check_block(lambda: columnar(
        q, [STRINGS[kind], ['x', 'y', '3']], int_weights))
    assert path == 'block'


@pytest.mark.parametrize('aggr,mins', [
    ('quantize', int), ('lquantize', int), ('lquantize', float),
    ('date', int)])
def test_bucketized_columns(aggr, mins):
    """Bucket minima reach the reply as bucket_min made them: ints,
    or floats where the step is one (10.0 is written `10`)."""
    if aggr == 'date':
        b = {'name': 'ts', 'field': 'time', 'date': '',
             'aggr': 'lquantize', 'step': 3600}
    elif aggr == 'quantize':
        b = {'name': 'lat', 'aggr': 'quantize'}
    else:
        b = {'name': 'lat', 'aggr': 'lquantize', 'step': 10}
    q = query_of([{'name': 'host'}, b])
    if mins is float:
        q.qc_bucketizers['lat'] = mod_query.LinearBucketizer(2.5)
    ords = (385000, 385900) if aggr == 'date' else (-4, 40)

    def make():
        return columnar(q, [['a', 'b', '7'], ords], int_weights)
    assert check_block(make) == 'block'
    table = make().point_block().tables[1]
    assert {type(v) for v in table} == {mins}


def big_exact(rng, n):
    # exact Python numbers, as the flat -> columnar conversion keeps
    return [int(rng.choice([1, 2, 2 ** 53 + 1, 2 ** 55 + 1, 10 ** 21,
                            2 ** 70])) if i % 3 else 2 ** 53 + 1 + i
            for i in range(n)]


WEIGHTS = {
    'exact_ints_above_2_53': big_exact,
    'exact_list_of_ints_and_floats':
        lambda rng, n: [0.5 * i if i % 2 else i for i in range(n)],
    'nonintegral_float64':
        lambda rng, n: rng.integers(1, 9, n) / 4.0,
    'integral_float64': int_weights,
    'float64_above_2_53':
        lambda rng, n: rng.integers(1, 9, n) * float(2 ** 60),
    'negative_and_zero':
        lambda rng, n: rng.integers(-3, 3, n).astype('float64'),
    'nan_and_inf':
        lambda rng, n: np.where(np.arange(n) % 2, np.nan, np.inf),
}


@pytest.mark.parametrize('kind', sorted(WEIGHTS))
def test_weights(kind):
    q = query_of([{'name': 'a'}, {'name': 'b'}])
    path = check_block(lambda: columnar(
        q, [['x', 'y', '1', '0'], ['p%d' % i for i in range(300)]],
        WEIGHTS[kind]))
    assert path == 'block'


@pytest.mark.parametrize('ndecomps', [1, 2, 3, 5])
def test_decompositions(ndecomps):
    names = ['f%d' % i for i in range(ndecomps)]
    q = query_of([{'name': n} for n in names])
    path = check_block(lambda: columnar(
        q, [['v%d' % j for j in range(7)]] * ndecomps, int_weights))
    assert path == 'block'


def test_no_decompositions_is_the_loops():
    """An aggregate with no decomposition is never columnar; a block
    with no columns, should one be made, goes to the loop."""
    q = query_of([])
    ag = mod_aggr.Aggregator(q)
    ag.write({}, 3)
    assert ag.point_block() is None
    assert written(ag.points()) == ('tuple', b'{"fields":{},"value":3}\n')
    block = mod_aggr.PointBlock([], [], [], [3, 4])
    assert written(block) == ('tuple', written(block.points())[1])


def test_empty_result():
    q = query_of([{'name': 'a'}, {'name': 'lat', 'aggr': 'quantize'}])
    path = check_block(lambda: columnar(
        q, [['x'], (0, 5)], np.zeros(0), nrows=0))
    assert path == 'block'
    block = columnar(q, [['x'], (0, 5)], np.zeros(0),
                     nrows=0).point_block()
    assert (len(block), written(block)) == (0, ('block', b''))


@pytest.mark.parametrize('case', ['undefined_value', 'one_name_twice'])
def test_what_the_loop_alone_can_write(case):
    """Where the loop drops a key (a value with no JSON text, two
    columns of one name) the block formatter stands aside."""
    if case == 'undefined_value':
        q = query_of([{'name': 'a'}, {'name': 'b'}])
        tables = [['x', jsv.UNDEFINED, 'y'], ['p', 'q']]
    else:
        q = query_of([{'name': 'a'}, {'name': 'a'}])
        tables = [['x', 'y', 'z'], ['p', 'q']]
    path = check_block(lambda: columnar(q, tables, int_weights))
    assert path == 'tuple'


def test_unused_dictionary_values_are_not_stringified(monkeypatch):
    """A table holds the engine's whole dictionary; only the values a
    code names are formatted, each once."""
    q = query_of([{'name': 'a'}])
    seen = []
    real = jsv.json_stringify

    def counting(v):
        seen.append(v)
        return real(v)
    table = ['v%d' % i for i in range(1000)]
    ag = mod_aggr.Aggregator(q)
    ag.set_columnar([np.array([5, 7, 9])], np.array([1.0, 2.0, 2.0]),
                    [('str', table)])
    block = ag.point_block()
    monkeypatch.setattr(jsv, 'json_stringify', counting)
    assert written(block)[0] == 'block'
    assert sorted(map(str, seen)) == ['1', '2', 'v5', 'v7', 'v9']


def test_text_size_needs_no_dicts(monkeypatch):
    """The result cache sizes a block from its arrays, about what the
    reply's text is, and the dicts stay unbuilt."""
    q = query_of([{'name': 'req.url'}, {'name': 'lat'}])
    ag = columnar(q, [['/a/%d' % i for i in range(50)],
                      [str(i) for i in range(90)]], int_weights)
    block = ag.point_block()
    monkeypatch.setattr(mod_aggr.PointBlock, '_make_points', None)
    from dragnet_tpu.datasource_file import ScanResult
    result = ScanResult(Pipeline(), points=block, query=q)
    est = mod_qcache._estimate_nbytes(result)
    assert result.has_points and result.npoints == len(block)
    monkeypatch.undo()
    nbytes = len(written(block)[1])
    assert 0.8 * nbytes <= est <= 1.3 * nbytes + 512


# -- the threshold the repo already has, through the CLI ---------------------

def run_cli(args):
    with mod_server.thread_stdio() as cap:
        rc = cli.main(list(args))
    out, err = cap.finish()
    return rc, out, err


def reply_tuples():
    return {dict(labels)['path']: m.value
            for name, labels, m in obs_metrics.global_registry().snapshot()
            if name == 'reply_tuples_total'}


@pytest.fixture(scope='module')
def flat_corpora(tmp_path_factory):
    """Two datasources of FLAT_COLUMNAR_MIN - 1 and FLAT_COLUMNAR_MIN
    distinct tuples (the second holds the first's records and one
    more), scanned by the per-record host engine: flat writes."""
    root = str(tmp_path_factory.mktemp('output_block'))
    nmin = mod_aggr.Aggregator.FLAT_COLUMNAR_MIN
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv('DRAGNET_CONFIG', os.path.join(root, 'rc.json'))
        mp.setenv('DN_ENGINE', 'host')
        for name, n in (('below', nmin - 1), ('at', nmin)):
            path = os.path.join(root, name + '.log')
            with open(path, 'w') as f:
                for i in range(n + 40):
                    k = i % n       # 40 tuples are met twice
                    f.write(json.dumps({
                        'host': 'h%d "\\' % (k % 13),
                        'n': str(k // 13),
                        'latency': k % 7}) + '\n')
            rc, _out, err = run_cli(['datasource-add', '--path', path,
                                     name])
            assert rc == 0, err
        yield nmin
    obs_metrics.reset_global_registry()


SCAN = ['scan', '--points', '--counters', '-b',
        'host,n,latency[aggr=quantize]']


@pytest.mark.parametrize('ds,path', [('below', 'tuple'), ('at', 'block')])
def test_flat_aggregate_at_the_threshold(flat_corpora, monkeypatch, ds,
                                         path):
    """One tuple under FLAT_COLUMNAR_MIN the reply is the loop's; at
    it the aggregate turns columnar and the reply is the block's: the
    same bytes and the same --counters as the loop gives that very
    result, and (all but the last tuple's line and the counters of
    one record) as the smaller result's."""
    nmin = flat_corpora
    n = nmin if ds == 'at' else nmin - 1
    obs_metrics.reset_global_registry()
    rc, out, err = run_cli(SCAN + [ds])
    assert rc == 0, err
    assert reply_tuples() == {path: n}
    assert out.count(b'\n') == n

    # the definition: the same result through the per-point loop
    monkeypatch.setattr(mod_output, '_block_text', lambda block: None)
    obs_metrics.reset_global_registry()
    rc, out_loop, err_loop = run_cli(SCAN + [ds])
    assert rc == 0
    assert reply_tuples() == {'tuple': n}
    assert (out, err) == (out_loop, err_loop)


def test_threshold_replies_differ_by_the_one_tuple(flat_corpora):
    nmin = flat_corpora
    _, below, err_below = run_cli(SCAN + ['below'])
    _, at, err_at = run_cli(SCAN + ['at'])
    extra = set(at.splitlines()) - set(below.splitlines())
    last = nmin - 1
    assert extra == {json.dumps(
        {'fields': {'host': 'h%d "\\' % (last % 13),
                    'n': str(last // 13),
                    'latency': mod_query.P2Bucketizer().bucket_min(
                        mod_query.P2Bucketizer().bucketize(last % 7))},
         'value': 1}, separators=(',', ':')).encode()}
    assert len(err_at.splitlines()) == len(err_below.splitlines())
