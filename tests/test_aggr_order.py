"""The columnar aggregate's emission order (Aggregator._columnar_order:
one rank column a level, one stable argsort of their fused key, a
lexsort over the same columns where the fused key would overflow)
against two independent statements of the rule:

* the flat map's nested `_walk()`: the same tuples written through
  `write_key` in the same arrival order with FLAT_COLUMNAR_MIN raised,
  `points()` and `rows()` compared list for list;
* the six-key lexsort this function was before it fused its keys
  (`lexsort_order` below, kept here as the statement of what the
  permutation was), compared index for index, also where the walk and
  the columnar order have always parted (the int 5 beside the string
  "5": two keys to Python's dict, one property to JS).
"""

import os
import random
import sys

import numpy as np
import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

from dragnet_tpu import aggr as mod_aggr                   # noqa: E402
from dragnet_tpu import engine as mod_engine               # noqa: E402
from dragnet_tpu import query as mod_query                 # noqa: E402
from dragnet_tpu.obs import metrics as obs_metrics         # noqa: E402

STR, ORD = 'str', 'ord'
# keys a plain column can hold: array-index-like strings, strings that
# only look like them, and strings that do not
INDEXLIKE = ['0', '7', '17', '100', '4294967294', '65536']
NEARLY = ['007', '4294967295', '-1', '1.5', '', '1e3', ' 5', '00']
WORDS = ['x', 'y', 'GET', 'null', 'undefined', 'true', '/a/b']


def query_of(kinds):
    """A query of one decomposition a level: a plain column (`STR`) or
    a bucketized one (`ORD`)."""
    return mod_query.query_load({'breakdowns': [
        {'name': 'f%d' % i} if kind == STR else
        {'name': 'f%d' % i, 'aggr': 'lquantize', 'step': 10}
        for i, kind in enumerate(kinds)]})


def distinct(tuples):
    return list(dict.fromkeys(tuples))


def walked(kinds, tuples, weights):
    """The reference: the tuples written one by one into the flat map
    and enumerated by the nested `_walk()`."""
    aggr = mod_aggr.Aggregator(query_of(kinds))
    for keys, w in zip(tuples, weights):
        aggr.write_key(keys, w)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(mod_aggr.Aggregator, 'FLAT_COLUMNAR_MIN', 10 ** 9)
        points, rows = aggr.points(), aggr.rows()
    assert aggr._cols is None          # the walk it was
    return points, rows


def columnar(kinds, tuples, weights, seed=3):
    """The same tuples installed as code columns in arrival order, as
    an engine hands them over: a plain column's dictionary in an order
    of its own, holding values that no tuple names."""
    rng = random.Random(seed)
    cols, decoders = [], []
    for depth, kind in enumerate(kinds):
        keys = [t[depth] for t in tuples]
        if kind == ORD:
            cols.append(np.array(keys, dtype=np.int64))
            decoders.append((ORD, None))
            continue
        values = distinct(keys) + ['unused', '3', 12]
        rng.shuffle(values)
        index = {(type(v), v): i for i, v in enumerate(values)}
        cols.append(np.array([index[(type(k), k)] for k in keys],
                             dtype=np.int64))
        decoders.append((STR, values))
    aggr = mod_aggr.Aggregator(query_of(kinds))
    aggr.set_columnar(cols, list(weights), decoders)
    return aggr


def lexsort_order(aggr):
    """The permutation as the function computed it before its keys
    were fused: per level a class column and a value column (the
    numeric value, or the first occurrence of the (parent group, code)
    pair), and one six-key, eight-key, ... lexsort."""
    n = len(aggr._cweights)
    if not n:
        return np.zeros(0, dtype=np.int64)
    levels = []
    gid = np.zeros(n, dtype=np.int64)
    for codes, dec in zip(aggr._cols, aggr._cdec):
        if dec[0] == ORD:
            nn = np.zeros(n, dtype=np.int8)
            sk = codes
        else:
            knn = np.array([0 if (isinstance(s, str) and
                                  mod_aggr._is_array_index(s)) or
                            (isinstance(s, int) and
                             not isinstance(s, bool)) else 1
                            for s in dec[1]], dtype=np.int8)
            kval = np.array([int(s) if k == 0 else 0
                             for s, k in zip(dec[1], knn)],
                            dtype=np.int64)
            nn = knn[codes]
            sk = kval[codes]
        _, first_idx, inv = np.unique(
            np.stack([gid, codes], axis=1), axis=0, return_index=True,
            return_inverse=True)
        inv = inv.reshape(-1)
        levels.append((nn, np.where(nn == 1, first_idx[inv], sk)))
        gid = inv
    seq = []
    for nn, sk in reversed(levels):
        seq.append(sk)
        seq.append(nn)
    return np.lexsort(tuple(seq))


def order_paths():
    return {dict(labels)['path']: m.value
            for name, labels, m in obs_metrics.global_registry().snapshot()
            if name == 'aggr_order_total'}


def grew(before):
    after = order_paths()
    return {p: after.get(p, 0) - before.get(p, 0)
            for p in ('fused', 'lexsort')
            if after.get(p, 0) != before.get(p, 0)}


def random_tuples(kinds, pools, n, seed):
    rng = random.Random(seed)
    return distinct(tuple(rng.choice(pool) for pool in pools)
                    for _ in range(n))


def pools_of(kinds, seed, pool=None):
    """A pool of keys a level: ordinals (negative ones too) for a
    bucketized level, a mix of the three kinds of string for a plain
    one."""
    rng = random.Random(seed)
    out = []
    for kind in kinds:
        if kind == ORD:
            out.append(list(range(-4, 9)))
        else:
            p = list(pool or INDEXLIKE + NEARLY + WORDS)
            rng.shuffle(p)
            out.append(p[:rng.randrange(3, len(p) + 1)])
    return out


def case(kinds, tuples, path='fused'):
    tuples = distinct(tuples)
    rng = random.Random(len(tuples))
    weights = [rng.choice([1, 2, 3, 2 ** 55 + 1]) for _ in tuples]
    return kinds, tuples, weights, path


# wide spans: three bucketized levels whose ordinals span 2^31 each,
# under two plain ones: the fused key would pass 2^62
WIDE = [(-2 ** 30, 2 ** 30 - 1), (0, 2 ** 31), (5, -2 ** 31)]


def wide_tuples(n, seed):
    rng = random.Random(seed)
    return [(rng.choice(['x', '9', 'y', '10']),
             rng.choice([a for ab in WIDE for a in ab] + [0, 1, 77]),
             rng.choice(WIDE[1] + (3, 4)), rng.choice(WIDE[2] + (6,)),
             rng.choice(['b', '2', 'a']))
            for _ in range(n)]


def cell_shape(n, seed):
    """Tuples at the high-cardinality cell's shape: 500 URLs under
    4,096 x 4,096 latencies (array-index-like strings)."""
    rng = np.random.default_rng(seed)
    urls = ['/%d/obj/%x' % (i % 7, i) for i in range(500)]
    lat = [str(int(v)) for v in rng.permutation(20000)[:4096]]
    a = rng.integers(0, 500, n).tolist()
    b = rng.integers(0, 4096, n).tolist()
    c = rng.integers(0, 4096, n).tolist()
    return [(urls[i], lat[j], lat[k]) for i, j, k in zip(a, b, c)]


S, O = STR, ORD
CASES = {
    'levels-1': case([S], random_tuples([S], pools_of([S], 1), 60, 1)),
    'levels-2': case([S, S], random_tuples(
        [S, S], pools_of([S, S], 2), 400, 2)),
    'levels-3': case([S, O, S], random_tuples(
        [S, O, S], pools_of([S, O, S], 3), 900, 3)),
    'levels-4': case([O, S, S, O], random_tuples(
        [O, S, S, O], pools_of([O, S, S, O], 4), 2000, 4)),
    'levels-5': case([S, S, O, S, S], random_tuples(
        [S, S, O, S, S], pools_of([S] * 2 + [O] + [S] * 2, 5), 4000, 5)),
    'all-numeric': case([S, S, S], random_tuples(
        [S] * 3, pools_of([S] * 3, 6, INDEXLIKE), 300, 6)),
    'all-non-numeric': case([S, S, S], random_tuples(
        [S] * 3, pools_of([S] * 3, 7, WORDS + NEARLY), 600, 7)),
    'mixed-classes-in-a-level': case([S, S], [
        ('x', 'b'), ('10', 'a'), ('x', '3'), ('9', 'b'), ('y', '20'),
        ('10', '4'), ('x', 'a'), ('9', '1'), ('y', 'b'), ('10', 'b')]),
    'index-like-beside-nearly': case([S, S], [
        (a, b) for b in ('k', '2') for a in
        ('007', '4294967295', '7', '-1', '4294967294', '1.5', '', '0',
         '00', '1e3')]),
    'ints-beside-strings': case([S, S], [
        ('x', 8), (3, 'x'), ('10', 7), (3, '6'), ('x', '9'), (7, 2),
        ('2', 'x'), (7, 'y'), (-1, 1), ('10', 'x'), (-1, -5)]),
    'ord-beside-str': case([O, S, O], random_tuples(
        [O, S, O], pools_of([O, S, O], 8), 500, 8)),
    'ord-only': case([O, O], random_tuples(
        [O, O], pools_of([O, O], 9), 120, 9)),
    # parent "2" sees y then x, parent "1" x then y, and y is the
    # first of all: a non-numeric key ranks within its parent
    'non-numeric-below-numeric': case([S, S], [
        ('2', 'y'), ('1', 'x'), ('1', 'y'), ('2', 'x'), ('1', 'z'),
        ('2', 'z')]),
    'non-numeric-below-ord-below-non-numeric': case([S, O, S], [
        ('b', 2, 'y'), ('a', 2, 'x'), ('a', 1, 'y'), ('b', 2, 'x'),
        ('a', 2, 'y'), ('b', 1, 'x'), ('a', 1, 'x'), ('b', 1, 'y')]),
    'numeric-below-non-numeric': case([S, S, S], random_tuples(
        [S] * 3, [WORDS, INDEXLIKE, INDEXLIKE], 200, 10)),
    'non-numeric-last': case([S, S, S], random_tuples(
        [S] * 3, [INDEXLIKE, INDEXLIKE, WORDS + NEARLY], 200, 11)),
    'non-numeric-in-the-middle': case([S, S, S], random_tuples(
        [S] * 3, [INDEXLIKE, WORDS, INDEXLIKE], 200, 12)),
    'one-tuple': case([S, O, S], [('x', 3, '7')]),
    'one-tuple-one-level': case([S], [('7',)]),
    'zero-tuples': case([S, O, S], []),
    'wide-spans-lexsort': case([S, O, O, O, S], wide_tuples(3000, 13),
                               path='lexsort'),
    'wide-spans-all-ord-lexsort': case(
        [O, O, O], [t[1:4] for t in wide_tuples(500, 14)],
        path='lexsort'),
    'cell-shape-200000': case([S, S, S], cell_shape(200000, 15)),
}


@pytest.mark.parametrize('name', sorted(CASES))
def test_columnar_order_is_the_nested_walks(name, monkeypatch):
    kinds, tuples, weights, path = CASES[name]
    points, rows = walked(kinds, tuples, weights)
    aggr = columnar(kinds, tuples, weights)
    before = order_paths()
    order = aggr._columnar_order()
    # which sort it took: the fused key's, or the lexsort where the
    # spans' product passes 2^62; nothing is counted for no tuple
    assert grew(before) == ({path: 1} if tuples else {})
    assert sorted(order.tolist()) == list(range(len(tuples)))
    assert aggr.points() == points
    assert aggr.rows() == rows
    assert len(points) == len(tuples)
    # the other sort over the same rank columns: the same permutation
    if path == 'fused':
        monkeypatch.setattr(mod_engine, 'fuse_codes', lambda cols: None)
        before = order_paths()
        assert np.array_equal(aggr._columnar_order(), order)
        assert grew(before) == ({'lexsort': 1} if tuples else {})
    # and the permutation this function gave before it fused its keys
    assert np.array_equal(lexsort_order(aggr), order)


def test_a_non_numeric_key_ranks_within_its_parent():
    """The case above, spelled out: y arrived first of all, but under
    parent "1" x did."""
    kinds, tuples, weights, _path = CASES['non-numeric-below-numeric']
    aggr = columnar(kinds, tuples, weights)
    assert [[r[0], r[1]] for r in aggr.rows()] == [
        ['1', 'x'], ['1', 'y'], ['1', 'z'],
        ['2', 'y'], ['2', 'x'], ['2', 'z']]


@pytest.mark.parametrize('seed', range(6))
def test_fused_order_is_the_lexsorts_permutation(seed):
    """Random nestings, the int 5 beside "5" and "05" among their keys
    (where the walk is no reference: Python's dict holds two keys for
    JS's one property, and the columnar order has always let their
    subtrees interleave): the fused sort gives the six-key lexsort's
    permutation, index for index."""
    rng = random.Random(100 + seed)
    kinds = [rng.choice([S, S, O]) for _ in range(rng.randrange(1, 5))]
    pool = INDEXLIKE + NEARLY + WORDS + [5, '5', '05', 17, 0, -3]
    pools = [list(range(-3, 40)) if k == O else pool for k in kinds]
    tuples = random_tuples(kinds, pools, 5000, seed)
    aggr = columnar(kinds, tuples, [1] * len(tuples), seed=seed)
    before = order_paths()
    order = aggr._columnar_order()
    assert grew(before) == {'fused': 1}
    assert np.array_equal(order, lexsort_order(aggr))


POOLS = {'W': (S, WORDS), 'N': (S, INDEXLIKE), 'O': (O, list(range(5)))}


@pytest.mark.parametrize('levels,groupings', [
    ('WNN', 1),         # the high-cardinality cell's: URLs, then numbers
    ('ONO', 0), ('NNN', 0), ('WNWN', 3), ('OOW', 3), ('NW', 2)])
def test_a_level_is_grouped_only_where_a_key_reads_it(levels, groupings,
                                                      monkeypatch):
    """The (parent group, code) grouping is computed down to the last
    level that holds a non-numeric key and no further: an all-numeric
    level below it costs its gathers only (its dictionary's unused
    non-numeric entries do not count)."""
    kinds = [POOLS[c][0] for c in levels]
    tuples = random_tuples(kinds, [POOLS[c][1] for c in levels], 300, 21)
    aggr = columnar(kinds, tuples, [1] * len(tuples))
    calls = []
    real = mod_aggr._unique_1d

    def counted(vals, span):
        calls.append(len(vals))
        return real(vals, span)
    monkeypatch.setattr(mod_aggr, '_unique_1d', counted)
    order = aggr._columnar_order()
    assert len(calls) == groupings
    assert np.array_equal(order, lexsort_order(aggr))
