"""A numeric key column's codes (engine.NativeColumns.string_codes over
a column that holds numbers): the value -> code table kept on the
engine column across batches (engine._native_num_codes) against the
per-value path it stands in front of (engine._number_codes_by_value:
np.unique and one String(v) a distinct value, which is all the
function was before the table and is still its miss path).

Every case translates a sequence of batches twice, through two native
parsers over the same bytes: once as the engine does, once with the
table taken away.  The codes of every batch AND the dictionary's value
list must be equal, element for element (`ValueDict.code` numbers its
values by first call, and a reply's emission order follows from that);
and every code must decode to the String(v) of the value json.loads
reads from the same text.
"""

import json
import os
import random
import sys
import threading

import numpy as np
import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

from dragnet_tpu import batch as mod_batch                 # noqa: E402
from dragnet_tpu import cli                                # noqa: E402
from dragnet_tpu import engine as mod_engine               # noqa: E402
from dragnet_tpu import jsvalues as jsv                    # noqa: E402
from dragnet_tpu import native as mod_native               # noqa: E402
from dragnet_tpu.obs import metrics as obs_metrics         # noqa: E402
from dragnet_tpu.serve import server as mod_server         # noqa: E402

pytestmark = pytest.mark.skipif(mod_native.get_lib() is None,
                                reason='native parser not built')

BOUND = mod_engine.NUM_TRANS_BOUND
MISSING = object()      # a record without the key


def lines(values):
    """One record a value; a value is JSON text (so that `-0.0`,
    `1e21` and `1E3` reach the parser as written) or MISSING."""
    return ''.join(
        '{"other":1}\n' if v is MISSING else '{"k":%s}\n' % v
        for v in values).encode()


def by_value_alone(column, vals):
    uniq, ucodes, inv = mod_engine._number_codes_by_value(column, vals)
    return ucodes[inv]


def translate(batches, table=True, column=None):
    """The codes of each batch and the column they were coded in."""
    parser = mod_native.NativeParser(['k'], [False])
    column = column or mod_batch.StringColumn()
    out = []
    with pytest.MonkeyPatch.context() as mp:
        if not table:
            mp.setattr(mod_engine, '_native_num_codes', by_value_alone)
        for values in batches:
            assert parser.parse(lines(values)) == len(values)
            provider = mod_engine.NativeColumns(parser)
            out.append(np.array(provider.string_codes('k', column)))
            parser.reset_batch()
    return out, column


def js_string(text):
    return jsv.to_string(jsv.UNDEFINED if text is MISSING
                         else json.loads(text))


def ints(rng, n, hi):
    return [str(rng.randrange(hi)) for _ in range(n)]


RNG = random.Random(51)
CASES = {
    'small-ints': [['3', '1', '2', '3', '200'], ['2', '7', '1'],
                   ['200', '0']],
    'one-value': [['5'] * 4, ['5'] * 3],
    'zero-first': [['0'], ['0', '1']],
    'descending-batches': [['900', '800'], ['700', '600', '900'],
                           ['1', '800']],
    'float-forms-of-ints': [['1E3', '1000', '10.0e2', '1.0', '1'],
                            ['1000.0', '2e0', '2']],
    'past-the-bound': [
        [str(BOUND - 1), str(BOUND), str(BOUND + 1), '7'],
        [str(2 ** 31), str(2 ** 32 + 5), str(BOUND), '7', '8'],
        [str(BOUND - 1), str(BOUND * 3)]],
    'past-2^53': [
        [str(2 ** 53), str(2 ** 53 + 1), str(2 ** 53 + 2), '1'],
        ['12345678901234567890', str(2 ** 63), str(2 ** 64), '1e19',
         str(2 ** 63 - 1), '1'],
        [str(-2 ** 63), str(2 ** 53 - 1), '123456789012345680000']],
    'non-integral': [['1.5', '0.1', '2.5', '3'], ['1e-7', '0.1', '3.25'],
                     ['5e-324', '1.7976931348623157e308', '1.5']],
    'negative': [['-1', '-5', '5'], ['-5', '-2.5', '1'],
                 [str(-BOUND), '-1', '0']],
    'minus-zero-first': [['-0.0', '0', '-0'], ['0', '-0.0', '1']],
    'zero-then-minus-zero': [['0', '1'], ['-0.0', '-0', '0.0']],
    'exponent-forms': [['1e21', '1e20', '1e22', '100000000000000000000'],
                       ['1e21', '1.5e300', '1e-7', '123e-20'],
                       ['1e400', '-1e400', '4']],
    'mixed-tags': [
        ['7', '"7"', 'null', MISSING, 'true', '3'],
        ['false', '{}', '{"a":1}', '[1,2]', '[]', '"abc"', '7', '3.5'],
        ['[3]', '3', '"3"', MISSING, 'null', '[1,2]', '"true"', '9'],
        ['[[1],[2]]', '"1,2"', '12', '""', '0']],
    'numbers-after-strings': [['"a"', '"b"'], ['1', '"a"', '2'],
                              ['2', '"2"', '1']],
    'strings-that-are-numbers': [['"200"', '"7"'], ['7', '200', '8'],
                                 ['"8"', '8', '"9"'], ['9']],
    'first-seen-in-batch-3': [['1', '2', '3'], ['3', '2', '1', '1'],
                              ['2', '4', '1', '0'], ['4', '0', '3']],
    'a-lone-straggler-past-the-table': [
        ints(RNG, 50, 64), ints(RNG, 50, 64) + ['5000'],
        ints(RNG, 50, 64) + ['5000', '63']],
    'many-values': [ints(RNG, 3000, 4096) for _ in range(4)],
    'many-values-and-the-rest': [
        ints(RNG, 2000, 3000) +
        RNG.sample(['1.5', '-3', '"x"', 'null', MISSING, '1e21',
                    str(BOUND + 9), '[7]', 'true', '{}'] * 20, 200)
        for _ in range(4)],
}


@pytest.mark.parametrize('name', sorted(CASES))
def test_the_table_gives_the_per_value_codes(name):
    batches = CASES[name]
    assert 2 <= len(batches) <= 4
    got, column = translate(batches)
    want, ref = translate(batches, table=False)
    for i, (g, w) in enumerate(zip(got, want)):
        assert g.tolist() == w.tolist(), 'batch %d' % i
    assert column.dict.values == ref.dict.values
    assert len(set(ref.dict.values)) == len(ref.dict.values)
    assert getattr(ref, '_native_num_trans', None) is None
    # and both are what a record-at-a-time reader would say
    for values, codes in zip(batches, got):
        assert [column.dict.values[c] for c in codes] == \
            [js_string(v) for v in values]


def counts():
    got = {'table': 0, 'values': 0}
    for name, labels, m in obs_metrics.global_registry().snapshot():
        if name == 'scan_key_translate_total':
            got[dict(labels)['path']] = m.value
    return got


def grown(before):
    after = counts()
    return {k: after[k] - before[k] for k in after}


def test_the_table_is_what_answers_a_seen_batch():
    """The path a translation took, by its counter: the first batch
    and a batch with one new value go value by value, a batch of seen
    values is the table's alone, a column of strings counts nothing;
    and values outside the table's domain never enter it."""
    before = counts()
    translate([['1', '2'], ['2', '1', '1'], ['1', '3'], ['3', '2']])
    assert grown(before) == {'values': 2, 'table': 2}
    before = counts()
    translate([['"a"', '"b"'], ['"b"']])
    assert grown(before) == {'values': 0, 'table': 0}
    before = counts()
    translate([['1.5', '2'], ['1.5', '2'], ['2', '2']])
    assert grown(before) == {'values': 2, 'table': 1}
    before = counts()
    translate([['1', 'null', '"x"'], ['null', '1', MISSING]])
    assert grown(before) == {'values': 1, 'table': 1}


def test_the_table_grows_to_its_bound_and_is_never_written_in_place():
    _, column = translate([['3', '1']])
    first = column._native_num_trans
    assert len(first) == 4 and first.tolist()[1::2] == [5, 6]
    kept = first.copy()
    translate([['3', '40', str(BOUND - 1), str(BOUND), '-1', '0.5']],
              column=column)
    second = column._native_num_trans
    assert second is not first and first.tolist() == kept.tolist()
    assert len(second) == BOUND
    held = np.flatnonzero(second >= 0)
    assert held.tolist() == [1, 3, 40, BOUND - 1]
    assert [column.dict.values[c] for c in second[held]] == \
        ['1', '3', '40', str(BOUND - 1)]
    # nothing new: the same array answers, and stays
    translate([['40', '1', str(BOUND - 1)]], column=column)
    assert column._native_num_trans is second


@pytest.mark.parametrize('nthreads', [2, 8])
def test_threads_translate_one_column(nthreads):
    """scan_mt's workers share an engine column: each thread's codes
    decode to its own values whatever the interleaving, and the table
    that was published last is consistent with the dictionary."""
    column = mod_batch.StringColumn()
    work = [[ints(random.Random(7 + i), 400, 1500) +
             ['2.5', '-1', str(BOUND + 3)] for _ in range(6)]
            for i in range(nthreads)]
    barrier = threading.Barrier(nthreads)
    out = [None] * nthreads
    errors = []

    def run(i):
        try:
            barrier.wait(timeout=30)
            out[i] = translate(work[i], column=column)[0]
        except Exception as e:      # noqa: BLE001 (reported below)
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(nthreads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not errors and not any(t.is_alive() for t in threads)
    values = column.dict.values
    for batches, got in zip(work, out):
        for texts, codes in zip(batches, got):
            assert [values[c] for c in codes] == \
                [js_string(v) for v in texts]
    table = column._native_num_trans
    held = np.flatnonzero(table >= 0)
    assert [values[c] for c in table[held]] == [str(v) for v in held]
    # a racer may have published over another's values; one more pass
    # codes nothing new and leaves every value of the work in the table
    nvalues = len(values)
    translate([b for batches in work for b in batches], column=column)
    assert len(column.dict.values) == nvalues
    seen = {int(v) for batches in work for b in batches for v in b
            if v.isdigit() and int(v) < BOUND}
    assert np.flatnonzero(column._native_num_trans >= 0).tolist() == \
        sorted(seen)


# -- end to end: dn scan --points by two numeric keys ----------------------

def run_cli(args):
    with mod_server.thread_stdio() as cap:
        rc = cli.main(list(args))
    out, err = cap.finish()
    return rc, out, err


def test_scan_by_two_numeric_keys_is_every_engines_reply(tmp_path,
                                                         monkeypatch):
    """`dn scan --points` broken down by two numeric fields and a
    string one, over several batches: the same bytes from the vector
    engine, the device engine and the per-record engine (scan.py)."""
    from dragnet_tpu import device_scan as mod_ds
    rng = random.Random(3)
    path = str(tmp_path / 'c.log')
    with open(path, 'w') as f:
        for i in range(3000):
            rec = {'req': {'url': '/u/%d' % rng.randrange(9)},
                   'latency': rng.randrange(40) + (i > 2500) * 1000,
                   'dataLatency': rng.choice(
                       [rng.randrange(30), 2.5, -1, BOUND + i % 3,
                        None, '7'])}
            if i % 97 == 0:
                del rec['latency']
            f.write(json.dumps(rec) + '\n')
    monkeypatch.setenv('DRAGNET_CONFIG', str(tmp_path / 'rc.json'))
    monkeypatch.setenv('DN_SCAN_THREADS', '0')
    monkeypatch.setenv('DN_READ_SIZE', '8192')
    monkeypatch.setattr(mod_engine, 'BATCH_SIZE', 512)
    monkeypatch.setattr(mod_ds, 'BATCH_SIZE', 512)
    rc, _, err = run_cli(['datasource-add', '--path', path, 'nk'])
    assert rc == 0, err
    replies, translations = {}, {}
    for eng in ('host', 'vector', 'jax'):
        monkeypatch.setenv('DN_ENGINE', eng)
        before = counts()
        rc, out, err = run_cli(['scan', '--points', '-b',
                                'req.url,latency,dataLatency', 'nk'])
        assert rc == 0, err
        replies[eng] = out
        translations[eng] = grown(before)
    # the two batch engines translate both columns every batch
    # (`latency` from the table once its forty values have been seen
    # and again after record 2,500 brings forty more, `dataLatency`
    # value by value: it holds 2.5 and -1), the per-record engine never
    assert translations['host'] == {'table': 0, 'values': 0}
    assert translations['vector'] == translations['jax']
    assert translations['jax']['table'] >= 2
    assert sum(translations['jax'].values()) >= 2 * 5
    assert replies['host'].count(b'\n') > 2000
    assert replies['vector'] == replies['host']
    assert replies['jax'] == replies['host']
