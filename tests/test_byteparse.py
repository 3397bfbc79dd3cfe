"""Raw-byte ingest projection (dragnet_tpu/byteparse.py): fuzz
differential against the host parser, scan/build byte parity across
DN_PARSE lanes, lane selection, counters.

The contract under test: with DN_PARSE=vector (or device) the scan and
build outputs are byte-identical to the host lane for ANY input —
escapes, UTF-8 multibyte, \\r\\n line endings, chunk-boundary line
splits, duplicate keys, exponent-form numbers, truncated final lines —
because every line the fast path cannot prove simple routes through
the very parser the host lane runs; and ineligible queries (dotted
paths, non-json formats) fall back to the host lane with a counter,
never an error."""

import json
import os
import random
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from dragnet_tpu import byteparse as mod_byteparse  # noqa: E402
from dragnet_tpu import native as mod_native  # noqa: E402
from dragnet_tpu import query as mod_query  # noqa: E402
from dragnet_tpu.byteparse import ByteParser  # noqa: E402
from dragnet_tpu.datasource_file import DatasourceFile  # noqa: E402
from dragnet_tpu.ops import byteparse_kernels as bk  # noqa: E402


# ---------------------------------------------------------------------------
# corpus generator: adversarial lines around every fallback trigger
# ---------------------------------------------------------------------------

ADVERSARIAL = [
    b'', b'null', b'true', b'[1,2]', b'"str"', b'12.5', b'xxx',
    b'{bad', b'{"a":}', b'{"a":1,}', b'{"a",1}', b'{"a":1:2}',
    b'{"a" :1}', b'{"a": 1}', b'{ }', b'{}', b'{"":1}',
    b'{"host":"a","host":"b"}',                 # duplicate key
    b'{"host":"x"}\r',                          # \r\n ending
    b'{"latency":01}', b'{"latency":1.}', b'{"latency":.5}',
    b'{"latency":+1}', b'{"latency":1e}', b'{"latency":-}',
    b'{"latency":truex}', b'{"latency":nul}',
    b'{"latency":1e3}', b'{"latency":-1.25e-2}',
    b'{"latency":184467440737095516150}',       # > uint64
    b'{"latency":0.30000000000000004}',
    b'{"host":"esc\\u0041pe"}', b'{"host":"tab\\there"}',
    '{"host":"café"}'.encode(),            # multibyte UTF-8
    '{"host":"\U0001f300"}'.encode(),           # astral plane
    b'{"deep":{"a":{"b":{"c":1}}},"host":"deep"}',
    b'{"arr":[1,[2,["x"]]],"host":"arrv"}',
    b'{"host":[1,"two"]}', b'{"host":{"nested":1}}',
    # non-canonical JSON numbers inside a projected array: the fast
    # path interns the raw span ('[1e2]'), the fallback/host lane a
    # round-tripped serialization ('[100.0]') — value-equivalent by
    # construction (both decode to the same array downstream), and
    # the scan-parity tests pin that outputs agree
    b'{"host":[1e2,1.50],"latency":1}',
    '{"host":[1e2],"pad":"café"}'.encode(),   # ...on a fallback line
    b'{"host":"}{not struct"}',                 # braces inside string
    b'{"host":"has,comma:and\\"quote"}',
    b'{"time":"2014-05-02T10:11:12.345Z","host":"t"}',
    b'{"time":"2014-05-02","host":"d"}',
    b'{"time":"  2014-05-02  ","host":"pad"}',
    b'{"time":"2014-02-30T00:00:00Z","host":"badday"}',
    b'{"time":1400000000,"host":"numdate"}',
    b'{"time":true,"host":"booldate"}',
]


def gen_lines(seed, count=1200, tame_numbers=False):
    rng = random.Random(seed)
    hosts = ['ralph', 'janey', 'k"q', 'with space', 'unié', '']
    out = []
    for i in range(count):
        r = rng.random()
        if r < 0.12:
            out.append(rng.choice(ADVERSARIAL))
            continue
        rec = {}
        if rng.random() < 0.9:
            rec['host'] = rng.choice(hosts)
        if rng.random() < 0.9:
            if tame_numbers:
                # index sinks store bucket minima as SQLite integers;
                # astronomically large quantize buckets overflow them
                # in EVERY lane, so the build corpus stays in range
                rec['latency'] = rng.choice([
                    rng.randrange(0, 5000), rng.uniform(0, 100),
                    '33', 'zz', None, True, [1, 'a'],
                ])
            else:
                rec['latency'] = rng.choice([
                    rng.randrange(-10**6, 10**6),
                    rng.uniform(-1e6, 1e6),
                    rng.randrange(-(1 << 60), 1 << 60), 1e300,
                    5e-324, 2**53, 2**53 + 2, -0.0, 0.1, '33', 'zz',
                    None, True, False, [1, 'a'], {'x': 1},
                    float('%de%d' % (rng.randrange(1, 999),
                                     rng.randrange(-30, 30))),
                ])
        if rng.random() < 0.8:
            rec['time'] = rng.choice([
                '2014-05-%02dT%02d:00:00Z' % (rng.randrange(1, 28),
                                              rng.randrange(24)),
                '2014-05-02T10:11:12.%03dZ' % rng.randrange(1000),
                '2016-02-29T00:00:00Z', rng.randrange(1, 2**31),
                'garbage', '2014-05-02',
            ])
        if rng.random() < 0.5:
            rec['pad%d' % rng.randrange(3)] = rng.choice(
                [[1, [2, [3]]], {'a': {'b': 2}}, 'x', 9])
        s = json.dumps(rec, separators=(',', ':'),
                       ensure_ascii=rng.random() < 0.5)
        if rng.random() < 0.05:
            cut = rng.randrange(0, len(s) + 1)
            s = s[:cut] + rng.choice(['', '}', 'x', '\\'])
        out.append(s.encode())
    return out


def write_corpus(path, seed, crlf=False, truncate=False,
                 tame_numbers=False):
    lines = gen_lines(seed, tame_numbers=tame_numbers)
    sep = b'\r\n' if crlf else b'\n'
    data = sep.join(lines)
    if not truncate:
        data += sep
    else:
        data += sep + b'{"host":"trunc","latency":'   # cut mid-line
    path.write_bytes(data)


QUERIES = [
    {'breakdowns': [{'name': 'host'}]},
    {'breakdowns': [{'name': 'host'},
                    {'name': 'latency', 'aggr': 'quantize'}]},
    {'filter': {'gt': ['latency', 50]},
     'breakdowns': [{'name': 'host'}]},
    {'timeAfter': '2014-05-05', 'timeBefore': '2014-05-20',
     'breakdowns': [{'name': 'host'}]},
    {'breakdowns': [{'name': 'latency'}]},     # high-cardinality keys
]

INELIGIBLE_QUERY = {'breakdowns': [{'name': 'req.method'},
                                   {'name': 'host'}]}


def _scan(monkeypatch, datafile, qconf, parse, native='1',
          threads=None, engine=None):
    monkeypatch.setenv('DN_PARSE', parse)
    monkeypatch.setenv('DN_NATIVE', native)
    if threads is not None:
        monkeypatch.setenv('DN_SCAN_THREADS', threads)
    if engine is not None:
        monkeypatch.setenv('DN_ENGINE', engine)
    ds = DatasourceFile({
        'ds_backend': 'file',
        'ds_backend_config': {'path': str(datafile),
                              'timeField': 'time'},
        'ds_filter': None, 'ds_format': 'json',
    })
    r = ds.scan(mod_query.query_load(dict(qconf)))
    counters = {(s.name, k): v for s in r.pipeline.stages
                for k, v in s.counters.items()
                if v and k not in s.hidden}
    return r.points, counters


# ---------------------------------------------------------------------------
# scan parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize('seed', [31, 32, 33])
def test_fuzz_scan_vector_matches_host(tmp_path, monkeypatch, seed):
    datafile = tmp_path / 'fuzz.log'
    write_corpus(datafile, seed)
    for qconf in QUERIES:
        hp, hc = _scan(monkeypatch, datafile, qconf, 'host',
                       native='0')
        vp, vc = _scan(monkeypatch, datafile, qconf, 'vector')
        assert hp == vp, (seed, qconf)
        assert hc == vc, (seed, qconf)


@pytest.mark.parametrize('crlf,truncate', [(True, False),
                                           (False, True),
                                           (True, True)])
def test_scan_crlf_and_truncated_final_line(tmp_path, monkeypatch,
                                            crlf, truncate):
    datafile = tmp_path / 'crlf.log'
    write_corpus(datafile, 41, crlf=crlf, truncate=truncate)
    q = QUERIES[1]
    hp, hc = _scan(monkeypatch, datafile, q, 'host', native='0')
    vp, vc = _scan(monkeypatch, datafile, q, 'vector')
    assert hp == vp
    assert hc == vc


def test_scan_chunk_boundaries(tmp_path, monkeypatch):
    """DN_READ_SIZE forces tiny read chunks, so parse() sees lines
    split at every boundary the joiner must repair."""
    datafile = tmp_path / 'chunk.log'
    write_corpus(datafile, 42)
    q = QUERIES[1]
    base, _ = _scan(monkeypatch, datafile, q, 'host', native='0')
    for size in ('17', '97', '4096'):
        monkeypatch.setenv('DN_READ_SIZE', size)
        vp, _ = _scan(monkeypatch, datafile, q, 'vector')
        assert vp == base, size


def test_scan_mt_workers_match(tmp_path, monkeypatch):
    datafile = tmp_path / 'mt.log'
    write_corpus(datafile, 43)
    q = QUERIES[1]
    base, bc = _scan(monkeypatch, datafile, q, 'vector', threads='0')
    for threads in ('1', '4'):
        vp, vc = _scan(monkeypatch, datafile, q, 'vector',
                       threads=threads)
        assert vp == base
        assert vc == bc


def test_scan_device_lane(tmp_path, monkeypatch):
    from dragnet_tpu.ops import get_jax
    if get_jax() is None:
        pytest.skip('jax unavailable')
    datafile = tmp_path / 'dev.log'
    write_corpus(datafile, 44)
    q = QUERIES[1]
    hp, hc = _scan(monkeypatch, datafile, q, 'host', native='0')
    dp, dc = _scan(monkeypatch, datafile, q, 'device')
    assert hp == dp
    assert hc == dc


def test_scan_device_lane_device_engine(tmp_path, monkeypatch):
    """DN_PARSE=device under DN_ENGINE=jax: byte lane feeding the
    device scan program."""
    from dragnet_tpu.ops import get_jax, backend_ready
    if get_jax() is None or not backend_ready():
        pytest.skip('jax unavailable')
    datafile = tmp_path / 'devj.log'
    write_corpus(datafile, 45)
    q = QUERIES[1]
    hp, _ = _scan(monkeypatch, datafile, q, 'host', native='0')
    dp, _ = _scan(monkeypatch, datafile, q, 'device', engine='jax')
    assert hp == dp


def test_ineligible_query_falls_back_with_counter(tmp_path,
                                                  monkeypatch):
    """A dotted projection under a forced vector lane keeps the host
    lane (no error) and bumps the hidden ineligibility counter."""
    datafile = tmp_path / 'inel.log'
    write_corpus(datafile, 46)
    monkeypatch.setenv('DN_PARSE', 'vector')
    ds = DatasourceFile({
        'ds_backend': 'file',
        'ds_backend_config': {'path': str(datafile),
                              'timeField': 'time'},
        'ds_filter': None, 'ds_format': 'json',
    })
    r = ds.scan(mod_query.query_load(dict(INELIGIBLE_QUERY)))
    hidden = {k: v for s in r.pipeline.stages
              for k, v in s.counters.items() if k in s.hidden}
    assert hidden.get('parse lane ineligible') == 1
    assert 'parse lines fast-path' not in hidden
    monkeypatch.setenv('DN_PARSE', 'host')
    monkeypatch.setenv('DN_NATIVE', '0')
    hp, _ = _scan(monkeypatch, datafile, INELIGIBLE_QUERY, 'host',
                  native='0')
    assert r.points == hp


def test_ineligible_counter_without_native(tmp_path, monkeypatch):
    """The ineligibility counter must appear even when the native
    library is absent (the configuration most likely to want the
    vector lane): the scan degrades to the per-record Python path,
    with the counter."""
    datafile = tmp_path / 'inel2.log'
    write_corpus(datafile, 56)
    monkeypatch.setenv('DN_PARSE', 'vector')
    monkeypatch.setenv('DN_NATIVE', '0')
    ds = DatasourceFile({
        'ds_backend': 'file',
        'ds_backend_config': {'path': str(datafile),
                              'timeField': 'time'},
        'ds_filter': None, 'ds_format': 'json',
    })
    r = ds.scan(mod_query.query_load(dict(INELIGIBLE_QUERY)))
    hidden = {k: v for s in r.pipeline.stages
              for k, v in s.counters.items() if k in s.hidden}
    assert hidden.get('parse lane ineligible') == 1


def test_lane_counters_surfaced(tmp_path, monkeypatch):
    datafile = tmp_path / 'ctr.log'
    write_corpus(datafile, 47)
    monkeypatch.setenv('DN_PARSE', 'vector')
    ds = DatasourceFile({
        'ds_backend': 'file',
        'ds_backend_config': {'path': str(datafile),
                              'timeField': 'time'},
        'ds_filter': None, 'ds_format': 'json',
    })
    r = ds.scan(mod_query.query_load(dict(QUERIES[0])))
    stage = next(s for s in r.pipeline.stages
                 if s.name == 'json parser')
    fast = stage.counters.get('parse lines fast-path', 0)
    fb = stage.counters.get('parse lines fallback', 0)
    assert fast > 0 and fb > 0
    assert fast + fb == stage.counters['ninputs']
    assert stage.counters.get('parse bytes projected', 0) > 0
    # hidden from the default dump, shown under DN_COUNTERS_ALL=1
    import io
    out = io.StringIO()
    stage.dump(out)
    assert 'fast-path' not in out.getvalue()
    monkeypatch.setenv('DN_COUNTERS_ALL', '1')
    out = io.StringIO()
    stage.dump(out)
    assert 'fast-path' in out.getvalue()


def test_dry_run_reports_parse_plan(tmp_path, monkeypatch):
    datafile = tmp_path / 'plan.log'
    write_corpus(datafile, 48)
    monkeypatch.setenv('DN_PARSE', 'vector')
    ds = DatasourceFile({
        'ds_backend': 'file',
        'ds_backend_config': {'path': str(datafile)},
        'ds_filter': None, 'ds_format': 'json',
    })
    r = ds.scan(mod_query.query_load(dict(QUERIES[0])),
                dry_run=True)
    assert r.parse_plan['parse_lane'] == 'vector'
    r2 = ds.scan(mod_query.query_load(dict(INELIGIBLE_QUERY)),
                 dry_run=True)
    assert r2.parse_plan['parse_lane'] == 'host'
    assert 'ineligible' in r2.parse_plan['reason']


# ---------------------------------------------------------------------------
# build parity
# ---------------------------------------------------------------------------

FLAT_METRICS = [
    {'name': 'a', 'breakdowns': [
        {'name': 'timestamp', 'field': 'time', 'date': '',
         'aggr': 'lquantize', 'step': 86400},
        {'name': 'host', 'field': 'host'}]},
    {'name': 'b', 'breakdowns': [
        {'name': 'timestamp', 'field': 'time', 'date': '',
         'aggr': 'lquantize', 'step': 86400},
        {'name': 'latency', 'field': 'latency', 'aggr': 'quantize'}],
     'filter': {'ne': ['host', 'janey']}},
]


@pytest.mark.parametrize('parse', ['vector', 'device'])
def test_build_byte_parity(tmp_path, monkeypatch, parse):
    if parse == 'device':
        from dragnet_tpu.ops import get_jax
        if get_jax() is None:
            pytest.skip('jax unavailable')
    datafile = tmp_path / 'b.log'
    write_corpus(datafile, 49, tame_numbers=True)
    metrics = [mod_query.metric_deserialize(dict(m))
               for m in FLAT_METRICS]

    def build(lane, native, sub):
        monkeypatch.setenv('DN_PARSE', lane)
        monkeypatch.setenv('DN_NATIVE', native)
        idx = str(tmp_path / sub)
        ds = DatasourceFile({
            'ds_backend': 'file',
            'ds_backend_config': {'path': str(datafile),
                                  'indexPath': idx,
                                  'timeField': 'time'},
            'ds_filter': None, 'ds_format': 'json',
        })
        ds.build(metrics, 'day')
        out = {}
        for root, dirs, files in os.walk(idx):
            for fn in sorted(files):
                p = os.path.join(root, fn)
                with open(p, 'rb') as f:
                    out[os.path.relpath(p, idx)] = f.read()
        return out

    host_tree = build('host', '0', 'ih')
    lane_tree = build(parse, '1', 'iv_' + parse)
    assert host_tree.keys() == lane_tree.keys()
    for rel in host_tree:
        assert host_tree[rel] == lane_tree[rel], rel


# ---------------------------------------------------------------------------
# parser-level differentials
# ---------------------------------------------------------------------------

def _columns_semantic(parser, field):
    """(tag-class, num, string) per row — the engine-visible semantics
    of a parser's columns.  INT/NUMBER are indistinguishable
    downstream and compare as one class; TAG_ARRAY dictionary entries
    compare by PARSED value, because lanes may intern different
    value-equivalent texts (the fast path keeps the raw span '[1e2]',
    the host fallback a round-trip '[100.0]') and the engine only
    ever consumes the json.loads of the entry
    (engine.NativeColumns._array_values)."""
    tags, nums, codes = parser.columns(field)
    d = parser.dictionary(field)
    out = []
    for i in range(len(tags)):
        t = int(tags[i])
        tclass = 4 if t == 5 else t
        num = float(nums[i]) if t in (4, 5) else None
        if num is not None and num != num:
            num = 'nan'
        sval = d[codes[i]] if t in (6, 8) and codes[i] >= 0 else None
        if t == 8 and sval is not None:
            sval = repr(json.loads(sval))
        out.append((tclass, num, sval))
    return out


@pytest.mark.parametrize('seed', [51, 52])
def test_parser_columns_match_force_fallback(tmp_path, seed):
    """The fast path vs the host parser at COLUMN level: ByteParser in
    forced-fallback mode runs every line through json.loads, so any
    disagreement pins a fast-path bug precisely."""
    lines = gen_lines(seed)
    buf = b'\n'.join(lines) + b'\n'
    paths = ['time', 'host', 'latency']
    hints = [True, False, False]
    dicts = [False, True, True]
    a = ByteParser(paths, hints, dicts)
    b = ByteParser(paths, hints, dicts, force_fallback=True)
    a.parse(buf)
    b.parse(buf)
    assert a.counters() == b.counters()
    assert a.batch_size() == b.batch_size()
    assert a.lines_fast > 0 and b.lines_fast == 0
    for f in paths:
        assert _columns_semantic(a, f) == _columns_semantic(b, f), f
    asec, aerr = a.date_columns('time')
    bsec, berr = b.date_columns('time')
    assert np.array_equal(aerr, berr)
    assert np.array_equal(asec, bsec)


@pytest.mark.skipif(mod_native.get_lib() is None,
                    reason='native parser unavailable')
@pytest.mark.parametrize('seed', [53, 54])
def test_parser_columns_match_native(seed):
    """ByteParser vs the C++ parser over split parse() calls (batch
    accumulation across chunk boundaries)."""
    lines = gen_lines(seed)
    rng = random.Random(seed)
    buf = b'\n'.join(lines) + b'\n'
    pieces = []
    pos = 0
    while pos < len(buf):
        nl = buf.find(b'\n', pos + rng.randrange(1, 500))
        if nl == -1:
            pieces.append(buf[pos:])
            break
        pieces.append(buf[pos:nl + 1])
        pos = nl + 1
    paths = ['time', 'host', 'latency']
    hints = [True, False, False]
    dicts = [False, True, True]
    a = ByteParser(paths, hints, dicts)
    b = mod_native.NativeParser(paths, hints, dicts)
    for p in pieces:
        a.parse(p)
        b.parse(p)
    assert a.counters() == b.counters()
    assert a.batch_size() == b.batch_size()
    for f in paths:
        assert _columns_semantic(a, f) == _columns_semantic(b, f), f
    asec, aerr = a.date_columns('time')
    bsec, berr = b.date_columns('time')
    assert np.array_equal(aerr, berr)
    assert np.array_equal(asec, bsec)


def test_structural_kernels_identical():
    """The jax-staged parity scan must be bit-identical to the numpy
    one (the device lane's correctness rests on it)."""
    from dragnet_tpu.ops import get_jax
    if get_jax() is None:
        pytest.skip('jax unavailable')
    data = b'\n'.join(gen_lines(55)) + b'\n'
    arr = np.frombuffer(data, dtype=np.uint8)
    a = bk.parity_numpy(arr)
    b = bk.parity_device(arr)
    assert np.array_equal(a, np.asarray(b))


def test_device_kernel_wedge_is_an_error(monkeypatch):
    """A hung jax parity kernel fails the scan under the probe
    deadline: DN_PARSE=device is a forced lane, so it neither hangs
    nor answers from the numpy kernel."""
    import time as mod_time
    from dragnet_tpu.errors import DNError

    def hang(arr):
        mod_time.sleep(60)
    monkeypatch.setattr(bk, '_parity_jax_call', hang)
    monkeypatch.setitem(bk._DEVICE_STATE, 'ok', None)
    monkeypatch.setenv('DN_DEVICE_PROBE_TIMEOUT', '1')
    arr = np.frombuffer(b'{"a":1}\n', dtype=np.uint8)
    t0 = mod_time.monotonic()
    with pytest.raises(DNError) as ei:
        bk.parity_device(arr)
    assert mod_time.monotonic() - t0 < 30
    assert 'probe timed out' in ei.value.message
    assert bk._DEVICE_STATE['ok'] is False


# ---------------------------------------------------------------------------
# lane selection
# ---------------------------------------------------------------------------

def _q(conf):
    return mod_query.query_load(dict(conf))


def test_choose_lane(monkeypatch):
    flat = [_q(QUERIES[1])]
    dotted = [_q(INELIGIBLE_QUERY)]
    monkeypatch.setenv('DN_PARSE', 'vector')
    assert mod_byteparse.choose_lane(flat, 'time', None, 'json',
                                     True).lane == 'vector'
    assert mod_byteparse.choose_lane(dotted, 'time', None, 'json',
                                     True).lane == 'host'
    assert mod_byteparse.choose_lane(flat, 'time', None,
                                     'json-skinner', True).lane == \
        'host'
    # a dotted datasource filter also disqualifies
    assert mod_byteparse.choose_lane(
        flat, 'time', {'eq': ['res.statusCode', 200]}, 'json',
        True).lane == 'host'
    monkeypatch.setenv('DN_PARSE', 'host')
    assert not mod_byteparse.choose_lane(flat, 'time', None, 'json',
                                         True).engaged
    monkeypatch.setenv('DN_PARSE', 'auto')
    assert mod_byteparse.choose_lane(flat, 'time', None, 'json',
                                     True).lane == 'host'
    assert mod_byteparse.choose_lane(flat, 'time', None, 'json',
                                     False).lane == 'vector'
    assert mod_byteparse.choose_lane(dotted, 'time', None, 'json',
                                     False).lane == 'host'
