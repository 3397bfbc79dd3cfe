"""Native C++ parser vs Python ingest: full-scan differential on
adversarial inputs.

The golden parity suites run whichever ingest path is default; this
test pins the two paths against each other on inputs chosen to hit every
parser edge: escape sequences (including lone and paired surrogates),
duplicate keys at several depths (JSON.parse last-wins), direct-key vs
nested-path projection priority, arrays/objects/null/bool in projected
positions, big and tiny numbers, numeric strings in bucketized fields,
invalid JSON lines (counted and skipped), non-object roots, and
ISO-8601 date edge cases."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from dragnet_tpu import native as mod_native  # noqa: E402
from dragnet_tpu import query as mod_query  # noqa: E402
from dragnet_tpu.datasource_file import DatasourceFile  # noqa: E402
from helpers.scan_differential import (  # noqa: E402
    LAYOUTS, batches_handed, serial_loop, write_layout)

pytestmark = pytest.mark.skipif(mod_native.get_lib() is None,
                                reason='native parser unavailable')

LINES = [
    '{"host":"a","req":{"method":"GET"},"latency":5,'
    '"time":"2014-05-01T10:00:00.123Z"}',
    # duplicate key: JSON.parse keeps the last occurrence
    '{"host":"a","host":"b","latency":1,"time":"2014-05-01T11:00:00Z"}',
    # duplicate nested subtree replaces earlier capture
    '{"req":{"method":"PUT"},"req":{"caller":"x"},"latency":2,'
    '"time":"2014-05-01T12:00:00Z"}',
    # direct dotted key beats the nested path (jsprim pluck)
    '{"req.method":"DIRECT","req":{"method":"NESTED"},"latency":3,'
    '"time":"2014-05-01T12:30:00Z"}',
    '{"req":{"method":"NESTED2"},"req.method":"DIRECT2","latency":3,'
    '"time":"2014-05-01T12:31:00Z"}',
    # escapes, unicode, surrogate pairs, lone surrogate
    '{"host":"sl\\\\ash\\"q\\u00e9\\ud83d\\ude00","latency":4,'
    '"time":"2014-05-01T13:00:00Z"}',
    '{"host":"lone\\ud800tail","latency":4,'
    '"time":"2014-05-01T13:00:01Z"}',
    # projected values of every JSON type
    '{"host":null,"latency":6,"time":"2014-05-01T14:00:00Z"}',
    '{"host":true,"latency":7,"time":"2014-05-01T14:01:00Z"}',
    '{"host":false,"latency":8,"time":"2014-05-01T14:02:00Z"}',
    '{"host":{"x":1},"latency":9,"time":"2014-05-01T14:03:00Z"}',
    '{"host":[1,"two",null],"latency":10,'
    '"time":"2014-05-01T14:04:00Z"}',
    '{"host":[],"latency":10,"time":"2014-05-01T14:05:00Z"}',
    # numbers: int, float, exponent, huge, tiny, -0
    '{"host":1234,"latency":11,"time":"2014-05-01T15:00:00Z"}',
    '{"host":12.5,"latency":12,"time":"2014-05-01T15:01:00Z"}',
    '{"host":1e3,"latency":1e2,"time":"2014-05-01T15:02:00Z"}',
    '{"host":123456789012345678901234567890,"latency":13,'
    '"time":"2014-05-01T15:03:00Z"}',
    '{"host":-0.0,"latency":5e-324,"time":"2014-05-01T15:04:00Z"}',
    '{"host":"h","latency":9007199254740993,'
    '"time":"2014-05-01T15:05:00Z"}',
    # numeric string in a bucketized field (JS coercion)
    '{"host":"h","latency":"26","time":"2014-05-01T16:00:00Z"}',
    '{"host":"h","latency":"26.9","time":"2014-05-01T16:01:00Z"}',
    '{"host":"h","latency":"notanum","time":"2014-05-01T16:02:00Z"}',
    # missing fields
    '{"latency":14,"time":"2014-05-01T17:00:00Z"}',
    '{"host":"nodate","latency":15}',
    # date edge cases: numeric passthrough, space separator, offsets,
    # bad dates
    '{"host":"d","latency":1,"time":1398970000}',
    '{"host":"d","latency":1,"time":"2014-05-01 18:00:00Z"}',
    '{"host":"d","latency":1,"time":"2014-05-01T18:00:00+02:30"}',
    '{"host":"d","latency":1,"time":"2014-05-01T18:00:00-0100"}',
    '{"host":"d","latency":1,"time":"2014-13-99T99:99:99Z"}',
    '{"host":"d","latency":1,"time":"yesterday"}',
    '{"host":"d","latency":1,"time":null}',
    # invalid JSON lines: counted, skipped
    '{"host":"bad"',
    '{bad}',
    'not json at all',
    '{"host":"trailing",} ',
    '{"host":"ctrl\tchar"}',
    '',
    # non-object roots are records with no fields
    '42',
    '"just a string"',
    '[1,2,3]',
    'null',
    'true',
    # whitespace layout
    '  {  "host" : "ws" , "latency" : 33 , '
    '"time" : "2014-05-01T19:00:00Z" }  ',
]

QUERIES = [
    {},
    {'breakdowns': [{'name': 'host'}]},
    {'breakdowns': [{'name': 'req.method'}, {'name': 'host'}]},
    {'breakdowns': [{'name': 'latency', 'aggr': 'quantize'}]},
    {'breakdowns': [{'name': 'host'},
                    {'name': 'latency', 'aggr': 'quantize'}]},
    {'filter': {'eq': ['host', 'a']},
     'breakdowns': [{'name': 'host'}]},
    {'filter': {'lt': ['latency', 10]},
     'breakdowns': [{'name': 'host'}]},
    {'filter': {'eq': ['req.caller', 'x']},
     'breakdowns': [{'name': 'req.method'}]},
    {'timeAfter': '2014-05-01T12:00:00Z',
     'timeBefore': '2014-05-01T16:00:00Z',
     'breakdowns': [{'name': 'host'}]},
]


def _scan(monkeypatch, datafile, qconf, native, threads='0',
          parse_threads='1', read_size=None, batch=None):
    monkeypatch.setenv('DN_NATIVE', native)
    if read_size is not None:
        monkeypatch.setenv('DN_READ_SIZE', str(read_size))
    if batch is not None:
        from dragnet_tpu import engine as mod_engine
        monkeypatch.setattr(mod_engine, 'BATCH_SIZE', batch)
    monkeypatch.setenv('DN_SCAN_THREADS', threads)
    # pin the parser's threading so both its single-threaded path and
    # the multithreaded deterministic merge are exercised regardless of
    # the host's core count
    monkeypatch.setenv('DN_PARSE_THREADS', parse_threads)
    ds = DatasourceFile({
        'ds_backend': 'file',
        'ds_backend_config': {'path': datafile,
                              'timeField': 'time'},
        'ds_filter': None,
        'ds_format': 'json',
    })
    r = ds.scan(mod_query.query_load(dict(qconf)))
    counters = {(s.name, k): v for s in r.pipeline.stages
                for k, v in s.counters.items() if v}
    return r.points, counters


@pytest.mark.parametrize('qi', range(len(QUERIES)))
def test_native_matches_python(tmp_path, monkeypatch, qi):
    datafile = str(tmp_path / 'edge.log')
    with open(datafile, 'w') as f:
        f.write('\n'.join(LINES) + '\n')
    qconf = QUERIES[qi]
    py_points, py_counters = _scan(monkeypatch, datafile, qconf,
                                   native='0')
    nat_points, nat_counters = _scan(monkeypatch, datafile, qconf,
                                     native='1')
    assert py_points == nat_points, qconf
    mt_points, mt_counters = _scan(monkeypatch, datafile, qconf,
                                   native='1', threads='3',
                                   parse_threads='4')
    assert py_points == mt_points, qconf
    # counters must agree between all paths (stage names may differ in
    # layout but the parse-level invalid count must match)
    for c in (py_counters, nat_counters, mt_counters):
        assert c[('json parser', 'invalid json')] == \
            py_counters[('json parser', 'invalid json')]
    assert nat_counters == mt_counters


# -- the batch hand-off (datasource_file._stream_native) ----------------------

# DN_READ_SIZE from less than one line (every line spans chunks) to the
# production chunk
READ_SIZES = (48, 4096, 1 << 24)


@pytest.mark.parametrize('read_size', READ_SIZES)
@pytest.mark.parametrize('layout', LAYOUTS)
def test_handoff_matches_serial_loop_and_python(tmp_path, monkeypatch,
                                                layout, read_size):
    """The parser's thread one batch ahead of the engine's gives what
    the serial loop over the same parser gives, points and counters,
    and what the Python ingest path gives: over chunk sizes, a last
    line with no newline, a line that spans two chunks and two files,
    and an empty file in the stream."""
    # the edge lines six times over, each round with strings of its
    # own, so that later batches grow the dictionaries
    lines = []
    for r in range(6):
        lines.extend(LINES)
        lines.append('{"host":"round%d","req":{"method":"M%d"},'
                     '"latency":%d,"time":"2014-05-02T10:00:00Z"}'
                     % (r, r, 3 ** r))
    datafile = write_layout(tmp_path, lines, layout)
    qconf = {'breakdowns': [{'name': 'req.method'}, {'name': 'host'},
                            {'name': 'latency', 'aggr': 'quantize'}],
             'filter': {'ne': ['host', 'd']}}
    py_points, py_counters = _scan(monkeypatch, datafile, qconf,
                                   native='0')
    for threads, parse_threads in (('0', '1'), ('3', '4')):
        kw = dict(native='1', threads=threads, read_size=read_size,
                  parse_threads=parse_threads, batch=8)
        h0 = batches_handed()
        ahead = _scan(monkeypatch, datafile, qconf, **kw)
        h1 = batches_handed()
        with monkeypatch.context() as mp:
            serial_loop(mp)
            serial = _scan(mp, datafile, qconf, **kw)
        # several batches crossed the hand-off, none in the serial loop
        assert h1 - h0 >= (4 if read_size <= 4096 else 1)
        assert batches_handed() == h1
        assert ahead == serial
        assert ahead[0] == py_points
        assert ahead[1][('json parser', 'invalid json')] == \
            py_counters[('json parser', 'invalid json')]
