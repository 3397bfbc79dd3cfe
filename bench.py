#!/usr/bin/env python3
"""The corpus generator and the queries that the cold smoke
(chip_smoke.py), the compile tests (tests/test_tpu_compile.py) and the
benchmark's generator test share.  It measures nothing: the benchmark
is benchmarks/run.py (BENCHMARK.json, PERF.md).

* gen_to_file: n muskie-style records (tools/mktestdata's shape)
  written to a file from a seed, by native/dngen.cc or in Python.
* QUERY, HC_QUERY, PALLAS_QUERY, METRICS: the scan queries and the
  three build metrics; tests/test_chip_smoke.py holds chip_smoke.py's
  and benchmarks/'s copies equal to these.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

QUERY = {
    'breakdowns': [
        {'name': 'host'},
        {'name': 'req.method'},
        {'name': 'operation'},
        {'name': 'latency', 'aggr': 'quantize'},
    ],
    'filter': {'ne': ['res.statusCode', 599]},
}

HC_QUERY = {'breakdowns': [{'name': 'req.url'}, {'name': 'latency'}]}

# small accumulator (16 x 32 segments): the one the Pallas one-hot
# MXU kernel takes
PALLAS_QUERY = {'breakdowns': [{'name': 'host'},
                               {'name': 'latency', 'aggr': 'quantize'}]}

METRICS = [
    {'name': 'm1', 'breakdowns': [
        {'name': 'timestamp', 'field': 'time', 'date': '',
         'aggr': 'lquantize', 'step': 86400},
        {'name': 'host', 'field': 'host'},
        {'name': 'req.method', 'field': 'req.method'},
        {'name': 'operation', 'field': 'operation'},
        {'name': 'latency', 'field': 'latency', 'aggr': 'quantize'}]},
    {'name': 'm2', 'breakdowns': [
        {'name': 'timestamp', 'field': 'time', 'date': '',
         'aggr': 'lquantize', 'step': 86400},
        {'name': 'host', 'field': 'host'},
        {'name': 'res.statusCode', 'field': 'res.statusCode'}]},
    {'name': 'm3', 'breakdowns': [
        {'name': 'timestamp', 'field': 'time', 'date': '',
         'aggr': 'lquantize', 'step': 86400},
        {'name': 'operation', 'field': 'operation'},
        {'name': 'latency', 'field': 'latency', 'aggr': 'lquantize',
         'step': 100}],
     'filter': {'ne': ['res.statusCode', 500]}},
]


def _mktestdata():
    import importlib.util
    import importlib.machinery
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        'tools', 'mktestdata')
    loader = importlib.machinery.SourceFileLoader('mktestdata', path)
    spec = importlib.util.spec_from_file_location('mktestdata', path,
                                                  loader=loader)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def gen_to_file(n, path, mindate_ms=None, maxdate_ms=None, seed=12345):
    """Write n generated records to path; native generator
    (native/dngen.cc, same shape/distributions as tools/mktestdata)
    when available, Python otherwise.  Timestamps increase linearly
    over [mindate_ms, maxdate_ms) (default: mktestdata's window);
    `seed` feeds the native generator's RNG."""
    mod = _mktestdata()
    if mindate_ms is None:
        mindate_ms = int(mod.MINDATE.timestamp() * 1000)
    if maxdate_ms is None:
        maxdate_ms = int(mod.MAXDATE.timestamp() * 1000)

    lib = None
    if os.environ.get('DN_NATIVE', '1') != '0':
        import ctypes
        from dragnet_tpu import native as mod_native
        so = os.path.join(mod_native._NATIVE_DIR, 'build',
                          'libdngen.so')
        if mod_native._build_target(
                so, os.path.join(mod_native._NATIVE_DIR, 'dngen.cc')):
            try:
                lib = ctypes.CDLL(so)
                lib.dn_gen.restype = ctypes.c_int64
                lib.dn_gen.argtypes = [
                    ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64,
                    ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
                    ctypes.c_int64, ctypes.c_uint64]
            except OSError:
                lib = None

    with open(path, 'wb') as f:
        if lib is not None:
            chunk = 200000
            buf = ctypes.create_string_buffer(min(chunk, n) * 512)
            for start in range(0, n, chunk):
                cnt = min(chunk, n - start)
                nb = lib.dn_gen(buf, len(buf), start, cnt, n,
                                mindate_ms, maxdate_ms, seed)
                if nb <= 0:
                    raise RuntimeError('dn_gen failed (rv=%d)' % nb)
                f.write(ctypes.string_at(buf, nb))
        else:
            for i in range(n):
                f.write(json.dumps(
                    mod.make_record(i, n, mindate_ms, maxdate_ms),
                    separators=(',', ':')).encode() + b'\n')
