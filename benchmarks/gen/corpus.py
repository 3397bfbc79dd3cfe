"""The benchmark's corpus: seeded muskie-style request logs.

`generate()` writes the newline-JSON file a datasource reads and
returns the same records as numpy columns, so that the plain reference
never parses JSON.  The generator is benchmarks/gen/benchgen.cc (a copy
of native/dngen.cc), compiled once into the checkout's cache directory.
"""

import ctypes
import hashlib
import os
import subprocess

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
SOURCE = os.path.join(HERE, 'benchgen.cc')

# the value lists of benchgen.cc, in its index order
HOSTS = ['ralph', 'janey', 'kearney', 'sherri', 'wendell']
METHODS = ['HEAD', 'GET', 'PUT', 'DELETE']
OPERATIONS = ['headstorage', 'headpublicstorage',
              'getjoberrors', 'getpublicstorage', 'getstorage',
              'putdirectory', 'putpublicobject', 'putobject',
              'deletestorage', 'deletepublicstorage']

COLUMNS = [('host', np.uint8), ('method', np.uint8), ('op', np.uint8),
           ('caller', np.uint8), ('url', np.int16), ('status', np.int16),
           ('latency', np.int32), ('dlatency', np.int32),
           ('dsize', np.int64), ('ts_ms', np.int64)]

CHUNK = 200000


def build_library(cache_dir):
    """Compile benchgen.cc into `cache_dir` (keyed by the source's
    digest, so an edited source never loads a stale library) and load
    it."""
    with open(SOURCE, 'rb') as f:
        tag = hashlib.sha256(f.read()).hexdigest()[:12]
    so = os.path.join(cache_dir, 'libbenchgen-%s.so' % tag)
    if not os.path.exists(so):
        os.makedirs(cache_dir, exist_ok=True)
        tmp = '%s.%d.tmp' % (so, os.getpid())
        subprocess.run(['g++', '-O3', '-fPIC', '-std=c++17', '-shared',
                        '-o', tmp, SOURCE], check=True)
        os.replace(tmp, so)
    lib = ctypes.CDLL(so)
    lib.bench_gen.restype = ctypes.c_int64
    lib.bench_gen.argtypes = [ctypes.c_char_p] + [ctypes.c_int64] * 6 + \
        [ctypes.c_uint64] + [ctypes.c_void_p] * len(COLUMNS)
    return lib


def generate(lib, path, records, mindate_ms, maxdate_ms, seed):
    """Write `records` records to `path`; returns (columns, bytes
    written).  Timestamps rise linearly over [mindate_ms, maxdate_ms).
    """
    cols = {name: np.empty(records, dtype=dt) for name, dt in COLUMNS}
    buf = ctypes.create_string_buffer(min(CHUNK, records) * 512)
    nbytes = 0
    with open(path, 'wb') as f:
        for start in range(0, records, CHUNK):
            cnt = min(CHUNK, records - start)
            ptrs = [cols[name][start:].ctypes.data for name, _ in COLUMNS]
            nb = lib.bench_gen(buf, len(buf), start, cnt, records,
                               mindate_ms, maxdate_ms, seed, *ptrs)
            if nb <= 0:
                raise RuntimeError('bench_gen failed (rv=%d)' % nb)
            f.write(memoryview(buf)[:nb])
            nbytes += nb
    return cols, nbytes
