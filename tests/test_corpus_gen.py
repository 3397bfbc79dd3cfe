"""bench.gen_to_file: the corpus every smoke, compile test and
benchmark cross-check starts from.  Seeded bytes, the record count,
the time window, and the Python fallback's record shape."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import bench                                        # noqa: E402
from dragnet_tpu import jsvalues as jsv             # noqa: E402
from dragnet_tpu import native as mod_native        # noqa: E402

N = 3000
MINDATE_MS = 1388534400000                  # 2014-01-01T00:00:00Z
MAXDATE_MS = MINDATE_MS + 3 * 86400000


@pytest.fixture(scope='module')
def native_gen():
    if mod_native.get_lib() is None:
        pytest.skip('native build unavailable')


def _gen(path, **kw):
    bench.gen_to_file(N, str(path), mindate_ms=MINDATE_MS,
                      maxdate_ms=MAXDATE_MS, **kw)
    with open(str(path), 'rb') as f:
        return f.read()


def _key_sets(data):
    """Top-level and nested key sets seen over the records."""
    keys = set()
    for line in data.splitlines():
        rec = json.loads(line)
        keys.update(rec)
        keys.update('req.' + k for k in rec['req'])
        keys.update('res.' + k for k in rec['res'])
    return keys


def test_seed_decides_the_bytes(native_gen, tmp_path):
    a = _gen(tmp_path / 'a.log', seed=7)
    assert _gen(tmp_path / 'b.log', seed=7) == a
    assert _gen(tmp_path / 'c.log', seed=8) != a
    assert _gen(tmp_path / 'd.log') == _gen(tmp_path / 'e.log', seed=12345)


def test_count_and_time_window(native_gen, tmp_path):
    lines = _gen(tmp_path / 'a.log').splitlines()
    assert len(lines) == N
    times = [jsv.date_parse(json.loads(ln)['time']) for ln in lines]
    assert times == sorted(times)
    assert MINDATE_MS <= times[0] and times[-1] < MAXDATE_MS
    # the window is used, not only respected: the last record falls in
    # its last day, so `dn build` writes every daily shard
    assert times[-1] >= MAXDATE_MS - 86400000


def test_python_fallback_writes_the_same_shape(native_gen, tmp_path,
                                               monkeypatch):
    native = _gen(tmp_path / 'native.log')
    monkeypatch.setenv('DN_NATIVE', '0')
    python = _gen(tmp_path / 'python.log')
    assert python != native                 # it was the other generator
    assert len(python.splitlines()) == N
    assert _key_sets(python) == _key_sets(native)
