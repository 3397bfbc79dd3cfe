"""The batch hand-off between the native parser's thread and the
request's (datasource_file._stream_native, native.NativeBatch): the
parser fills batch N+1 on a producer thread while the request's thread
stages batch N.

What the hand-off must not change is pinned against the serial loop in
tests/test_native_differential.py and tests/test_device_build.py; here:
the detached batch itself, the dictionaries it sees, errors on either
side, and what a stopped consumer leaves behind.
"""

import json
import os
import sys
import threading
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from dragnet_tpu import datasource_file as mod_dsf        # noqa: E402
from dragnet_tpu import ingest as mod_ingest              # noqa: E402
from dragnet_tpu import native as mod_native              # noqa: E402
from dragnet_tpu.obs import metrics as obs_metrics        # noqa: E402
from helpers.scan_differential import (                   # noqa: E402
    LAYOUTS, scan_points_counters, serial_loop, write_layout)

pytestmark = pytest.mark.skipif(mod_native.get_lib() is None,
                                reason='native parser unavailable')

PATHS = ['host', 'req.method', 'latency', 'time']
HINTS = [False, False, False, True]
PRODUCER = 'dn-parse-ahead'


def _line(i, host=None):
    return json.dumps({
        'host': host if host is not None else 'h%d' % (i % 7),
        'req': {'method': ('GET', 'PUT', 'HEAD')[i % 3]},
        'latency': (i * 13) % 500 if i % 11 else 'slow',
        'time': '2014-05-%02dT%02d:00:%02dZ' % (1 + i % 3, i % 24,
                                                 i % 60),
    }, separators=(',', ':'))


def _files(path):
    return [(str(path), os.stat(str(path)))]


def _ds():
    return mod_dsf.DatasourceFile({
        'ds_backend': 'file', 'ds_backend_config': {'path': '/nowhere'},
        'ds_filter': None, 'ds_format': 'json'})


def _batch_doc(batch):
    """Everything a consumer can read of a batch, as plain values."""
    doc = {'n': batch.batch_size(), 'counters': batch.counters()}
    for p, hint in zip(PATHS, HINTS):
        doc[p] = {
            'columns': [a.tolist() for a in batch.columns(p)],
            'tags': batch.tags_col(p).tolist(),
            'strcodes': batch.strcodes_col(p).tolist(),
            'stats': batch.field_stats(p),
            'dict': list(batch.dictionary(p)),
        }
        if hint:
            doc[p]['date'] = [a.tolist() for a in batch.date_columns(p)]
            doc[p]['date_stats'] = batch.date_stats(p)
            doc[p]['date_i32'] = batch.date_i32(p).tolist()
            doc[p]['date_err'] = batch.date_err(p).tolist()
    doc['latency']['i32'] = batch.nums_i32('latency').tolist()
    return doc


def _wait_for(cond, seconds=10.0):
    deadline = time.monotonic() + seconds
    while not cond():
        assert time.monotonic() < deadline, 'timed out'
        time.sleep(0.002)


def _live_producers():
    return [t for t in threading.enumerate() if t.name == PRODUCER]


def _unreleased():
    return [b for b in mod_native._BATCH_LEAKS.items if b.h is not None]


# -- (e) the detached batch ---------------------------------------------------

def test_detached_batch_equals_the_parsers_own(monkeypatch):
    """A batch that was given away answers every accessor as the
    parser answered for it in place, after a multithreaded parse (the
    ordered merge of 4 workers) and after a single-threaded one; and
    the parser goes on into an empty batch with its dictionaries."""
    # over 2 MiB: below that the native side parses on one thread
    buf = ('\n'.join(_line(i) for i in range(30000)) + '\n').encode()
    assert len(buf) > (1 << 21)
    docs = []
    for nthreads in ('4', '1'):
        monkeypatch.setenv('DN_PARSE_THREADS', nthreads)
        parser = mod_native.NativeParser(PATHS, HINTS)
        assert parser.nthreads == int(nthreads)
        assert parser.parse(buf) == 30000
        in_place = _batch_doc(parser)
        batch = parser.detach_batch()
        assert parser.batch_size() == 0
        assert parser.counters() == (30000, 0)
        assert _batch_doc(batch) == in_place
        # the parser's next batch starts empty, on the same dictionaries
        assert parser.parse((_line(5) + '\n').encode()) == 1
        assert parser.columns('host')[2].tolist() == \
            [batch.columns('host')[2][5]]
        docs.append(_batch_doc(batch))
        batch.release()
        assert batch.h is None
    assert docs[0] == docs[1]


# -- (b) the dictionaries a batch sees ----------------------------------------

def test_batch_dictionary_is_pinned_at_the_hand_off():
    parser = mod_native.NativeParser(PATHS, HINTS)
    parser.parse((_line(0, 'alpha') + '\n' + _line(1, 'beta') +
                  '\n').encode())
    first = parser.detach_batch()
    parser.parse((_line(2, 'gamma') + '\n' + _line(3, 'alpha') +
                  '\n').encode())
    second = parser.detach_batch()
    d = first.dictionary('host')
    assert list(d) == ['alpha', 'beta'] and len(d) == 2
    assert d[-1] == 'beta' and d[0:5] == ['alpha', 'beta']
    with pytest.raises(IndexError):
        d[2]
    assert list(second.dictionary('host')) == ['alpha', 'beta', 'gamma']
    # codes mean the same strings in both
    assert first.strcodes_col('host').tolist() == [0, 1]
    assert second.strcodes_col('host').tolist() == [2, 0]
    first.release()
    second.release()


def test_strings_of_the_next_batch_stay_out_of_this_ones_dictionary(
        tmp_path):
    """Every record brings a host of its own, so a dictionary's length
    is the count of records parsed so far.  Each flush waits until the
    producer has parsed on (its mirror has grown) and still reads the
    dictionary the serial loop would have shown it: as long as the
    records up to this batch's end, codes unshifted."""
    nrecords, batch_size = 400, 50
    path = tmp_path / 'uniq.log'
    path.write_text(''.join(_line(i, 'host-%05d' % i) + '\n'
                            for i in range(nrecords)))
    parser = mod_native.NativeParser(PATHS, HINTS)
    seen = []

    def flush(batch):
        n = batch.batch_size()
        upto = batch.counters()[0]
        if upto < nrecords:
            # the producer is a batch ahead, in the same dictionaries
            _wait_for(lambda: len(parser._dicts[0]) > upto)
        d = batch.dictionary('host')
        assert len(d) == upto
        assert list(d)[-1] == 'host-%05d' % (upto - 1)
        codes = batch.strcodes_col('host')
        assert codes.tolist() == list(range(upto - n, upto))
        assert [d[c] for c in codes[:3]] == \
            ['host-%05d' % i for i in range(upto - n, upto - n + 3)]
        seen.append(n)

    os.environ['DN_READ_SIZE'] = '2048'
    try:
        _ds()._stream_native(_files(path), parser, flush, batch_size)
    finally:
        del os.environ['DN_READ_SIZE']
    assert sum(seen) == nrecords and len(seen) >= 5
    assert not _live_producers() and not _unreleased()


# -- (a) the forced device scan over the hand-off -----------------------------

@pytest.mark.parametrize('layout', LAYOUTS)
def test_device_scan_over_the_hand_off(tmp_path, monkeypatch, layout):
    """DN_ENGINE=jax behind the producer thread: the serial loop's
    points and counters, and the host engine's."""
    datafile = write_layout(tmp_path, [_line(i) for i in range(900)],
                            layout)
    qconf = {'breakdowns': [{'name': 'host'}, {'name': 'req.method'},
                            {'name': 'latency', 'aggr': 'quantize'}],
             'filter': {'ne': ['req.method', 'HEAD']}}
    kw = dict(batch=64, read_size=1500, time_field='time')
    ahead = scan_points_counters(monkeypatch, datafile, qconf, 'jax',
                                 **kw)
    with monkeypatch.context() as mp:
        serial_loop(mp)
        serial = scan_points_counters(mp, datafile, qconf, 'jax', **kw)
    host = scan_points_counters(monkeypatch, datafile, qconf, 'vector',
                                **kw)
    assert ahead == serial == host and ahead[0]


# -- (c) producer errors ------------------------------------------------------

def _two_files(tmp_path):
    for name in ('a.log', 'b.log'):
        (tmp_path / name).write_text(
            ''.join(_line(i) + '\n' for i in range(300)))
    return [(str(tmp_path / n), os.stat(str(tmp_path / n)))
            for n in ('a.log', 'b.log')]


def _stream(files, flush=None, batch_size=40, read_size='4096'):
    parser = mod_native.NativeParser(PATHS, HINTS)
    os.environ['DN_READ_SIZE'] = read_size
    try:
        _ds()._stream_native(files, parser,
                             flush or (lambda batch: None), batch_size)
    finally:
        del os.environ['DN_READ_SIZE']
    return parser


@pytest.mark.parametrize('fault', ['unreadable-file', 'parser-error'])
def test_producer_error_surfaces_at_the_caller(tmp_path, monkeypatch,
                                               fault):
    """A file that cannot be read in mid-stream, or a parser that
    fails, raises from `_stream_native` on the caller's thread as it
    did from the serial loop, after the batches before it; nothing is
    left running."""
    files = _two_files(tmp_path)
    if fault == 'unreadable-file':
        real = mod_ingest.open_byte_source

        def source(path, chunk_size=1 << 20):
            if path.endswith('b.log'):
                raise PermissionError(13, 'Permission denied', path)
            return real(path, chunk_size)
        monkeypatch.setattr(mod_ingest, 'open_byte_source', source)
        expected = PermissionError
    else:
        real = mod_native.NativeParser.parse_at
        calls = []

        def parse_at(self, buf, length):
            calls.append(length)
            if len(calls) == 6:
                raise MemoryError('parser out of memory')
            return real(self, buf, length)
        monkeypatch.setattr(mod_native.NativeParser, 'parse_at', parse_at)
        expected = MemoryError
    flushed = []
    with pytest.raises(expected):
        _stream(files, lambda batch: flushed.append(batch.batch_size()))
    assert flushed and sum(flushed) < 600
    _wait_for(lambda: not _live_producers())
    assert not _unreleased()


def test_producer_error_reaches_a_scan(tmp_path, monkeypatch):
    """The same through `DatasourceFile.scan`: the caller sees the
    error, not a short answer."""
    (tmp_path / 'a.log').write_text(
        ''.join(_line(i) + '\n' for i in range(300)))
    real = mod_native.NativeParser.parse_at

    def parse_at(self, buf, length):
        if self.counters()[0] >= 100:
            raise MemoryError('parser out of memory')
        return real(self, buf, length)
    monkeypatch.setattr(mod_native.NativeParser, 'parse_at', parse_at)
    with pytest.raises(MemoryError):
        scan_points_counters(monkeypatch, str(tmp_path / 'a.log'),
                             {'breakdowns': [{'name': 'host'}]},
                             'vector', batch=32, read_size=2048)
    assert not _live_producers() and not _unreleased()


# -- (d) a consumer that stops ------------------------------------------------

class _Abandoned(BaseException):
    """Not an Exception: what a deadline or an interrupt looks like."""


@pytest.mark.parametrize('error', [ValueError, _Abandoned])
def test_stopped_consumer_leaves_nothing_behind(tmp_path, error):
    """The request's thread raises in the middle of the stream (an
    engine error, or a BaseException as an interrupt is): the producer
    thread has ended by the time the error leaves `_stream_native`, no
    detached batch is left unreleased — not the one in flush, not the
    one queued, not the one the producer was filling — and the
    read-ahead thread under it ends too."""
    files = _two_files(tmp_path)
    flushed = []

    def flush(batch):
        flushed.append(batch)
        if len(flushed) == 3:
            # let the producer fill the queue and block on it
            time.sleep(0.05)
            raise error('consumer stops')

    with pytest.raises(error):
        _stream(files, flush)
    assert len(flushed) == 3
    assert not _live_producers()
    assert all(b.h is None for b in flushed)
    assert not _unreleased()
    _wait_for(lambda: not [t for t in threading.enumerate()
                           if t.name == 'dn-read-ahead'])


def test_consumer_stops_while_the_producer_waits_for_a_chunk(
        tmp_path, monkeypatch):
    """The producer sits in its wait for the next chunk of a source
    that has stalled (a pipe): a consumer that stops does not wait for
    the chunk to arrive."""
    files = _two_files(tmp_path)[:1]
    stalled = threading.Event()
    real = mod_ingest.open_byte_source

    def source(path, chunk_size=1 << 20):
        for i, chunk in enumerate(real(path, chunk_size)):
            if i == 4:
                stalled.wait(30)
            yield chunk
    monkeypatch.setattr(mod_ingest, 'open_byte_source', source)

    def flush(batch):
        raise ValueError('consumer stops')

    t0 = time.monotonic()
    try:
        with pytest.raises(ValueError):
            _stream(files, flush, batch_size=10, read_size='1024')
        assert time.monotonic() - t0 < 5.0
        assert not _live_producers() and not _unreleased()
    finally:
        stalled.set()


def test_handoff_counters_and_wait_stage(tmp_path):
    """`scan_batches_handed` counts the batches that crossed the
    hand-off, `scan_batches_ready` those that were waiting when the
    consumer asked; a slow consumer finds every batch but the first
    ready, and its `scan.parse_wait` holds one wait a batch and one
    for the end of the stream (two where the stream ends on a batch
    boundary: the empty tail is waited for and not handed)."""
    files = _two_files(tmp_path)
    obs_metrics.reset_global_registry()
    sizes = []

    def flush(batch):
        sizes.append(batch.batch_size())
        time.sleep(0.02)

    parser = _stream(files, flush)
    assert sum(sizes) == 600 == parser.counters()[0]
    reg = obs_metrics.global_registry()
    handed = reg.counter('scan_batches_handed').value
    ready = reg.counter('scan_batches_ready').value
    assert handed == len(sizes) >= 5
    assert handed - 2 <= ready <= handed
    waits = [m for name, labels, m in reg.snapshot()
             if name == 'stage_ms' and
             dict(labels)['stage'] == 'scan.parse_wait']
    assert waits[0].total in (handed + 1, handed + 2)
    assert np.isfinite(waits[0].sum)
