"""File enumeration: strftime-patterned path expansion + recursive find.

Re-implements the behavior (including --counters observability) of the
reference's input-enumeration layer:

* parse_strftime_pattern: the `timefilter` dependency's pattern parser
  (%Y %m %d %H and %% only), with its exact error messages
  (reference: tests/lib/tst.path_enum.js expectations),
* PathEnumerator: expands a pattern over [start, end) with unit-aligned
  increments so month arithmetic stays correct
  (reference: lib/path-enum.js:64-265),
* find_walk: the FindStream pipeline (FindStart -> FindStatter ->
  FindTraverser -> FindFeedback) emulated as a FIFO walk with
  generation-numbered EOF signals, reproducing the reference's per-stage
  counters byte-for-byte (reference: lib/fs-find.js:70-224).
"""

import os
import stat as mod_stat
from datetime import datetime, timezone

from .errors import DNError


def parse_strftime_pattern(pattern):
    """Returns a list of {'kind': 'str', 'value': s} / {'kind': Y|m|d|H}
    entries, or DNError."""
    entries = []
    buf = []
    i = 0
    n = len(pattern)
    while i < n:
        ch = pattern[i]
        if ch != '%':
            buf.append(ch)
            i += 1
            continue
        if i == n - 1:
            return DNError('unexpected "%%" at char %d' % (i + 1))
        conv = pattern[i + 1]
        if conv == '%':
            buf.append('%')
            i += 2
            continue
        if conv not in ('Y', 'm', 'd', 'H'):
            return DNError('unsupported conversion "%%%s" at char %d'
                           % (conv, i + 1))
        if buf:
            entries.append({'kind': 'str', 'value': ''.join(buf)})
            buf = []
        entries.append({'kind': conv})
        i += 2
    if buf:
        entries.append({'kind': 'str', 'value': ''.join(buf)})
    return entries


_UNIT_ORDER = {'Y': 365 * 24, 'm': 30 * 24, 'd': 24, 'H': 1}


class PathEnumerator(object):
    """Expand `pattern` for each time unit in [start_ms, end_ms)."""

    def __init__(self, pattern, start_ms, end_ms, generator):
        self.pattern = pattern
        self.generator = generator
        self.end_ms = end_ms
        self.noutputs = 0

        minunit = None
        minval = float('inf')
        for entry in generator:
            if entry['kind'] == 'str':
                continue
            unit = _UNIT_ORDER[entry['kind']]
            if unit < minval:
                minval = unit
                minconv = entry['kind']
        if minval != float('inf'):
            minunit = minconv
        self.minunit = minunit

        dt = datetime.fromtimestamp(start_ms / 1000.0, tz=timezone.utc)
        dt = dt.replace(minute=0, second=0, microsecond=0)
        if minunit == 'Y':
            dt = dt.replace(month=1, day=1, hour=0)
        elif minunit == 'm':
            dt = dt.replace(day=1, hour=0)
        elif minunit == 'd':
            dt = dt.replace(hour=0)
        self.next = dt

    def _expand(self, dt):
        parts = []
        for entry in self.generator:
            k = entry['kind']
            if k == 'str':
                parts.append(entry['value'])
            elif k == 'Y':
                parts.append(str(dt.year))
            elif k == 'm':
                parts.append('%02d' % dt.month)
            elif k == 'd':
                parts.append('%02d' % dt.day)
            else:
                parts.append('%02d' % dt.hour)
        return ''.join(parts)

    def _increment(self):
        dt = self.next
        if self.minunit is None:
            self.next = None
            return
        if self.minunit == 'Y':
            dt = dt.replace(year=dt.year + 1)
        elif self.minunit == 'm':
            if dt.month == 12:
                dt = dt.replace(year=dt.year + 1, month=1)
            else:
                dt = dt.replace(month=dt.month + 1)
        elif self.minunit == 'd':
            from datetime import timedelta
            dt = dt + timedelta(days=1)
        else:
            from datetime import timedelta
            dt = dt + timedelta(hours=1)
        if dt.timestamp() * 1000 >= self.end_ms:
            dt = None
        self.next = dt

    def paths(self):
        rv = []
        while self.next is not None:
            rv.append(self._expand(self.next))
            self.noutputs += 1
            self._increment()
        # The reference's Readable (highWaterMark 20) counts the final
        # null push only when it happens in the same burst as the last
        # value; with >= 20 paths backpressure defers it to a counterless
        # _read call (lib/path-enum.js:173-192).
        if len(rv) < 20:
            self.noutputs += 1
        return rv


def create_path_enumerator(pattern, start_ms, end_ms):
    if start_ms is None:
        return DNError('"timeStart" is not a valid date')
    if end_ms is None:
        return DNError('"timeEnd" is not a valid date')
    if start_ms > end_ms:
        return DNError('"timeStart" may not be after "timeEnd"')
    generator = parse_strftime_pattern(pattern)
    if isinstance(generator, DNError):
        return generator
    return PathEnumerator(pattern, start_ms, end_ms, generator)


class _Eof(object):
    def __init__(self, gen):
        self.gen = gen


def find_walk(roots, pipeline, pathenum=None, skip=None):
    """Walk `roots` recursively, returning [(path, statbuf)] for every
    regular file and character device, in the reference's emission order
    (FIFO/BFS with lexicographic dirents).  Registers the pipeline stages
    and counters that `dn --counters` reports.  Directory entries whose
    name `skip` accepts are not there for the walk (an index walk
    passes index_journal.is_index_litter: a builder's tmps and journals
    are no part of the tree before their commit, in the counters as in
    the answer).
    """
    if pathenum is not None:
        pe_stage = pipeline.stage('PathEnumerator')
        pe_stage.counters['noutputs'] = pathenum.noutputs
    start = pipeline.stage('FindStart')
    statter = pipeline.stage('FindStatter')
    traverser = pipeline.stage('FindTraverser')
    feedback = pipeline.stage('FindFeedback')

    results = []
    queue = []
    for root in roots:
        start.bump('ninputs')
        start.bump('noutputs')
        queue.append(root)

    generation = -1
    queue.append(_Eof(generation))
    signal_sent = True

    qi = 0
    while qi < len(queue):
        item = queue[qi]
        qi += 1

        statter.bump('ninputs')
        if isinstance(item, _Eof):
            statter.bump('noutputs')
            traverser.bump('ninputs')
            traverser.bump('noutputs')
            feedback.bump('ninputs')
            if item.gen == generation:
                break
            continue

        # stat
        try:
            st = os.stat(item)
        except OSError as e:
            statter.warn(e, 'badstat')
            continue
        statter.bump('noutputs')

        traverser.bump('ninputs')
        if mod_stat.S_ISDIR(st.st_mode):
            try:
                dirents = sorted(os.listdir(item))
            except OSError as e:
                traverser.warn(e, 'badreaddir')
                continue
            if skip is not None:
                dirents = [d for d in dirents if not skip(d)]
            traverser.bump('noutputs')
            feedback.bump('ninputs')
            feedback.bump('ndirectories')
            for d in dirents:
                queue.append(os.path.join(item, d))
            if signal_sent and len(dirents) > 0:
                generation += 1
                queue.append(_Eof(generation))
            continue

        traverser.bump('noutputs')
        feedback.bump('ninputs')
        if mod_stat.S_ISREG(st.st_mode):
            feedback.bump('nregfiles')
            feedback.bump('noutputs')
            results.append((item, st))
        elif mod_stat.S_ISCHR(st.st_mode) or mod_stat.S_ISFIFO(st.st_mode):
            # On the reference's platform (SmartOS) /dev/stdin is a
            # character device; on Linux a piped stdin stats as a FIFO.
            # Accept both so `--path=/dev/stdin` datasources work.
            feedback.bump('nchrdevs')
            feedback.bump('noutputs')
            results.append((item, st))
        else:
            feedback.warn(DNError('not file or directory'), 'ignored')

    return results
