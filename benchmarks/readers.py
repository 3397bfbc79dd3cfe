"""What several per-layer metrics share.  A metric's own file
(metrics/<name>.py) says which layer it belongs to and what it moves,
and calls one of these with its operation; every function returns None
when it finds nothing to read, and the harness then leaves the metric
out of the line."""

import json
import math
import os
import re

from loader import load_module
from obs import prom

HERE = os.path.dirname(os.path.abspath(__file__))
COMPILED = re.compile(r'Finished XLA compilation of (.*?) in ')


def idle_share_pct(r):
    """1 - union of device-operation intervals / traced window, of the
    most idle chip, in percent."""
    if not r.trace or not r.trace['chips']:
        return None
    return 100.0 * max(c['idle_share'] for c in r.trace['chips'])


def window_compiles(r):
    """Compilations the child logged inside the window (JAX_LOG_COMPILES
    lines; the server binds stderr per request, so a request's own
    lines come back in its reply)."""
    names = COMPILED.findall(r.window_stderr)
    for o in r.outcomes:
        if o.err:
            names += COMPILED.findall(o.err.decode('utf-8', 'replace'))
    if names:
        r.say('compiled inside the window: %s' % ', '.join(names[:20]))
    return float(len(names))


def h2d_bytes_per_record(r, op):
    moved, records = r.delta('device_h2d_bytes'), r.records(op)
    if moved is None or not records:
        return None
    return moved / records


def stage_ms(r, *stages):
    """Summed `stage_ms{stage}` over the window, in ms."""
    got = [r.delta('stage_ms_sum', stage=s) for s in stages]
    if all(g is None for g in got):
        return None
    return sum(g or 0.0 for g in got)


def peaks(r):
    """The peaks of the device the child named; a device that is not in
    the table is an error, not a default."""
    with open(os.path.join(HERE, 'trace', 'peaks.json')) as f:
        table = json.load(f)['devices']
    return table[r.device['kind']]


def queue_wait_p95_ms(r):
    return prom.histogram_quantile(r.before, r.after,
                                   'serve_queue_wait_ms', 0.95)


def device_seconds(r):
    """Device-busy seconds of the whole window, from the traced part of
    it: the traced window's busy share (mean over chips) times the
    window.  The trace covers a few seconds of a steady window, so the
    share carries over."""
    if not r.trace or not r.trace['window_s']:
        return None
    return r.trace['busy_s'] / r.trace['window_s'] * r.window_s


def fold_roofline_pct(r):
    """The least time the chip could take over the bytes the fold has
    to move (trace/costs.py), against the device time it took."""
    costs = load_module('trace', 'costs')
    busy, moved = device_seconds(r), r.delta('device_h2d_bytes')
    if not busy or not moved:
        return None
    need = costs.fold_bytes(moved, r.delta('device_d2h_bytes') or 0.0)
    return 100.0 * costs.least_seconds(need, 0.0, peaks(r)) / busy


def percentile(values, q):
    """Nearest rank; the values may hold inf for a request that never
    got its answer."""
    s = sorted(values)
    return s[max(0, int(math.ceil(q * len(s))) - 1)]


def latency_ms(r, op, q):
    """Percentile of the finished requests' latencies on the client's
    clock, in ms."""
    lat = [o.latency_s * 1000.0 for o in r.done(op)]
    return percentile(lat, q) if lat else None
