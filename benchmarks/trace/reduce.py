"""Reduce a jax.profiler trace (.xplane.pb) to the seconds the per-layer
metrics read.

    python benchmarks/trace/reduce.py TRACE_DIR OUT.json [--events EV.json]

Two steps, so that the arithmetic can be tested on a small recorded
trace without the profiler: `load_events` turns the xplane file into
plain lists (the only step that imports jax), `reduce_events` turns
those into numbers.

What a TPU trace looks like (TPU v5 lite, jax 0.9.0, looked at by hand
in PR 25): one plane `/device:TPU:<n>` per chip, whose line `XLA Ops`
holds one event per device operation, named by its whole HLO text
(`%fusion.1 = s32[32769]{0:T(1024)S(1)} fusion(s32[131072]... `), and
whose line `XLA Modules` holds one event per program run
(`jit_run(<fingerprint>)`); the plane `/host:CPU` holds one line per
host thread, with the runtime's own spans (`np.asarray(jax.Array)`,
`PjitFunction(run)`) even when the Python tracer is off.  `short_op`
cuts an operation's name to `fusion.1 s32[32769] fusion`.

The traced window runs from the first event to the last.  The launcher
(drivers/launch_serve.py) keeps one host event, `WINDOW_MARK`, open
from the trace's start to its stop, so the window is the trace's whole
length also where the device and every span are silent for most of it
(a reply formatted for seconds under no leaf); it names no gap.
"""

import glob
import json
import os
import re
import sys

DEVICE_PLANE = re.compile(r'^/device:TPU:(\d+)$')
OPS_LINE = 'XLA Ops'
MODULES_LINE = 'XLA Modules'
HOST_PLANE = '/host:CPU'
WINDOW_MARK = 'bench.traced_window'

# device operations that move data between chips
COLLECTIVE = re.compile(
    r'^(all-reduce|all-gather|all-to-all|reduce-scatter|'
    r'collective-permute|collective-broadcast|send|recv)')

TOP = 10


_HLO = re.compile(r'^%?(\S+) = (\(?[a-z0-9]+\[[^\]]*\])\S*.*? ([a-z][a-z0-9-]*)\(')


def short_op(name):
    """`%fusion.1 = s32[32769]{0:T(1024)S(1)} fusion(...)` ->
    `fusion.1 s32[32769] fusion`; any other name as it is."""
    m = _HLO.match(name)
    return '%s %s %s' % m.groups() if m else name[:100]


def load_events(trace_dir):
    """{'planes': [{'name', 'lines': [{'name', 'events': [[name,
    start_ns, dur_ns], ...]}]}]} of the newest .xplane.pb under
    `trace_dir`.  Host lines keep only their longest events: they are
    there to name gaps, not to be summed."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(
        trace_dir, 'plugins', 'profile', '*', '*.xplane.pb')))
    if not files:
        raise RuntimeError('no .xplane.pb under %s' % trace_dir)
    data = ProfileData.from_file(files[-1])
    planes = []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        if not device and plane.name != HOST_PLANE:
            continue
        lines = []
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            evs = [[short_op(e.name) if line.name == OPS_LINE else e.name,
                    int(e.start_ns), int(e.duration_ns)]
                   for e in line.events]
            if not device:
                evs = [e for e in evs if e[2] >= 1000000]
            if evs:
                lines.append({'name': line.name, 'events': evs})
        planes.append({'name': plane.name, 'lines': lines})
    return {'planes': planes}


def union_ns(intervals):
    """Nanoseconds covered by a list of (start_ns, end_ns)."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def union_s(intervals):
    return union_ns(intervals) / 1e9


def gaps(intervals, t0, t1):
    """The idle gaps (start_ns, end_ns) of [t0, t1] that `intervals`
    leave."""
    out, at = [], t0
    for s, e in sorted(intervals):
        if s > at:
            out.append((at, min(s, t1)))
        at = max(at, e)
    if at < t1:
        out.append((at, t1))
    return [g for g in out if g[1] > g[0]]


def name_gap(g0, g1, host):
    """What the host was doing over the idle gap [g0, g1]: the host
    event that covers most of it, if it covers half.  Where none does,
    the events that cover half of it together, by name, largest first,
    each with its share (a gap between two scans holds two reply leaves
    of a third each); where those do not reach half either, nothing was
    traced for most of it: a span at the gap's edge is not what the host
    was doing.  `host` is [(thread, [name, start_ns, dur_ns])]."""
    best, cover, by_name = 'host: nothing traced', 0, {}
    for thread, (name, s, dur) in host:
        s, e = max(g0, s), min(g1, s + dur)
        if e <= s:
            continue
        by_name.setdefault(name, []).append((s, e))
        if e - s > cover:
            best, cover = '%s (%s)' % (name, thread), e - s
    if not 0 < 2 * cover < g1 - g0:
        return best
    together, parts = [], []
    for covered, name in sorted(((union_ns(spans), name)
                                 for name, spans in by_name.items()),
                                key=lambda x: (-x[0], x[1])):
        together += by_name[name]
        parts.append('%s %d%%' % (name, 100 * covered // (g1 - g0)))
        if 2 * union_ns(together) >= g1 - g0:
            return ' + '.join(parts)
    return 'host: nothing traced for most of it (%s covers %d%%)' \
        % (best[:60], 100 * cover // (g1 - g0))


def reduce_events(doc):
    """The numbers the metrics read.  Keys: window_s, busy_s (mean over
    chips), chips [{busy_s, idle_share, collective_s, modules {name:
    [runs, seconds]}, ops {name: seconds}}], breakdown."""
    spans = [(e[1], e[1] + e[2]) for p in doc['planes']
             for ln in p['lines'] for e in ln['events']]
    if not spans:
        return {'window_s': 0.0, 'busy_s': 0.0, 'chips': [],
                'breakdown': {'device_ops': [], 'idle_gaps': []}}
    t0 = min(s for s, _ in spans)
    t1 = max(e for _, e in spans)
    window_s = (t1 - t0) / 1e9
    host = [(ln['name'], e) for p in doc['planes']
            if p['name'] == HOST_PLANE
            for ln in p['lines'] for e in ln['events']
            if e[0] != WINDOW_MARK]
    chips, op_total = [], {}
    for p in doc['planes']:
        if not DEVICE_PLANE.match(p['name']):
            continue
        ops = [e for ln in p['lines'] if ln['name'] == OPS_LINE
               for e in ln['events']]
        mods = [e for ln in p['lines'] if ln['name'] == MODULES_LINE
                for e in ln['events']]
        busy = [(e[1], e[1] + e[2]) for e in ops]
        per_op, per_mod = {}, {}
        for name, _, dur in ops:
            per_op[name] = per_op.get(name, 0.0) + dur / 1e9
            op_total[name] = op_total.get(name, 0.0) + dur / 1e9
        for name, _, dur in mods:
            key = re.sub(r'\(.*$', '', name)
            runs, secs = per_mod.get(key, [0, 0.0])
            per_mod[key] = [runs + 1, secs + dur / 1e9]
        busy_s = union_s(busy)
        chips.append({
            'plane': p['name'], 'busy_s': busy_s,
            'idle_share': 1.0 - busy_s / window_s if window_s else None,
            'collective_s': union_s(
                [(e[1], e[1] + e[2]) for e in ops
                 if COLLECTIVE.match(e[0])]),
            'ops': per_op, 'modules': per_mod,
            'gaps': gaps(busy, t0, t1)})
    nchips = max(1, len(chips))
    device_ops = sorted(([n, s / nchips] for n, s in op_total.items()),
                        key=lambda x: -x[1])[:TOP]
    # the longest idle gaps of the first chip, each named by what the
    # host was doing
    idle = [[name_gap(g0, g1, host)[:120], (g1 - g0) / 1e9]
            for g0, g1 in sorted(chips[0]['gaps'] if chips else [],
                                 key=lambda g: g[0] - g[1])[:TOP]]
    for c in chips:
        del c['gaps']
    return {'window_s': window_s,
            'busy_s': sum(c['busy_s'] for c in chips) / nchips,
            'chips': chips,
            'breakdown': {'device_ops': device_ops, 'idle_gaps': idle}}


def main(argv):
    trace_dir, out = argv[1], argv[2]
    doc = load_events(trace_dir)
    if '--events' in argv:
        with open(argv[argv.index('--events') + 1], 'w') as f:
            json.dump(doc, f)
    with open(out, 'w') as f:
        json.dump(reduce_events(doc), f)
    return 0


if __name__ == '__main__':
    sys.exit(main(sys.argv))
