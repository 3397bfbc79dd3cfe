#!/usr/bin/env python3
"""The control at a cell's own size: the plain reference computed the
way the cell's `control` says (`bfloat16`, the default: sums rounded to
bfloat16 after every partial; `key32`: the bit-packed key folded to its
low 32 bits), put in the program's place.

    python benchmarks/tests/control_at_size.py --workload <cell> \
        --seeds 11 12 13 [--control bfloat16]

`--control` reads another control than the cell's own, to show why the
cell does not name it.

For every seed it generates the cell's corpus at the configuration's
size, draws the cell's traffic as a run would, and prints for each
request class the two numbers a run compares (mismatched tuples, summed
count difference) of the control against the exact reference.  Both
limits are 0 (an exact comparison), so the control fails a cell when
its smallest reading over the classes it must fail is above 0; the
last line gives the smallest and the largest reading per seed.  The
benchmark's own runs never run this.
"""

import argparse
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

from gen import corpus                                   # noqa: E402
from reference.groupby import Reference, compare        # noqa: E402
import traffic                                           # noqa: E402

DAY_MS = 86400000


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seeds', type=int, nargs='+', required=True)
    ap.add_argument('--seconds', type=float, default=30.0)
    ap.add_argument('--control', default=None)
    args = ap.parse_args()
    with open(os.path.join(BENCH, 'workloads', args.workload + '.json')) as f:
        wl = json.load(f)
    control = args.control or wl.get('control', 'bfloat16')
    with open(os.path.join(BENCH, 'configs', wl['config'] + '.json')) as f:
        cfg = json.load(f)
    c = cfg['corpus']
    scratch = os.path.join(ROOT, '.cache', 'bench', 'control')
    os.makedirs(scratch, exist_ok=True)
    lib = corpus.build_library(os.path.join(ROOT, '.cache', 'bench', 'gen'))
    try:
        for seed in args.seeds:
            cols, _ = corpus.generate(
                lib, os.path.join(scratch, 'muskie.log'), c['records'],
                c['mindate_ms'], c['mindate_ms'] + c['days'] * DAY_MS, seed)
            ref = Reference(cols, {'host': corpus.HOSTS,
                                   'method': corpus.METHODS,
                                   'op': corpus.OPERATIONS})
            if wl['loop'] == 'open':
                reqs = traffic.open_loop(wl, seed, args.seconds, c['days'])
            else:
                reqs = traffic.warmup(wl)
            reqs += [traffic.Request(None, t, None, None)
                     for t in wl.get('verify') or []]
            worst, readings = {}, []
            for r in reqs:
                t = r.template
                if 'query' not in t:
                    continue
                q = dict(t['query'])
                if r.days is not None:
                    q['timeAfter'] = c['mindate_ms'] + r.start_day * DAY_MS
                    q['timeBefore'] = q['timeAfter'] + r.days * DAY_MS
                part = t.get('part', 'batch')
                exact = ref.expected_lines(q, part=part)
                low = ref.expected_lines(q, part=part, accumulate=control)
                got = compare(b'\n'.join(low), exact) + (len(exact),)
                readings.append(got)
                key = (t['name'], r.days)
                worst[key] = min(worst.get(key, got), got)
            for key in sorted(worst, key=str):
                print('seed %d class %s/%s: control %s mismatched_tuples=%d '
                      'count_difference=%d (limits 0, 0) of %d tuples'
                      % ((seed,) + key + (control,) + worst[key]))
            failing = sum(1 for g in readings if g[0] > 0)
            print('seed %d: %d of %d requests fail under the control; over '
                  'the run the largest reading is mismatched_tuples=%d, and '
                  'the run as a whole %s'
                  % (seed, failing, len(readings),
                     max(g[0] for g in readings),
                     'FAILS' if failing else 'passes (control too weak)'))
    finally:
        shutil.rmtree(scratch, ignore_errors=True)


if __name__ == '__main__':
    main()
