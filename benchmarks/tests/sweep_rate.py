#!/usr/bin/env python3
"""Find the rate an open-loop cell sustains, once, on the chip.

    python benchmarks/tests/sweep_rate.py --workload <cell> \
        --rates 25 50 100 200 --seconds 15 [--seed 1]

For each rate it writes a throw-away copy of the cell's workload file
as an open loop (Poisson arrivals, 8 senders) with that `rate_per_s`, runs it once through run.py, prints the result
line and run.py's `backlog:` line (median and p95 latency by thirds of
the window: a backlog that grows shows as a last third well above the
first), and removes the copy.  The cell's own file then gets four
fifths of the highest rate that held, as a whole number.
"""

import argparse
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--workload', required=True)
    ap.add_argument('--rates', type=int, nargs='+', required=True)
    ap.add_argument('--seconds', type=float, default=15.0)
    ap.add_argument('--seed', type=int, default=2147483900)
    args = ap.parse_args()
    with open(os.path.join(BENCH, 'workloads', args.workload + '.json')) as f:
        wl = json.load(f)
    for i, rate in enumerate(args.rates):
        name = 'sweep-%d.%s' % (rate, args.workload)
        path = os.path.join(BENCH, 'workloads', name + '.json')
        with open(path, 'w') as f:
            json.dump(dict(wl, name=name, loop='open', rate_per_s=rate,
                           senders=wl.get('senders', 8)), f)
        try:
            p = subprocess.run(
                [sys.executable, os.path.join(BENCH, 'run.py'),
                 '--workload', name, '--seed', str(args.seed + i),
                 '--seconds', str(args.seconds), '--trace', '0'],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        finally:
            os.unlink(path)
        lines = p.stdout.decode('utf-8', 'replace').splitlines()
        print('rate %d/s: exit %d' % (rate, p.returncode))
        for ln in lines:
            if ln.startswith(('window:', 'backlog:', 'problem:',
                              'failed request', '{', 'rehearsal')):
                print('   ' + ln)
        sys.stdout.flush()


if __name__ == '__main__':
    main()
