"""Driver `serve_rollup`: drivers/serve.py's run over an index tree
that has its rollup shards.

run.py has built the fine tree by now (`prebuilt_index`, at the
configuration's `index_interval`).  This driver adds the one step the
deployment has beyond it: one `dn rollup --interval <index_interval>`
child under the configuration's `setup_build_environment` (the host
engine, off the chip: the rollups are merged from the fine shards, no
raw rescan), whose seconds are set-up's.  It then checks that the child
built exactly the day and month shards the corpus's window holds (a
tree short of a rollup would still answer, from its fine shards, and
the cell would measure another deployment), and hands over to
`drivers/serve.run(ctx)` unchanged: the child server, the warm-up, the
window, the comparison and what run.py gets back are that driver's.

This process never touches the jax backend.
"""

import datetime
import os
import re
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from loader import load_module  # noqa: E402

serve = load_module('drivers', 'serve')

DAY_MS = 86400000
BUILT = re.compile(r'dn rollup: (\d+) shard\(s\) built')


def rollup_shards(corpus):
    """How many rollup shards `dn rollup` makes of an hourly tree over
    the corpus's window: one a day that holds a record and one a month
    that holds a day (timestamps rise linearly from `mindate_ms`, so
    every day of the window holds records)."""
    day0 = datetime.datetime.fromtimestamp(
        corpus['mindate_ms'] // 1000, datetime.timezone.utc).date()
    days = [day0 + datetime.timedelta(days=k)
            for k in range(corpus['days'])]
    return len(days) + len({(d.year, d.month) for d in days})


def build_rollups(ctx):
    """One `dn rollup` child over the run's tree; returns the number
    of shards it says it built."""
    env = dict(os.environ, DRAGNET_CONFIG=ctx.rc_path)
    env.update(ctx.config.get('setup_build_environment') or {})
    t0 = time.monotonic()
    p = subprocess.run(
        [sys.executable, os.path.join(ctx.root, 'bin', 'dn.py'), 'rollup',
         '--interval', ctx.config['index_interval']],
        env=env, cwd=ctx.run_dir, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE)
    err = p.stderr.decode('utf-8', 'replace')
    said = BUILT.search(err)
    if p.returncode != 0 or said is None:
        raise RuntimeError('set-up rollup failed (%d): %s'
                           % (p.returncode, err[-2000:]))
    ctx.say('set-up rollup build: %s shards, %.1fs'
            % (said.group(1), time.monotonic() - t0))
    return int(said.group(1))


def run(ctx):
    built, want = build_rollups(ctx), rollup_shards(ctx.config['corpus'])
    if built != want:
        raise RuntimeError('set-up rollup built %d shards, the corpus\'s '
                           'window holds %d' % (built, want))
    return serve.run(ctx)
