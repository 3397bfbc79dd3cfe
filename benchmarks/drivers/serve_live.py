"""Driver `serve_live`: a tree that is queried while the next day is
published into it.

One resident `dn serve` child (drivers/serve.py's `Child`: the
program's normal entry, its control socket) answers `query` and `build`
requests for the SAME datasource, as `dn query --remote` and
`dn build --remote --after --before` send them.

Set-up.  run.py has written the corpus (`corpus.days` days in one
file).  This driver builds the standing tree, the first
`corpus.standing_days` days, with one `dn build --after --before` child
under the configuration's `setup_build_environment` (the host engine,
off the chip), starts the server, publishes the day after the standing
tree through the server (held to the same three checks as a publish of
the window: it compiles the build's programs and is read back), and
warms every reader class up.  Every request has the cell's `timeout_s`,
the warm-up's build too: a program whose build compiles for longer
cannot run the cell (one that compiles a program a window of bounds, as
the program before PR 50 does, would compile inside the measured
window again), and its run ends there, in set-up, with an error.

The window, and only it, is this file's own:

* the readers, a closed loop of `clients` callers over the cell's
  templates and windows, start days drawn over the STANDING days alone
  (a reader never asks for a day that a publish may or may not have
  landed, so the reference needs no clock);
* the publisher, one more client outside the closed loop's count:
  publish k of `publishes` is due at (k + 0.5) x seconds / publishes
  whatever the readers do, and is three requests in a row: a query of
  the first template over exactly the day to come (no tuple: the day
  is not there, and the empty answer is now in the result cache), the
  `build` of that day, the same query again (the reference's answer
  for that day: an acknowledged build is read back, and not from the
  cache).  A publish that is still running when the next is due makes
  the next late; one not finished when the readers stop is finished
  and counted.

What run.py gets back: `outcomes` holds the readers' queries and the
builds (a build's `op` is `build`, so a rate over `op` `query` does not
count it); the publisher's queries are checked here and held apart in
`publisher_queries`, so `query_completed_per_s` and the client-side
latencies are the readers' alone.  `window_s` is the readers' window.

This process never touches the jax backend.
"""

import datetime
import os
import subprocess
import sys
import threading
import time
import uuid

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from loader import load_module  # noqa: E402
from readers import percentile  # noqa: E402

serve = load_module('drivers', 'serve')
Child, send, check_outcome = serve.Child, serve.send, serve.check_outcome
Tracer, prom = serve.Tracer, serve.prom

DAY_MS = 86400000
BUILD = {'name': 'publish', 'op': 'build'}


# -- the publisher's plan ----------------------------------------------------

def publish_due(seconds, publishes):
    """Seconds from the window's start at which each publish is due."""
    return [(k + 0.5) * seconds / publishes for k in range(publishes)]


def publish_day(config, k):
    """The day publish k of the window writes: the warm-up has written
    the day after the standing tree."""
    return config['corpus']['standing_days'] + 1 + k


def day_bounds_ms(config, day):
    """[after, before) of one day of the corpus, in epoch ms."""
    after = config['corpus']['mindate_ms'] + day * DAY_MS
    return after, after + DAY_MS


# -- set-up ------------------------------------------------------------------

def iso_day(ms):
    return datetime.datetime.fromtimestamp(
        ms // 1000, datetime.timezone.utc).strftime('%Y-%m-%d')


def build_standing_tree(ctx):
    """One `dn build --after <day 0> --before <standing_days>` child,
    off the chip; both bounds, as check_time_args wants them."""
    c = ctx.config['corpus']
    env = dict(os.environ, DRAGNET_CONFIG=ctx.rc_path)
    env.update(ctx.config.get('setup_build_environment') or {})
    after, _ = day_bounds_ms(ctx.config, 0)
    before, _ = day_bounds_ms(ctx.config, c['standing_days'])
    t0 = time.monotonic()
    p = subprocess.run(
        [sys.executable, os.path.join(ctx.root, 'bin', 'dn.py'), 'build',
         '--interval', ctx.config.get('index_interval', 'day'),
         '--after', iso_day(after), '--before', iso_day(before),
         ctx.datasource],
        env=env, cwd=ctx.run_dir, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE)
    if p.returncode != 0:
        raise RuntimeError('set-up build failed (%d): %s' % (
            p.returncode, p.stderr.decode('utf-8', 'replace')[-2000:]))
    root = os.path.join(ctx.run_dir, 'idx', ctx.datasource, 'by_day')
    built = len([n for n in os.listdir(root) if n.endswith('.sqlite')])
    ctx.say('set-up standing tree: %d daily shards, %.1fs'
            % (built, time.monotonic() - t0))
    if built != c['standing_days']:
        raise RuntimeError('the standing tree holds %d shards, the '
                           'configuration says %d'
                           % (built, c['standing_days']))


# -- one publish -------------------------------------------------------------

def send_build(ctx, child, req, due_at):
    """serve.send for a build with bounds: the document `dn build
    --remote --after --before` ships."""
    from dragnet_tpu.serve import client
    from dragnet_tpu.errors import DNError
    after, before = day_bounds_ms(ctx.config, req.start_day)
    doc = {'op': 'build', 'ds': ctx.datasource, 'config': ctx.rc_path,
           'interval': ctx.config.get('index_interval', 'day'),
           'after': after, 'before': before, 'index_config': None,
           'idempotency': uuid.uuid4().hex,
           'opts': {'raw': False, 'points': True, 'counters': False,
                    'gnuplot': False, 'dry_run': False}}
    o = serve.Outcome(req, ctx.datasource)
    t0 = time.monotonic()
    o.sent_late_s = t0 - due_at
    try:
        o.rc, _, o.out, o.err = client.request_bytes(
            child.sock, doc, timeout_s=ctx.workload.get('timeout_s', 300),
            pooled=True)
    except (OSError, ValueError, DNError) as e:
        o.error = repr(e)
    o.latency_s = time.monotonic() - t0
    return o


def tuples_of(o):
    return len([ln for ln in (o.out or b'').split(b'\n') if ln])


class Publish(object):
    """One publish: its three outcomes and what they were held to."""

    def __init__(self, day, due_s):
        self.day, self.due_s = day, due_s
        self.pre = self.build = self.readback = None

    def run(self, ctx, child, due_at):
        import traffic
        ask = traffic.Request(self.due_s, ctx.workload['templates'][0], 1,
                              self.day)
        self.pre = send(ctx, child, ask, ctx.datasource)
        self.build = send_build(
            ctx, child, traffic.Request(self.due_s, BUILD, 1, self.day),
            due_at)
        self.readback = send(ctx, child, ask, ctx.datasource)

    def queries(self):
        return [o for o in (self.pre, self.readback) if o is not None]

    def failed(self):
        return sum(1 for o in self.queries() if not o.ok)

    def built(self):
        return self.build is not None and self.build.ok and \
            b'built' in (self.build.err or b'')

    def checks(self, ctx):
        """(tuples before the build, (mismatched, count difference) of
        the read-back); a query that failed reads as one tuple wrong."""
        pre = tuples_of(self.pre) if self.pre is not None and \
            self.pre.ok else 1
        back = check_outcome(ctx, self.readback) \
            if self.readback is not None and self.readback.ok else (1, 0)
        return pre, back

    def say(self, ctx):
        ms = lambda o: o.latency_s * 1000.0 if o is not None else -1.0
        ctx.say('publish of day %d due %.1fs: %.1f ms late, pre-query '
                '%.1f ms, build %.1f ms, read-back %.1f ms'
                % (self.day, self.due_s,
                   (self.build.sent_late_s if self.build is not None
                    else 0.0) * 1000.0,
                   ms(self.pre), ms(self.build), ms(self.readback)))


# -- the window --------------------------------------------------------------

def live_window(ctx, child, seconds, seed, npublishes):
    """The readers' closed loop and the publisher's schedule side by
    side; returns (readers' outcomes, publishes, readers' seconds).  A
    reader's request carries the seconds from the window's start at
    which it was sent (`due_s`), so what overlapped a build is known.
    (The ramp-up is this with no publish.)"""
    import traffic
    wl, cfg = ctx.workload, ctx.config
    gen = traffic.closed_loop(wl, seed, cfg['corpus']['standing_days'])
    lock = threading.Lock()
    outcomes = []
    w0 = time.monotonic()
    t_end = w0 + seconds
    readers_done = [w0]

    def reader():
        while time.monotonic() < t_end:
            with lock:
                req = next(gen)
            o = send(ctx, child,
                     req._replace(due_s=time.monotonic() - w0),
                     ctx.datasource)
            with lock:
                outcomes.append(o)
        with lock:
            readers_done[0] = max(readers_done[0], time.monotonic())

    publishes = [Publish(publish_day(cfg, k), due) for k, due in
                 enumerate(publish_due(seconds, npublishes))]

    def publisher():
        for p in publishes:
            wait = w0 + p.due_s - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            p.run(ctx, child, w0 + p.due_s)

    threads = [threading.Thread(target=reader, name='bench-reader-%d' % k)
               for k in range(wl.get('clients', 1))]
    threads.append(threading.Thread(target=publisher,
                                    name='bench-publisher'))
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return outcomes, publishes, readers_done[0] - w0


def say_overlap(ctx, outcomes, publishes):
    """The readers' latencies, those that overlapped a build against
    those that did not: what a publish costs a reader, on the client's
    clock (a program without the lock's histogram is read here)."""
    spans = [(p.build.req.due_s + p.build.sent_late_s,
              p.build.req.due_s + p.build.sent_late_s + p.build.latency_s)
             for p in publishes if p.build is not None]
    inside, outside = [], []
    for o in outcomes:
        if not o.ok:
            continue
        t0, t1 = o.req.due_s, o.req.due_s + o.latency_s
        hit = any(t0 < b1 and t1 > b0 for b0, b1 in spans)
        (inside if hit else outside).append(o.latency_s * 1000.0)
    for label, lat in (('overlapping a build', inside),
                       ('beside no build', outside)):
        if lat:
            ctx.say('readers %s: %d queries, latency mean %.1f ms, p50 '
                    '%.1f, p95 %.1f, max %.1f'
                    % (label, len(lat), sum(lat) / len(lat),
                       percentile(lat, 0.5), percentile(lat, 0.95),
                       max(lat)))


def run(ctx):
    """Steps 4 to 7 of a run; returns what run.py reduces."""
    sys.path.insert(0, ctx.root)   # the program's client
    from dragnet_tpu.serve import client
    import traffic
    wl, cfg = ctx.workload, ctx.config
    build_standing_tree(ctx)
    child = Child(ctx)
    res = {'checks': [], 'problems': []}
    try:
        child.start()
        # 5. warm up the publisher's shapes, then the readers': the
        # day after the standing tree goes in through the server
        first = Publish(cfg['corpus']['standing_days'], 0.0)
        t0 = time.monotonic()
        first.run(ctx, child, t0)
        if first.failed() or not first.built():
            bad = first.build if not first.built() else \
                next(o for o in first.queries() if not o.ok)
            raise RuntimeError('warm-up publish failed: %s' % (
                bad.error or (bad.err or b'')[-2000:].decode(
                    'utf-8', 'replace')))
        first.say(ctx)
        pre, warm = first.checks(ctx)
        warm = max(warm, (pre, 0))
        for req in traffic.warmup(wl):
            o = send(ctx, child, req, ctx.datasource)
            if not o.ok:
                raise RuntimeError('warm-up %s failed: %s' % (
                    req.template['name'],
                    o.error or (o.err or b'')[-2000:].decode(
                        'utf-8', 'replace')))
            warm = max(warm, check_outcome(ctx, o))
        if wl.get('rampup_s'):
            # the readers' own traffic at its own concurrency, on other
            # start days: what only concurrent requests warm
            outs, _, _ = live_window(ctx, child, wl['rampup_s'],
                                     ctx.seed + 1, 0)
            for o in outs:
                if not o.ok:
                    raise RuntimeError('ramp-up request failed')
                warm = max(warm, check_outcome(ctx, o))
        res['checks'].append(('warmup.mismatched_tuples', warm[0], 0))
        res['setup_done'] = time.monotonic()

        # 6. the window
        res['stats_before'] = client.stats(child.sock)
        res['prom_before'] = prom(client, child)
        mark0 = child.stderr_size()
        tracer = None
        if ctx.trace:
            tracer = Tracer(ctx, child, ctx.seconds)
            tracer.start()
        outcomes, publishes, res['window_s'] = live_window(
            ctx, child, ctx.seconds, ctx.seed, wl['publishes'])
        if tracer is not None:
            res['trace'] = tracer.finish()
        mark1 = child.stderr_size()
        res['stats_after'] = client.stats(child.sock)
        res['prom_after'] = prom(client, child)
        res['window_stderr'] = child.stderr_text(mark0, mark1)

        # the window's answers, checked now that it is closed
        worst, nfail = (0, 0), 0
        for o in outcomes:
            if not o.ok:
                nfail += 1
                continue
            worst = max(worst, check_outcome(ctx, o))
        pre_tuples, back, not_built = 0, (0, 0), 0
        for p in publishes:
            p.say(ctx)
            pre, b = p.checks(ctx)
            pre_tuples, back = pre_tuples + pre, max(back, b)
            not_built += 0 if p.built() else 1
            nfail += p.failed() + (0 if p.build is not None and
                                   p.build.ok else 1)
        say_overlap(ctx, outcomes, publishes)
        res['outcomes'] = outcomes + [p.build for p in publishes
                                      if p.build is not None]
        res['publisher_queries'] = [o for p in publishes
                                    for o in p.queries()]
        for o in res['publisher_queries']:
            res['problems'] += [
                'warning on a forced lane (publisher): ' + ln
                for ln in (o.err or b'').decode(
                    'utf-8', 'replace').splitlines()
                if ln.startswith('dn: warning:')][:1]
        tree = serve.verify_tree(ctx, child, ctx.datasource, 'whole tree')
        # run.py's contract is three comparisons a cell, so the window's
        # two carry the worst of the steps, each printed by its name
        tuples = [('window.mismatched_tuples', worst[0]),
                  ('publish.prepublish_tuples', pre_tuples),
                  ('publish.readback_mismatched_tuples', back[0]),
                  ('publish.not_built', not_built),
                  ('tree.mismatched_tuples', tree[0])]
        counts = [('window.count_difference', worst[1]),
                  ('publish.readback_count_difference', back[1]),
                  ('tree.count_difference', tree[1])]
        for name, value in tuples + counts:
            ctx.say('step %s = %d (limit 0)' % (name, value))
        res['checks'] += [
            ('window.mismatched_tuples', max(v for _, v in tuples), 0),
            ('window.count_difference', max(v for _, v in counts), 0)]
        res['failed'] = nfail
        # asked after the window: the peak is the window's
        res['device'] = child.ask('device')
    finally:
        rc = child.stop()
        res['child_rc'] = rc
        res['stderr'] = child.stderr_text() if child.proc else ''
    if rc != 0:
        res['problems'].append('dn serve exited %r' % rc)
    return res

