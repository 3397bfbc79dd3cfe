"""Driver `serve_cluster`: the configuration's `cluster` as resident
`dn serve --cluster=TOPOLOGY --member=NAME` children on unix sockets,
one for each member, each shown ONE chip and only that one, any of
them the router of a client's query (dragnet_tpu/serve/router.py).

What a request is, how it is checked and how a window runs are
drivers/serve.py's, by import: `send` reads the child's `sock`, so the
`Dealer` below stands where the child stands, and its `sock` is the
calling client thread's member's: the clients `bench-client-<k>` go
round-robin over the members (eight clients over four members are two
to each), and every member routes and serves partials.

What run.py gets back has the shape serve.py's has:

* `device`: the platform and kind the members report; `count` is the
  number of distinct members that each report ONE device of the
  platform `tpu` (4 where the cell asks for 4 chips: a chip belongs to
  one process at a time, so four live members are four chips; off the
  chip, the members on the first one's platform);
  `memory_peak_bytes` is the largest of theirs; `members` has each
  member's own document and the chip index it was shown.
* `stats_before`/`stats_after`: the members' /stats counters added;
  for a counter that the cell names under `engagement.counters` the
  growth over the window is the SMALLEST of the members' (the device
  must have engaged on every member, not on some).
* `prom_before`/`prom_after`: the members' scrapes added sample by
  sample (counters, histogram sums, counts and buckets; a gauge reads
  the members' sum) and written back as exposition text.
* the profiler is started and stopped in every member; the first
  member's directory goes to the reduction (the members are symmetric
  by construction: the traced chip is one of four), the others' files
  are kept under --artifacts.

This process never touches the jax backend.

How a member is shown one chip (my chip run, PR 44, call 0: libtpu on
a v5litepod-4 host honours these; four children ran side by side, each
with `count` 1): TPU_VISIBLE_CHIPS=<k>, TPU_CHIPS_PER_PROCESS_BOUNDS
and TPU_PROCESS_BOUNDS 1,1,1, and a TPU_PROCESS_PORT of its own.  On a
host without chips they do nothing and the members run on XLA:CPU (a
rehearsal).
"""

import glob
import json
import os
import shutil
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from loader import load_module  # noqa: E402

serve = load_module('drivers', 'serve')

TOPOLOGY = 'topology.json'
BASE_PORT = 8476


def chip_env(k):
    """The variables that show a process chip `k` of its host and no
    other."""
    port = BASE_PORT + k
    return {'TPU_VISIBLE_CHIPS': str(k),
            'TPU_CHIPS_PER_PROCESS_BOUNDS': '1,1,1',
            'TPU_PROCESS_BOUNDS': '1,1,1',
            'TPU_PROCESS_ADDRESSES': 'localhost:%d' % port,
            'TPU_PROCESS_PORT': str(port),
            'CLOUD_TPU_TASK_ID': '0'}


def topology_doc(cluster):
    """The topology file of the configuration's `cluster`: members on
    unix sockets in the run directory, no `members[].config` (one
    shared index tree)."""
    return {'epoch': cluster.get('epoch', 1),
            'assign': cluster.get('assign', 'hash'),
            'members': {m: {'endpoint': 'dn-%s.sock' % m}
                        for m in cluster['members']},
            'partitions': [{'id': i, 'replicas': list(reps)}
                           for i, reps in
                           enumerate(cluster['partitions'])]}


class Member(serve.Child):
    """One `dn serve --cluster --member` child, its control socket and
    the chip it is shown."""

    def __init__(self, ctx, name, chip):
        serve.Child.__init__(self, ctx)
        self.name, self.chip = name, chip
        self.sock = 'dn-%s.sock' % name
        self.control = 'control-%s.sock' % name
        self.stderr_path = os.path.join(ctx.run_dir,
                                        'serve-%s.stderr' % name)

    def spawn(self):
        for p in (self.sock, self.control):
            if os.path.exists(p):
                os.unlink(p)
        self._stderr = open(self.stderr_path, 'wb')
        env = serve.child_env(self.ctx.config.get('environment') or {},
                              self.ctx.rc_path)
        env.update(chip_env(self.chip))
        self.proc = subprocess.Popen(
            [sys.executable,
             self.ctx.workload.get('launcher') or serve.LAUNCHER,
             self.control, '--socket', self.sock,
             '--cluster', TOPOLOGY, '--member', self.name],
            env=env, cwd=self.ctx.run_dir, stdout=subprocess.DEVNULL,
            stderr=self._stderr)

    def wait_listening(self, deadline):
        from dragnet_tpu.serve import lifecycle
        while not lifecycle.probe(socket_path=self.sock):
            if self.proc.poll() is not None:
                raise RuntimeError(
                    'member %s exited %d before it listened:\n%s'
                    % (self.name, self.proc.returncode,
                       self.stderr_text()[-4000:]))
            if time.monotonic() > deadline:
                raise RuntimeError('member %s did not listen in time'
                                   % self.name)
            time.sleep(0.05)


class Dealer(object):
    """Stands where serve.send expects the child: `sock` is the
    calling client thread's member's socket (`bench-client-<k>` takes
    member k modulo the members), and for any other thread the member
    that `pin` named last."""

    def __init__(self, members):
        self.members = members
        self.pinned = members[0]

    def pin(self, member):
        self.pinned = member

    @property
    def sock(self):
        name = threading.current_thread().name
        if name.startswith('bench-client-'):
            k = int(name.rsplit('-', 1)[1])
            return self.members[k % len(self.members)].sock
        return self.pinned.sock


# -- the members' documents, merged -----------------------------------------

def merge_prom(texts):
    """Scrapes added sample by sample, as exposition text again."""
    from obs import prom
    total = {}
    for text in texts:
        for key, v in prom.parse(text).items():
            total[key] = total.get(key, 0.0) + v
    lines = []
    for (name, labels), v in sorted(total.items()):
        lab = ','.join('%s="%s"' % kv for kv in labels)
        lines.append('%s%s %r' % (name, '{%s}' % lab if lab else '', v))
    return '\n'.join(lines) + '\n'


def merge_stats(docs):
    """The members' /stats `counters` added (the other sections stay
    each member's own, under `members`)."""
    counters = {}
    for doc in docs.values():
        for k, v in (doc.get('counters') or {}).items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                counters[k] = counters.get(k, 0) + v
    return {'counters': counters, 'members': docs}


def least_growth(before, after, name):
    """Set the merged `after` so that the counter's growth over the
    merged `before` is the smallest of the members'; returns the
    members' growths."""
    grew = [(after['members'][m].get('counters') or {}).get(name, 0)
            - (before['members'][m].get('counters') or {}).get(name, 0)
            for m in sorted(after['members'])]
    after['counters'][name] = before['counters'].get(name, 0) + min(grew)
    return grew


def merge_devices(members, docs):
    """One device document for the cell: `count` is the number of
    members on the first member's platform, each of which, on `tpu`,
    sees one device."""
    first = docs[members[0].name]
    counted = [m for m in members
               if docs[m.name].get('platform') == first.get('platform')
               and (first.get('platform') != 'tpu'
                    or docs[m.name].get('count') == 1)]
    return {'platform': first.get('platform'), 'kind': first.get('kind'),
            'count': len(counted),
            'memory_peak_bytes': max(d.get('memory_peak_bytes', 0)
                                     for d in docs.values()),
            'members': {m.name: dict(docs[m.name], chip=m.chip)
                        for m in members}}


def scrape(client, members):
    return ({m.name: client.stats(m.sock) for m in members},
            [serve.prom(client, m) for m in members])


class ClusterTracer(serve.Tracer):
    """serve.Tracer over every member: each traces into a directory of
    its own; the first member's is the one the reduction reads."""

    def __init__(self, ctx, members, seconds):
        serve.Tracer.__init__(self, ctx, members[0], seconds)
        self.members = members
        self.dirs = {m.name: os.path.join(ctx.run_dir,
                                          'trace-%s' % m.name)
                     for m in members}
        self.dir = self.dirs[members[0].name]
        self.doc = {'dir': self.dir}

    def _each(self, cmd, **kw):
        errors = []

        def ask(m):
            try:
                m.ask(cmd, **dict(kw, dir=self.dirs[m.name]))
            except Exception as e:
                errors.append('%s: %r' % (m.name, e))
        threads = [threading.Thread(target=ask, args=(m,))
                   for m in self.members]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise RuntimeError('; '.join(errors))

    def _run(self):
        try:
            time.sleep(self.delay)
            t0 = time.monotonic()
            self._each('trace_start', python_tracer=self.python_tracer)
            time.sleep(self.length)
            self._each('trace_stop', timeout_s=300.0)
            self.doc['asked_s'] = time.monotonic() - t0
        except Exception as e:      # reported by run.py as a problem
            self.doc['error'] = repr(e)

    def keep_others(self, artifacts, cell):
        """The other members' profiles, for a look by hand."""
        for name, d in self.dirs.items():
            if d == self.dir:
                continue
            dest = os.path.join(artifacts, '%s.trace-%s' % (cell, name))
            os.makedirs(dest, exist_ok=True)
            for f in glob.glob(os.path.join(d, 'plugins', 'profile',
                                            '*', '*.xplane.pb')):
                shutil.copy(f, dest)


# -- the run ----------------------------------------------------------------

def run(ctx):
    """Steps 4 to 7 of a run; returns what run.py reduces."""
    sys.path.insert(0, ctx.root)   # the program's client
    from dragnet_tpu.serve import client
    import traffic
    wl, cluster = ctx.workload, ctx.config['cluster']
    with open(os.path.join(ctx.run_dir, TOPOLOGY), 'w') as f:
        json.dump(topology_doc(cluster), f)
    members = [Member(ctx, name, k)
               for k, name in enumerate(cluster['members'])]
    dealer = Dealer(members)
    res = {'checks': [], 'problems': []}

    def picker(req):
        return ctx.datasource

    try:
        for m in members:
            m.spawn()
        deadline = time.monotonic() + 300
        for m in members:
            m.wait_listening(deadline)
        # 5. warm up through every member exactly the shapes the cell
        # uses: one request of each class with each member the router,
        # then the cell's own traffic, dealt as the window's is
        warm_worst = (0, 0)
        for m in members:
            dealer.pin(m)
            for req in traffic.warmup(wl):
                o = serve.send(ctx, dealer, req, picker(req))
                if not o.ok:
                    raise RuntimeError('warm-up %s through %s failed: %s' % (
                        req.template['name'], m.name,
                        o.error or (o.err or b'')[-2000:].decode(
                            'utf-8', 'replace')))
                warm_worst = max(warm_worst, serve.check_outcome(ctx, o))
        if wl.get('rampup_s'):
            for o in serve.closed_window(ctx, dealer, wl['rampup_s'],
                                         picker, seed=ctx.seed + 1):
                if o is None or not o.ok:
                    raise RuntimeError('ramp-up request failed')
                warm_worst = max(warm_worst, serve.check_outcome(ctx, o))
        res['checks'].append(('warmup.mismatched_tuples', warm_worst[0], 0))
        res['setup_done'] = time.monotonic()

        # 6. the window
        stats0, prom0 = scrape(client, members)
        marks0 = [m.stderr_size() for m in members]
        tracer = None
        if ctx.trace:
            tracer = ClusterTracer(ctx, members, ctx.seconds)
            tracer.start()
        w0 = time.monotonic()
        outcomes = serve.closed_window(ctx, dealer, ctx.seconds, picker)
        res['window_s'] = time.monotonic() - w0
        if tracer is not None:
            res['trace'] = tracer.finish()
            if ctx.artifacts:
                tracer.keep_others(ctx.artifacts, wl['name'])
        marks1 = [m.stderr_size() for m in members]
        stats1, prom1 = scrape(client, members)
        res['stats_before'] = merge_stats(stats0)
        res['stats_after'] = merge_stats(stats1)
        for name in (wl.get('engagement') or {}).get('counters') or []:
            grew = least_growth(res['stats_before'], res['stats_after'],
                                name)
            ctx.say('engagement: the members\' growth of "%s": %s'
                    % (name, ' '.join(str(g) for g in grew)))
        res['prom_before'] = merge_prom(prom0)
        res['prom_after'] = merge_prom(prom1)
        res['outcomes'] = outcomes
        res['window_stderr'] = ''.join(
            m.stderr_text(a, b)
            for m, a, b in zip(members, marks0, marks1))

        worst, nfail = (0, 0), 0
        for o in outcomes:
            if o is None or not o.ok:
                nfail += 1
                continue
            worst = max(worst, serve.check_outcome(ctx, o))
        res['checks'].append(('window.mismatched_tuples', worst[0], 0))
        res['checks'].append(('window.count_difference', worst[1], 0))
        res['failed'] = nfail
        # asked after the window: the peaks are the window's
        docs = {m.name: m.ask('device') for m in members}
        res['device'] = merge_devices(members, docs)
        for m in members:
            d = docs[m.name]
            if d.get('platform') == 'tpu' and d.get('count') != 1:
                res['problems'].append(
                    'member %s sees %r chips, not the one it was shown'
                    % (m.name, d.get('count')))
    finally:
        rcs = {m.name: m.stop() for m in members}
        res['child_rc'] = max((rc for rc in rcs.values()
                               if rc is not None), default=None)
        res['stderr'] = ''.join(
            '== member %s ==\n%s' % (m.name, m.stderr_text())
            for m in members if m.proc)
        # one file for run.py's --artifacts, the members in order
        with open(os.path.join(ctx.run_dir, 'serve.stderr'), 'w') as f:
            f.write(res['stderr'])
    for name, rc in sorted(rcs.items()):
        if rc != 0:
            res['problems'].append('member %s exited %r' % (name, rc))
    return res
