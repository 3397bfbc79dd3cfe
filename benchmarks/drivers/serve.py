"""Driver `serve`: one resident `dn serve` child owns the chips for the
whole run; the traffic is driven from the client's side with the
program's own client (dragnet_tpu/serve/client.py).

This process never touches the jax backend.  What it learns about the
device it learns from the child, through the launcher's control socket.
"""

import json
import os
import queue
import signal
import socket
import subprocess
import sys
import threading
import time
import uuid

HERE = os.path.dirname(os.path.abspath(__file__))
LAUNCHER = os.path.join(HERE, 'launch_serve.py')
DAY_MS = 86400000

# variables that would re-route the child behind the configuration's
# back (chip_smoke.py's list, plus the serve and remote knobs)
SCRUB_PREFIXES = ('DN_', 'DRAGNET_')
SCRUB = ('LOG_LEVEL', 'JAX_LOG_COMPILES', 'BENCH_RUN')


def child_env(config_env, rc_path):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(SCRUB_PREFIXES) and k not in SCRUB}
    env.update(config_env)
    env['DRAGNET_CONFIG'] = rc_path
    env['JAX_LOG_COMPILES'] = '1'
    return env


class Child(object):
    """The `dn serve` child and its control socket."""

    def __init__(self, ctx):
        self.ctx = ctx
        # relative paths, with the run directory as this process's and
        # the child's working directory: a unix socket path holds 107
        # bytes, and nobody knows how long the checkout's path is
        self.sock = 'dn.sock'
        self.control = 'control.sock'
        self.stderr_path = os.path.join(ctx.run_dir, 'serve.stderr')
        self.proc = None

    def start(self):
        for p in (self.sock, self.control):
            if os.path.exists(p):
                os.unlink(p)
        self._stderr = open(self.stderr_path, 'wb')
        self.proc = subprocess.Popen(
            [sys.executable, self.ctx.workload.get('launcher') or LAUNCHER,
             self.control, '--socket', self.sock],
            env=child_env(self.ctx.config.get('environment') or {},
                          self.ctx.rc_path),
            cwd=self.ctx.run_dir, stdout=subprocess.DEVNULL,
            stderr=self._stderr)
        from dragnet_tpu.serve import lifecycle
        deadline = time.monotonic() + 300
        while not lifecycle.probe(socket_path=self.sock):
            if self.proc.poll() is not None:
                raise RuntimeError('dn serve exited %d before it listened:'
                                   '\n%s' % (self.proc.returncode,
                                             self.stderr_text()[-4000:]))
            if time.monotonic() > deadline:
                raise RuntimeError('dn serve did not listen in 300 s')
            time.sleep(0.05)

    def ask(self, cmd, timeout_s=120.0, **kw):
        s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        s.settimeout(timeout_s)
        try:
            s.connect(self.control)
            with s.makefile('rwb') as f:
                f.write(json.dumps(dict(kw, cmd=cmd)).encode() + b'\n')
                f.flush()
                reply = json.loads(f.readline().decode())
        finally:
            s.close()
        if 'error' in reply:
            raise RuntimeError('child refused "%s": %s'
                               % (cmd, reply['error']))
        return reply

    def stderr_size(self):
        return os.path.getsize(self.stderr_path)

    def stderr_text(self, start=0, end=None):
        with open(self.stderr_path, 'rb') as f:
            f.seek(start)
            data = f.read() if end is None else f.read(end - start)
        return data.decode('utf-8', 'replace')

    def stop(self):
        """SIGTERM (the program's clean drain), then wait; SIGKILL only
        if the drain does not end."""
        if self.proc is None:
            return None
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self._stderr.close()
        return self.proc.returncode


def request_doc(ctx, req, ds):
    """The document the `dn` client ships for this request
    (cli.cmd_scan / cmd_query / cmd_build with --remote)."""
    t = req.template
    opts = {'raw': False, 'points': True, 'counters': False,
            'gnuplot': False, 'dry_run': False}
    doc = {'op': t['op'], 'ds': ds, 'config': ctx.rc_path, 'opts': opts}
    if t['op'] == 'build':
        doc.update({'interval': ctx.config.get('index_interval', 'day'),
                    'before': None, 'after': None, 'index_config': None,
                    'idempotency': uuid.uuid4().hex})
        return doc
    doc['queryconfig'] = query_doc(ctx, req)
    if t['op'] == 'query':
        doc['interval'] = ctx.config.get('index_interval', 'day')
    return doc


def query_doc(ctx, req):
    q = req.template['query']
    qc = {'breakdowns': [dict(b, field=b.get('field') or b['name'])
                         for b in q['breakdowns']]}
    if q.get('filter') is not None:
        qc['filter'] = q['filter']
    if req.days is not None:
        after = ctx.config['corpus']['mindate_ms'] + req.start_day * DAY_MS
        qc['timeAfter'] = after
        qc['timeBefore'] = after + req.days * DAY_MS
    return qc


class Outcome(object):
    __slots__ = ('req', 'ds', 'latency_s', 'sent_late_s', 'rc', 'out',
                 'err', 'error')

    def __init__(self, req, ds):
        self.req, self.ds = req, ds
        self.latency_s = self.sent_late_s = None
        self.rc = self.out = self.err = self.error = None

    @property
    def ok(self):
        return self.error is None and self.rc == 0


def send(ctx, child, req, ds, due_at=None):
    """One request through the program's client, timed on the host
    clock from when it was due (open loop) or sent (closed loop) to the
    last byte of the reply."""
    from dragnet_tpu.serve import client
    from dragnet_tpu.errors import DNError
    doc = request_doc(ctx, req, ds)
    o = Outcome(req, ds)
    t0 = time.monotonic()
    start = t0 if due_at is None else due_at
    o.sent_late_s = t0 - start
    try:
        # as `dn --remote` does: scans stream over a dialled
        # connection, queries and builds ride the pooled one
        o.rc, _, o.out, o.err = client.request_bytes(
            child.sock, doc, timeout_s=ctx.workload.get('timeout_s', 300),
            pooled=req.template['op'] != 'scan')
    except (OSError, ValueError, DNError) as e:
        o.error = repr(e)
    o.latency_s = time.monotonic() - start
    return o


# -- checking ---------------------------------------------------------------

def check_outcome(ctx, o):
    """(mismatched tuples, summed count difference) of a finished
    request against the plain reference; a build only has to say that
    it built."""
    from reference.groupby import compare
    t = o.req.template
    if t['op'] == 'build':
        return (0, 0) if b'built' in (o.err or b'') else (1, 0)
    expected = ctx.reference.expected_lines(
        query_doc(ctx, o.req), part=t.get('part', 'batch'))
    return compare(o.out, expected)


def verify_tree(ctx, child, ds, label):
    """Query a built tree back, every `verify` template of the
    workload over the whole tree, and hold it to the reference."""
    import traffic
    worst = (0, 0)
    for t in ctx.workload.get('verify') or []:
        req = traffic.Request(None, t, None, None)
        o = send(ctx, child, req, ds)
        if not o.ok:
            ctx.say('verify %s %s: request failed: %s'
                    % (label, t['name'], o.error or o.err[-300:]))
            return (1, 0)
        worst = max(worst, check_outcome(ctx, o))
    return worst


# -- the window -------------------------------------------------------------

def closed_window(ctx, child, seconds, picker, seed=None):
    """`clients` callers that each wait for a reply: a client's next
    request goes out when its last reply is in.  The requests in flight
    when the time is up are finished and counted, so a rate is over all
    the work and all its time."""
    import traffic
    gen = traffic.closed_loop(ctx.workload,
                              ctx.seed if seed is None else seed,
                              ctx.config['corpus']['days'])
    lock = threading.Lock()
    outcomes = []
    t_end = time.monotonic() + seconds

    def client():
        while time.monotonic() < t_end:
            with lock:
                req = next(gen)
                ds = picker(req)
            o = send(ctx, child, req, ds)
            with lock:
                outcomes.append(o)

    threads = [threading.Thread(target=client, name='bench-client-%d' % k)
               for k in range(ctx.workload.get('clients', 1))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return outcomes


def open_window(ctx, child, seconds, picker, seed=None):
    """Requests go out on the schedule whatever the server does; a few
    sender threads take them in order, so one that finds every sender
    busy goes out late and its wait counts in its latency."""
    import traffic
    sched = traffic.open_loop(ctx.workload,
                              ctx.seed if seed is None else seed, seconds,
                              ctx.config['corpus']['days'])
    todo = queue.Queue()
    for i, req in enumerate(sched):
        todo.put((i, req))
    outcomes = [None] * len(sched)
    t0 = time.monotonic() + 0.05

    def sender():
        while True:
            try:
                i, req = todo.get_nowait()
            except queue.Empty:
                return
            due = t0 + req.due_s
            wait = due - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            outcomes[i] = send(ctx, child, req, picker(req), due_at=due)

    threads = [threading.Thread(target=sender, name='bench-sender-%d' % k)
               for k in range(ctx.workload.get('senders', 8))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return outcomes


def run(ctx):
    """Steps 4 to 7 of a run; returns what run.py reduces."""
    sys.path.insert(0, ctx.root)   # the program's client
    from dragnet_tpu.serve import client
    import traffic
    wl = ctx.workload
    child = Child(ctx)
    res = {'checks': [], 'problems': []}
    fresh = iter(ctx.build_trees)

    def picker(req):
        # a build goes into a tree nobody has built yet
        return next(fresh) if req.template['op'] == 'build' \
            else ctx.datasource

    try:
        child.start()
        # 5. warm up exactly the shapes the cell uses, and check them
        warm_worst, warm_tree = (0, 0), None
        for req in traffic.warmup(wl):
            o = send(ctx, child, req, picker(req))
            if not o.ok:
                raise RuntimeError('warm-up %s failed: %s' % (
                    req.template['name'],
                    o.error or (o.err or b'')[-2000:].decode(
                        'utf-8', 'replace')))
            warm_worst = max(warm_worst, check_outcome(ctx, o))
            if req.template['op'] == 'build':
                warm_tree = o.ds
        if warm_tree is not None:
            warm_worst = max(warm_worst,
                             verify_tree(ctx, child, warm_tree, 'warm-up'))
        if wl.get('rampup_s'):
            # the last of the warm-up: the cell's own traffic at its own
            # rate and concurrency, on other start days, so that what
            # only concurrent requests warm (every worker thread's
            # handles) is warm when the window opens
            window = open_window if wl['loop'] == 'open' else closed_window
            for o in window(ctx, child, wl['rampup_s'], picker,
                            seed=ctx.seed + 1):
                if o is None or not o.ok:
                    raise RuntimeError('ramp-up request failed')
                warm_worst = max(warm_worst, check_outcome(ctx, o))
        res['checks'].append(('warmup.mismatched_tuples', warm_worst[0], 0))
        res['setup_done'] = time.monotonic()

        # 6. the window
        seconds = ctx.seconds
        res['stats_before'] = client.stats(child.sock)
        res['prom_before'] = prom(client, child)
        mark0 = child.stderr_size()
        tracer = None
        if ctx.trace:
            tracer = Tracer(ctx, child, seconds)
            tracer.start()
        w0 = time.monotonic()
        if wl['loop'] == 'open':
            outcomes = open_window(ctx, child, seconds, picker)
        else:
            outcomes = closed_window(ctx, child, seconds, picker)
        res['window_s'] = time.monotonic() - w0
        if tracer is not None:
            res['trace'] = tracer.finish()
        mark1 = child.stderr_size()
        res['stats_after'] = client.stats(child.sock)
        res['prom_after'] = prom(client, child)
        res['outcomes'] = outcomes
        res['window_stderr'] = child.stderr_text(mark0, mark1)

        # the window's answers, checked now that it is closed
        worst, nfail = (0, 0), 0
        for o in outcomes:
            if o is None or not o.ok:
                nfail += 1
                continue
            worst = max(worst, check_outcome(ctx, o))
        built = [o.ds for o in outcomes
                 if o is not None and o.ok
                 and o.req.template['op'] == 'build']
        if built:
            worst = max(worst, verify_tree(ctx, child, built[-1],
                                           'last tree'))
        res['checks'].append(('window.mismatched_tuples', worst[0], 0))
        res['checks'].append(('window.count_difference', worst[1], 0))
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


def prom(client, child):
    rc, _, out, _ = client.request_bytes(child.sock, {'op': 'metrics'},
                                         timeout_s=30.0, retry=True)
    return out.decode('utf-8', 'replace')


class Tracer(object):
    """Under --trace 1: a profiler trace of a few seconds of the steady
    window, started and stopped in the child on this process's word,
    from a thread of its own so the traffic is not held up."""

    def __init__(self, ctx, child, seconds):
        self.ctx, self.child = ctx, child
        t = ctx.workload.get('trace') or {}
        self.length = min(float(t.get('seconds', 4.0)), seconds * 0.6)
        self.delay = min(float(t.get('after_s', 2.0)), seconds * 0.2)
        self.python_tracer = int(t.get('python_tracer', 0))
        self.dir = os.path.join(ctx.run_dir, 'trace')
        self.doc = {'dir': self.dir}
        self.thread = threading.Thread(target=self._run, name='bench-trace')

    def start(self):
        self.thread.start()

    def _run(self):
        try:
            time.sleep(self.delay)
            t0 = time.monotonic()
            self.child.ask('trace_start', dir=self.dir,
                           python_tracer=self.python_tracer)
            time.sleep(self.length)
            self.child.ask('trace_stop', timeout_s=300.0)
            self.doc['asked_s'] = time.monotonic() - t0
        except Exception as e:      # reported by run.py as a problem
            self.doc['error'] = repr(e)

    def finish(self):
        self.thread.join()
        return self.doc
