#!/usr/bin/env python3
"""One run of one benchmark cell.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Everything that belongs to one cell, configuration, per-layer metric or
driver is a file of its own under benchmarks/, found by name:
workloads/<cell>.json, configs/<configuration>.json,
metrics/<metric>.py, drivers/<driver>.py.  This file names none of them.

The last line of stdout is the run's one JSON result, and it is printed
only when the child reported the TPU and the number of chips the cell
asks for.  Anywhere else the run still goes to its end (a rehearsal),
prints what it found on a line that starts with `rehearsal `, and exits
with a code other than 0.
"""

import argparse
import json
import math
import os
import shutil
import subprocess
import sys
import time

T_START = time.monotonic()

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

from loader import load_module  # noqa: E402
from readers import percentile  # noqa: E402

EXIT_NOT_CORRECT = 1
EXIT_NO_PROGRAM = 2
EXIT_NO_CHIP = 3


def say(msg):
    sys.stdout.write(msg + '\n')
    sys.stdout.flush()


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


class Ctx(object):
    """What a driver needs of a run."""
    root = ROOT
    say = staticmethod(say)


class TimedReference(object):
    """The plain reference with its seconds counted apart: a check's
    time belongs to no metric."""

    def __init__(self, ref):
        self.ref, self.seconds = ref, 0.0

    def expected_lines(self, *a, **kw):
        t0 = time.monotonic()
        try:
            return self.ref.expected_lines(*a, **kw)
        finally:
            self.seconds += time.monotonic() - t0


# -- set-up -----------------------------------------------------------------

def make_corpus(ctx):
    """Step 1: the corpus from the seed, and the dragnet config that
    names the configuration's datasources and metrics."""
    from gen import corpus
    c = ctx.config['corpus']
    lib = corpus.build_library(os.path.join(ROOT, '.cache', 'bench', 'gen'))
    path = os.path.join(ctx.run_dir, 'muskie.log')
    t0 = time.monotonic()
    cols, nbytes = corpus.generate(
        lib, path, c['records'], c['mindate_ms'],
        c['mindate_ms'] + c['days'] * 86400000, ctx.seed)
    say('corpus: %d records, %d bytes, seed %d, %.1fs'
        % (c['records'], nbytes, ctx.seed, time.monotonic() - t0))
    from reference.groupby import Reference
    ctx.reference = TimedReference(Reference(
        cols, {'host': corpus.HOSTS, 'method': corpus.METHODS,
               'op': corpus.OPERATIONS}))

    doc = run_document(ctx.config, ctx.workload, ctx.run_dir, path)
    names = [d['name'] for d in doc['datasources']]
    ctx.datasource, ctx.build_trees = names[0], names[1:]
    ctx.rc_path = os.path.join(ctx.run_dir, 'dragnetrc.json')
    with open(ctx.rc_path, 'w') as f:
        json.dump(doc, f)


def run_document(config, workload, run_dir, corpus_path):
    """The dragnet config of a run: the datasource `muskie`, which the
    scans and queries name, and one `muskie_b<i>` for each of the
    workload's `build_trees` (each build goes into a tree of its own),
    every one with an index path of its own and the configuration's
    metrics, whatever the backend: a cluster datasource builds too."""
    dsconf = config['datasource']
    names = ['muskie'] + ['muskie_b%d' % i
                          for i in range(workload.get('build_trees', 0))]
    return {'vmaj': 0, 'vmin': 0,
            'datasources': [
                {'name': n, 'backend': dsconf['backend'],
                 'backend_config': {
                     'path': corpus_path, 'timeField': dsconf['timeField'],
                     'indexPath': os.path.join(run_dir, 'idx', n)},
                 'filter': None, 'dataFormat': dsconf['dataFormat']}
                for n in names],
            'metrics': [dict(m, datasource=n) for n in names
                        for m in config['metrics']]}


def prebuild_index(ctx):
    """Step 2: the index tree a query cell reads, built by the quickest
    correct means: one `dn build` child on the host engine, which never
    touches the chip."""
    env = dict(os.environ, DRAGNET_CONFIG=ctx.rc_path)
    env.update(ctx.config.get('setup_build_environment') or {})
    t0 = time.monotonic()
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, 'bin', 'dn.py'), 'build',
         '--interval', ctx.config.get('index_interval', 'day'),
         ctx.datasource],
        env=env, cwd=ctx.run_dir, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE)
    if p.returncode != 0:
        raise RuntimeError('set-up build failed (%d): %s' % (
            p.returncode, p.stderr.decode('utf-8', 'replace')[-2000:]))
    say('set-up index build: %.1fs' % (time.monotonic() - t0))


# -- reduction --------------------------------------------------------------

def end_to_end(spec, res, ctx, setup_s):
    """One end-to-end metric, by the statistic its workload names."""
    stat = spec['stat']
    if stat == 'setup_s':
        return setup_s
    outs = [o for o in res['outcomes']
            if o is not None and o.req.template['op'] == spec['op']]
    done = [o for o in outs if o.ok]
    if stat == 'closed_loop_rate':
        # all the records of all the finished requests over all their
        # seconds: one client, so the seconds are the window's
        secs = sum(o.latency_s for o in done)
        return ctx.config['corpus']['records'] * len(done) / secs \
            if secs > 0 else None
    if stat == 'completed_per_s':
        return len(done) / res['window_s']
    if stat in ('latency_p50_ms', 'latency_p95_ms'):
        lat = [o.latency_s * 1000.0 if o.ok else float('inf')
               for o in outs]
        if not lat:
            return None
        v = percentile(lat, 0.5 if stat == 'latency_p50_ms' else 0.95)
        return None if math.isinf(v) else v
    raise ValueError('unknown end-to-end statistic "%s"' % stat)


def reduce_trace(ctx, res):
    """The profiler's trace reduced to seconds per chip, operation and
    gap, in a process of its own (it imports jax to read the file, and
    is held to the CPU: the chip is free again by now)."""
    doc = res.get('trace')
    if not doc or 'error' in doc:
        return None
    out = os.path.join(ctx.run_dir, 'trace.json')
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    argv = [sys.executable, os.path.join(HERE, 'trace', 'reduce.py'),
            doc['dir'], out]
    if ctx.artifacts:
        argv += ['--events', os.path.join(ctx.run_dir, 'events.json')]
    p = subprocess.run(argv, env=env, stdout=subprocess.PIPE,
                       stderr=subprocess.PIPE)
    if p.returncode != 0:
        res['problems'].append('trace reduction failed: %s' % (
            p.stderr.decode('utf-8', 'replace')[-1000:]))
        return None
    with open(out) as f:
        return json.load(f)


class Reading(object):
    """What a per-layer metric's reader may read."""

    def __init__(self, ctx, res, trace):
        from obs import prom
        self.config, self.workload = ctx.config, ctx.workload
        self.say = ctx.say
        self.trace = trace
        self.before = prom.parse(res['prom_before'])
        self.after = prom.parse(res['prom_after'])
        self.stats_before = res['stats_before']
        self.stats_after = res['stats_after']
        self.window_stderr = res['window_stderr']
        self.outcomes = [o for o in res['outcomes'] if o is not None]
        self.window_s = res['window_s']
        self.device = res['device']

    def delta(self, name, **labels):
        """A counter's (or a histogram's _sum / _count) growth over the
        window; None when the server never wrote it."""
        from obs import prom
        a = prom.value(self.after, name, labels)
        if a is None:
            return None
        return a - (prom.value(self.before, name, labels) or 0.0)

    def done(self, op):
        return [o for o in self.outcomes
                if o.ok and o.req.template['op'] == op]

    def records(self, op):
        return self.config['corpus']['records'] * len(self.done(op))


# -- main -------------------------------------------------------------------

def engagement_problems(ctx, res):
    """The proof that the device did the work, by chip_smoke.py's
    rules: the lane counters the cell names grew over the window, the
    child wrote no `dn: warning:` line, and where the cell names a
    kernel record every such record of the window agrees with it."""
    problems = []
    need = ctx.workload.get('engagement') or {}
    c0 = res['stats_before'].get('counters') or {}
    c1 = res['stats_after'].get('counters') or {}
    for name in need.get('counters') or []:
        grew = c1.get(name, 0) - c0.get(name, 0)
        say('engagement: counter "%s" grew by %d over the window'
            % (name, grew))
        if grew <= 0:
            problems.append('device did not engage: counter "%s" did not '
                            'grow' % name)
    warnings = [ln for ln in res['stderr'].splitlines()
                if ln.startswith('dn: warning:')]
    for o in res.get('outcomes') or []:
        if o is not None and o.err:
            warnings += [ln for ln in o.err.decode(
                'utf-8', 'replace').splitlines()
                if ln.startswith('dn: warning:')]
    if warnings:
        problems.append('warning on a forced lane: %s' % warnings[0])
    want = need.get('kernel_log')
    if want:
        recs = []
        text = res['window_stderr'] + ''.join(
            o.err.decode('utf-8', 'replace')
            for o in res.get('outcomes') or [] if o is not None and o.err)
        for ln in text.splitlines():
            if ln.startswith('{') and 'device aggregate kernel' in ln:
                try:
                    recs.append(json.loads(ln))
                except ValueError:
                    pass
        say('engagement: %d kernel records in the window, want %s'
            % (len(recs), json.dumps(want, sort_keys=True)))
        if not recs or any(r.get(k) != v for r in recs
                           for k, v in want.items()):
            problems.append('kernel records of the window do not all say '
                            '%s' % json.dumps(want, sort_keys=True))
    return problems


def main(argv):
    ap = argparse.ArgumentParser()
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, required=True)
    ap.add_argument('--seconds', type=float, required=True)
    ap.add_argument('--trace', type=int, choices=(0, 1), default=0)
    ap.add_argument('--artifacts', default=None,
                    help='a directory to keep the child\'s stderr and the '
                    'reduced trace in (for a look by hand)')
    args = ap.parse_args(argv)

    if not os.path.exists(os.path.join(ROOT, 'bin', 'dn.py')) or \
            not os.path.isdir(os.path.join(ROOT, 'dragnet_tpu')):
        sys.stderr.write('benchmarks/run.py: the program (bin/dn.py, '
                         'dragnet_tpu/) is not in this checkout\n')
        return EXIT_NO_PROGRAM

    ctx = Ctx()
    ctx.workload = load_json('workloads', args.workload + '.json')
    ctx.config = load_json('configs', ctx.workload['config'] + '.json')
    ctx.seed, ctx.seconds, ctx.trace = args.seed, args.seconds, \
        bool(args.trace)
    ctx.artifacts = args.artifacts and os.path.abspath(args.artifacts)
    ctx.run_dir = os.path.join(ROOT, '.cache', 'bench', 'run',
                               ctx.workload['name'])
    shutil.rmtree(ctx.run_dir, ignore_errors=True)
    os.makedirs(ctx.run_dir)
    os.chdir(ctx.run_dir)
    try:
        return run(ctx)
    finally:
        os.chdir(ROOT)
        if ctx.artifacts:
            os.makedirs(ctx.artifacts, exist_ok=True)
            for name in ('serve.stderr', 'replies.stderr', 'trace.json',
                         'events.json'):
                src = os.path.join(ctx.run_dir, name)
                if os.path.exists(src):
                    shutil.copy(src, os.path.join(
                        ctx.artifacts, '%s.%s' % (ctx.workload['name'], name)))
        shutil.rmtree(ctx.run_dir, ignore_errors=True)


def run(ctx):
    wl = ctx.workload
    make_corpus(ctx)
    if wl.get('prebuilt_index'):
        prebuild_index(ctx)
    driver = load_module('drivers', wl['driver'])
    res = driver.run(ctx)
    if ctx.artifacts:
        # what the replies' own stderr said (the server binds stderr per
        # request), for a look by hand
        with open(os.path.join(ctx.run_dir, 'replies.stderr'), 'wb') as f:
            for o in res['outcomes']:
                if o is not None and o.err:
                    f.write(o.err)
    setup_s = res['setup_done'] - T_START - ctx.reference.seconds \
        if 'setup_done' in res else None

    problems = list(res['problems'])
    problems += engagement_problems(ctx, res)
    attempted = len(res['outcomes'])
    failed = res['failed']
    if failed:
        problems.append('%d of %d requests failed' % (failed, attempted))
        for o in res['outcomes']:
            if o is not None and not o.ok:
                say('failed request %s: %s' % (
                    o.req.template['name'], o.error or
                    (o.err or b'')[-300:].decode('utf-8', 'replace')))
                break
    for name, value, limit in res['checks']:
        say('check %s = %d (limit %d)' % (name, value, limit))
        if value > limit:
            problems.append('%s is %d, over its limit %d'
                            % (name, value, limit))
    late = [o.sent_late_s for o in res['outcomes'] if o is not None]
    say('window: %.2fs, %d requests, %d failed; generator at most %.1f ms '
        'late; reference %.1fs'
        % (res['window_s'], attempted, failed,
           max(late) * 1000.0 if late else 0.0, ctx.reference.seconds))

    device = dict(res['device'])
    metrics = {}
    line = {'attempted': attempted, 'failed': failed, 'metrics': metrics,
            'device': device}
    if not ctx.trace:
        for name, spec in wl['end_to_end'].items():
            v = end_to_end(spec, res, ctx, setup_s)
            if v is None:
                problems.append('end-to-end metric %s has no value' % name)
            else:
                metrics[name] = {'value': v, 'unit': spec['unit']}
    else:
        trace = reduce_trace(ctx, res)
        if trace is None:
            problems.append('no trace to read: %s' % (
                (res.get('trace') or {}).get('error', 'not taken')))
        else:
            device['busy_s'] = trace['busy_s']
            device['window_s'] = trace['window_s']
            if not trace['busy_s'] > 0:
                problems.append('no operation ran on the device in the '
                                'traced window')
            line['breakdown'] = trace['breakdown']
        reading = Reading(ctx, res, trace)
        for name in wl['per_layer']:
            mod = load_module('metrics', name)
            v = mod.read(reading)
            if v is None:
                say('per-layer %s: nothing to read' % name)
                continue
            metrics[name] = {'value': v, 'unit': mod.META['unit']}

    thirds = [[], [], []]
    for o in res['outcomes']:
        if o is not None and o.ok and o.req.due_s is not None:
            thirds[min(2, int(3 * o.req.due_s / ctx.seconds))].append(
                o.latency_s * 1000.0)
    if all(thirds):
        say('backlog: median latency by thirds of the window, ms: '
            + ' '.join('%.1f' % percentile(t, 0.5) for t in thirds)
            + '; p95: ' + ' '.join('%.1f' % percentile(t, 0.95)
                                   for t in thirds))
    for p in problems:
        say('problem: ' + p)
    line['correct'] = not problems
    # each number compared beside its limit: the line's last key (the
    # keys are sorted) and the last lines of stderr
    line['numbers_compared'] = {
        name: {'value': value, 'limit': limit}
        for name, value, limit in res['checks']}
    for name, value, limit in res['checks']:
        sys.stderr.write('compared %s = %d (limit %d)\n'
                         % (name, value, limit))
    sys.stderr.flush()
    want = (u'tpu', ctx.config['chips'])
    have = (device.get('platform'), device.get('count'))
    out = json.dumps(line, sort_keys=True)
    if have != want:
        say('no result: the child reports %s x%s, the cell needs %s x%d'
            % (have + want))
        say('rehearsal ' + out)
        return EXIT_NO_CHIP
    say(out)
    return 0 if line['correct'] else EXIT_NOT_CORRECT


if __name__ == '__main__':
    sys.exit(main(sys.argv[1:]))
