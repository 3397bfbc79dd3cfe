#!/usr/bin/env python3
"""What a scan or a build costs the host's memory system, counted on a
resident `dn serve` of any checkout of this repository.

    python3 tools/hostmem_count.py --checkout DIR --label NAME --op scan \\
        [--requests 10] [--soak 60] [--seed N] [--records N] [NAME=VALUE ...]

One run: the benchmark's own corpus, config and `dn serve` child of
`muskie-30d` (DIR/benchmarks, so DIR may be the parent's `git archive`),
NAME=VALUE pairs added to the child's environment (the `MALLOC_*_`
variables set one part of the allocator policy alone on a program that
has none), a warm-up of one request, and then

1. `--requests` requests with every thread of the server traced from
   system call to system call (tools/memcalls.c): mmap, munmap, madvise,
   brk, mprotect, mremap a request.  The server runs slower meanwhile;
   nothing of this phase is a time;
2. the same number untraced: minor faults (`/proc/<pid>/stat`), seconds
   and the leaf stages' milliseconds (`stage_ms`) a request;
3. `--soak` more, `VmRSS` read after each: what the server holds when
   the soak begins (after 1 + 2 x `--requests` requests) and after its
   last, and its `VmHWM`.

The last line of stdout is one JSON document; a copy goes to
chiprun_out/hostmem/NAME.json.  Needs the chip like the benchmark does
(the child is held to the device lanes); with JAX_PLATFORMS=cpu and a
small --records it rehearses on the CPU, and then no count is the chip
host's.
"""

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
CELLS = {'scan': 'muskie-30d.scan-dense', 'build': 'muskie-30d.build-daily'}


def build_memcalls():
    out = os.path.join(REPO, '.cache', 'tools', 'memcalls')
    src = os.path.join(HERE, 'memcalls.c')
    if not os.path.exists(out) or \
            os.path.getmtime(out) < os.path.getmtime(src):
        os.makedirs(os.path.dirname(out), exist_ok=True)
        subprocess.run(['cc', '-O2', '-Wall', '-o', out, src], check=True)
    return out


def proc_memory(pid):
    """minor faults, VmRSS and VmHWM (bytes) of a process."""
    with open('/proc/%d/stat' % pid) as f:
        # the fields after the command, which may hold spaces
        fields = f.read().rsplit(')', 1)[1].split()
    doc = {'minflt': int(fields[7])}
    with open('/proc/%d/status' % pid) as f:
        for line in f:
            key, _, rest = line.partition(':')
            if key in ('VmRSS', 'VmHWM'):
                doc[key] = int(rest.split()[0]) * 1024
    return doc


def stage_ms(samples):
    """{stage: summed ms} of the server's `stage_ms` histogram, from a
    scrape parsed by benchmarks/obs/prom.py."""
    return {dict(labels)['stage']: v for (name, labels), v in samples.items()
            if name == 'dn_stage_ms_sum'}


def main(argv):
    ap = argparse.ArgumentParser()
    ap.add_argument('--checkout', default=REPO)
    ap.add_argument('--label', required=True)
    ap.add_argument('--op', choices=sorted(CELLS), required=True)
    ap.add_argument('--requests', type=int, default=10)
    ap.add_argument('--soak', type=int, default=60)
    ap.add_argument('--seed', type=int, default=2147484301)
    ap.add_argument('--records', type=int, default=None)
    ap.add_argument('env', nargs='*', metavar='NAME=VALUE')
    args = ap.parse_args(argv)

    checkout = os.path.abspath(args.checkout)
    memcalls = build_memcalls()
    sys.path.insert(0, checkout)
    sys.path.insert(0, os.path.join(checkout, 'benchmarks'))
    import run as bench                     # DIR/benchmarks/run.py
    import traffic
    from loader import load_module
    from obs import prom
    serve = load_module('drivers', 'serve')

    ctx = bench.Ctx()
    ctx.workload = dict(bench.load_json('workloads', CELLS[args.op] + '.json'))
    ctx.workload['build_trees'] = 2 * args.requests + args.soak + 2
    ctx.config = bench.load_json('configs', ctx.workload['config'] + '.json')
    if args.records:
        ctx.config['corpus']['records'] = args.records
    ctx.seed = args.seed
    ctx.run_dir = os.path.join(checkout, '.cache', 'bench', 'run',
                               'hostmem-count')
    shutil.rmtree(ctx.run_dir, ignore_errors=True)
    os.makedirs(ctx.run_dir)
    os.chdir(ctx.run_dir)
    for pair in args.env:
        name, _, value = pair.partition('=')
        os.environ[name] = value
    bench.make_corpus(ctx)

    from dragnet_tpu.serve import client
    child = serve.Child(ctx)
    trees = iter(ctx.build_trees)
    template = ctx.workload['templates'][0]
    doc = {'label': args.label, 'op': args.op, 'checkout': checkout,
           'env': args.env, 'requests': args.requests,
           'records': ctx.config['corpus']['records']}

    def request():
        req = traffic.Request(None, template, None, None)
        ds = next(trees) if args.op == 'build' else ctx.datasource
        o = serve.send(ctx, child, req, ds)
        if not o.ok:
            raise RuntimeError('request failed: %s' % (
                o.error or (o.err or b'')[-2000:].decode('utf-8', 'replace')))
        return o

    def scrape():
        return prom.parse(serve.prom(client, child))

    try:
        child.start()
        pid = child.proc.pid
        warm = request()
        doc['warmup_mismatched'] = serve.check_outcome(ctx, warm)[0]
        doc['after_warmup'] = proc_memory(pid)
        doc['allocator_policy_held'] = [
            '%s %d' % (dict(labels).get('reason'), v)
            for (name, labels), v in sorted(scrape().items())
            if name == 'dn_allocator_policy_held']

        # 1. traced
        tracer = subprocess.Popen([memcalls, str(pid), '3600'],
                                  stdout=subprocess.PIPE)
        time.sleep(0.5)          # every thread seized before the first
        try:
            for _ in range(args.requests):
                request()
        finally:
            tracer.send_signal(signal.SIGTERM)
            out = tracer.communicate(timeout=60)[0]
        doc['traced'] = json.loads(out.decode() or '{}')
        doc['traced_rc'] = tracer.returncode

        # 2. untraced
        m0, s0 = proc_memory(pid), stage_ms(scrape())
        lat = [request().latency_s for _ in range(args.requests)]
        m1, s1 = proc_memory(pid), stage_ms(scrape())
        doc['minflt_per_request'] = (m1['minflt'] - m0['minflt']) \
            / float(args.requests)
        doc['latency_s'] = lat
        doc['stage_ms_per_request'] = {
            k: round((s1[k] - s0.get(k, 0.0)) / args.requests, 2)
            for k in sorted(s1) if s1[k] != s0.get(k, 0.0)}

        # 3. the soak: what is held
        rss, soak_lat = [], []
        doc['after_untraced'] = proc_memory(pid)
        for _ in range(args.soak):
            soak_lat.append(request().latency_s)
            rss.append(proc_memory(pid)['VmRSS'])
        doc['soak_latency_s'] = soak_lat
        doc['soak_rss'] = rss
        doc['at_end'] = proc_memory(pid)
        if args.soak:
            doc['soak_minflt_per_request'] = \
                (doc['at_end']['minflt'] - doc['after_untraced']['minflt']) \
                / float(args.soak)
        doc['device'] = child.ask('device')
    finally:
        doc['child_rc'] = child.stop()
        os.chdir(REPO)
        shutil.rmtree(ctx.run_dir, ignore_errors=True)

    out_dir = os.path.join(REPO, 'chiprun_out', 'hostmem')
    os.makedirs(out_dir, exist_ok=True)
    line = json.dumps(doc, sort_keys=True)
    with open(os.path.join(out_dir, args.label + '.json'), 'w') as f:
        f.write(line + '\n')
    sys.stdout.write(line + '\n')
    return 0


if __name__ == '__main__':
    sys.exit(main(sys.argv[1:]))
