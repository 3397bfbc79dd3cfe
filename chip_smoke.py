#!/usr/bin/env python3
"""chip_smoke.py: the quickest proof that dragnet-tpu still starts on
the chip.

Drives the system's three core data operations — scan, build, query —
through `bin/dn`, with the device lanes FORCED, over a seeded corpus of
muskie-style request logs (default 2,000,000 records spread over 30
days), and holds every answer byte for byte
to the vectorized host engine (`DN_ENGINE=vector`), the plain reference
of the same semantics.

Contract (what the driver relies on):

* this process never imports jax: every phase is one `bin/dn` child,
  run to its end before the next starts, so the chip has one owner at
  a time.  The device's identity comes from a child too.
* everything is built from what git would commit: `make -C native`
  runs first and its failure is fatal.
* a forced phase passes only when the program's own counters say the
  device did the work, and no `dn: warning:` line was written.
* the LAST line of stdout is one JSON object
  {"ok": ..., "device": {"platform": ..., "kind": ..., "count": N}};
  `ok` is true only on platform `tpu` with every phase passed, and
  the exit code is 0 only then.
* `--chips 4` runs ONLY the mesh path (`--backend=cluster` over the
  four local chips: the dense scan and the high-cardinality one) and
  the one-chip scans they are compared with.

Rehearsal without the chip:
    JAX_PLATFORMS=cpu python chip_smoke.py --records 20000
passes every phase and ends `ok: false` for the single reason that the
platform is not `tpu`.
"""

import argparse
import hashlib
import json
import os
import re
import shutil
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
DN = os.path.join(ROOT, 'bin', 'dn')

# corpus window: 30 days from 2014-01-01T00:00:00Z, so `dn build`
# writes 30 daily shards
MINDATE_MS = 1388534400000
DAYS = 30

# bench.py's QUERY and PALLAS_QUERY as dn arguments (held equal, like
# METRIC_ARGS below, by tests/test_chip_smoke.py)
QUERY_ARGS = ['-b', 'host,req.method,operation,latency[aggr=quantize]',
              '-f', '{"ne":["res.statusCode",599]}']
PALLAS_ARGS = ['-b', 'host,latency[aggr=quantize]']
# bench.py's HC_QUERY (`req.url,latency`) does not reach the sparse
# program on this corpus: its accumulator (1024 urls x 2048 latencies at
# 2M records) fits the dense budget.  A third wide column pushes the key
# space past MAX_DENSE_SEGMENTS at every corpus size, which is what
# routes a scan to the device-resident sparse sort-merge program (the
# filter keeps the unique tuples, 341k at 2M records, inside the set's
# first capacity)
SPARSE_ARGS = ['-b', 'req.url,latency,dataLatency',
               '-f', '{"eq":["req.method","GET"]}']

# bench.py's METRICS as `dn metric-add` arguments
_TS = 'timestamp[field=time,date,aggr=lquantize,step=86400]'
METRIC_ARGS = [
    ('m1', ['-b', _TS + ',host,req.method,operation,'
            'latency[aggr=quantize]']),
    ('m2', ['-b', _TS + ',host,res.statusCode']),
    ('m3', ['-b', _TS + ',operation,latency[aggr=lquantize,step=100]',
            '-f', '{"ne":["res.statusCode",500]}']),
]

# two index queries: the whole tree (served by m1), and a 7-day window
# with a filter (served by m2)
QUERIES = [
    ('whole-tree', QUERY_ARGS[:2]),
    ('7-day-window', ['--after', '2014-01-08', '--before', '2014-01-15',
                      '-b', 'host,res.statusCode',
                      '-f', '{"ne":["res.statusCode",500]}']),
]

# hidden telemetry counters (DN_COUNTERS_ALL=1) that name the lane a
# result came from: they are how engagement is proved, and the only
# counter lines allowed to differ between a device run and its
# reference
LANE_COUNTERS = ('ndevicebatches', 'nstackedbatches', 'ncompactflush',
                 'index device sums',
                 # each engine decides per batch between its dense and
                 # its sparse accumulator (the host also spills narrow
                 # batches, engine.py `num_segments > max(65536, 4*n)`),
                 # so the count of records that took the sparse route
                 # names a lane, not a result
                 'nspillrecords')

# variables that would re-route a child behind this script's back
_SCRUB = ('DN_ENGINE', 'DN_INDEX_DEVICE', 'DN_PARSE', 'DN_PALLAS',
          'DN_COUNTERS_ALL', 'LOG_LEVEL', 'DN_TRACE', 'DRAGNET_CONFIG',
          'JAX_LOG_COMPILES')


# a line of a --counters dump (vpipe.Stage.dump: '%-18s %-13s%8d'), or
# the build's one status line
_COUNTER_LINE = re.compile(r'^(\S.*:\s*\d+|indexes for ".*" built)$')


class PhaseFailed(Exception):
    pass


def say(msg):
    sys.stdout.write(msg + '\n')
    sys.stdout.flush()


def child_env(extra):
    env = {k: v for k, v in os.environ.items() if k not in _SCRUB}
    env.update(extra)
    return env


def run_child(argv, env):
    """One child, run to its end; (rc, stdout, stderr, seconds)."""
    t0 = time.monotonic()
    p = subprocess.run(argv, env=env, cwd=ROOT, stdout=subprocess.PIPE,
                       stderr=subprocess.PIPE)
    return p.returncode, p.stdout, p.stderr, time.monotonic() - t0


def must_run(argv, env, what):
    rc, out, err, secs = run_child(argv, env)
    if rc != 0:
        show_failure(what, argv, rc, out, err)
        raise PhaseFailed('%s exited %d' % (what, rc))
    return out, err, secs


def show_failure(what, argv, rc, out, err):
    say('--- %s FAILED (exit %d): %s' % (what, rc, ' '.join(argv)))
    for name, data in (('stdout', out), ('stderr', err)):
        text = data.decode('utf-8', 'replace')
        if len(text) > 6000:
            text = text[:2000] + '\n[...]\n' + text[-4000:]
        say('--- %s of %s:\n%s' % (name, what, text.rstrip('\n')))


def split_stderr(err):
    """(counter lines, `dn:` lines, debug-log records) of a dn child's
    stderr.  Anything else — the XLA runtime's own log lines, Python
    warnings of the installed jax — belongs to neither the program's
    answer nor its warnings and is dropped."""
    counters, warnings, logs = [], [], []
    for line in err.decode('utf-8', 'replace').splitlines():
        if line.startswith('{'):
            try:
                logs.append(json.loads(line))
                continue
            except ValueError:
                pass
        if line.startswith('dn: '):
            warnings.append(line)
        elif _COUNTER_LINE.match(line):
            counters.append(line)
    return counters, warnings, logs


_COMPILED = re.compile(
    r'Finished XLA compilation of (\S+) in ([0-9.]+) sec')


def compile_summary(err):
    """'compiled N programs in S s' from a child's JAX_LOG_COMPILES
    lines (programs the persistent cache served are not compiled and
    not counted)."""
    secs = [float(m.group(2))
            for m in _COMPILED.finditer(err.decode('utf-8', 'replace'))]
    return 'compiled %d programs in %.1fs' % (len(secs), sum(secs))


def lane_counts(counter_lines):
    """{lane counter: summed value} from a --counters dump."""
    got = dict.fromkeys(LANE_COUNTERS, 0)
    for line in counter_lines:
        for name in LANE_COUNTERS:
            marker = ' ' + name + ':'
            if marker in line:
                got[name] += int(line.rsplit(None, 1)[1])
    return got


def comparable(counter_lines):
    """The counter dump with the lane-naming telemetry removed: what
    must be byte-equal between the device run and its reference."""
    return [ln for ln in counter_lines
            if not any((' ' + name + ':') in ln
                       for name in LANE_COUNTERS)]


def digest(data):
    return hashlib.sha256(data).hexdigest()[:16]


class Smoke(object):
    def __init__(self, opts, device):
        self.opts = opts
        self.on_tpu = device['platform'] == 'tpu'
        self.failed = []
        self.scratch = tempfile.mkdtemp(prefix='dn_chip_smoke_')
        self.datafile = os.path.join(self.scratch, 'muskie.log')
        self.base_env = {
            'DRAGNET_CONFIG': os.path.join(self.scratch, 'dragnetrc'),
            'DN_COUNTERS_ALL': '1',
            # every XLA compilation reports its seconds on stderr, so
            # that a child's wall-clock can be split into compile and
            # the rest
            'JAX_LOG_COMPILES': '1',
        }

    # -- set-up --------------------------------------------------------------

    def dn(self, args, extra_env, what):
        """One `bin/dn` child run to its end: (stdout, stderr,
        seconds); a non-zero exit fails the phase with its output."""
        env = dict(self.base_env)
        env.update(extra_env)
        rc, out, err, secs = run_child([DN] + args, child_env(env))
        say('  %s: exit %d in %.1fs (%s)'
            % (what, rc, secs, compile_summary(err)))
        if rc != 0:
            show_failure(what, args, rc, out, err)
            raise PhaseFailed('%s exited %d' % (what, rc))
        return out, err, secs

    def setup(self):
        o = self.opts
        t0 = time.monotonic()
        must_run(
            [sys.executable, '-c',
             'import sys; sys.path.insert(0, %r); import bench; '
             'bench.gen_to_file(%d, %r, mindate_ms=%d, maxdate_ms=%d, '
             'seed=%d); assert "jax" not in sys.modules'
             % (ROOT, o.records, self.datafile, MINDATE_MS,
                MINDATE_MS + DAYS * 86400000, o.seed)],
            child_env({}), 'corpus generation')
        say('corpus: %d records, seed %d, %d bytes, %.1fs'
            % (o.records, o.seed, os.path.getsize(self.datafile),
               time.monotonic() - t0))

    def add_datasource(self, name, indexdir=None, backend=None):
        args = ['datasource-add', name, '--path=' + self.datafile,
                '--time-field=time']
        if indexdir is not None:
            args.append('--index-path=' + indexdir)
        if backend is not None:
            args.append('--backend=' + backend)
        self.dn(args, {}, 'datasource-add')
        if indexdir is not None:
            for mname, margs in METRIC_ARGS:
                self.dn(['metric-add'] + margs + [name, mname], {},
                        'metric-add')

    # -- one phase -----------------------------------------------------------

    def device_vs_reference(self, name, dev_args, dev_env, ref_args,
                            ref_env, need, forced=True):
        """Run the device command and its reference; fail unless the
        bytes agree, the lane counters in `need` are > 0 and (forced
        phases) no warning was written.  Returns the device run's lane
        counters, debug-log records and stdout, and both runs'
        seconds."""
        out, err, secs = self.dn(dev_args, dev_env, name)
        rout, rerr, rsecs = self.dn(ref_args, ref_env,
                                    name + ' (reference)')
        counters, warnings, logs = split_stderr(err)
        # the reference build names its own datasource
        rcounters, _, _ = split_stderr(
            rerr.replace(b'"smoke_ref"', b'"smoke"'))
        if forced and warnings:
            raise PhaseFailed('warning on a forced lane: %s'
                              % ' | '.join(warnings))
        if out != rout:
            raise PhaseFailed(
                'stdout differs from DN_ENGINE=vector (%s vs %s)'
                % (digest(out), digest(rout)))
        if comparable(counters) != comparable(rcounters):
            raise PhaseFailed(
                '--counters differ from DN_ENGINE=vector:\n%s\n--- vs\n%s'
                % ('\n'.join(comparable(counters)),
                   '\n'.join(comparable(rcounters))))
        lanes = lane_counts(counters)
        for cname in need:
            if lanes[cname] <= 0:
                raise PhaseFailed(
                    'device did not engage: counter "%s" is 0' % cname)
        rlanes = lane_counts(rcounters)
        if rlanes['ndevicebatches'] or rlanes['nstackedbatches'] or \
                rlanes['index device sums']:
            raise PhaseFailed('the reference run used the device')
        return {'lanes': lanes, 'logs': logs, 'secs': secs,
                'ref_secs': rsecs, 'out': out}

    def phase(self, name, fn):
        try:
            detail = fn()
        except PhaseFailed as e:
            self.failed.append(name)
            say('phase %s: FAILED: %s' % (name, e))
            return False
        say('phase %s: passed %s' % (name, detail))
        return True

    @staticmethod
    def _fmt(r, extra=''):
        lanes = ' '.join('%s=%d' % (k.replace(' ', '_'), v)
                         for k, v in sorted(r['lanes'].items()) if v)
        return ('device %.1fs reference %.1fs %s sha256=%s%s'
                % (r['secs'], r['ref_secs'], lanes or 'no-device-counters',
                   digest(r['out']), extra))

    # -- the phases ----------------------------------------------------------

    def scan_phase(self, name, qargs, kernel=None):
        """A forced device scan; `kernel` names the aggregation kernel
        every device program of the scan must have run."""
        def run():
            env = {'DN_ENGINE': 'jax', 'LOG_LEVEL': 'debug'}
            interpret = False
            if kernel == 'pallas-onehot' and not self.on_tpu:
                # off the chip Mosaic cannot compile: the rehearsal
                # runs the same program in interpret mode
                env['DN_PALLAS'] = 'force'
                interpret = True
            args = ['scan', '--counters'] + qargs + ['smoke']
            r = self.device_vs_reference(
                name, args, env, args, {'DN_ENGINE': 'vector'},
                ('ndevicebatches',))
            kernels = logged_kernels(r['logs'])
            if kernel is not None and \
                    kernels != {(kernel, interpret)}:
                raise PhaseFailed(
                    'expected kernel %r (interpret=%r) on every device '
                    'program, the scan logged %r'
                    % (kernel, interpret, sorted(kernels)))
            return self._fmt(r, ' kernel=' + fmt_kernels(kernels))
        return self.phase(name, run)

    def build_phase(self):
        def run():
            r = self.device_vs_reference(
                'build', ['build', '--counters', 'smoke'],
                {'DN_ENGINE': 'jax', 'LOG_LEVEL': 'debug'},
                ['build', '--counters', 'smoke_ref'],
                {'DN_ENGINE': 'vector'},
                ('ndevicebatches', 'nstackedbatches'))
            dev = tree_digests(os.path.join(self.scratch, 'idx'))
            ref = tree_digests(os.path.join(self.scratch, 'idx_ref'))
            if dev != ref:
                diff = sorted(set(dev.items()) ^ set(ref.items()))
                raise PhaseFailed(
                    'index tree differs from the DN_ENGINE=vector '
                    'build: %r' % diff[:6])
            shards = [p for p in dev if p.endswith('.sqlite')]
            if len(shards) != DAYS:
                raise PhaseFailed('expected %d daily shards, built %d'
                                  % (DAYS, len(shards)))
            return self._fmt(r, ' shards=%d files=%d kernel=%s'
                             % (len(shards), len(dev),
                                fmt_kernels(logged_kernels(r['logs']))))
        return self.phase('build', run)

    def query_phase(self):
        def run():
            parts = []
            for qname, qargs in QUERIES:
                r = self.device_vs_reference(
                    'query ' + qname,
                    ['query', '--counters'] + qargs + ['smoke'],
                    {'DN_INDEX_DEVICE': '1'},
                    ['query', '--counters', '--iq-stack=1'] + qargs +
                    ['smoke'],
                    {'DN_ENGINE': 'vector', 'DN_INDEX_DEVICE': '0'},
                    ('index device sums',))
                if not r['out'].strip():
                    raise PhaseFailed('query %s answered nothing'
                                      % qname)
                parts.append('%s: %s' % (qname, self._fmt(r)))
            return '; '.join(parts)
        return self.phase('query', run)

    def auto_phase(self):
        """Informational: which lane the default engine takes.  Only
        wrong bytes can fail it."""
        def run():
            args = ['scan', '--counters'] + QUERY_ARGS + ['smoke']
            r = self.device_vs_reference(
                'auto', args, {'LOG_LEVEL': 'debug'}, args,
                {'DN_ENGINE': 'vector'}, (), forced=False)
            engine = [rec.get('engine') for rec in r['logs']
                      if rec.get('msg') == 'scan done']
            nb = r['lanes']['ndevicebatches']
            say('auto lane: engine=%s ndevicebatches=%d -> %s'
                % (','.join(str(e) for e in engine) or '?', nb,
                   'device took batches' if nb else 'host only'))
            return self._fmt(r)
        return self.phase('auto', run)

    def mesh_phase(self, name, qargs, kernel, merge):
        """--chips 4: a scan through the cluster backend on a mesh of
        the local chips vs the same scan on one chip; every device
        program of the mesh scan must have run `kernel` over the
        chips and merged them by `merge`."""
        def run():
            want = self.opts.chips
            env = {'DN_ENGINE': 'jax', 'LOG_LEVEL': 'debug'}
            out, err, secs = self.dn(
                ['scan', '--counters'] + qargs + ['smoke_mesh'],
                env, name)
            rout, rerr, rsecs = self.dn(
                ['scan', '--counters'] + qargs + ['smoke'],
                env, name + ' (one chip)')
            counters, warnings, logs = split_stderr(err)
            rcounters, rwarnings, rlogs = split_stderr(rerr)
            if warnings or rwarnings:
                raise PhaseFailed('warning on a forced lane: %s'
                                  % ' | '.join(warnings + rwarnings))
            if out != rout or \
                    comparable(counters) != comparable(rcounters):
                raise PhaseFailed(
                    'mesh scan differs from the one-chip scan (%s vs %s)'
                    % (digest(out), digest(rout)))
            lanes = lane_counts(counters)
            if lanes['ndevicebatches'] <= 0 or \
                    lane_counts(rcounters)['ndevicebatches'] <= 0:
                raise PhaseFailed('device did not engage')
            meshes = [(rec.get('kernel'), rec.get('mesh_devices'),
                       rec.get('merge'))
                      for rec in logs
                      if rec.get('msg') == 'device aggregate kernel']
            if not meshes or any(m != (kernel, want, merge)
                                 for m in meshes):
                raise PhaseFailed(
                    'expected every program to run %s over %d devices '
                    'merged by %s, the scan logged %r'
                    % (kernel, want, merge, meshes))
            one = [(rec.get('kernel'), rec.get('mesh_devices'))
                   for rec in rlogs
                   if rec.get('msg') == 'device aggregate kernel']
            if not one or any(o != (kernel, 0) for o in one):
                raise PhaseFailed(
                    'the one-chip scan did not run %s on one chip: %r'
                    % (kernel, one))
            return ('mesh %.1fs one-chip %.1fs shards=%d kernel=%s '
                    'merge=%s ndevicebatches=%d sha256=%s'
                    % (secs, rsecs, want, kernel, merge,
                       lanes['ndevicebatches'], digest(out)))
        return self.phase(name, run)

    def run(self):
        self.setup()
        if self.opts.chips > 1:
            self.add_datasource('smoke')
            self.add_datasource('smoke_mesh', backend='cluster')
            self.mesh_phase('mesh-scan', QUERY_ARGS, 'segment-sum',
                            'psum+pmin')
            self.mesh_phase('mesh-scan-sparse', SPARSE_ARGS,
                            'sparse-sort-merge', 'allgather+sparse-fold')
            return
        self.add_datasource('smoke', os.path.join(self.scratch, 'idx'))
        self.add_datasource('smoke_ref',
                            os.path.join(self.scratch, 'idx_ref'))
        self.scan_phase('scan-dense', QUERY_ARGS, 'segment-sum')
        self.scan_phase('scan-pallas', PALLAS_ARGS, 'pallas-onehot')
        self.scan_phase('scan-sparse', SPARSE_ARGS, 'sparse-sort-merge')
        if self.build_phase():
            self.query_phase()
        else:
            self.failed.append('query')
            say('phase query: FAILED: skipped, no index tree to query')
        self.auto_phase()


def logged_kernels(logs):
    """{(kernel, interpret)} of a run's 'device aggregate kernel'
    debug records (device_scan._log_kernel)."""
    return set((rec.get('kernel'), bool(rec.get('interpret')))
               for rec in logs
               if rec.get('msg') == 'device aggregate kernel')


def fmt_kernels(kernels):
    return ','.join(sorted('%s%s' % (k, '(interpret)' if i else '')
                           for k, i in kernels)) or 'none-logged'


def tree_digests(root):
    """{relative path: sha256} of an index tree's files (lock files
    hold no data)."""
    got = {}
    for dirpath, _dirs, files in os.walk(root):
        for fn in files:
            if fn.endswith('.lock'):
                continue
            p = os.path.join(dirpath, fn)
            with open(p, 'rb') as f:
                got[os.path.relpath(p, root)] = digest(f.read())
    return got


def probe_device():
    """The device as JAX reports it, asked of a child so that this
    process never holds the chip."""
    code = ('import json, jax; d = jax.devices(); '
            'print(json.dumps({"platform": d[0].platform, '
            '"kind": d[0].device_kind, "count": len(d)}))')
    out, _err, _ = must_run([sys.executable, '-c', code], child_env({}),
                            'device probe')
    return json.loads(out.decode().strip().splitlines()[-1])


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    ap.add_argument('--seed', type=int, default=12345)
    ap.add_argument('--records', type=int, default=2000000)
    ap.add_argument('--chips', type=int, default=1, choices=(1, 4))
    opts = ap.parse_args(argv)

    if not os.path.exists(DN):
        sys.stderr.write('chip_smoke: %s is missing: run from a '
                         'checkout of the repo\n' % DN)
        return 2
    try:
        device = probe_device()
    except PhaseFailed as e:
        sys.stderr.write('chip_smoke: no JAX device: %s\n' % e)
        return 2
    say('device: %s' % json.dumps(device))
    if device['platform'] != 'tpu':
        say('NOT A TPU: platform is %r; the phases below are a '
            'rehearsal and the verdict can only be ok: false'
            % device['platform'])
    if device['count'] < opts.chips:
        sys.stderr.write('chip_smoke: need %d device(s), JAX reports %d\n'
                         % (opts.chips, device['count']))
        return 2

    try:
        must_run(['make', '-C', os.path.join(ROOT, 'native')],
                 child_env({}), 'make -C native')
    except PhaseFailed as e:
        sys.stderr.write('chip_smoke: %s\n' % e)
        return 2

    smoke = Smoke(opts, device)
    t0 = time.monotonic()
    try:
        try:
            smoke.run()
        except PhaseFailed as e:
            smoke.failed.append('set-up')
            say('set-up FAILED: %s' % e)
    finally:
        shutil.rmtree(smoke.scratch, ignore_errors=True)
    say('total: %.1fs' % (time.monotonic() - t0))

    reasons = []
    if smoke.failed:
        reasons.append('failed phases: %s' % ', '.join(smoke.failed))
    if device['platform'] != 'tpu':
        reasons.append('platform is %r, not tpu' % device['platform'])
    ok = not reasons
    if reasons:
        say('not ok: ' + '; '.join(reasons))
    assert 'jax' not in sys.modules, 'the parent must stay off jax'
    say(json.dumps({'ok': ok, 'device': device}))
    return 0 if ok else 1


if __name__ == '__main__':
    sys.exit(main())
