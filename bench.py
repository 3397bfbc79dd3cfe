#!/usr/bin/env python3
"""Benchmark harness: records/sec through `dn scan`/`dn build` on
muskie-style JSON, plus chip-level truth (kernel-resident throughput,
transport bandwidth, MFU).

Legs (all best-of-N with min/median recorded per metric — single-number
round-over-round tracking was VERDICT r4 weak #7):

* headline: 2M-record multi-field group-by scan, auto engine — the
  configuration where the engine router (host MT / device) actually has
  a decision to make.  The 300k leg r1-r4 used as the headline is kept
  in extra for comparability.
* large-scan trio: vectorized host, forced device, auto at 2M records.
* high-cardinality: req.url x latency at 2M records (~410k output
  tuples), host vs forced-device — the device runs the resident sparse
  sort-merge program (the reference's OOM regime, README.md:668-681).
* build trio: default/auto, host, forced-device (stacked multi-metric
  program) at 2M records x 3 metrics.
* many-shard index query: 365 daily shards, p50/p95 full-tree and
  30-day-window queries, concurrency-10 fan-in vs sequential.
* kernel-resident device microbenchmark (dragnet_tpu/devbench.py):
  the production scan program over device-resident inputs — chip
  rec/s, HBM GB/s, H2D/D2H bandwidth, and MFU for the pallas
  aggregation — separating transport cost from chip capability.
* DN_BENCH_SCALE=1 adds a 10M-record scan+build leg in a subprocess
  with peak-RSS accounting and a budget gate.

Prints exactly one JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "extra": {...}}
"""

import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from dragnet_tpu import query as mod_query
from dragnet_tpu.scan import StreamScan
from dragnet_tpu.vpipe import Pipeline

QUERY = {
    'breakdowns': [
        {'name': 'host'},
        {'name': 'req.method'},
        {'name': 'operation'},
        {'name': 'latency', 'aggr': 'quantize'},
    ],
    'filter': {'ne': ['res.statusCode', 599]},
}

HC_QUERY = {'breakdowns': [{'name': 'req.url'}, {'name': 'latency'}]}

# flat-projection query for the parse-lane legs: every field path is
# a top-level key, so the raw-byte lanes (DN_PARSE=vector|device) are
# eligible and all four lanes answer the same scan
PARSE_QUERY = {
    'breakdowns': [
        {'name': 'host'},
        {'name': 'operation'},
        {'name': 'latency', 'aggr': 'quantize'},
    ],
    'filter': {'ne': ['host', 'zzz']},
}

# small accumulator (16 x 32 segments): the one-hot MXU kernel's home
# turf, used for the MFU measurement
PALLAS_QUERY = {'breakdowns': [{'name': 'host'},
                               {'name': 'latency', 'aggr': 'quantize'}]}

METRICS = [
    {'name': 'm1', 'breakdowns': [
        {'name': 'timestamp', 'field': 'time', 'date': '',
         'aggr': 'lquantize', 'step': 86400},
        {'name': 'host', 'field': 'host'},
        {'name': 'req.method', 'field': 'req.method'},
        {'name': 'operation', 'field': 'operation'},
        {'name': 'latency', 'field': 'latency', 'aggr': 'quantize'}]},
    {'name': 'm2', 'breakdowns': [
        {'name': 'timestamp', 'field': 'time', 'date': '',
         'aggr': 'lquantize', 'step': 86400},
        {'name': 'host', 'field': 'host'},
        {'name': 'res.statusCode', 'field': 'res.statusCode'}]},
    {'name': 'm3', 'breakdowns': [
        {'name': 'timestamp', 'field': 'time', 'date': '',
         'aggr': 'lquantize', 'step': 86400},
        {'name': 'operation', 'field': 'operation'},
        {'name': 'latency', 'field': 'latency', 'aggr': 'lquantize',
         'step': 100}],
     'filter': {'ne': ['res.statusCode', 500]}},
]


def _mktestdata():
    import importlib.util
    import importlib.machinery
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        'tools', 'mktestdata')
    loader = importlib.machinery.SourceFileLoader('mktestdata', path)
    spec = importlib.util.spec_from_file_location('mktestdata', path,
                                                  loader=loader)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def gen_to_file(n, path, mindate_ms=None, maxdate_ms=None, seed=12345):
    """Write n generated records to path; native generator
    (native/dngen.cc, same shape/distributions as tools/mktestdata)
    when available, Python otherwise.  Timestamps increase linearly
    over [mindate_ms, maxdate_ms) (default: mktestdata's window);
    `seed` feeds the native generator's RNG."""
    mod = _mktestdata()
    if mindate_ms is None:
        mindate_ms = int(mod.MINDATE.timestamp() * 1000)
    if maxdate_ms is None:
        maxdate_ms = int(mod.MAXDATE.timestamp() * 1000)

    lib = None
    if os.environ.get('DN_NATIVE', '1') != '0':
        import ctypes
        from dragnet_tpu import native as mod_native
        so = os.path.join(mod_native._NATIVE_DIR, 'build',
                          'libdngen.so')
        if mod_native._build_target(
                so, os.path.join(mod_native._NATIVE_DIR, 'dngen.cc')):
            try:
                lib = ctypes.CDLL(so)
                lib.dn_gen.restype = ctypes.c_int64
                lib.dn_gen.argtypes = [
                    ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64,
                    ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
                    ctypes.c_int64, ctypes.c_uint64]
            except OSError:
                lib = None

    with open(path, 'wb') as f:
        if lib is not None:
            chunk = 200000
            buf = ctypes.create_string_buffer(min(chunk, n) * 512)
            for start in range(0, n, chunk):
                cnt = min(chunk, n - start)
                nb = lib.dn_gen(buf, len(buf), start, cnt, n,
                                mindate_ms, maxdate_ms, seed)
                if nb <= 0:
                    raise RuntimeError('dn_gen failed (rv=%d)' % nb)
                f.write(ctypes.string_at(buf, nb))
        else:
            for i in range(n):
                f.write(json.dumps(
                    mod.make_record(i, n, mindate_ms, maxdate_ms),
                    separators=(',', ':')).encode() + b'\n')


def _count_shards(idx):
    """Shard files in an index tree — build machinery (journals,
    tmps, the integrity catalog) excluded, exactly as readers filter
    the walk."""
    from dragnet_tpu import index_journal as mod_journal
    nshards = 0
    for root, dirs, files in os.walk(idx):
        dirs[:] = [d for d in dirs
                   if not mod_journal.is_index_litter(d)]
        nshards += sum(1 for f in files
                       if not mod_journal.is_index_litter(f))
    return nshards


def make_ds(datafile, indexdir=None):
    from dragnet_tpu.datasource_file import DatasourceFile
    bc = {'path': datafile}
    if indexdir is not None:
        bc['indexPath'] = indexdir
        bc['timeField'] = 'time'
    return DatasourceFile({
        'ds_backend': 'file', 'ds_backend_config': bc,
        'ds_filter': None, 'ds_format': 'json',
    })


def run_scan(datafile, query):
    """The real `dn scan` execution path (find -> ingest -> engine)."""
    return make_ds(datafile).scan(query)


def run_host(lines, query):
    pipeline = Pipeline()
    s = StreamScan(query, None, pipeline)
    for line in lines:
        s.write(json.loads(line), 1)
    return s.aggr


class Runs(object):
    """Per-metric repeat collection: best/median/all recorded so
    round-over-round drift is attributable to noise or real change."""

    def __init__(self):
        self.all = {}

    def add(self, name, value):
        self.all.setdefault(name, []).append(value)

    def best(self, name):
        return max(self.all[name])

    def summary(self):
        out = {}
        for name, vals in self.all.items():
            out[name] = {
                'best': round(max(vals)),
                'median': round(statistics.median(vals)),
                'all': [round(v) for v in vals],
            }
        return out


def _engine_env(engine):
    if engine is None:
        os.environ.pop('DN_ENGINE', None)
    else:
        os.environ['DN_ENGINE'] = engine


def timed_scan(runs, name, datafile, nrecords, qconf, engine,
               repeats=3):
    """Engine-pinned scan; records every repeat's records/s.  Returns
    (best_rps, npoints, ndevicebatches_of_best_run)."""
    prior = os.environ.get('DN_ENGINE')
    _engine_env(engine)
    try:
        best = None
        for _ in range(repeats):
            t0 = time.monotonic()
            result = run_scan(datafile,
                              mod_query.query_load(dict(qconf)))
            dt = time.monotonic() - t0
            runs.add(name, nrecords / dt)
            if best is None or dt < best[0]:
                ndev = sum(s.counters.get('ndevicebatches', 0)
                           for s in result.pipeline.stages)
                best = (dt, len(result.points), ndev)
    finally:
        _engine_env(prior)
    return nrecords / best[0], best[1], best[2]


def timed_build(runs, name, datafile, nrecords, engine, repeats=2):
    import shutil
    prior = os.environ.get('DN_ENGINE')
    _engine_env(engine)
    metrics = [mod_query.metric_deserialize(dict(m)) for m in METRICS]
    idx = datafile + '.idx.' + (engine or 'auto')
    try:
        best = None
        for _ in range(repeats):
            shutil.rmtree(idx, ignore_errors=True)
            t0 = time.monotonic()
            result = make_ds(datafile, idx).build(metrics, 'day')
            dt = time.monotonic() - t0
            runs.add(name, nrecords / dt)
            if best is None or dt < best[0]:
                stacked = sum(
                    s.counters.get('nstackedbatches', 0)
                    for s in result.pipeline.stages)
                best = (dt, stacked)
    finally:
        _engine_env(prior)
        shutil.rmtree(idx, ignore_errors=True)
    return nrecords / best[0], best[1]


def _iq_stack_mode():
    from dragnet_tpu.index_query_stack import stack_mode
    return stack_mode()


def index_query_bench(tmpdir):
    """Many-shard index tree: 365 daily shards (the shape the
    reference's per-file fan-in was built for,
    lib/datasource-file.js:629-689).  p50/p95 for full-tree and
    30-day-window queries; the DN_IQ_THREADS reader pool + shard-handle
    cache (index_query_mt) vs the sequential open/query/close loop,
    plus the shards-pruned count for the windowed query."""
    import shutil
    from dragnet_tpu import index_query_mt as mod_iqmt
    datafile = os.path.join(tmpdir, 'year.log')
    idx = os.path.join(tmpdir, 'year.idx')
    n = 1000000
    # one year of timestamps -> 365-366 daily shards
    start_ms = 1388534400000             # 2014-01-01
    end_ms = start_ms + 365 * 86400000
    gen_to_file(n, datafile, mindate_ms=start_ms, maxdate_ms=end_ms)
    ds = make_ds(datafile, idx)
    metrics = [mod_query.metric_deserialize(dict(m)) for m in METRICS]
    t0 = time.monotonic()
    ds.build(metrics, 'day')
    build_s = time.monotonic() - t0
    nshards = _count_shards(idx)

    def q(after=None, before=None):
        conf = {'breakdowns': [{'name': 'host'},
                               {'name': 'latency', 'aggr': 'quantize'}],
                'filter': {'eq': ['req.method', 'GET']}}
        if after:
            conf['timeAfter'] = after
            conf['timeBefore'] = before
        return mod_query.query_load(conf)

    def measure(query, reps):
        times = []
        for _ in range(reps):
            t0 = time.monotonic()
            ds.query(query, 'day')
            times.append((time.monotonic() - t0) * 1000)
        times.sort()
        return (times[len(times) // 2],
                times[min(len(times) - 1, int(len(times) * 0.95))])

    def iq_env(threads):
        prior = os.environ.get('DN_IQ_THREADS')
        if threads is None:
            os.environ.pop('DN_IQ_THREADS', None)
        else:
            os.environ['DN_IQ_THREADS'] = threads
        return prior

    def stack_env(mode):
        prior = os.environ.get('DN_IQ_STACK')
        if mode is None:
            os.environ.pop('DN_IQ_STACK', None)
        else:
            os.environ['DN_IQ_STACK'] = mode
        return prior

    # pin BOTH knobs: an ambient DN_QUERY_CONCURRENCY=1 (the old
    # harness's sequential override, a legacy alias for the pool size)
    # must not silently turn the parallel legs sequential
    prior_legacy = os.environ.pop('DN_QUERY_CONCURRENCY', None)
    prior_auto = iq_env('auto')
    prior_stack = stack_env('auto')
    try:
        # cold: the shipping default (stacked), nothing cached yet
        # (first query after a rebuild in a long-running server)
        mod_iqmt.shard_cache_clear()
        t0 = time.monotonic()
        ds.query(q(), 'day')
        cold_ms = (time.monotonic() - t0) * 1000

        # stacked (default DN_IQ_STACK=auto), warm handle cache — the
        # serving workload: shard blocks concatenate into one columnar
        # batch, one vectorized filter+group-by (index_query_stack)
        stk_p50, stk_p95 = measure(q(), 11)
        stk_win_p50, stk_win_p95 = measure(
            q('2014-06-01', '2014-07-01'), 11)
        # shards-pruned observability: hidden per-stage counter on the
        # windowed query (365-shard tree, 30 in window)
        win_result = ds.query(q('2014-06-01', '2014-07-01'), 'day')
        pruned = queried = 0
        for s in win_result.pipeline.stages:
            pruned += s.counters.get('index shards pruned', 0)
            queried += s.counters.get('index shards queried', 0)
        cache_stats = mod_iqmt.shard_cache_stats()

        # per-shard parallel (PR 1's reader pool, DN_IQ_STACK=0) —
        # the prior serving path, kept as a pinned column.  The
        # fan-out self-selects pool vs degraded-sequential from
        # measured whole-fan-out cost; record the verdict so a
        # degraded pool is attributable in the artifact
        stack_env('0')
        par_p50, par_p95 = measure(q(), 11)
        par_win_p50, par_win_p95 = measure(
            q('2014-06-01', '2014-07-01'), 11)
        fanout = mod_iqmt.fanout_stats()

        # sequential baseline: DN_IQ_THREADS=0 (uncached
        # open/query/close per shard — what every query paid before
        # the reader pool)
        iq_env('0')
        seq_p50, seq_p95 = measure(q(), 5)

        # rollup planner (PR 16): month-from-day rollup shards answer
        # the full-year query from ~12 coarse reads instead of 365
        # fine ones — byte-identical by construction, asserted here
        from dragnet_tpu import rollup as mod_rollup
        iq_env('auto')
        stack_env('auto')
        fine_points = ds.query(q(), 'day').points
        roll_doc = mod_rollup.build_rollups(idx, 'day')
        roll_result = ds.query(q(), 'day')
        assert roll_result.points == fine_points, \
            'rollup points diverge from fine shards'
        covered = rollup_read = 0
        for s in roll_result.pipeline.stages:
            covered += s.counters.get('index shards via rollup', 0)
            rollup_read += s.counters.get('rollup shards queried', 0)
        # shards the year query actually READS with rollups in place:
        # coarse shards plus any fine shards the plan left uncovered
        roll_shards_read = rollup_read + (nshards - covered)
        roll_p50, roll_p95 = measure(q(), 11)
    finally:
        iq_env(prior_auto)
        stack_env(prior_stack)
        if prior_legacy is not None:
            os.environ['DN_QUERY_CONCURRENCY'] = prior_legacy
    mod_iqmt.shard_cache_clear()
    shutil.rmtree(idx, ignore_errors=True)
    os.unlink(datafile)
    return {
        'index_query_shards': nshards,
        'index_query_build_records_per_sec': round(n / build_s),
        # r1-r4 recorded a single-shard p50 (~0.8 ms); the comparable
        # figure here is per-shard, not the 365-shard total
        'index_query_per_shard_ms': round(stk_p50 / max(nshards, 1),
                                          3),
        # headline = the shipping default path (stacked)
        'index_query_p50_ms': round(stk_p50, 2),
        'index_query_p95_ms': round(stk_p95, 2),
        'index_query_stacked_p50_ms': round(stk_p50, 2),
        'index_query_stacked_p95_ms': round(stk_p95, 2),
        'index_query_stacked_window_p50_ms': round(stk_win_p50, 2),
        'index_query_stacked_window_p95_ms': round(stk_win_p95, 2),
        'index_query_parallel_p50_ms': round(par_p50, 2),
        'index_query_parallel_p95_ms': round(par_p95, 2),
        'index_query_parallel_window_p50_ms': round(par_win_p50, 2),
        'index_query_parallel_window_p95_ms': round(par_win_p95, 2),
        # which strategy the parallel legs actually ran (the fan-out
        # degrades itself to the cached sequential loop when that
        # measures faster) + the measured per-shard costs behind it
        'index_query_parallel_mode': fanout['last_mode'],
        'index_query_pool_ms_per_shard':
            round(fanout['pool_ms_per_shard'], 4)
            if fanout['pool_ms_per_shard'] is not None else None,
        'index_query_seq_ms_per_shard':
            round(fanout['seq_ms_per_shard'], 4)
            if fanout['seq_ms_per_shard'] is not None else None,
        'index_query_cold_ms': round(cold_ms, 2),
        'index_query_window_p50_ms': round(stk_win_p50, 2),
        'index_query_window_p95_ms': round(stk_win_p95, 2),
        'index_query_sequential_p50_ms': round(seq_p50, 2),
        'index_query_sequential_p95_ms': round(seq_p95, 2),
        'index_query_shards_pruned': pruned,
        'index_query_window_shards_queried': queried,
        'index_query_cache_hits': cache_stats['hits'],
        'index_query_cache_misses': cache_stats['misses'],
        'index_query_threads': mod_iqmt.iq_threads(),
        'index_query_stack_mode': _iq_stack_mode(),
        # the rollup-planner year query (byte-identical, asserted):
        # p50 over the rollup-served tree and how few shards it read
        'index_query_rollup_p50_ms': round(roll_p50, 2),
        'index_query_rollup_p95_ms': round(roll_p95, 2),
        'index_query_rollup_shards_built': roll_doc['built'],
        'index_query_rollup_shards_read': roll_shards_read,
        'index_query_rollup_covered_shards': covered,
        'index_query_rollup_byte_identical': True,
    }


def index_query_device_bench(tmpdir, probe_doc=None, runs=None):
    """Device-offloaded index query (device_index): the 365-shard year
    query host vs forced-device (DN_INDEX_DEVICE=1), byte identity
    asserted, then residency legs — the exact-repeat accumulator pin
    (zero transfer) and the pinned-shard repeat path (host pins
    churned, staged shard tensors served from HBM, measured skipped
    H2D bytes).  A device leg that cannot engage records the probe's
    skip attribution, never a bare null."""
    import shutil
    from dragnet_tpu import device_index as mod_di
    from dragnet_tpu import index_query_mt as mod_iqmt
    datafile = os.path.join(tmpdir, 'iqdev.log')
    idx = os.path.join(tmpdir, 'iqdev.idx')
    n = int(os.environ.get('DN_BENCH_IQ_DEVICE_RECORDS', '600000'))
    start_ms = 1388534400000             # 2014-01-01, 365 daily shards
    gen_to_file(n, datafile, mindate_ms=start_ms,
                maxdate_ms=start_ms + 365 * 86400000)
    ds = make_ds(datafile, idx)
    metrics = [mod_query.metric_deserialize(dict(m)) for m in METRICS]
    ds.build(metrics, 'day')
    nshards = _count_shards(idx)
    conf = {'breakdowns': [{'name': 'host'},
                           {'name': 'latency', 'aggr': 'quantize'}],
            'filter': {'eq': ['req.method', 'GET']}}

    def q():
        return mod_query.query_load(dict(conf))

    def measure(reps, leg, before_rep=None):
        times = []
        for _ in range(reps):
            if before_rep is not None:
                before_rep()
            t0 = time.monotonic()
            ds.query(q(), 'day')
            ms = (time.monotonic() - t0) * 1000
            times.append(ms)
            if runs is not None:
                runs.add(leg, ms)
        times.sort()
        return (times[len(times) // 2],
                times[min(len(times) - 1, int(len(times) * 0.95))])

    def iqd_env(v):
        prior = os.environ.get('DN_INDEX_DEVICE')
        if v is None:
            os.environ.pop('DN_INDEX_DEVICE', None)
        else:
            os.environ['DN_INDEX_DEVICE'] = v
        return prior

    out = {'index_query_device_shards': nshards}
    prior_legacy = os.environ.pop('DN_QUERY_CONCURRENCY', None)
    prior_mode = iqd_env('0')
    try:
        # host leg: the stacked path with the device lane pinned off
        mod_iqmt.shard_cache_clear()
        ds.query(q(), 'day')                 # warm handle cache
        host_p50, host_p95 = measure(9, 'iq_device_host')
        host_points = ds.query(q(), 'day').points
        out['index_query_host_p50_ms'] = round(host_p50, 2)
        out['index_query_host_p95_ms'] = round(host_p95, 2)

        # forced-device leg (DN_INDEX_DEVICE=1): engagement measured
        # from the lane's own counters, identity asserted byte-for-
        # byte against the host points (canonical order included)
        allow = probe_doc is None or probe_doc.get('alive', True)
        engaged = False
        if allow:
            iqd_env('1')
            mod_di._reset_engagement()
            ds.query(q(), 'day')             # warm (jit compiles here)
            dev_points = ds.query(q(), 'day').points
            assert dev_points == host_points, \
                'device index-query points diverge from host'
            out['index_query_device_byte_identical'] = True
            mod_di._reset_engagement()
            dev_p50, dev_p95 = measure(9, 'iq_device_forced')
            eng = mod_di.stats_doc()
            engaged = eng['dispatches'] > 0
            if engaged:
                out['index_query_device_p50_ms'] = round(dev_p50, 2)
                out['index_query_device_p95_ms'] = round(dev_p95, 2)
                out['index_query_device_vs_host'] = \
                    round(host_p50 / dev_p50, 3) if dev_p50 else None
                out['index_device_dispatches'] = eng['dispatches']
                out['index_device_shards_per_dispatch'] = \
                    eng['shards_per_dispatch']
                out['index_device_rows'] = eng['rows']
        out['index_query_device_engaged'] = engaged
        if not engaged:
            # attribution, not a bare null: why the leg is absent
            skip = {'reason': (probe_doc or {}).get('reason')
                    or 'device lane did not engage '
                    '(backend unavailable or exactness gate)'}
            if probe_doc is not None:
                skip['probe_duration_s'] = probe_doc.get('duration_s')
            out['index_query_device_skip'] = skip

        # residency legs: arm the serve residency manager in-process
        # and measure (a) the exact-repeat accumulator pin and (b) the
        # pinned-shard repeat path — host pins churned between reps
        # (drop_host_pins, the state distinct-query traffic converges
        # to), staged shard tensors answering from HBM
        if engaged:
            from dragnet_tpu.serve import residency as mod_residency
            mgr = mod_residency.configure(256 << 20)
            try:
                mod_di._reset_engagement()
                ds.query(q(), 'day')         # populate the pins
                base = mod_di.stats_doc()['dispatches']
                ds.query(q(), 'day')         # exact repeat: acc pin
                out['index_device_acc_repeat_zero_dispatch'] = \
                    mod_di.stats_doc()['dispatches'] == base
                out['index_device_acc_d2h_saved_bytes'] = \
                    mgr.stats()['d2h_saved_bytes']
                mod_di._reset_engagement()
                res_p50, res_p95 = measure(
                    9, 'iq_device_resident',
                    before_rep=mgr.drop_host_pins)
                eng = mod_di.stats_doc()
                hit_rate = eng['pinned_shard_hits'] / eng['shards'] \
                    if eng['shards'] else 0.0
                out['index_device_resident_p50_ms'] = round(res_p50, 2)
                out['index_device_resident_p95_ms'] = round(res_p95, 2)
                out['index_device_pinned_shard_hits'] = \
                    eng['pinned_shard_hits']
                out['index_device_pinned_shard_hit_rate'] = \
                    round(hit_rate, 4)
                out['index_device_h2d_saved_bytes'] = \
                    eng['h2d_saved_bytes']
                out['index_device_h2d_bytes'] = eng['h2d_bytes']
            finally:
                mod_residency.deconfigure()
    finally:
        iqd_env(prior_mode)
        if prior_legacy is not None:
            os.environ['DN_QUERY_CONCURRENCY'] = prior_legacy
    mod_iqmt.shard_cache_clear()
    shutil.rmtree(idx, ignore_errors=True)
    os.unlink(datafile)
    return out


def main_iq_device():
    """Device index-query legs only (`make bench-iq-device` /
    --iq-device-only)."""
    import shutil
    import tempfile
    probe_doc = device_probe()
    tmpdir = tempfile.mkdtemp(prefix='dn_bench_iqdev_')
    try:
        iqd = index_query_device_bench(tmpdir, probe_doc=probe_doc)
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)

    def fmt(v):
        return ('%.1f' % v) if v is not None else 'n/a'
    sys.stderr.write(
        'bench-iq-device: %d shards; host p50 %sms device p50 %sms '
        '(%sx); dispatches %s (%s shards/dispatch); resident p50 %sms '
        'pinned hits %s (rate %s) h2d saved %s bytes; engaged=%s\n'
        % (iqd['index_query_device_shards'],
           fmt(iqd.get('index_query_host_p50_ms')),
           fmt(iqd.get('index_query_device_p50_ms')),
           fmt(iqd.get('index_query_device_vs_host')),
           iqd.get('index_device_dispatches', 'n/a'),
           iqd.get('index_device_shards_per_dispatch', 'n/a'),
           fmt(iqd.get('index_device_resident_p50_ms')),
           iqd.get('index_device_pinned_shard_hits', 'n/a'),
           iqd.get('index_device_pinned_shard_hit_rate', 'n/a'),
           iqd.get('index_device_h2d_saved_bytes', 'n/a'),
           iqd['index_query_device_engaged']))
    if not iqd['index_query_device_engaged']:
        sys.stderr.write('bench-iq-device: skip attribution: %s\n'
                         % iqd.get('index_query_device_skip'))
    print(json.dumps({
        'metric': 'index_query_device_p50_ms',
        'value': iqd.get('index_query_device_p50_ms'),
        'unit': 'ms',
        'vs_baseline': iqd.get('index_query_device_vs_host'),
        'extra': iqd,
    }))


def index_build_bench(tmpdir):
    """Build-focused legs (`make bench-build` / --build-only): the
    write side of the 365-shard daily tree index_query_bench reads.
    Measures the full build (scan + index write, the figure
    index_query_build_records_per_sec also reports) and then isolates
    the index-write phase — per-metric columnar blocks are prepared
    once, and index_build_mt.write_index_blocks is timed sequential
    (DN_BUILD_THREADS=0) vs parallel (auto), p50/p95 over repeats."""
    import shutil
    from dragnet_tpu import index_build_mt as mod_ibmt
    from dragnet_tpu import index_query_mt as mod_iqmt
    datafile = os.path.join(tmpdir, 'build_year.log')
    idx = os.path.join(tmpdir, 'build_year.idx')
    n = 1000000
    start_ms = 1388534400000             # 2014-01-01, 365 daily shards
    end_ms = start_ms + 365 * 86400000
    gen_to_file(n, datafile, mindate_ms=start_ms, maxdate_ms=end_ms)
    ds = make_ds(datafile, idx)
    metrics = [mod_query.metric_deserialize(dict(m)) for m in METRICS]

    prior_bt = os.environ.pop('DN_BUILD_THREADS', None)
    try:
        # full build, default (parallel) writer pool
        times = []
        for _ in range(2):
            shutil.rmtree(idx, ignore_errors=True)
            t0 = time.monotonic()
            ds.build(metrics, 'day')
            times.append(time.monotonic() - t0)
        build_s = min(times)
        nshards = _count_shards(idx)

        # prepare the columnar blocks once (untimed): the index-write
        # phase is then measured alone, against the same inputs the
        # build hands it
        tagged = ds.index_scan(metrics, 'day').points
        queries = [mod_query.metric_query(m, None, None, 'day', 'time')
                   for m in metrics]
        names = [[b['name'] for b in q.qc_breakdowns] for q in queries]
        cols = [[[] for _ in nm] for nm in names]
        weights = [[] for _ in metrics]
        for fields, value in tagged:
            mi = fields['__dn_metric']
            for c, nm in zip(cols[mi], names[mi]):
                c.append(fields[nm])
            weights[mi].append(value)
        blocks = [(names[mi], cols[mi], weights[mi])
                  for mi in range(len(metrics))]
        npoints = sum(len(w) for w in weights)

        def timed_write(nworkers, reps):
            out = []
            for _ in range(reps):
                shutil.rmtree(idx, ignore_errors=True)
                t0 = time.monotonic()
                mod_ibmt.write_index_blocks(metrics, 'day', idx, blocks,
                                            nworkers=nworkers)
                out.append((time.monotonic() - t0) * 1000)
            out.sort()
            return (out[len(out) // 2],
                    out[min(len(out) - 1, int(len(out) * 0.95))])

        seq_p50, seq_p95 = timed_write(0, 5)
        par_n = mod_ibmt.build_threads()
        par_p50, par_p95 = timed_write(par_n, 5)
    finally:
        if prior_bt is not None:
            os.environ['DN_BUILD_THREADS'] = prior_bt
        mod_iqmt.shard_cache_clear()
        shutil.rmtree(idx, ignore_errors=True)
        os.unlink(datafile)
    return {
        'index_build_records_per_sec': round(n / build_s),
        'index_build_shards': nshards,
        'index_build_points': npoints,
        'index_build_threads': par_n,
        'index_build_write_points_per_sec':
            round(npoints / (par_p50 / 1000.0)) if par_p50 else None,
        'index_build_write_sequential_p50_ms': round(seq_p50, 2),
        'index_build_write_sequential_p95_ms': round(seq_p95, 2),
        'index_build_write_parallel_p50_ms': round(par_p50, 2),
        'index_build_write_parallel_p95_ms': round(par_p95, 2),
    }


def parse_bench_extras(datafile, nrecords, use_device,
                       end_to_end=False):
    """Parse-lane measurements on the dense corpus: MB/s for each
    ingest lane over the same byte slice (DN_BENCH_PARSE_BYTES caps
    the slice so the leg stays bounded), plus — with end_to_end — the
    full `dn scan` rec/s per lane on the flat-projection PARSE_QUERY.

    Lanes: `host` is the per-record reference parser (json.loads +
    flat pluck — the path whose per-record dicts the byte lanes
    delete); `native` is the C++ SIMD parser; `vector`/`device` are
    the byteparse structural lanes (numpy / jax-staged kernel)."""
    import json as mod_json
    from dragnet_tpu import byteparse as mod_byteparse
    from dragnet_tpu import native as mod_native

    cap = int(os.environ.get('DN_BENCH_PARSE_BYTES', str(48 << 20)))
    with open(datafile, 'rb') as f:
        data = f.read(cap)
    nl = data.rfind(b'\n')
    data = data[:nl + 1]
    nbytes = len(data)

    paths = ['host', 'operation', 'latency']
    hints = [False, False, False]
    dicts = [True, True, True]

    def feed_columnar(parser):
        pos = 0
        t0 = time.monotonic()
        while pos < nbytes:
            end = min(pos + (4 << 20), nbytes)
            cut = data.rfind(b'\n', pos, end)
            if cut < pos:
                cut = end - 1
            parser.parse(data[pos:cut + 1])
            pos = cut + 1
            if parser.batch_size() >= (1 << 20):
                parser.reset_batch()
        return nbytes / (time.monotonic() - t0) / 1e6

    def best(fn, reps=2):
        return max(fn() for _ in range(reps))

    out = {'parse_bytes_measured': nbytes}

    # host reference lane, equivalent work: json.loads + per-record
    # conversion into the SAME tagged columnar batch (the byte
    # parser's forced-fallback mode — literally the host parser the
    # fast path falls back to)
    out['parse_host_mb_per_sec'] = round(best(
        lambda: feed_columnar(mod_byteparse.ByteParser(
            paths, hints, dicts, force_fallback=True))), 1)
    # raw json.loads + flat pluck into lists, for scale (no columnar
    # conversion — the loosest possible host-parse reading)
    lines = data.split(b'\n')
    sample = lines[:min(len(lines), 200000)]
    sbytes = sum(len(ln) + 1 for ln in sample)

    def loads_only():
        t0 = time.monotonic()
        cols = {p: [] for p in paths}
        ud = object()
        for ln in sample:
            try:
                r = mod_json.loads(ln)
            except ValueError:
                continue
            isdict = type(r) is dict
            for p in paths:
                cols[p].append(r.get(p, ud) if isdict else ud)
        return sbytes / (time.monotonic() - t0) / 1e6
    out['parse_loads_pluck_mb_per_sec'] = round(best(loads_only), 1)

    if mod_native.get_lib() is not None:
        out['parse_native_mb_per_sec'] = round(best(
            lambda: feed_columnar(mod_native.NativeParser(
                paths, hints, dicts))), 1)
    else:
        out['parse_native_mb_per_sec'] = None

    last = {}

    def vector_rate():
        p = mod_byteparse.ByteParser(paths, hints, dicts)
        last['p'] = p        # fallback counters come from a timed rep
        return feed_columnar(p)
    out['parse_vector_mb_per_sec'] = round(best(vector_rate), 1)
    vec = last['p']
    total_lines = vec.lines_fast + vec.lines_fb
    out['parse_vector_fallback_pct'] = round(
        100.0 * vec.lines_fb / max(total_lines, 1), 3)

    from dragnet_tpu.ops import byteparse_kernels as bk
    if use_device and bk.device_parity_available():
        out['parse_device_mb_per_sec'] = round(best(
            lambda: feed_columnar(mod_byteparse.ByteParser(
                paths, hints, dicts, device=True))), 1)
    else:
        out['parse_device_mb_per_sec'] = None

    if end_to_end:
        runs = Runs()
        q = dict(PARSE_QUERY)
        prior = os.environ.get('DN_PARSE')
        npts = {}
        try:
            for lane in ('host', 'vector') + (
                    ('device',) if out['parse_device_mb_per_sec']
                    is not None else ()):
                os.environ['DN_PARSE'] = lane
                rps, np_, _ = timed_scan(
                    runs, 'parse_scan_' + lane, datafile, nrecords,
                    q, 'vector', repeats=2)
                out['parse_%s_records_per_sec' % lane] = round(rps)
                npts[lane] = np_
        finally:
            if prior is None:
                os.environ.pop('DN_PARSE', None)
            else:
                os.environ['DN_PARSE'] = prior
        assert len(set(npts.values())) == 1, 'parse lanes diverge'
        out['parse_runs'] = runs.summary()
    return out


def kernel_bench_extras(datafile):
    """Chip-level measurements (None values when no device backend)."""
    try:
        from dragnet_tpu import devbench
        main = devbench.kernel_bench(datafile, QUERY)
    except Exception as e:
        sys.stderr.write('bench: kernel bench unavailable: %s\n' % e)
        return {}
    if main is None:
        return {}
    out = {
        'device_kernel_records_per_sec':
            round(main['kernel_records_per_sec']),
        'device_kernel_ms_per_batch':
            round(main['kernel_ms_per_batch'], 3),
        'device_kernel_segments': main['segments'],
        'device_hbm_gb_per_sec': round(main['hbm_gb_per_sec'], 2),
        'device_h2d_gb_per_sec': round(main['h2d_gb_per_sec'], 3),
        'device_h2d_bytes_per_record':
            round(main['h2d_bytes_per_record'], 1),
        'device_d2h_mb_per_sec': round(main['d2h_mb_per_sec'], 2),
        'device_kind': main['device_kind'],
    }
    try:
        pl = devbench.kernel_bench(datafile, PALLAS_QUERY)
    except Exception:
        pl = None
    if pl is not None:
        out['device_pallas_records_per_sec'] = \
            round(pl['kernel_records_per_sec'])
        out['device_pallas_engaged'] = pl['pallas']
        if 'aggregate_flops_per_sec' in pl:
            out['device_aggregate_tflops'] = \
                round(pl['aggregate_flops_per_sec'] / 1e12, 3)
        if 'mfu_pct' in pl:
            out['device_mfu_pct'] = round(pl['mfu_pct'], 2)
    return out


# peak-RSS budget for the 10M-record scale leg: results are bounded by
# output tuples, so memory must not scale with input records (the
# reference's 250k-record test held 90 MB; 40x the records gets a
# proportionally tighter per-record bar, not a 40x budget).  Measured
# 305 MB on this rig; the budget leaves ~5x headroom, not 13x.
SCALE_RSS_BUDGET_MB = 1536


def scale_leg(tmpdir, n):
    """10M-record scan+build in a subprocess (its peak RSS is then this
    leg's alone, not the whole bench's)."""
    import subprocess
    code = (
        'import json, os, resource, sys, time\n'
        'sys.path.insert(0, %r)\n'
        'import bench\n'
        'from dragnet_tpu import query as mod_query\n'
        'n = %d\n'
        'datafile = os.path.join(%r, "scale.log")\n'
        'bench.gen_to_file(n, datafile)\n'
        't0 = time.monotonic()\n'
        'r = bench.run_scan(datafile,'
        ' mod_query.query_load(dict(bench.QUERY)))\n'
        'scan_s = time.monotonic() - t0\n'
        'npts = len(r.points)\n'
        'idx = datafile + ".idx"\n'
        'metrics = [mod_query.metric_deserialize(dict(m))'
        ' for m in bench.METRICS]\n'
        't0 = time.monotonic()\n'
        'bench.make_ds(datafile, idx).build(metrics, "day")\n'
        'build_s = time.monotonic() - t0\n'
        'rss_mb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss'
        ' / 1024.0\n'
        'import shutil\n'
        'shutil.rmtree(idx, ignore_errors=True)\n'
        'os.unlink(datafile)\n'
        'print(json.dumps({"scale_records": n,'
        ' "scale_scan_records_per_sec": round(n / scan_s),'
        ' "scale_build_records_per_sec": round(n / build_s),'
        ' "scale_output_points": npts,'
        ' "scale_peak_rss_mb": round(rss_mb, 1)}))\n'
    ) % (os.path.dirname(os.path.abspath(__file__)), n, tmpdir)
    out = subprocess.run([sys.executable, '-c', code],
                         capture_output=True, timeout=1800)
    if out.returncode != 0:
        sys.stderr.write('bench: scale leg failed: %s\n'
                         % out.stderr.decode()[-500:])
        return {}
    res = json.loads(out.stdout.decode().strip().splitlines()[-1])
    res['scale_rss_budget_mb'] = SCALE_RSS_BUDGET_MB
    res['scale_rss_within_budget'] = \
        res['scale_peak_rss_mb'] <= SCALE_RSS_BUDGET_MB
    return res


def device_probe(timeout_s=None):
    """Probe the device backend under a deadline: a backend that never
    answers hangs every device op, and a benchmark that hangs records
    nothing.  Times out -> device legs are skipped and the bench still
    emits its JSON line (host legs + nulls).

    Returns {'alive', 'reason', 'duration_s', 'reset_retries'} so a
    ``device_path_engaged: false`` artifact is always ATTRIBUTABLE:
    the skip reason and how long the probe spent deciding ride the
    extras.  (`reset_retries` is always 0: the installed jax has no
    in-process backend reset to retry with.)"""
    import threading
    if timeout_s is None:
        timeout_s = int(os.environ.get('DN_DEVICE_PROBE_TIMEOUT',
                                       '420'))
    doc = {'alive': False, 'reason': None, 'duration_s': 0.0,
           'reset_retries': 0}
    t0 = time.monotonic()
    result = []

    def probe():
        try:
            import numpy as _np
            from dragnet_tpu.ops import get_jax, backend_ready
            if not backend_ready():
                result.append(False)
                return
            jax, _ = get_jax()
            x = jax.device_put(_np.ones(8))
            float((x + 1).sum())
            result.append(True)
        except Exception:
            result.append(False)

    t = threading.Thread(target=probe, daemon=True)
    t.start()
    t.join(timeout_s)
    if result and result[0]:
        doc['alive'] = True
    else:
        doc['reason'] = 'probe failed' if result else 'probe timeout'
    doc['duration_s'] = round(time.monotonic() - t0, 3)
    if not doc['alive']:
        sys.stderr.write('bench: device backend %s after %.1fs; '
                         'device legs skipped\n'
                         % ('probe failed' if doc['reason'] ==
                            'probe failed'
                            else 'unresponsive (probe timeout)',
                            doc['duration_s']))
    return doc


def device_alive(timeout_s=None):
    return device_probe(timeout_s)['alive']


def main_device_legs(datafile, large_n):
    """Run ONLY the device legs against an existing datafile and print
    one JSON line — the re-exec target for wedge *recovery*: a fresh
    process gets a fresh plugin initialization, so a wedge observed in
    the parent doesn't have to null the whole artifact."""
    if not device_alive():
        print(json.dumps({'ok': False}))
        return
    runs = Runs()
    device_large, np_dev, dev_batches = timed_scan(
        runs, 'scan_large_device', datafile, large_n, QUERY, 'jax')
    hc_dev, hc_tuples, hc_batches = timed_scan(
        runs, 'highcard_device', datafile, large_n, HC_QUERY, 'jax',
        repeats=2)
    build_dev, build_stacked = timed_build(
        runs, 'build_device', datafile, large_n, 'jax')
    kb = kernel_bench_extras(datafile)
    print(json.dumps({
        'ok': True,
        'device_large_records_per_sec': round(device_large),
        'device_output_points': np_dev,
        'device_batches': dev_batches,
        'highcard_device_records_per_sec': round(hc_dev),
        'highcard_output_tuples': hc_tuples,
        'highcard_device_batches': hc_batches,
        'build_device_records_per_sec': round(build_dev),
        'build_device_stacked_batches': build_stacked,
        'kernel_extras': kb,
        'runs': runs.summary(),
    }))


def device_retry_subprocess(datafile, large_n):
    """Wedge recovery: re-exec the device legs in a fresh subprocess
    (fresh plugin init) and retry once before recording nulls.
    Returns the subprocess's result dict, or None."""
    import subprocess
    sys.stderr.write('bench: retrying device legs in a fresh '
                     'subprocess\n')
    try:
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             '--device-legs', datafile, str(large_n)],
            capture_output=True,
            timeout=int(os.environ.get('DN_BENCH_DEVICE_RETRY_TIMEOUT',
                                       '3600')))
    except subprocess.TimeoutExpired:
        sys.stderr.write('bench: device-leg subprocess timed out\n')
        return None
    if out.returncode != 0:
        sys.stderr.write('bench: device-leg subprocess failed: %s\n'
                         % out.stderr.decode()[-300:])
        return None
    sys.stderr.write(out.stderr.decode())
    try:
        res = json.loads(out.stdout.decode().strip().splitlines()[-1])
    except (ValueError, IndexError):
        return None
    if not res.get('ok'):
        sys.stderr.write('bench: device backend still unresponsive in '
                         'subprocess; recording nulls\n')
        return None
    return res


def serve_bench(tmpdir):
    """The `dn serve` legs (--serve-only / make bench-serve): the same
    index-query workload as bench-iq, but measured the way the serving
    tier actually pays for it — a COLD CLI process per query (the
    pre-serve reality: interpreter boot + import + open/parse per
    invocation) vs a warm resident server answering over the unix
    socket with its shard-handle/find-memo caches and compiled
    programs hot.  Also records end-to-end scan rec/s through the
    server, a coalescing burst, and the /stats document's
    device_path_engaged + cache hit rates in the artifact extras."""
    import shutil
    import signal
    import subprocess
    from dragnet_tpu import config as mod_config
    from dragnet_tpu.serve import client as mod_scl
    from dragnet_tpu.serve import lifecycle as mod_lc

    n = int(os.environ.get('DN_BENCH_SERVE_RECORDS', '200000'))
    days = int(os.environ.get('DN_BENCH_SERVE_DAYS', '120'))
    cold_reps = int(os.environ.get('DN_BENCH_SERVE_COLD_REPS', '5'))
    warm_reps = int(os.environ.get('DN_BENCH_SERVE_WARM_REPS', '25'))

    datafile = os.path.join(tmpdir, 'serve.log')
    idx = os.path.join(tmpdir, 'serve.idx')
    rc_path = os.path.join(tmpdir, 'serve_rc.json')
    sock = os.path.join(tmpdir, 'dn.sock')
    start_ms = 1388534400000             # 2014-01-01
    gen_to_file(n, datafile, mindate_ms=start_ms,
                maxdate_ms=start_ms + days * 86400000)

    # a dragnet config the CLI (cold subprocess) and the server share
    cfg = mod_config.create_initial_config()
    cfg = cfg.datasource_add({
        'name': 'servebench', 'backend': 'file',
        'backend_config': {'path': datafile, 'indexPath': idx,
                           'timeField': 'time'},
        'filter': None, 'dataFormat': 'json'})
    for m in METRICS:
        cfg = cfg.metric_add({'name': m['name'],
                              'datasource': 'servebench',
                              'filter': m.get('filter'),
                              'breakdowns': m['breakdowns']})
    mod_config.ConfigBackendLocal(rc_path).save(cfg.serialize())

    metrics = [mod_query.metric_deserialize(dict(m)) for m in METRICS]
    ds = make_ds(datafile, idx)
    ds.build(metrics, 'day')
    nshards = _count_shards(idx)

    env = dict(os.environ, DRAGNET_CONFIG=rc_path)
    dn = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      'bin', 'dn.py')
    query_args = ['query', '-b', 'host,latency[aggr=quantize]', '-f',
                  '{"eq": ["req.method", "GET"]}', 'servebench']

    def pctl(times):
        times = sorted(times)
        return (times[len(times) // 2],
                times[min(len(times) - 1, int(len(times) * 0.95))])

    # cold: one full CLI process per query (the pre-serve shape)
    cold_times = []
    cold_out = None
    for _ in range(cold_reps):
        t0 = time.monotonic()
        p = subprocess.run([sys.executable, dn] + query_args,
                           capture_output=True, env=env, timeout=300)
        cold_times.append((time.monotonic() - t0) * 1000)
        if p.returncode != 0:
            raise RuntimeError('cold CLI query failed: %s'
                               % p.stderr.decode()[-300:])
        cold_out = p.stdout
    cold_p50, cold_p95 = pctl(cold_times)

    # the warm resident server
    proc = subprocess.Popen([sys.executable, dn, 'serve', '--socket',
                             sock], env=env,
                            stdout=subprocess.DEVNULL,
                            stderr=subprocess.DEVNULL)
    try:
        deadline = time.monotonic() + 60
        while not mod_lc.probe(socket_path=sock):
            if time.monotonic() > deadline or proc.poll() is not None:
                raise RuntimeError('serve daemon failed to start')
            time.sleep(0.1)

        req = {'op': 'query', 'ds': 'servebench', 'interval': 'day',
               'config': rc_path,
               'queryconfig': {
                   'breakdowns': [
                       {'name': 'host', 'field': 'host'},
                       {'name': 'latency', 'field': 'latency',
                        'aggr': 'quantize'}],
                   'filter': {'eq': ['req.method', 'GET']}},
               'opts': {}}
        rc0, _, warm_out, _ = mod_scl.request_bytes(sock, req)
        assert rc0 == 0
        warm_times = []
        for _ in range(warm_reps):
            t0 = time.monotonic()
            rc0, _, out_b, _ = mod_scl.request_bytes(sock, req)
            warm_times.append((time.monotonic() - t0) * 1000)
            assert rc0 == 0
            warm_out = out_b
        warm_p50, warm_p95 = pctl(warm_times)
        output_match = warm_out == cold_out

        # end-to-end scan rec/s through the warm server
        scan_req = {'op': 'scan', 'ds': 'servebench',
                    'config': rc_path,
                    'queryconfig': {'breakdowns': [
                        {'name': 'host', 'field': 'host'},
                        {'name': 'operation', 'field': 'operation'}]},
                    'opts': {}}
        mod_scl.request_bytes(sock, scan_req, timeout_s=600)
        t0 = time.monotonic()
        rc0, _, _, _ = mod_scl.request_bytes(sock, scan_req,
                                             timeout_s=600)
        scan_rps = n / (time.monotonic() - t0) if rc0 == 0 else None

        # coalescing burst: concurrent identical queries share one
        # stacked execution (serve-side payoff of index_query_stack)
        import threading
        burst = int(os.environ.get('DN_BENCH_SERVE_BURST', '8'))
        barrier = threading.Barrier(burst)

        def fire():
            barrier.wait()
            mod_scl.request_bytes(sock, req)
        threads = [threading.Thread(target=fire)
                   for _ in range(burst)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

        st = mod_scl.stats(sock)
        proc.send_signal(signal.SIGTERM)
        drained = proc.wait(timeout=60) == 0 and \
            not os.path.exists(sock)

        # history-snapshotter overhead: the same warm workload with
        # DN_METRICS_HISTORY_S=1s, proving the off path above is free
        # (it ran with the rings disabled) and the on path is honest
        hist_p50 = hist_p95 = None
        hist_env = dict(env, DN_METRICS_HISTORY_S='1')
        proc = subprocess.Popen([sys.executable, dn, 'serve',
                                 '--socket', sock], env=hist_env,
                                stdout=subprocess.DEVNULL,
                                stderr=subprocess.DEVNULL)
        deadline = time.monotonic() + 60
        while not mod_lc.probe(socket_path=sock):
            if time.monotonic() > deadline or proc.poll() is not None:
                raise RuntimeError('history-armed serve daemon '
                                   'failed to start')
            time.sleep(0.1)
        rc0, _, hist_out, _ = mod_scl.request_bytes(sock, req)
        assert rc0 == 0
        hist_times = []
        for _ in range(warm_reps):
            t0 = time.monotonic()
            rc0, _, hist_out, _ = mod_scl.request_bytes(sock, req)
            hist_times.append((time.monotonic() - t0) * 1000)
            assert rc0 == 0
        hist_p50, hist_p95 = pctl(hist_times)
        hist_identical = hist_out == warm_out
        hist_st = mod_scl.stats(sock)
        hist_samples = (hist_st.get('history') or {}).get('samples')
        proc.send_signal(signal.SIGTERM)
        proc.wait(timeout=60)

        # result-cache leg (PR 16): the same warm repeat with
        # DN_SERVE_CACHE_MB armed — identical repeats answer from the
        # server-side result cache (no admission slot, no shard
        # reads), byte-identical to the uncached response
        cache_env = dict(env, DN_SERVE_CACHE_MB='64')
        proc = subprocess.Popen([sys.executable, dn, 'serve',
                                 '--socket', sock], env=cache_env,
                                stdout=subprocess.DEVNULL,
                                stderr=subprocess.DEVNULL)
        deadline = time.monotonic() + 60
        while not mod_lc.probe(socket_path=sock):
            if time.monotonic() > deadline or proc.poll() is not None:
                raise RuntimeError('cache-armed serve daemon '
                                   'failed to start')
            time.sleep(0.1)
        rc0, _, cache_out, _ = mod_scl.request_bytes(sock, req)
        assert rc0 == 0
        cached_times = []
        for _ in range(warm_reps):
            t0 = time.monotonic()
            rc0, _, cache_out, _ = mod_scl.request_bytes(sock, req)
            cached_times.append((time.monotonic() - t0) * 1000)
            assert rc0 == 0
        cached_p50, cached_p95 = pctl(cached_times)
        cached_identical = cache_out == warm_out
        cache_st = mod_scl.stats(sock)
        rcache = (cache_st.get('caches') or {}).get('results') or {}
        proc.send_signal(signal.SIGTERM)
        proc.wait(timeout=60)

        # device-residency leg: the same warm repeat against a server
        # with the device lane forced AND DN_DEVICE_RESIDENCY_MB
        # armed — repeats of the stacked aggregation answer from the
        # pinned HBM accumulator (zero H2D re-upload, zero D2H
        # re-fetch), byte-identical to the host-lane warm response.
        # DN_ENGINE=jax works on any backend (CPU included), so this
        # leg measures the residency machinery even on host-only rigs.
        resident_env = dict(env, DN_ENGINE='jax',
                            DN_DEVICE_RESIDENCY_MB='64')
        proc = subprocess.Popen([sys.executable, dn, 'serve',
                                 '--socket', sock], env=resident_env,
                                stdout=subprocess.DEVNULL,
                                stderr=subprocess.DEVNULL)
        deadline = time.monotonic() + 60
        while not mod_lc.probe(socket_path=sock):
            if time.monotonic() > deadline or proc.poll() is not None:
                raise RuntimeError('residency-armed serve daemon '
                                   'failed to start')
            time.sleep(0.1)
        rc0, _, resid_out, _ = mod_scl.request_bytes(sock, req)
        assert rc0 == 0
        resid_times = []
        for _ in range(warm_reps):
            t0 = time.monotonic()
            rc0, _, resid_out, _ = mod_scl.request_bytes(sock, req)
            resid_times.append((time.monotonic() - t0) * 1000)
            assert rc0 == 0
        resid_p50, resid_p95 = pctl(resid_times)
        resid_identical = resid_out == warm_out
        resid_st = mod_scl.stats(sock)
        resid_dev = resid_st.get('device') or {}
        residency = resid_dev.get('residency') or {}
        prewarm = resid_dev.get('prewarm') or {}
        resid_gauges = (resid_st.get('metrics') or {}) \
            .get('gauges') or {}
        proc.send_signal(signal.SIGTERM)
        proc.wait(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
        shutil.rmtree(idx, ignore_errors=True)
        os.unlink(datafile)

    reqs = st['requests']
    caches = st['caches']['shard_handles']
    # the typed-metrics view (PR 7): per-op latency quantiles and the
    # device engagement/residency gauges (ROADMAP open item 4's
    # reporting half — honest zeros on CPU rigs)
    mx = st.get('metrics') or {}
    gauges = mx.get('gauges') or {}
    hists = mx.get('histograms') or {}
    qlat = hists.get('serve_op_latency_ms{op=query}') or {}
    return {
        'serve_records': n,
        'serve_shards': nshards,
        'serve_query_cold_cli_p50_ms': round(cold_p50, 2),
        'serve_query_cold_cli_p95_ms': round(cold_p95, 2),
        'serve_query_warm_p50_ms': round(warm_p50, 2),
        'serve_query_warm_p95_ms': round(warm_p95, 2),
        'serve_warm_vs_cold': round(cold_p50 / warm_p50, 2)
        if warm_p50 else None,
        'serve_scan_records_per_sec': round(scan_rps)
        if scan_rps else None,
        'serve_output_byte_identical': output_match,
        'serve_requests': reqs['requests'],
        'serve_executions': reqs['executions'],
        'serve_coalesced_requests': reqs['coalesced'],
        'serve_cache_hits': caches['hits'],
        'serve_cache_misses': caches['misses'],
        'device_path_engaged': st['device']['engaged'],
        'device_residency_pct': gauges.get('device_residency_pct'),
        'device_engaged_gauge': gauges.get('device_engaged'),
        'serve_query_latency_p50_ms': qlat.get('p50'),
        'serve_query_latency_p99_ms': qlat.get('p99'),
        'serve_drained_clean': bool(drained),
        # the history-snapshotter overhead pair: warm p50 with the
        # rings off (the main leg above) vs DN_METRICS_HISTORY_S=1
        'serve_history_off_warm_p50_ms': round(warm_p50, 2),
        'serve_history_1s_warm_p50_ms': round(hist_p50, 2)
        if hist_p50 is not None else None,
        'serve_history_1s_warm_p95_ms': round(hist_p95, 2)
        if hist_p95 is not None else None,
        'serve_history_output_byte_identical': hist_identical,
        'serve_history_samples': hist_samples,
        # the result-cache repeat pair (PR 16): warm repeats against
        # a DN_SERVE_CACHE_MB-armed server vs the uncached warm leg
        'serve_cached_repeat_p50_ms': round(cached_p50, 2),
        'serve_cached_repeat_p95_ms': round(cached_p95, 2),
        'serve_cached_output_byte_identical': cached_identical,
        'serve_result_cache_hits': rcache.get('hits'),
        'serve_result_cache_hit_rate': rcache.get('hit_rate'),
        # the device-residency repeat pair: warm repeats against a
        # DN_ENGINE=jax + DN_DEVICE_RESIDENCY_MB-armed server; a
        # hit_rate > 0 with byte-identical output is the tentpole's
        # serving proof (pinned HBM accumulators, no per-request
        # transfer)
        'serve_resident_repeat_p50_ms': round(resid_p50, 2),
        'serve_resident_repeat_p95_ms': round(resid_p95, 2),
        'serve_resident_output_byte_identical': resid_identical,
        'serve_residency_hits': residency.get('hits'),
        'serve_residency_hit_rate': residency.get('hit_rate'),
        'serve_residency_pinned_bytes': residency.get('bytes'),
        'serve_residency_h2d_saved_bytes':
            residency.get('h2d_saved_bytes'),
        'serve_residency_d2h_saved_bytes':
            residency.get('d2h_saved_bytes'),
        'serve_prewarm_state': prewarm.get('state'),
        'serve_prewarm_programs': prewarm.get('programs'),
        'serve_prewarm_ms': prewarm.get('ms'),
        'serve_resident_device_engaged':
            resid_gauges.get('device_engaged'),
    }


def main_serve():
    """Serve legs only (`make bench-serve` / --serve-only)."""
    import shutil
    import tempfile
    tmpdir = tempfile.mkdtemp(prefix='dn_bench_serve_')
    try:
        sv = serve_bench(tmpdir)
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)
    sys.stderr.write(
        'bench-serve: %d shards; warm p50 %.1fms p95 %.1fms vs cold '
        'CLI p50 %.1fms (%.1fx); scan %s rec/s; coalesced %d/%d '
        'requests; cache %d hits / %d misses; device engaged %s; '
        'output identical %s; drained %s\n'
        % (sv['serve_shards'], sv['serve_query_warm_p50_ms'],
           sv['serve_query_warm_p95_ms'],
           sv['serve_query_cold_cli_p50_ms'],
           sv['serve_warm_vs_cold'] or 0.0,
           sv['serve_scan_records_per_sec'],
           sv['serve_coalesced_requests'], sv['serve_requests'],
           sv['serve_cache_hits'], sv['serve_cache_misses'],
           sv['device_path_engaged'],
           sv['serve_output_byte_identical'],
           sv['serve_drained_clean']))
    sys.stderr.write(
        'bench-serve residency: p50 %.1fms; hit rate %s; pinned %s '
        'bytes; h2d saved %s; d2h saved %s; prewarm %s (%s '
        'programs); identical %s\n'
        % (sv['serve_resident_repeat_p50_ms'],
           sv['serve_residency_hit_rate'],
           sv['serve_residency_pinned_bytes'],
           sv['serve_residency_h2d_saved_bytes'],
           sv['serve_residency_d2h_saved_bytes'],
           sv['serve_prewarm_state'], sv['serve_prewarm_programs'],
           sv['serve_resident_output_byte_identical']))
    print(json.dumps({
        'metric': 'serve_query_warm_p50_ms',
        'value': sv['serve_query_warm_p50_ms'],
        'unit': 'ms',
        'vs_baseline': sv['serve_warm_vs_cold'],
        'extra': sv,
    }))


def subscribe_bench(tmpdir):
    """The standing-query legs (--subscribe-only / make
    bench-subscribe): N subscribers hold one standing query against
    an embedded `dn serve` while a publisher appends records and
    merge-publishes the last day's shards.

    * publish-to-push latency: publish committed -> every subscriber
      holds the new frame (p50/p95 over DN_BENCH_SUB_REPS publishes;
      the DN_SUB_COALESCE_MS batching window is part of the measured
      number ON PURPOSE — it is the latency a dashboard experiences);
    * fan-out economics, counter-asserted: N subscribers x P
      publishes cost exactly P group recomputes (ONE incremental
      merge per publish, not N aggregations) and N*P pushes, while N
      pollers pay N full queries per refresh;
    * byte identity: every pushed frame must equal a fresh poll."""
    import queue as mod_queue
    import threading
    from dragnet_tpu import config as mod_config
    from dragnet_tpu.serve import client as mod_scl
    from dragnet_tpu.serve import server as mod_srv

    n = int(os.environ.get('DN_BENCH_SUB_RECORDS', '60000'))
    reps = int(os.environ.get('DN_BENCH_SUB_REPS', '8'))
    nsubs = int(os.environ.get('DN_BENCH_SUB_FANOUT', '8'))
    burst = int(os.environ.get('DN_BENCH_SUB_BURST', '400'))
    days = 5

    datafile = os.path.join(tmpdir, 'sub.log')
    idx = os.path.join(tmpdir, 'sub.idx')
    rc_path = os.path.join(tmpdir, 'sub_rc.json')
    sock = os.path.join(tmpdir, 'dn.sock')
    start_ms = 1388534400000             # 2014-01-01
    end_ms = start_ms + days * 86400000
    last_day_ms = end_ms - 86400000
    gen_to_file(n, datafile, mindate_ms=start_ms, maxdate_ms=end_ms)

    cfg = mod_config.create_initial_config()
    cfg = cfg.datasource_add({
        'name': 'subbench', 'backend': 'file',
        'backend_config': {'path': datafile, 'indexPath': idx,
                           'timeField': 'time'},
        'filter': None, 'dataFormat': 'json'})
    for m in METRICS:
        cfg = cfg.metric_add({'name': m['name'],
                              'datasource': 'subbench',
                              'filter': m.get('filter'),
                              'breakdowns': m['breakdowns']})
    mod_config.ConfigBackendLocal(rc_path).save(cfg.serialize())

    metrics = [mod_query.metric_deserialize(dict(m)) for m in METRICS]
    ds = make_ds(datafile, idx)
    ds.build(metrics, 'day')
    nshards = _count_shards(idx)

    prior = os.environ.get('DN_SUB_COALESCE_MS')
    os.environ['DN_SUB_COALESCE_MS'] = '10'
    srv = mod_srv.DnServer(
        socket_path=sock,
        conf={'max_inflight': 8, 'queue_depth': 32, 'deadline_ms': 0,
              'coalesce': True, 'drain_s': 10}).start()
    try:
        qdoc = {'breakdowns': [
            {'name': 'host', 'field': 'host'},
            {'name': 'latency', 'field': 'latency',
             'aggr': 'quantize'}],
            'filter': {'eq': ['req.method', 'GET']}}
        sub_req = {'op': 'subscribe', 'ds': 'subbench',
                   'config': rc_path, 'interval': 'day',
                   'queryconfig': qdoc, 'opts': {}}
        poll_req = {'op': 'query', 'ds': 'subbench',
                    'config': rc_path, 'interval': 'day',
                    'queryconfig': qdoc, 'opts': {}}

        # each subscriber: a reader thread draining its stream into
        # a queue (receipt-stamped), so fan-out latency is measured
        # at the consumer, concurrently for all N
        streams = [mod_scl.subscribe_stream(sock, dict(sub_req))
                   for _ in range(nsubs)]
        queues = [mod_queue.Queue() for _ in range(nsubs)]

        def reader(stream, q):
            from dragnet_tpu.errors import DNError
            try:
                for fr in stream:
                    q.put((time.monotonic(), fr))
            except DNError:
                pass
            q.put(None)

        threads = [threading.Thread(target=reader, args=(s, q),
                                    daemon=True)
                   for s, q in zip(streams, queues)]
        for t in threads:
            t.start()
        seeds = [q.get(timeout=120)[1] for q in queues]
        rc0, _, poll_out, _ = mod_scl.request_bytes(
            sock, dict(poll_req))
        assert rc0 == 0
        identical = all(fr['payload'] == poll_out for fr in seeds)

        before = mod_scl.stats(sock)['subscriptions']['counters']
        mod = _mktestdata()
        lat_all = []
        lat_first = []
        per_sub_frames = [0] * nsubs
        bi = n
        final_poll = poll_out
        for rep in range(reps):
            with open(datafile, 'a') as f:
                for _ in range(burst):
                    f.write(json.dumps(
                        mod.make_record(bi % n, n, last_day_ms,
                                        end_ms),
                        separators=(',', ':')) + '\n')
                    bi += 1
            ds.build(metrics, 'day', time_after=last_day_ms,
                     time_before=end_ms)
            t0 = time.monotonic()
            rcp, _, final_poll, _ = mod_scl.request_bytes(
                sock, dict(poll_req))
            assert rcp == 0
            # a publish whose write hooks straddle a coalesce window
            # may push an intermediate frame first: drain each
            # subscriber to the COMMITTED bytes (the fresh poll)
            stamps = []
            for i, q in enumerate(queues):
                while True:
                    item = q.get(timeout=120)
                    assert item is not None, 'stream died mid-bench'
                    per_sub_frames[i] += 1
                    if item[1]['payload'] == final_poll:
                        stamps.append(item[0])
                        break
            lat_first.append((min(stamps) - t0) * 1000)
            lat_all.append((max(stamps) - t0) * 1000)
        after = mod_scl.stats(sock)['subscriptions']['counters']
        recomputes = after['recomputes'] - before['recomputes']
        pushes = after['pushes'] - before['pushes']
        # THE economics contract: per-publish cost is O(1) in
        # subscriber count — each pushed version cost ONE incremental
        # merge shared by all N subscribers (a publish may split
        # across coalesce windows, but never multiplies by N), where
        # N pollers would have paid N full aggregations per refresh
        versions = per_sub_frames[0]
        if per_sub_frames != [versions] * nsubs:
            raise RuntimeError('subscribers diverged: %r'
                               % (per_sub_frames,))
        if pushes != versions * nsubs:
            raise RuntimeError('expected %d pushes (%d versions x %d '
                               'subscribers), got %d'
                               % (versions * nsubs, versions, nsubs,
                                  pushes))
        if not reps <= recomputes <= 2 * reps + 1:
            raise RuntimeError('expected ~%d recomputes for %d '
                               'publishes (never %d), got %d'
                               % (reps, reps, reps * nsubs,
                                  recomputes))

        # the polling alternative: N pollers refreshing once — N
        # full queries through admission, per refresh, forever
        t0 = time.monotonic()
        for _ in range(nsubs):
            rcp, _, pout, _ = mod_scl.request_bytes(
                sock, dict(poll_req))
            assert rcp == 0
            identical = identical and pout == final_poll
        poll_fanout_ms = (time.monotonic() - t0) * 1000

        # stopping the server pushes every subscriber an 'end' frame,
        # which exhausts the reader generators cleanly (a generator
        # blocked in next() cannot be close()d from here)
        srv.stop()
        for t in threads:
            t.join(timeout=10)

        lat_all.sort()
        lat_first.sort()
        p50 = lat_all[len(lat_all) // 2]
        p95 = lat_all[min(len(lat_all) - 1,
                          int(len(lat_all) * 0.95))]
        return {
            'sub_records': n,
            'sub_shards': nshards,
            'sub_subscribers': nsubs,
            'sub_publishes': reps,
            'sub_burst_records': burst,
            'sub_publish_to_push_p50_ms': round(p50, 1),
            'sub_publish_to_push_p95_ms': round(p95, 1),
            'sub_publish_to_first_push_p50_ms': round(
                lat_first[len(lat_first) // 2], 1),
            'sub_recomputes_per_publish': round(recomputes / reps,
                                                2),
            'sub_merges_if_polled': reps * nsubs,
            'sub_pushes': pushes,
            'sub_shards_folded': (after['shards_folded'] -
                                  before['shards_folded']),
            'sub_shards_reused': (after['shards_reused'] -
                                  before['shards_reused']),
            'sub_poller_fanout_ms': round(poll_fanout_ms, 1),
            'sub_frames_delta': after['frames_delta'],
            'sub_output_byte_identical': identical,
        }
    finally:
        srv.stop()
        if prior is None:
            os.environ.pop('DN_SUB_COALESCE_MS', None)
        else:
            os.environ['DN_SUB_COALESCE_MS'] = prior


def main_subscribe():
    """Standing-query legs only (`make bench-subscribe` /
    --subscribe-only)."""
    import shutil
    import tempfile
    tmpdir = tempfile.mkdtemp(prefix='dn_bench_sub_')
    try:
        sb = subscribe_bench(tmpdir)
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)
    sys.stderr.write(
        'bench-subscribe: %d subscribers x %d publishes; publish-to-'
        'push p50 %.1fms p95 %.1fms (first %.1fms); %.1f recomputes/'
        'publish (%d pushes, %d folded / %d reused shards); %d '
        'pollers refresh %.1fms; delta frames %d; identical %s\n'
        % (sb['sub_subscribers'], sb['sub_publishes'],
           sb['sub_publish_to_push_p50_ms'],
           sb['sub_publish_to_push_p95_ms'],
           sb['sub_publish_to_first_push_p50_ms'],
           sb['sub_recomputes_per_publish'], sb['sub_pushes'],
           sb['sub_shards_folded'], sb['sub_shards_reused'],
           sb['sub_subscribers'], sb['sub_poller_fanout_ms'],
           sb['sub_frames_delta'],
           sb['sub_output_byte_identical']))
    print(json.dumps({
        'metric': 'sub_publish_to_push_p50_ms',
        'value': sb['sub_publish_to_push_p50_ms'],
        'unit': 'ms',
        'vs_baseline': None,
        'extra': sb,
    }))


def cluster_bench(tmpdir):
    """The scatter-gather cluster legs (--cluster-only / make
    bench-cluster): the same warm index-query workload as bench-serve,
    measured three ways — a single resident server (the PR 5 shape,
    the baseline), a 3-member x 2-replica `dn serve` cluster routing
    through one member (scatter + partial merge cost), and the same
    cluster after SIGKILLing a partition owner (failover-added
    latency: every partition still has a live replica, so bytes stay
    identical while the router pays the dead-primary dial).  Hedging
    is armed (DN_BENCH_CLUSTER_HEDGE_MS floor) so the hedge fire rate
    under real latencies lands in the extras."""
    import shutil
    import signal
    import subprocess
    from dragnet_tpu import config as mod_config
    from dragnet_tpu.serve import client as mod_scl
    from dragnet_tpu.serve import lifecycle as mod_lc

    n = int(os.environ.get('DN_BENCH_CLUSTER_RECORDS', '200000'))
    days = int(os.environ.get('DN_BENCH_CLUSTER_DAYS', '120'))
    warm_reps = int(os.environ.get('DN_BENCH_CLUSTER_WARM_REPS', '25'))
    hedge_ms = os.environ.get('DN_BENCH_CLUSTER_HEDGE_MS', '8')

    datafile = os.path.join(tmpdir, 'cluster.log')
    idx = os.path.join(tmpdir, 'cluster.idx')
    rc_path = os.path.join(tmpdir, 'cluster_rc.json')
    start_ms = 1388534400000             # 2014-01-01
    gen_to_file(n, datafile, mindate_ms=start_ms,
                maxdate_ms=start_ms + days * 86400000)

    cfg = mod_config.create_initial_config()
    cfg = cfg.datasource_add({
        'name': 'clusterbench', 'backend': 'file',
        'backend_config': {'path': datafile, 'indexPath': idx,
                           'timeField': 'time'},
        'filter': None, 'dataFormat': 'json'})
    for m in METRICS:
        cfg = cfg.metric_add({'name': m['name'],
                              'datasource': 'clusterbench',
                              'filter': m.get('filter'),
                              'breakdowns': m['breakdowns']})
    mod_config.ConfigBackendLocal(rc_path).save(cfg.serialize())

    metrics = [mod_query.metric_deserialize(dict(m)) for m in METRICS]
    ds = make_ds(datafile, idx)
    ds.build(metrics, 'day')
    nshards = _count_shards(idx)

    socks = {m: os.path.join(tmpdir, 'dn-%s.sock' % m) for m in 'abc'}
    topo_path = os.path.join(tmpdir, 'topo.json')
    with open(topo_path, 'w') as f:
        json.dump({
            'epoch': 1, 'assign': 'hash',
            'members': {m: {'endpoint': socks[m]} for m in 'abc'},
            'partitions': [
                {'id': 0, 'replicas': ['a', 'b']},
                {'id': 1, 'replicas': ['b', 'c']},
                {'id': 2, 'replicas': ['c', 'a']},
            ],
        }, f)

    env = dict(os.environ, DRAGNET_CONFIG=rc_path,
               DN_ROUTER_HEDGE_MS=hedge_ms,
               DN_ROUTER_PROBE_MS='200',
               DN_REMOTE_RETRIES='1', DN_REMOTE_BACKOFF_MS='5',
               DN_REMOTE_CONNECT_TIMEOUT_S='2')
    dn = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      'bin', 'dn.py')
    req = {'op': 'query', 'ds': 'clusterbench', 'interval': 'day',
           'config': rc_path,
           'queryconfig': {
               'breakdowns': [
                   {'name': 'host', 'field': 'host'},
                   {'name': 'latency', 'field': 'latency',
                    'aggr': 'quantize'}],
               'filter': {'eq': ['req.method', 'GET']}},
           'opts': {}}

    def spawn(args):
        return subprocess.Popen([sys.executable, dn] + args, env=env,
                                stdout=subprocess.DEVNULL,
                                stderr=subprocess.DEVNULL)

    def wait_up(sock, proc):
        deadline = time.monotonic() + 60
        while not mod_lc.probe(socket_path=sock):
            if time.monotonic() > deadline or proc.poll() is not None:
                raise RuntimeError('serve daemon failed to start')
            time.sleep(0.1)

    def pctl(times):
        times = sorted(times)
        return (times[len(times) // 2],
                times[min(len(times) - 1, int(len(times) * 0.95))])

    def warm_leg(sock, reps):
        rc0, _, out_b, err_b = mod_scl.request_bytes(sock, req,
                                                     timeout_s=300)
        if rc0 != 0:
            raise RuntimeError('bench query failed: %s'
                               % err_b.decode()[-300:])
        times = []
        for _ in range(reps):
            t0 = time.monotonic()
            rc0, _, out_b, _ = mod_scl.request_bytes(sock, req,
                                                     timeout_s=300)
            times.append((time.monotonic() - t0) * 1000)
            assert rc0 == 0
        return pctl(times) + (out_b,)

    procs = []
    try:
        # baseline: one resident server owning the whole tree
        single_sock = os.path.join(tmpdir, 'dn-single.sock')
        single = spawn(['serve', '--socket', single_sock])
        procs.append(single)
        wait_up(single_sock, single)
        single_p50, single_p95, single_out = warm_leg(single_sock,
                                                      warm_reps)
        single.send_signal(signal.SIGTERM)
        single.wait(timeout=60)

        # the 3-member cluster, routed through member a
        members = {}
        for m in 'abc':
            members[m] = spawn(['serve', '--socket', socks[m],
                                '--cluster', topo_path,
                                '--member', m])
            procs.append(members[m])
        for m in 'abc':
            wait_up(socks[m], members[m])
        cl_p50, cl_p95, cl_out = warm_leg(socks['a'], warm_reps)
        output_match = cl_out == single_out

        # failover: SIGKILL member b (primary of partition 1); every
        # partition keeps a live replica, so bytes must still match
        members['b'].kill()
        members['b'].wait()
        fo_p50, fo_p95, fo_out = warm_leg(socks['a'], warm_reps)
        failover_match = fo_out == single_out

        st = mod_scl.stats(socks['a'], timeout_s=30.0)
        cl_sec = st.get('cluster') or {}
        counters = cl_sec.get('counters') or {}
    finally:
        for p in procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        for p in procs:
            if p.poll() is None:
                try:
                    p.wait(timeout=30)
                except subprocess.TimeoutExpired:
                    p.kill()
                    p.wait()
        shutil.rmtree(idx, ignore_errors=True)
        os.unlink(datafile)

    scatters = counters.get('scatters') or 0
    hedges = counters.get('hedges_fired') or 0
    return {
        'cluster_records': n,
        'cluster_shards': nshards,
        'cluster_members': 3,
        'cluster_partitions': 3,
        'single_query_warm_p50_ms': round(single_p50, 2),
        'single_query_warm_p95_ms': round(single_p95, 2),
        'cluster_query_warm_p50_ms': round(cl_p50, 2),
        'cluster_query_warm_p95_ms': round(cl_p95, 2),
        'cluster_vs_single': round(cl_p50 / single_p50, 2)
        if single_p50 else None,
        'cluster_output_byte_identical': output_match,
        'failover_query_p50_ms': round(fo_p50, 2),
        'failover_query_p95_ms': round(fo_p95, 2),
        'failover_added_p50_ms': round(fo_p50 - cl_p50, 2),
        'failover_output_byte_identical': failover_match,
        'cluster_failovers': counters.get('failovers'),
        'cluster_scatters': scatters,
        'cluster_hedges_fired': hedges,
        'cluster_hedge_fire_rate': round(hedges / scatters, 3)
        if scatters else None,
        'cluster_hedges_won': counters.get('hedges_won'),
        'cluster_degraded': counters.get('degraded'),
    }


def main_cluster():
    """Cluster legs only (`make bench-cluster` / --cluster-only)."""
    import shutil
    import tempfile
    tmpdir = tempfile.mkdtemp(prefix='dn_bench_cluster_')
    try:
        cb = cluster_bench(tmpdir)
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)
    sys.stderr.write(
        'bench-cluster: %d shards over %d members; scatter-gather '
        'p50 %.1fms p95 %.1fms vs single-server p50 %.1fms (%.2fx); '
        'failover p50 %.1fms (+%.1fms, %s failovers); hedges fired '
        '%s/%s scatters (rate %s); bytes identical %s / after kill '
        '%s\n'
        % (cb['cluster_shards'], cb['cluster_members'],
           cb['cluster_query_warm_p50_ms'],
           cb['cluster_query_warm_p95_ms'],
           cb['single_query_warm_p50_ms'],
           cb['cluster_vs_single'] or 0.0,
           cb['failover_query_p50_ms'], cb['failover_added_p50_ms'],
           cb['cluster_failovers'], cb['cluster_hedges_fired'],
           cb['cluster_scatters'], cb['cluster_hedge_fire_rate'],
           cb['cluster_output_byte_identical'],
           cb['failover_output_byte_identical']))
    print(json.dumps({
        'metric': 'cluster_query_warm_p50_ms',
        'value': cb['cluster_query_warm_p50_ms'],
        'unit': 'ms',
        'vs_baseline': cb['cluster_vs_single'],
        'extra': cb,
    }))


def follow_bench(tmpdir):
    """The continuous-ingest legs (--follow-only / make bench-follow):

    * steady-state catch-up throughput: a pre-grown log ingested by
      the real FollowLoop in --once semantics (tail -> mini-batch ->
      scan -> merge-publish -> checkpoint), rec/s and MB/s;
    * append-to-queryable latency: a resident FollowLoop tails the
      log while record bursts are appended, measuring append ->
      batch published (shards renamed + caches invalidated — the
      instant a query sees the data) p50/p95 over DN_BENCH_FOLLOW_REPS
      bursts.  The batch-cut latency target (DN_FOLLOW_LATENCY_MS
      semantics, 25 ms here) is part of the measured number ON
      PURPOSE: it is the latency a reader actually experiences."""
    import threading
    from dragnet_tpu import query as mod_query
    from dragnet_tpu.follow.loop import FollowLoop

    n = int(os.environ.get('DN_BENCH_FOLLOW_RECORDS', '60000'))
    reps = int(os.environ.get('DN_BENCH_FOLLOW_REPS', '12'))
    burst = int(os.environ.get('DN_BENCH_FOLLOW_BURST', '400'))

    datafile = os.path.join(tmpdir, 'follow.log')
    idx = os.path.join(tmpdir, 'follow.idx')
    start_ms = 1388534400000             # 2014-01-01
    window_ms = 5 * 86400000
    gen_to_file(n, datafile, mindate_ms=start_ms,
                maxdate_ms=start_ms + window_ms)
    nbytes = os.path.getsize(datafile)
    metrics = [mod_query.metric_deserialize(dict(m)) for m in METRICS]
    ds = make_ds(datafile, idx)

    # leg 1: catch-up over the pre-grown log (one process lifetime,
    # bounded batches — the restart/recovery story in steady state)
    conf = {'latency_ms': 0, 'max_bytes': 1 << 20, 'poll_ms': 5}
    loop = FollowLoop(ds, metrics, 'day', [datafile], conf, once=True)
    t0 = time.monotonic()
    rc = loop.run()
    catchup_s = time.monotonic() - t0
    if rc != 0 or loop.records != n:
        raise RuntimeError('follow catch-up failed (rc=%s, %d/%d '
                           'records)' % (rc, loop.records, n))
    catchup_batches = loop.batches

    # leg 2: append-to-queryable against a resident loop; bursts land
    # inside the same 5-day window, so every publish is a read-
    # modify-publish rewrite of existing shards (the steady state)
    mod = _mktestdata()
    conf = {'latency_ms': 25, 'max_bytes': 1 << 20, 'poll_ms': 5}
    live = FollowLoop(ds, metrics, 'day', [datafile], conf)
    thr = threading.Thread(target=live.run, daemon=True)
    thr.start()
    lat = []
    bi = n
    for rep in range(reps):
        target = live.records + burst
        with open(datafile, 'a') as f:
            for _ in range(burst):
                f.write(json.dumps(
                    mod.make_record(bi % n, n, start_ms,
                                    start_ms + window_ms),
                    separators=(',', ':')) + '\n')
                bi += 1
        t0 = time.monotonic()
        deadline = t0 + 120
        while live.records < target and thr.is_alive() and \
                time.monotonic() < deadline:
            time.sleep(0.001)
        if live.records < target:
            raise RuntimeError('append burst %d never became '
                               'queryable' % rep)
        lat.append((time.monotonic() - t0) * 1000)
    live.request_stop()
    thr.join(timeout=60)

    lat.sort()
    p50 = lat[len(lat) // 2]
    p95 = lat[min(len(lat) - 1, int(len(lat) * 0.95))]
    return {
        'follow_records': n,
        'follow_mb': round(nbytes / 1e6, 1),
        'follow_catchup_rec_per_sec': round(n / catchup_s),
        'follow_catchup_mb_per_sec': round(nbytes / 1e6 / catchup_s,
                                           1),
        'follow_catchup_batches': catchup_batches,
        'follow_burst_records': burst,
        'follow_bursts': reps,
        'follow_append_to_queryable_p50_ms': round(p50, 1),
        'follow_append_to_queryable_p95_ms': round(p95, 1),
        'follow_live_batches': live.batches,
    }


def main_follow():
    """Continuous-ingest legs only (`make bench-follow` /
    --follow-only)."""
    import shutil
    import tempfile
    tmpdir = tempfile.mkdtemp(prefix='dn_bench_follow_')
    try:
        fb = follow_bench(tmpdir)
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)
    sys.stderr.write(
        'bench-follow: catch-up %s rec/s (%s MB/s, %d batches over '
        '%d records); append-to-queryable p50 %.1fms p95 %.1fms '
        '(%d bursts x %d records, %d live batches)\n'
        % (fb['follow_catchup_rec_per_sec'],
           fb['follow_catchup_mb_per_sec'],
           fb['follow_catchup_batches'], fb['follow_records'],
           fb['follow_append_to_queryable_p50_ms'],
           fb['follow_append_to_queryable_p95_ms'],
           fb['follow_bursts'], fb['follow_burst_records'],
           fb['follow_live_batches']))
    print(json.dumps({
        'metric': 'follow_catchup_rec_per_sec',
        'value': fb['follow_catchup_rec_per_sec'],
        'unit': 'rec/s',
        'vs_baseline': None,
        'extra': fb,
    }))


def fanin_bench(tmpdir):
    """The high fan-in legs (--fanin-only / make bench-fanin):
    pooled persistent multiplexed connections (protocol v2, pool.py)
    vs dial-per-request on the cluster partial path — the exact
    exchange the scatter-gather router pays once per partition per
    query — plus an overload flood recording the shed rate and the
    retry_after_ms contract."""
    import shutil
    import threading
    from dragnet_tpu import config as mod_config
    from dragnet_tpu.serve import client as mod_scl
    from dragnet_tpu.serve import pool as mod_pool
    from dragnet_tpu.serve import server as mod_server
    from dragnet_tpu.serve import topology as mod_topology

    n = int(os.environ.get('DN_BENCH_FANIN_RECORDS', '60000'))
    days = int(os.environ.get('DN_BENCH_FANIN_DAYS', '30'))
    reps = int(os.environ.get('DN_BENCH_FANIN_REPS', '80'))

    datafile = os.path.join(tmpdir, 'fanin.log')
    idx = os.path.join(tmpdir, 'fanin.idx')
    rc_path = os.path.join(tmpdir, 'fanin_rc.json')
    sock = os.path.join(tmpdir, 'fanin.sock')
    topo_path = os.path.join(tmpdir, 'fanin_topo.json')
    start_ms = 1388534400000
    gen_to_file(n, datafile, mindate_ms=start_ms,
                maxdate_ms=start_ms + days * 86400000)

    cfg = mod_config.create_initial_config()
    cfg = cfg.datasource_add({
        'name': 'faninbench', 'backend': 'file',
        'backend_config': {'path': datafile, 'indexPath': idx,
                           'timeField': 'time'},
        'filter': None, 'dataFormat': 'json'})
    for m in METRICS:
        cfg = cfg.metric_add({'name': m['name'],
                              'datasource': 'faninbench',
                              'filter': m.get('filter'),
                              'breakdowns': m['breakdowns']})
    mod_config.ConfigBackendLocal(rc_path).save(cfg.serialize())
    prior_cfg = os.environ.get('DRAGNET_CONFIG')
    os.environ['DRAGNET_CONFIG'] = rc_path

    metrics = [mod_query.metric_deserialize(dict(m)) for m in METRICS]
    ds = make_ds(datafile, idx)
    ds.build(metrics, 'day')

    with open(topo_path, 'w') as f:
        json.dump({'epoch': 1, 'assign': 'hash',
                   'members': {'a': {'endpoint': sock}},
                   'partitions': [{'id': 0, 'replicas': ['a']}]}, f)
    topo = mod_topology.load_topology(topo_path, member='a')
    srv = mod_server.DnServer(
        socket_path=sock,
        conf={'max_inflight': 2, 'queue_depth': 4, 'deadline_ms': 0,
              'coalesce': False, 'drain_s': 10, 'tenant_quota': 2},
        cluster=topo, member='a').start()

    partial_req = {
        'op': 'query_partial', 'ds': 'faninbench', 'config': rc_path,
        'interval': 'day', 'epoch': 1, 'partitions': [0],
        'queryconfig': {'breakdowns': [
            {'name': 'host', 'field': 'host'}]},
    }
    query_req = {
        'op': 'query', 'ds': 'faninbench', 'config': rc_path,
        'interval': 'day',
        'queryconfig': {'breakdowns': [
            {'name': 'host', 'field': 'host'}]},
        'opts': {},
    }

    def pctl(times):
        times = sorted(times)
        return (times[len(times) // 2],
                times[min(len(times) - 1, int(len(times) * 0.95))])

    def stats_protocol():
        return mod_scl.stats(sock).get('protocol') or {}

    try:
        # warm both paths (jit, shard handles, the pooled conn)
        for pooled in (False, True):
            rc0, _, out, err = mod_scl.request_bytes(
                sock, dict(partial_req), timeout_s=300,
                pooled=pooled)
            assert rc0 == 0, err

        conns0 = stats_protocol().get('conns_accepted', 0)
        dial_times = []
        for _ in range(reps):
            t0 = time.monotonic()
            rc0, _, _, _ = mod_scl.request_bytes(
                sock, dict(partial_req), timeout_s=300, pooled=False)
            dial_times.append((time.monotonic() - t0) * 1000)
            assert rc0 == 0
        conns_dial = stats_protocol().get('conns_accepted',
                                          0) - conns0

        conns0 = stats_protocol().get('conns_accepted', 0)
        pooled_times = []
        for _ in range(reps):
            t0 = time.monotonic()
            rc0, _, _, _ = mod_scl.request_bytes(
                sock, dict(partial_req), timeout_s=300, pooled=True)
            pooled_times.append((time.monotonic() - t0) * 1000)
            assert rc0 == 0
        conns_pooled = stats_protocol().get('conns_accepted',
                                            0) - conns0

        dial_p50, dial_p95 = pctl(dial_times)
        pooled_p50, pooled_p95 = pctl(pooled_times)

        # overload flood: 16 tenants' worth of concurrent queries
        # against 2 execution slots — record the shed rate and that
        # every busy/overloaded rejection carried retry_after_ms
        flood = {'total': 0, 'ok': 0, 'shed': 0, 'shed_with_hint': 0,
                 'transport': 0}
        flock = threading.Lock()

        def flood_worker(tid):
            for i in range(10):
                req = dict(query_req, tenant='t%d' % (tid % 4),
                           deadline_ms=20000)
                try:
                    rc0, hd, out, err = mod_scl.request_bytes(
                        sock, req, timeout_s=60, pooled=True)
                except Exception:
                    with flock:
                        flood['total'] += 1
                        flood['transport'] += 1
                    continue
                with flock:
                    flood['total'] += 1
                    if rc0 == 0:
                        flood['ok'] += 1
                    else:
                        flood['shed'] += 1
                        if hd.get('retry_after_ms') is not None:
                            flood['shed_with_hint'] += 1

        threads = [threading.Thread(target=flood_worker, args=(t,))
                   for t in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        pool_stats = mod_pool.get().stats()
    finally:
        srv.stop()
        if prior_cfg is None:
            os.environ.pop('DRAGNET_CONFIG', None)
        else:
            os.environ['DRAGNET_CONFIG'] = prior_cfg
        shutil.rmtree(idx, ignore_errors=True)
        os.unlink(datafile)

    shed_rate = flood['shed'] / float(flood['total']) \
        if flood['total'] else None
    return {
        'fanin_records': n,
        'fanin_reps': reps,
        'fanin_partial_dial_p50_ms': round(dial_p50, 3),
        'fanin_partial_dial_p95_ms': round(dial_p95, 3),
        'fanin_partial_pooled_p50_ms': round(pooled_p50, 3),
        'fanin_partial_pooled_p95_ms': round(pooled_p95, 3),
        'fanin_pooled_vs_dial_p50': round(dial_p50 / pooled_p50, 3)
        if pooled_p50 else None,
        'fanin_conns_dialed_leg': conns_dial,
        'fanin_conns_pooled_leg': conns_pooled,
        'fanin_pool_dials': pool_stats.get('dials'),
        'fanin_pool_reuses': pool_stats.get('reuses'),
        'fanin_flood_requests': flood['total'],
        'fanin_flood_completed': flood['ok'],
        'fanin_flood_shed': flood['shed'],
        'fanin_flood_transport': flood['transport'],
        'fanin_shed_rate': round(shed_rate, 4)
        if shed_rate is not None else None,
        'fanin_shed_retry_after_present':
            flood['shed'] == flood['shed_with_hint'],
    }


def verified_read_bench(tmpdir):
    """Verified-read overhead (integrity.py): the warm index-query
    path under DN_VERIFY=off vs open, recorded honestly so the
    default can be chosen on data.  `open` verifies size+crc32 only
    on FRESH shard-handle opens (the handle cache amortizes it), so
    the warm p50 should be ~flat; the cold leg (cache cleared per
    rep: every open verifies) is the worst case the knob can cost."""
    from dragnet_tpu import index_query_mt as mod_iqmt
    from dragnet_tpu import integrity as mod_integrity
    datafile = os.path.join(tmpdir, 'verify.log')
    idx = os.path.join(tmpdir, 'verify.idx')
    n = 200000
    start_ms = 1388534400000             # 2014-01-01, 60 daily shards
    gen_to_file(n, datafile, mindate_ms=start_ms,
                maxdate_ms=start_ms + 60 * 86400000)
    ds = make_ds(datafile, idx)
    metrics = [mod_query.metric_deserialize(dict(m)) for m in METRICS]
    ds.build(metrics, 'day')
    nshards = len(list(mod_integrity.iter_tree_shards(idx)))
    conf = {'breakdowns': [{'name': 'host'},
                           {'name': 'latency', 'aggr': 'quantize'}],
            'filter': {'eq': ['req.method', 'GET']}}
    query = mod_query.query_load(conf)

    def measure(reps, cold=False):
        times = []
        for _ in range(reps):
            if cold:
                mod_iqmt.shard_cache_clear()
            t0 = time.monotonic()
            ds.query(query, 'day')
            times.append((time.monotonic() - t0) * 1000)
        times.sort()
        return (times[len(times) // 2],
                times[min(len(times) - 1, int(len(times) * 0.95))])

    out = {'verify_shards': nshards}
    prior = os.environ.get('DN_VERIFY')
    try:
        for mode in ('off', 'open'):
            os.environ['DN_VERIFY'] = mode
            mod_integrity.reset_memo()
            mod_iqmt.shard_cache_clear()
            ds.query(query, 'day')          # warm the handle cache
            warm_p50, warm_p95 = measure(15)
            cold_p50, cold_p95 = measure(5, cold=True)
            out['verify_%s_warm_p50_ms' % mode] = round(warm_p50, 3)
            out['verify_%s_warm_p95_ms' % mode] = round(warm_p95, 3)
            out['verify_%s_cold_p50_ms' % mode] = round(cold_p50, 3)
            out['verify_%s_cold_p95_ms' % mode] = round(cold_p95, 3)
    finally:
        if prior is None:
            os.environ.pop('DN_VERIFY', None)
        else:
            os.environ['DN_VERIFY'] = prior
        mod_integrity.reset_memo()
        mod_iqmt.shard_cache_clear()
    off, on = out['verify_off_warm_p50_ms'], \
        out['verify_open_warm_p50_ms']
    out['verify_open_warm_overhead_pct'] = \
        round((on - off) / off * 100.0, 1) if off else None
    coff, con = out['verify_off_cold_p50_ms'], \
        out['verify_open_cold_p50_ms']
    out['verify_open_cold_overhead_pct'] = \
        round((con - coff) / coff * 100.0, 1) if coff else None
    return out


def main_verify():
    """Verified-read legs only (`make bench-verify` /
    --verify-only)."""
    import shutil
    import tempfile
    tmpdir = tempfile.mkdtemp(prefix='dn_bench_verify_')
    try:
        vb = verified_read_bench(tmpdir)
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)
    sys.stderr.write(
        'bench-verify: %d shards; warm p50 open %.1fms vs off %.1fms '
        '(%+.1f%%), p95 %.1f/%.1fms; cold-open p50 open %.1fms vs '
        'off %.1fms (%+.1f%%)\n'
        % (vb['verify_shards'], vb['verify_open_warm_p50_ms'],
           vb['verify_off_warm_p50_ms'],
           vb['verify_open_warm_overhead_pct'] or 0.0,
           vb['verify_open_warm_p95_ms'],
           vb['verify_off_warm_p95_ms'],
           vb['verify_open_cold_p50_ms'],
           vb['verify_off_cold_p50_ms'],
           vb['verify_open_cold_overhead_pct'] or 0.0))
    print(json.dumps({
        'metric': 'verify_open_warm_overhead_pct',
        'value': vb['verify_open_warm_overhead_pct'],
        'unit': 'pct',
        'vs_baseline': None,
        'extra': vb,
    }))


def main_fanin():
    """High fan-in legs only (`make bench-fanin` / --fanin-only)."""
    import shutil
    import tempfile
    tmpdir = tempfile.mkdtemp(prefix='dn_bench_fanin_')
    try:
        fb = fanin_bench(tmpdir)
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)
    sys.stderr.write(
        'bench-fanin: partial p50 pooled %.2fms vs dial %.2fms '
        '(%.2fx, p95 %.2f vs %.2f); conns %d pooled vs %d dialed; '
        'flood %d reqs -> %d ok / %d shed / %d transport '
        '(shed rate %s, retry_after on every shed: %s)\n'
        % (fb['fanin_partial_pooled_p50_ms'],
           fb['fanin_partial_dial_p50_ms'],
           fb['fanin_pooled_vs_dial_p50'] or 0.0,
           fb['fanin_partial_pooled_p95_ms'],
           fb['fanin_partial_dial_p95_ms'],
           fb['fanin_conns_pooled_leg'], fb['fanin_conns_dialed_leg'],
           fb['fanin_flood_requests'], fb['fanin_flood_completed'],
           fb['fanin_flood_shed'], fb['fanin_flood_transport'],
           fb['fanin_shed_rate'],
           fb['fanin_shed_retry_after_present']))
    print(json.dumps({
        'metric': 'fanin_partial_pooled_p50_ms',
        'value': fb['fanin_partial_pooled_p50_ms'],
        'unit': 'ms',
        'vs_baseline': fb['fanin_pooled_vs_dial_p50'],
        'extra': fb,
    }))


def main_parse():
    """Parse-lane legs only (`make bench-parse` / --parse-only):
    host-record vs native vs vector vs device parse MB/s plus
    end-to-end `dn scan` rec/s per lane on the dense corpus."""
    import shutil
    import tempfile
    nrecords = int(os.environ.get('DN_BENCH_PARSE_RECORDS', '2000000'))
    tmpdir = tempfile.mkdtemp(prefix='dn_bench_parse_')
    datafile = os.path.join(tmpdir, 'parse.log')
    try:
        gen_to_file(nrecords, datafile)
        use_device = device_alive()
        pb = parse_bench_extras(datafile, nrecords, use_device,
                                end_to_end=True)
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)

    def fmt(v):
        return ('%.1f' % v) if v is not None else 'n/a'
    sys.stderr.write(
        'bench-parse: host %s MB/s, native %s, vector %s, device %s; '
        'end-to-end host %s rec/s vector %s device %s; '
        'vector fallback %.3f%%\n'
        % (fmt(pb['parse_host_mb_per_sec']),
           fmt(pb['parse_native_mb_per_sec']),
           fmt(pb['parse_vector_mb_per_sec']),
           fmt(pb['parse_device_mb_per_sec']),
           pb.get('parse_host_records_per_sec', 'n/a'),
           pb.get('parse_vector_records_per_sec', 'n/a'),
           pb.get('parse_device_records_per_sec', 'n/a'),
           pb['parse_vector_fallback_pct']))
    host = pb['parse_host_mb_per_sec']
    vec = pb['parse_vector_mb_per_sec']
    print(json.dumps({
        'metric': 'parse_vector_mb_per_sec',
        'value': vec,
        'unit': 'MB/s',
        'vs_baseline': round(vec / host, 3) if host else None,
        'extra': pb,
    }))


def main_iq():
    """Index-query legs only (`make bench-iq` / --iq-only): the serving
    path's artifact without the scan/build/device legs."""
    import shutil
    import tempfile
    tmpdir = tempfile.mkdtemp(prefix='dn_bench_iq_')
    try:
        iq = index_query_bench(tmpdir)
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)
    seq = iq['index_query_sequential_p50_ms']
    par = iq['index_query_parallel_p50_ms']
    stk = iq['index_query_stacked_p50_ms']
    sys.stderr.write(
        'bench-iq: %d shards; stacked p50 %.1fms / parallel %.1fms / '
        'seq %.1fms (%.1fx over parallel, %.1fx over seq); '
        'window p50 stacked %.1fms parallel %.1fms (%d pruned); '
        'cache %d hits / %d misses\n'
        % (iq['index_query_shards'], stk, par, seq,
           par / stk if stk else 0.0,
           seq / stk if stk else 0.0,
           iq['index_query_stacked_window_p50_ms'],
           iq['index_query_parallel_window_p50_ms'],
           iq['index_query_shards_pruned'],
           iq['index_query_cache_hits'],
           iq['index_query_cache_misses']))
    print(json.dumps({
        'metric': 'index_query_stacked_p50_ms',
        'value': stk,
        'unit': 'ms',
        'vs_baseline': round(seq / stk, 3) if stk else None,
        'extra': iq,
    }))


def main_build():
    """Index-build legs only (`make bench-build` / --build-only): the
    write-path artifact without the scan/device legs."""
    import shutil
    import tempfile
    tmpdir = tempfile.mkdtemp(prefix='dn_bench_build_')
    try:
        ib = index_build_bench(tmpdir)
    finally:
        shutil.rmtree(tmpdir, ignore_errors=True)
    seq = ib['index_build_write_sequential_p50_ms']
    par = ib['index_build_write_parallel_p50_ms']
    sys.stderr.write(
        'bench-build: %d shards, %d points; full build %d rec/s; '
        'index-write %s pts/s; shard-flush p50 parallel %.1fms '
        '(seq %.1fms, %.1fx), p95 %.1f/%.1fms; threads %d\n'
        % (ib['index_build_shards'], ib['index_build_points'],
           ib['index_build_records_per_sec'],
           ib['index_build_write_points_per_sec'], par, seq,
           seq / par if par else 0.0,
           ib['index_build_write_parallel_p95_ms'],
           ib['index_build_write_sequential_p95_ms'],
           ib['index_build_threads']))
    print(json.dumps({
        'metric': 'index_build_records_per_sec',
        'value': ib['index_build_records_per_sec'],
        'unit': 'records/s',
        'vs_baseline': round(seq / par, 3) if par else None,
        'extra': ib,
    }))


def main():
    if '--device-legs' in sys.argv[1:]:
        i = sys.argv.index('--device-legs')
        return main_device_legs(sys.argv[i + 1], int(sys.argv[i + 2]))
    if '--iq-only' in sys.argv[1:] or \
            os.environ.get('DN_BENCH_ONLY') == 'iq':
        return main_iq()
    if '--iq-device-only' in sys.argv[1:] or \
            os.environ.get('DN_BENCH_ONLY') == 'iq-device':
        return main_iq_device()
    if '--build-only' in sys.argv[1:] or \
            os.environ.get('DN_BENCH_ONLY') == 'build':
        return main_build()
    if '--parse-only' in sys.argv[1:] or \
            os.environ.get('DN_BENCH_ONLY') == 'parse':
        return main_parse()
    if '--serve-only' in sys.argv[1:] or \
            os.environ.get('DN_BENCH_ONLY') == 'serve':
        return main_serve()
    if '--cluster-only' in sys.argv[1:] or \
            os.environ.get('DN_BENCH_ONLY') == 'cluster':
        return main_cluster()
    if '--follow-only' in sys.argv[1:] or \
            os.environ.get('DN_BENCH_ONLY') == 'follow':
        return main_follow()
    if '--subscribe-only' in sys.argv[1:] or \
            os.environ.get('DN_BENCH_ONLY') == 'subscribe':
        return main_subscribe()
    if '--fanin-only' in sys.argv[1:] or \
            os.environ.get('DN_BENCH_ONLY') == 'fanin':
        return main_fanin()
    if '--verify-only' in sys.argv[1:] or \
            os.environ.get('DN_BENCH_ONLY') == 'verify':
        return main_verify()
    nrecords = int(os.environ.get('DN_BENCH_RECORDS', '300000'))
    large_n = int(os.environ.get('DN_BENCH_LARGE_RECORDS', '2000000'))
    host_sample = min(nrecords, 50000)

    import tempfile
    import shutil

    tmpdir = tempfile.mkdtemp(prefix='dn_bench_')
    datafile = os.path.join(tmpdir, 'bench.log')
    largefile = os.path.join(tmpdir, 'bench_large.log')
    t0 = time.monotonic()
    gen_to_file(nrecords, datafile)
    gen_to_file(large_n, largefile)
    gen_s = time.monotonic() - t0
    with open(datafile) as f:
        lines = [f.readline().rstrip('\n') for _ in range(host_sample)]

    runs = Runs()

    # warm up (jit compilation / native-library build happens here,
    # outside the timed region, as it would be cached in a long-running
    # service)
    run_scan(datafile, mod_query.query_load(dict(QUERY)))

    # per-record reference rate (the architectural stand-in for the
    # reference's stream-per-record model; vs_baseline denominator)
    t0 = time.monotonic()
    run_host(lines[:host_sample], mod_query.query_load(dict(QUERY)))
    host_rps = host_sample / (time.monotonic() - t0)

    # r1-r4 comparability leg: 300k auto scan
    scan300_rps, npoints, _ = timed_scan(
        runs, 'scan_300k', datafile, nrecords, QUERY, None)

    probe_doc = device_probe()
    use_device = probe_doc['alive']
    # wedge RECOVERY, not just detection: a probe timeout re-execs the
    # device legs in a fresh subprocess (fresh plugin init) and
    # retries once before nulls reach the artifact
    device_sub = None
    device_retries = 0
    if not use_device and \
            os.environ.get('DN_BENCH_DEVICE_RETRY', '1') != '0':
        device_retries = 1
        device_sub = device_retry_subprocess(largefile, large_n)

    # the large trio — auto is the headline (it must beat the best
    # single engine or the router is costing throughput)
    host_large, np_host, _ = timed_scan(
        runs, 'scan_large_host', largefile, large_n, QUERY, 'vector')
    if use_device:
        device_large, np_dev, dev_batches = timed_scan(
            runs, 'scan_large_device', largefile, large_n, QUERY,
            'jax')
    elif device_sub is not None:
        device_large = device_sub['device_large_records_per_sec']
        np_dev = device_sub['device_output_points']
        dev_batches = device_sub['device_batches']
    else:
        device_large, np_dev, dev_batches = None, np_host, 0
    auto_large, np_auto, _ = timed_scan(
        runs, 'scan_large_auto', largefile, large_n, QUERY, None)
    assert np_dev == np_auto == np_host, 'engine outputs diverge'
    device_engaged = dev_batches > 0

    # high-cardinality at scale: host sparse/deferred merge vs the
    # device-resident sparse sort-merge program.  The radix merge's
    # own telemetry (scan_mt._MERGE_STATS) splits the leg into scan
    # phase (parse + per-batch fold) and merge phase (partition
    # compaction + ordered emission) — reset first so the warm-up and
    # large-trio legs don't pollute the split
    from dragnet_tpu import scan_mt as mod_scan_mt
    mod_scan_mt.reset_merge_stats()
    hc_host, hc_tuples, _ = timed_scan(
        runs, 'highcard_host', largefile, large_n, HC_QUERY, 'vector',
        repeats=2)
    hc_merge = mod_scan_mt.merge_stats()
    # mean merge cost per scan (merge_ms accumulates across repeats);
    # scan phase = the best rep's wall clock minus that merge share
    hc_total_ms = large_n / hc_host * 1000.0
    hc_merge_ms = (hc_merge['merge_ms'] / hc_merge['engaged']
                   if hc_merge['engaged'] else 0.0)
    if use_device:
        hc_dev, hc_tuples_d, hc_batches = timed_scan(
            runs, 'highcard_device', largefile, large_n, HC_QUERY,
            'jax', repeats=2)
        assert hc_tuples == hc_tuples_d, 'highcard outputs diverge'
    elif device_sub is not None:
        hc_dev = device_sub['highcard_device_records_per_sec']
        hc_batches = device_sub['highcard_device_batches']
        assert hc_tuples == device_sub['highcard_output_tuples'], \
            'highcard outputs diverge (subprocess)'
    else:
        hc_dev, hc_batches = None, 0

    # build trio (3-metric daily index)
    build_auto, _ = timed_build(runs, 'build_auto', largefile, large_n,
                                None)
    build_host, _ = timed_build(runs, 'build_host', largefile, large_n,
                                'vector')
    if use_device:
        build_dev, build_stacked = timed_build(
            runs, 'build_device', largefile, large_n, 'jax')
    elif device_sub is not None:
        build_dev = device_sub['build_device_records_per_sec']
        build_stacked = device_sub['build_device_stacked_batches']
    else:
        build_dev, build_stacked = None, 0

    iq = index_query_bench(tmpdir)
    iqd = index_query_device_bench(tmpdir, probe_doc=probe_doc,
                                   runs=runs)
    pb = parse_bench_extras(largefile, large_n, use_device)
    if use_device:
        kb = kernel_bench_extras(largefile)
    elif device_sub is not None:
        kb = device_sub.get('kernel_extras', {})
    else:
        kb = {}

    scale = {}
    if os.environ.get('DN_BENCH_SCALE') == '1':
        scale = scale_leg(tmpdir,
                          int(os.environ.get('DN_BENCH_SCALE_RECORDS',
                                             '10000000')))

    headline = runs.best('scan_large_auto')

    def fmt(v):
        return '%.0f' % v if v is not None else 'n/a'

    sys.stderr.write(
        'bench: headline(auto@%d) %.0f rec/s; 300k %.0f; '
        'large host %.0f dev %s; highcard host %.0f dev %s '
        '(%d tuples, dev batches %d); build auto %.0f host %.0f '
        'dev %s (stacked %d); iq p50 %.1fms/%d shards; '
        'kernel %s rec/s\n'
        % (large_n, headline, scan300_rps, host_large,
           fmt(device_large), hc_host, fmt(hc_dev), hc_tuples,
           hc_batches, build_auto, build_host, fmt(build_dev),
           build_stacked, iq.get('index_query_p50_ms', -1),
           iq.get('index_query_shards', 0),
           kb.get('device_kernel_records_per_sec', 'n/a')))

    shutil.rmtree(tmpdir, ignore_errors=True)

    extra = {
        'headline_config':
            '%d-record multi-field group-by scan, auto engine'
            % large_n,
        'large_records': large_n,
        'scan_300k_records_per_sec': round(scan300_rps),
        'scan_300k_output_points': npoints,
        'host_large_records_per_sec': round(host_large),
        'device_large_records_per_sec':
            round(device_large) if device_engaged else None,
        'device_path_engaged': device_engaged,
        'auto_large_records_per_sec': round(auto_large),
        'highcard_records_per_sec':
            round(hc_dev) if hc_dev is not None else None,
        'highcard_host_records_per_sec': round(hc_host),
        'highcard_device_engaged': hc_batches > 0,
        'highcard_output_tuples': hc_tuples,
        # scan-phase vs merge-phase split for the host highcard leg:
        # merge = the radix partitions' final compaction + ordered
        # emission (scan_mt.RadixMerge), scan = everything before it
        # (parse + per-batch fold + partition routing)
        'highcard_host_total_ms': round(hc_total_ms, 2),
        'highcard_host_merge_ms': round(hc_merge_ms, 2),
        'highcard_host_scan_ms':
            round(max(0.0, hc_total_ms - hc_merge_ms), 2),
        'highcard_merge_partitions': hc_merge['partitions'],
        'highcard_merge_rows_in': hc_merge['rows'],
        'highcard_merge_unique_rows': hc_merge['unique'],
        'build_records_per_sec': round(build_auto),
        'build_host_records_per_sec': round(build_host),
        'build_device_records_per_sec':
            round(build_dev) if build_dev is not None else None,
        'build_device_stacked_batches': build_stacked,
        'device_probe_recovered': device_sub is not None,
        'device_probe_retries': device_retries,
        # attribution for device_path_engaged:false — why the probe
        # said no and how long it spent deciding (incl. the one
        # backend-reset retry device_probe gives a clean failure)
        'device_probe_skip_reason': probe_doc['reason'],
        'device_probe_duration_s': probe_doc['duration_s'],
        'device_probe_reset_retries': probe_doc['reset_retries'],
        'runs': runs.summary(),
    }
    # per-leg skip attribution: when a device leg nulls out, the
    # artifact names the leg and WHY (the probe verdict that skipped
    # it and what recovery was attempted), not just a bare null
    if not use_device and device_sub is None:
        skip = {'reason': probe_doc['reason'],
                'probe_duration_s': probe_doc['duration_s'],
                'backend_reset_retries': probe_doc['reset_retries'],
                'subprocess_retry_attempted': device_retries > 0}
        extra['device_leg_skips'] = {
            leg: dict(skip) for leg in
            ('scan_large_device', 'highcard_device', 'build_device',
             'kernel_bench', 'index_query_device')}
    # the persisted audition cache the auto router escalates from —
    # lets a driver correlate "auto reached the device lane" with the
    # verdicts that were on disk when the run started
    from dragnet_tpu import device_scan as _mod_ds
    apath, aentries, awins = _mod_ds.audition_cache_entries()
    extra['audition_cache_path'] = apath
    extra['audition_cache_entries'] = aentries
    extra['audition_cache_wins'] = awins
    # pipelined-dispatch accounting (device legs run in-process):
    # what fraction of H2D upload bytes were issued while the previous
    # batch was still computing — the double-buffering win itself
    from dragnet_tpu.obs import metrics as _obs_metrics
    _reg = _obs_metrics.global_registry()
    _h2d = _reg.counter('device_h2d_bytes').value
    _h2d_ov = _reg.counter('device_h2d_overlapped_bytes').value
    extra['device_pipe_dispatches'] = \
        _reg.counter('device_pipe_dispatches').value
    extra['device_pipe_overlapped'] = \
        _reg.counter('device_pipe_overlapped').value
    extra['h2d_overlapped_pct'] = \
        round(100.0 * _h2d_ov / _h2d, 2) if _h2d else None
    if device_sub is not None:
        extra['device_subprocess_runs'] = device_sub.get('runs')
    extra.update(iq)
    extra.update(iqd)
    extra.update(pb)
    extra.update(kb)
    extra.update(scale)

    print(json.dumps({
        'metric': 'scan_records_per_sec',
        'value': round(headline),
        'unit': 'records/s',
        'vs_baseline': round(headline / host_rps, 3),
        'extra': extra,
    }))


if __name__ == '__main__':
    main()
