"""Typed metrics: counters, gauges, fixed-bucket latency histograms.

The repo's telemetry before this module was a flat bag of hidden
counters (vpipe.counter_bump) plus ad-hoc totals in `dn serve`'s
/stats — no latencies, no distributions, no types.  This registry is
the replacement substrate:

* ``Counter``    — monotonically increasing count.
* ``Gauge``      — last-set value (device residency, engagement).
* ``Histogram``  — fixed upper-bound buckets (DN_METRICS_BUCKETS,
  default DEFAULT_BUCKETS_MS) with count/sum, cumulative export, and
  quantile estimates (p50/p90/p99 in /stats).

Everything is MERGE-able (like faults.stats()): a request-scoped
registry accumulates without contention and merges into the process
registry when the request ends — the serving hot path takes one lock
per merge, not one per observation.  Metric identity is
``name`` + optional label pairs (``observe('op_latency_ms', 12.5,
op='query')``); exports render labels in Prometheus form.

Writes route through the module helpers (``inc`` / ``set_gauge`` /
``observe``): inside a request scope that carries an obs context
(vpipe.Scope.obs) they land in the request's private registry,
otherwise in the process-global one.  Either way the cost is a dict
lookup and a few adds under a registry lock that is only ever
contended by /stats snapshots.
"""

import bisect
import contextlib
import os
import resource
import sys
import threading
import time

from .. import hostmem as mod_hostmem
from .. import vpipe as mod_vpipe

# Default latency buckets (milliseconds).  Upper bounds, ascending;
# +Inf is implicit.  Chosen to straddle the measured serving range:
# warm coalesced hits ~1-15 ms, cold stacked queries ~30-150 ms,
# builds and device first-contact in the seconds.
DEFAULT_BUCKETS_MS = (1.0, 2.0, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0,
                      500.0, 1000.0, 2500.0, 5000.0, 10000.0, 30000.0)

COUNTER, GAUGE, HISTOGRAM = 'counter', 'gauge', 'histogram'


def bucket_bounds(env=None):
    """The configured histogram upper bounds: DN_METRICS_BUCKETS
    (comma-separated, strictly increasing, positive) or the default.
    Malformed values fall back to the default here — config.obs_config
    is where they are REJECTED (dn serve --validate / serve startup);
    a long-lived reader must not crash on an env edit."""
    if env is None:
        env = os.environ
    raw = env.get('DN_METRICS_BUCKETS')
    if not raw:
        return DEFAULT_BUCKETS_MS
    try:
        bounds = tuple(float(p) for p in raw.split(',') if p.strip())
    except ValueError:
        return DEFAULT_BUCKETS_MS
    if not bounds or any(b <= 0 for b in bounds) or \
            any(b >= c for b, c in zip(bounds, bounds[1:])):
        return DEFAULT_BUCKETS_MS
    return bounds


def metric_key(name, labels):
    """Canonical identity: ('op_latency_ms', (('op', 'query'),))."""
    if not labels:
        return (name, ())
    return (name, tuple(sorted(labels.items())))


class Counter(object):
    kind = COUNTER
    __slots__ = ('value',)

    def __init__(self):
        self.value = 0

    def inc(self, n=1):
        self.value += n

    def merge(self, other):
        self.value += other.value


class Gauge(object):
    kind = GAUGE
    __slots__ = ('value',)

    def __init__(self):
        self.value = 0.0

    def set(self, v):
        self.value = float(v)

    def merge(self, other):
        # last write wins: a request-scoped gauge overrides on merge
        self.value = other.value


class Histogram(object):
    """Fixed-bucket histogram.  `counts[i]` is the NON-cumulative
    count of observations <= bounds[i]; the final slot is +Inf.
    Export layers cumulate (Prometheus `le` semantics)."""

    kind = HISTOGRAM
    __slots__ = ('bounds', 'counts', 'total', 'sum')

    def __init__(self, bounds=None):
        if bounds is None:
            bounds = bucket_bounds()
        self.bounds = tuple(float(b) for b in bounds)
        self.counts = [0] * (len(self.bounds) + 1)
        self.total = 0
        self.sum = 0.0

    def observe(self, v):
        v = float(v)
        self.total += 1
        self.sum += v
        self.counts[self._slot(v)] += 1

    def _slot(self, v):
        # the first bound >= v; past the last one, the +Inf slot
        return bisect.bisect_left(self.bounds, v)

    def merge(self, other):
        if other.bounds == self.bounds:
            for i, n in enumerate(other.counts):
                self.counts[i] += n
        else:
            # a bucket-layout change mid-flight (env edit between
            # requests): re-bin the other side's mass at its bucket
            # upper bounds — approximate, but never lost or crashed
            for i, n in enumerate(other.counts):
                if not n:
                    continue
                at = other.bounds[min(i, len(other.bounds) - 1)] \
                    if other.bounds else 0.0
                self.counts[self._slot(at)] += n
        self.total += other.total
        self.sum += other.sum

    def quantile(self, q):
        """Bucket-resolution quantile estimate: the upper bound of the
        bucket holding the q-th observation (linear within the bucket
        against its lower bound).  None when empty."""
        if self.total <= 0:
            return None
        rank = q * self.total
        seen = 0
        for i, n in enumerate(self.counts):
            if not n:
                continue
            if seen + n >= rank:
                lo = self.bounds[i - 1] if i > 0 else 0.0
                hi = self.bounds[i] if i < len(self.bounds) \
                    else self.bounds[-1] if self.bounds else lo
                frac = (rank - seen) / n
                return lo + (hi - lo) * min(1.0, max(0.0, frac))
            seen += n
        return self.bounds[-1] if self.bounds else 0.0


_CTOR = {COUNTER: Counter, GAUGE: Gauge, HISTOGRAM: Histogram}


class Registry(object):
    """A thread-safe metric table keyed by (name, labels)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._metrics = {}

    def _get(self, kind, name, labels):
        key = metric_key(name, labels)
        with self._lock:
            m = self._metrics.get(key)
            if m is None:
                m = _CTOR[kind]()
                self._metrics[key] = m
            elif m.kind != kind:
                raise TypeError('metric %r is a %s, not a %s'
                                % (name, m.kind, kind))
            return m

    def counter(self, name, **labels):
        return self._get(COUNTER, name, labels)

    def gauge(self, name, **labels):
        return self._get(GAUGE, name, labels)

    def histogram(self, name, **labels):
        return self._get(HISTOGRAM, name, labels)

    def inc(self, name, n=1, **labels):
        with self._lock:
            key = metric_key(name, labels)
            m = self._metrics.get(key)
            if m is None:
                m = self._metrics[key] = Counter()
            m.inc(n)

    def set_gauge(self, name, v, **labels):
        with self._lock:
            key = metric_key(name, labels)
            m = self._metrics.get(key)
            if m is None:
                m = self._metrics[key] = Gauge()
            m.set(v)

    def observe(self, name, v, **labels):
        with self._lock:
            key = metric_key(name, labels)
            m = self._metrics.get(key)
            if m is None:
                m = self._metrics[key] = Histogram()
            m.observe(v)

    def merge(self, other):
        """Fold `other`'s metrics into this registry (request-end
        merge; also how a cluster router will fold replica stats)."""
        with other._lock:
            items = list(other._metrics.items())
        with self._lock:
            for key, m in items:
                mine = self._metrics.get(key)
                if mine is None:
                    mine = self._metrics[key] = _CTOR[m.kind]()
                if mine.kind == m.kind:
                    mine.merge(m)

    def snapshot(self):
        """[(name, labels, metric-copy)] sorted by identity — the
        input both exports consume."""
        with self._lock:
            items = sorted(self._metrics.items())
        out = []
        for (name, labels), m in items:
            if m.kind == HISTOGRAM:
                c = Histogram(m.bounds)
                c.counts = list(m.counts)
                c.total = m.total
                c.sum = m.sum
            else:
                c = _CTOR[m.kind]()
                c.value = m.value
            out.append((name, labels, c))
        return out


_GLOBAL = Registry()


def global_registry():
    return _GLOBAL


def reset_global_registry():
    """Test hook."""
    global _GLOBAL
    _GLOBAL = Registry()


def _active_registry():
    """The request-scoped registry when this thread is inside a scope
    whose obs context carries one, else the global registry."""
    obs = getattr(mod_vpipe.current_scope(), 'obs', None)
    reg = getattr(obs, 'registry', None)
    return reg if reg is not None else _GLOBAL


def inc(name, n=1, **labels):
    _active_registry().inc(name, n, **labels)


def set_gauge(name, v, **labels):
    _active_registry().set_gauge(name, v, **labels)


def observe(name, v, **labels):
    _active_registry().observe(name, v, **labels)


@contextlib.contextmanager
def timed_stage(name, metric='stage_ms', labels=None, **span_attrs):
    """THE shape of per-stage instrumentation: a trace span `name`
    (live only when tracing is on) around the body, and an always-on
    `metric` observation in milliseconds on exit — success OR failure,
    so error paths are accounted like the happy path.  `labels`
    defaults to ``{'stage': name}`` for the shared stage_ms histogram;
    dedicated histograms pass their own (``labels={}`` for none).
    Yields the span for attr updates (``as sp: ... sp.set(...)``)."""
    from . import trace as mod_trace
    if labels is None:
        labels = {'stage': name}
    t0 = time.perf_counter()
    try:
        with mod_trace.span(name, **span_attrs) as sp:
            yield sp
    finally:
        observe(metric, (time.perf_counter() - t0) * 1000.0, **labels)


# .top: this thread's open leaf stage; .ms: its ended leaves' self time
_LEAF = threading.local()
_TRACE_ANNOTATION = None        # jax.profiler.TraceAnnotation, once seen


def _annotation(name, tctx):
    """An entered `jax.profiler.TraceAnnotation(name)`, or None in a
    process that has not imported jax (a host-engine process never
    imports it for this).  A TraceMe: a flag test while no profiler
    session runs, an event on the trace's /host:CPU plane while one
    does — the device planes' clock.  A traced request's id (`tctx`,
    its TraceContext) rides as the event's `trace` stat, so the event
    joins its DN_TRACE line."""
    global _TRACE_ANNOTATION
    cls = _TRACE_ANNOTATION
    if cls is None:
        profiler = getattr(sys.modules.get('jax'), 'profiler', None)
        if profiler is None:
            return None
        cls = _TRACE_ANNOTATION = profiler.TraceAnnotation
    ann = cls(name) if tctx is None else cls(name, trace=tctx.trace_id)
    ann.__enter__()
    return ann


class leaf_stage(object):
    """timed_stage for a LEAF of the request: a stage that encloses
    no other.  The same span `name` and the same always-on
    `stage_ms{stage=name}` observation, plus a third leg, the
    profiler annotation (_annotation).  Only leaves go to the
    profiler: the trace reducer names a device-idle gap by the host
    event that covers most of it, so an annotation around other
    stages would take every gap inside it — serve.execute and the
    other enclosing spans stay timed_stage / span.

    A leaf opened inside another on the same thread (an epoch flush
    in the middle of staging) suspends the outer one: its annotation
    ends and is opened again afterwards, and its `stage_ms` is its
    self time.  So leaves never overlap, and their sum is at most the
    request's own time.

    Every leaf's self time is also added to a total of its thread
    (`thread_ms`): a request reads it at its start and its end, and
    the difference is what its own thread spent under leaves,
    whatever their names (`serve_leaf_ms{op}`, serve/server.py).

    A leaf whose end lies in another function than its start (the
    request's resolution ends at its admission slot, a scan's set-up
    at the stream's first batch) is opened by `with` where it starts
    and ended by `leaf_stage.end_open(name)` where it ends; the
    `with`'s own exit then does nothing, and ends it on an error
    path.

    A scan meets some 200 of these, each after native code has had
    the caches, so the off path is kept short: the request's scope is
    read once, and no span object exists unless tracing is on
    (``as sp: sp.set(...)`` works either way)."""

    __slots__ = ('name', 'attrs', 'outer', 'registry', 'tctx', 'span',
                 'ann', 't0', 'inner_ms')

    def __init__(self, name, **span_attrs):
        self.name = name
        self.attrs = span_attrs
        self.inner_ms = 0.0
        self.t0 = None

    @staticmethod
    def thread_ms():
        """Self milliseconds of every leaf that has ended on this
        thread."""
        return getattr(_LEAF, 'ms', 0.0)

    def __enter__(self):
        self.outer = outer = getattr(_LEAF, 'top', None)
        if outer is not None and outer.ann is not None:
            outer.ann.__exit__(None, None, None)
        _LEAF.top = self
        obs = getattr(mod_vpipe.current_scope(), 'obs', None)
        reg = getattr(obs, 'registry', None)
        self.registry = reg if reg is not None else _GLOBAL
        self.tctx = tctx = getattr(obs, 'trace', None)
        self.span = None
        if tctx is not None:
            from . import trace as mod_trace
            self.span = mod_trace.span(self.name, **self.attrs)
        self.ann = _annotation(self.name, tctx)
        self.t0 = time.perf_counter()
        return self

    def set(self, **attrs):
        if self.span is not None:
            self.span.set(**attrs)
        return self

    @staticmethod
    def end_open(name):
        """End this thread's open leaf if it is `name`; nothing where
        another leaf, or none, is open (the caller runs under no such
        leaf, or one opened inside it has not ended)."""
        top = getattr(_LEAF, 'top', None)
        if top is not None and top.name == name:
            top.__exit__(None, None, None)

    def __exit__(self, *exc):
        # ended already (end_open), or not this thread's open leaf:
        # leaves end innermost first, on the thread that opened them
        if self.t0 is None or getattr(_LEAF, 'top', None) is not self:
            return False
        ms = (time.perf_counter() - self.t0) * 1000.0
        self.t0 = None
        if self.ann is not None:
            self.ann.__exit__(*exc)
        if self.span is not None:
            self.span.__exit__(*exc)
        self_ms = ms - self.inner_ms
        self.registry.observe('stage_ms', self_ms, stage=self.name)
        _LEAF.ms = getattr(_LEAF, 'ms', 0.0) + self_ms
        _LEAF.top = outer = self.outer
        if outer is not None:
            outer.inner_ms += ms
            if outer.ann is not None:
                outer.ann = _annotation(outer.name, outer.tctx)
        return False


# -- compilations (jax.monitoring) ------------------------------------------

# .hit: the persistent cache answered this thread's compile request
_COMPILE_TLS = threading.local()


def watch_compiles(jax):
    """Count XLA compilations in the GLOBAL registry (a compile
    belongs to the process; a listener has no request scope).  Called
    once per process, by ops.get_jax when it imports jax — so only in
    a process where the program imported jax itself.

    On the installed jax (0.9.0, looked at in PR 26) every compile
    request ends in one `/jax/core/compile/backend_compile_duration`
    duration event — whether XLA compiled or the persistent cache
    answered (it is also when `Finished XLA compilation` is logged) —
    and a cache answer is preceded, on the same thread, by one
    `/jax/compilation_cache/cache_hits` event.  `cache_misses` fires
    only when an entry is WRITTEN (subject to the size and time
    thresholds), so it does not count compiles.  Hence:

    * ``xla_compiles_total`` / ``xla_compile_ms`` — real compiles:
      duration events with no cache hit before them.
    * ``xla_cache_loads_total`` — programs loaded from the cache."""
    def on_event(event, **_kw):
        if event == '/jax/compilation_cache/cache_hits':
            _COMPILE_TLS.hit = True

    def on_duration(event, secs, **_kw):
        if event != '/jax/core/compile/backend_compile_duration':
            return
        reg = _GLOBAL
        if getattr(_COMPILE_TLS, 'hit', False):
            _COMPILE_TLS.hit = False
            reg.inc('xla_cache_loads_total')
        else:
            reg.inc('xla_compiles_total')
            reg.observe('xla_compile_ms', secs * 1000.0)

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    # present from the start, so a scrape reads 0 and not nothing
    reg = _GLOBAL
    reg.inc('xla_compiles_total', 0)
    reg.inc('xla_cache_loads_total', 0)


# -- device gauges (ROADMAP open item 4: the reporting half) ---------------

_DEVICE_COUNTER_GAUGES = (
    ('ndevicebatches', 'device_batches'),
    ('nstackedbatches', 'device_stacked_batches'),
    ('index device sums', 'device_index_sums'),
)

# serve/residency.py registers its stats() here at configure time (and
# clears it at drain) — obs stays import-independent of the serve
# package while the device gauges still see pinned-memory truth
_RESIDENCY_SOURCE = None


def set_residency_source(fn):
    """Install (or clear, fn=None) the device-residency stats provider
    refresh_device_gauges consults: a zero-arg callable returning the
    serve/residency.py stats doc."""
    global _RESIDENCY_SOURCE
    _RESIDENCY_SOURCE = fn


def refresh_device_gauges(counters, registry=None):
    """Wire the device-lane engagement picture into typed gauges from
    the existing hidden counters (vpipe.global_counters()):

    * ``device_engaged``          — 1.0 when any device-lane counter
      is non-zero (the same signal /stats' `device.engaged` reports).
    * ``device_batches`` / ``device_stacked_batches`` /
      ``device_index_sums``      — the raw engagement counters.
    * ``device_residency_pct``   — share of engine batches that ran on
      the device lane (device / (device + host)); 0 when nothing ran.
    * ``device_residency_hit_rate`` / ``device_pinned_bytes`` /
      ``device_h2d_saved_bytes`` / ``device_d2h_saved_bytes`` — HBM
      residency (serve/residency.py), present only when a serve
      process has configured it (set_residency_source).
    """
    reg = registry if registry is not None else _GLOBAL
    total_dev = 0
    for counter, gauge in _DEVICE_COUNTER_GAUGES:
        v = int(counters.get(counter, 0) or 0)
        total_dev += v
        reg.set_gauge(gauge, v)
    reg.set_gauge('device_engaged', 1.0 if total_dev else 0.0)
    host_batches = int(counters.get('nhostbatches', 0) or 0)
    dev_batches = int(counters.get('ndevicebatches', 0) or 0) + \
        int(counters.get('nstackedbatches', 0) or 0)
    denom = host_batches + dev_batches
    reg.set_gauge('device_residency_pct',
                  100.0 * dev_batches / denom if denom else 0.0)
    src = _RESIDENCY_SOURCE
    if src is not None:
        try:
            rs = src() or {}
        except Exception:
            rs = {}
        if rs.get('enabled'):
            reg.set_gauge('device_residency_hit_rate',
                          float(rs.get('hit_rate', 0.0) or 0.0))
            reg.set_gauge('device_pinned_bytes',
                          float(rs.get('bytes', 0) or 0))
            reg.set_gauge('device_h2d_saved_bytes',
                          float(rs.get('h2d_saved_bytes', 0) or 0))
            reg.set_gauge('device_d2h_saved_bytes',
                          float(rs.get('d2h_saved_bytes', 0) or 0))


# -- the process's memory (read at scrape) ----------------------------------

def _status_bytes():
    """{'VmRSS': bytes, 'VmHWM': bytes} of this process from the
    kernel's status file; empty where there is none (not Linux)."""
    out = {}
    try:
        with open('/proc/self/status') as f:
            for line in f:
                key, _, rest = line.partition(':')
                if key in ('VmRSS', 'VmHWM'):
                    out[key] = int(rest.split()[0]) * 1024
    except (OSError, ValueError, IndexError):
        return {}
    return out


def refresh_process_gauges(registry=None):
    """What the process costs its host, read when a scrape asks:

    * ``process_minor_faults_total`` — page faults served without IO
      since the process began (`getrusage`): a counter, set to the
      kernel's figure.  Its growth over a request is the pages the
      request touched for the first time, so it says whether the
      allocator policy (hostmem.hold_allocator) engages.
    * ``process_resident_bytes`` / ``process_peak_resident_bytes`` —
      `VmRSS` and `VmHWM` of /proc/self/status (Linux; absent
      elsewhere): what the policy costs.
    * ``allocator_policy_held{reason}`` — 1 with reason `applied`, 0
      with the reason it was skipped (`user_env`, `no_mallopt`,
      `refused`); absent in a process that `dn` did not start.
    """
    reg = registry if registry is not None else _GLOBAL
    faults = resource.getrusage(resource.RUSAGE_SELF).ru_minflt
    reg.counter('process_minor_faults_total').value = faults
    status = _status_bytes()
    if 'VmRSS' in status:
        reg.set_gauge('process_resident_bytes', status['VmRSS'])
    if 'VmHWM' in status:
        reg.set_gauge('process_peak_resident_bytes', status['VmHWM'])
    state = mod_hostmem.state()
    if state is not None:
        held, reason = state
        reg.set_gauge('allocator_policy_held', 1.0 if held else 0.0,
                      reason=reason)
