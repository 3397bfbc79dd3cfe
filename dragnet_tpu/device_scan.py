"""Device-resident scan: the whole per-batch pipeline in one jit.

The reference's hot loop ran predicate eval, date checks, bucketize and
the aggregation hash update per record in JS callbacks
(lib/krill-skinner-stream.js:29-52, lib/stream-scan.js:40-96; SURVEY
§3.1).  VectorScan (engine.py) vectorizes those stages on the host and
optionally offloads only the final segment-sum.  DeviceScan moves the
*entire* post-parse pipeline onto the accelerator:

    host:    C++ parse -> tagged columns -> one-pass batch stats ->
             upload (dtype-narrowed columns + small lookup tables;
             inputs the stats prove constant are synthesized on
             device instead of uploaded — see the sticky upload
             profile in _stage_device)
    device:  predicate table-gathers + numeric compares -> ternary
             and/or fold -> date-error & time-bounds masks -> p2/linear
             bucketize -> mixed-radix key fusion -> segment-sum (or
             one-hot MXU matmul) + first-occurrence segment-min
             -> (dense accumulator, first-index, stage counters)

and, critically, it does NOT synchronize per batch: each batch's
(dense, first, counters) triple is folded into a device-RESIDENT i64
accumulator inside the same jit (dense/counters add; first-occurrence
keys take a global min over batch_base + row), so a scan performs ONE
device->host fetch per program epoch rather than one per batch.
Emission order is preserved
exactly: the accumulated first-occurrence key (batch_index << row
ordering) sorts keys by submission batch then first row within the
batch, which is precisely the order the host engine inserts them.

Exactness contract: everything uploaded is integer (i32 columns, i32
weights) or a table gather, so device arithmetic is exact; any batch
that cannot be represented exactly (non-integral weights or values,
out-of-i32-range numbers, array-typed values in filter fields, ...)
falls back to the host engine for that batch, after flushing the device
buffer so insertion order survives.  Differential tests pin
DeviceScan == VectorScan == StreamScan.
"""

import collections
import re
import threading
import time

import numpy as np

from . import jsvalues as jsv
from . import log as mod_log
from . import query as mod_query
from . import vpipe as mod_vpipe
from . import watchdog
from .errors import DNError
from .engine import (VectorScan, NativeColumns, MAX_DENSE_SEGMENTS,
                     BATCH_SIZE, engine_mode)
from .ops.kernels import FALSE, TRUE, ERROR, I64MAX, sparse_fold
from .ops import get_jax, backend_ready, accelerator_likely
from .obs import metrics as obs_metrics

I32MIN = -(2 ** 31)
I32MAX = 2 ** 31 - 1

# numeric-row plans: outcome of <leaf op const> for an exact-int32 row
NUM_FALSE, NUM_TRUE, NUM_EQ, NUM_NE, NUM_LE, NUM_GE = range(6)

I16MIN = -(2 ** 15)
I16MAX = 2 ** 15 - 1

# dispatch barrier interval: how many async device batches may be in
# flight before the submitting thread waits for the accumulator (a
# block, not a fetch) — bounds pinned input-buffer memory.  Retained as
# a hard backstop; the pipeline depth below is the working bound.
SYNC_EVERY_BATCHES = 32


def pipeline_depth():
    """How many device batches may be in flight before dispatch blocks
    on the oldest (DN_DEVICE_PIPELINE_DEPTH, default 2): depth 2 is
    classic double buffering — the host stages and uploads batch N+1
    while the device folds batch N."""
    import os
    v = os.environ.get('DN_DEVICE_PIPELINE_DEPTH', '')
    if v:
        try:
            return max(1, int(v))
        except ValueError:
            pass
    return 2


def _acc_ready(acc):
    """True/False when every/any leaf of a device accumulator reports
    execution completeness via is_ready(); None when the backend's
    arrays don't expose it (then overlap cannot be observed)."""
    saw = None
    for leaf in acc if isinstance(acc, (tuple, list)) else (acc,):
        if isinstance(leaf, (tuple, list)):
            r = _acc_ready(leaf)
        else:
            fn = getattr(leaf, 'is_ready', None)
            r = fn() if callable(fn) else None
        if r is False:
            return False
        if r is not None:
            saw = True
    return saw


def _donate_kw():
    """jit kwargs donating the accumulator argument.  Donation lets XLA
    reuse the previous accumulator's buffers for the next one (no
    per-batch accumulator alloc while the pipeline keeps several
    batches in flight); the CPU backend ignores donation with a
    warning, so only ask for it on real devices."""
    jax, _ = get_jax()
    try:
        if jax.default_backend() == 'cpu':
            return {}
    except Exception:
        return {}
    return {'donate_argnums': 1}

# device-resident sparse set (high-cardinality mode): initial capacity,
# growth ceiling.  24 bytes/slot of HBM (a 1M-slot set is 24 MB —
# nothing next to device memory, and starting big avoids the mid-scan
# flush a capacity growth forces); the host-side pressure guard
# flushes + grows before a batch could overflow the set
SPARSE_CAP0 = 1 << 20
SPARSE_CAP_MAX = 1 << 23

LOG = mod_log.get('device-scan')


# a DeviceScan dropped with batches still folded in its device
# accumulator means those results never merged
_SCAN_LEAKS = watchdog.LeakCheck(
    'device scan(s) with unflushed accumulators; results may be '
    'incomplete',
    lambda s: s._acc is not None or bool(s._pending_flush))


def _rate_field(r):
    """Rates for log records: None when unknown, the float itself when
    non-finite (round(inf) raises)."""
    if r is None:
        return None
    try:
        import math
        return round(r) if math.isfinite(r) else r
    except (TypeError, ValueError):
        return r


# -- wedge armor: probe deadlines -------------------------------------------

def probe_deadline_s():
    """Deadline (seconds) for first-contact device operations —
    DN_DEVICE_PROBE_TIMEOUT, which every first device op of `dn` runs
    under.  The default is far above the ~15 s a process takes to
    reach a directly attached chip, so a slow cold start is never
    misclassified as a backend that will not answer."""
    import os
    try:
        return float(os.environ.get('DN_DEVICE_PROBE_TIMEOUT', '420'))
    except ValueError:
        return 420.0


def run_with_deadline(fn, seconds, what):
    """The probe deadline: run `fn` on a daemon thread and wait at
    most `seconds`.  Returns ('ok', result),
    ('error', exception), or ('timeout', None).  A wedged device
    plugin hangs the daemon thread, not the caller; the abandoned
    thread is leaked deliberately — there is no way to cancel a stuck
    device op, and the process-exit path does not join daemons."""
    box = []
    done = threading.Event()
    # the thread's stages and counters belong to the caller's request
    scope = mod_vpipe.current_scope()

    def _go():
        try:
            with mod_vpipe.adopt_scope(scope):
                box.append(('ok', fn()))
        except BaseException as e:
            box.append(('error', e))
        finally:
            done.set()

    t = threading.Thread(target=_go, daemon=True,
                         name='dn-deadline-%s' % what)
    t.start()
    done.wait(seconds)
    if not box:
        return ('timeout', None)
    return box[0]


# -- audition verdict cache --------------------------------------------------

def _audition_cache_file():
    """Path of the persisted audition-verdict cache, in the compile
    cache's directory (ops.cache_dir), or None when disabled
    (DN_AUDITION_CACHE=0)."""
    import os
    if os.environ.get('DN_AUDITION_CACHE', '1') == '0':
        return None
    from .ops import cache_dir
    return os.path.join(cache_dir(), 'dn_auditions.json')


def _audition_ttl_s():
    """How long a persisted verdict stays trusted (DN_AUDITION_TTL_S,
    default one day): rigs change — a chip is swapped, a host gets
    busier — so verdicts age out rather than pinning a stale routing
    decision forever."""
    import os
    try:
        return float(os.environ.get('DN_AUDITION_TTL_S', '86400'))
    except ValueError:
        return 86400.0


def _backend_id():
    """Identity of the initialized backend for audition-cache keys: a
    verdict measured against one chip (or transport) must not route a
    different one."""
    from .ops import get_jax
    try:
        jax, _ = get_jax()
        dev = jax.devices()[0]
        return '%s/%s' % (jax.default_backend(),
                          getattr(dev, 'device_kind', '') or '')
    except Exception:
        return 'unknown'


def audition_cache_get(key):
    """The cached verdict for `key`: True (device won), False (device
    lost), or None (no fresh entry).  All failures read as None — the
    cache only ever skips work, never adds requirements."""
    path = _audition_cache_file()
    if path is None:
        return None
    import json
    try:
        with open(path) as f:
            data = json.load(f)
        ent = data.get(key)
        if not isinstance(ent, dict) or 'won' not in ent:
            return None
        # wall clock ON PURPOSE (clock-audit, PR 7): `ts` persists
        # across processes and reboots, where a monotonic reading is
        # meaningless; an NTP step only widens/narrows the TTL once
        if time.time() - float(ent.get('ts', 0)) > _audition_ttl_s():
            return None
        return bool(ent['won'])
    except Exception:
        return None


def audition_cache_put(key, won, device_rate=None, host_rate=None):
    """Persist an audition (or probation-crossover) verdict.  Expired
    entries are pruned on write; the file is swapped atomically
    (tmp+rename) so concurrent CLI invocations never read torn JSON,
    and the read-modify-write runs under a `.lock` sidecar flock so
    two concurrent writers (`dn serve` pre-warm and a `dn build`, say)
    cannot silently drop each other's verdicts — the same lost-update
    class the integrity catalog already guards against.  Best-effort:
    an unwritable cache directory (or a flock-less filesystem) never
    blocks the in-process decision that already happened."""
    path = _audition_cache_file()
    if path is None:
        return
    import json
    import os
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        lockf = None
        try:
            lockf = open(path + '.lock', 'a')
            import fcntl
            fcntl.flock(lockf.fileno(), fcntl.LOCK_EX)
        except Exception:
            pass        # best-effort on filesystems without flock
        try:
            try:
                with open(path) as f:
                    data = json.load(f)
                if not isinstance(data, dict):
                    data = {}
            except Exception:
                data = {}
            now = time.time()
            ttl = _audition_ttl_s()
            data = {k: v for k, v in data.items()
                    if isinstance(v, dict)
                    and now - float(v.get('ts', 0)) <= ttl}
            data[key] = {'won': bool(won), 'ts': now,
                         'device_rate': _rate_field(device_rate),
                         'host_rate': _rate_field(host_rate)}
            tmp = '%s.%d' % (path, os.getpid())
            try:
                with open(tmp, 'w') as f:
                    json.dump(data, f)
                os.rename(tmp, path)
            except Exception:
                # crash hygiene (the index sinks' tmp contract): a
                # failed write/rename must not strand litter
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
        finally:
            if lockf is not None:
                lockf.close()       # releases the flock
    except Exception:
        pass


def _audition_entries_raw():
    """The fresh (unexpired) entries of the persisted audition cache,
    or {}.  All failures read as empty — reporting helpers only."""
    path = _audition_cache_file()
    if path is None:
        return None, {}
    import json
    try:
        with open(path) as f:
            data = json.load(f)
        if not isinstance(data, dict):
            return path, {}
    except Exception:
        return path, {}
    now = time.time()
    ttl = _audition_ttl_s()
    return path, {k: v for k, v in data.items()
                  if isinstance(v, dict) and 'won' in v
                  and now - float(v.get('ts', 0)) <= ttl}


def audition_cache_entries():
    """(path, fresh entries, fresh wins) of the persisted audition
    cache — `dn serve --validate` and the serve pre-warm doc report
    it; (None, 0, 0) when disabled."""
    path, data = _audition_entries_raw()
    if path is None:
        return None, 0, 0
    wins = sum(1 for v in data.values() if v.get('won'))
    return path, len(data), wins


def audition_cache_shape_hint(shape):
    """Whether ANY backend ever auditioned this query shape: True when
    some fresh entry for `shape` won, False when entries exist and all
    lost, None when the shape was never auditioned.  A HEURISTIC only
    — the full shape+backend key still gates the actual takeover (a
    verdict measured on one chip must not route another); this hint
    only decides how eagerly auto mode starts probing, which is safe
    on a mismatch because the real audition still runs."""
    _, data = _audition_entries_raw()
    prefix = shape + '@'
    verdicts = [bool(v.get('won')) for k, v in data.items()
                if k.startswith(prefix)]
    if not verdicts:
        return None
    return True if any(verdicts) else False

# traced scan programs are shared across DeviceScan instances (a CLI
# `dn scan` and a server's repeat requests would otherwise re-trace
# identical programs per scan); keyed by the full static structure of
# the program (see _program_key)
_PROGRAM_CACHE = {}
_ACC_INIT_CACHE = {}

# what a scan's batch program is: acc_init makes its empty accumulator,
# fold is the UNJITTED (args, acc, use_pallas) -> acc body that
# DeviceScanStack composes, one a scan, into the one jit of a batch
_Programs = collections.namedtuple('_Programs', 'acc_init fold')

# the jitted batch programs (DeviceScanStack._stacked_program), keyed
# by the tuple of member program keys + pallas flags: a lone scan's is
# a tuple of one
_STACK_CACHE = {}


def _pow2(x):
    p = 8
    while p < x:
        p <<= 1
    return p


def _pad_pow2(arr):
    """Zero-pad a 1-D table to a power-of-two length so device-side
    shapes (= jit cache keys) change O(log) times as it grows."""
    pw = _pow2(len(arr))
    if len(arr) < pw:
        arr = np.concatenate(
            [arr, np.zeros(pw - len(arr), dtype=arr.dtype)])
    return arr


def numeric_leaf_plan(op, const):
    """(mode, threshold) evaluating `value <op> const` for values that
    are exact int32 numbers, with JS coercion semantics for const.
    Returns None when no exact integer plan exists (then any batch with
    numeric rows in that field falls back to the host engine)."""
    import math
    if isinstance(const, bool):
        cf = 1.0 if const else 0.0
    elif isinstance(const, (int, float)):
        cf = jsv.as_float(const)
    elif isinstance(const, str):
        # number-vs-string compares coerce the string in JS (both for
        # loose == and for relational operators)
        cf = jsv.to_number(const)
    else:
        return None
    if cf != cf:  # NaN: == false, != true, relational false
        if op == 'ne':
            return (NUM_TRUE, 0)
        return (NUM_FALSE, 0)
    if op in ('eq', 'ne'):
        if math.isinf(cf) or cf != math.floor(cf) or \
                not (I32MIN <= cf <= I32MAX):
            return ((NUM_FALSE, 0) if op == 'eq' else (NUM_TRUE, 0))
        t = int(cf)
        return ((NUM_EQ, t) if op == 'eq' else (NUM_NE, t))
    if math.isinf(cf):
        big = cf > 0
        if op in ('lt', 'le'):
            return (NUM_TRUE, 0) if big else (NUM_FALSE, 0)
        return (NUM_FALSE, 0) if big else (NUM_TRUE, 0)
    f = math.floor(cf)
    if op == 'lt':
        t = int(f) - 1 if cf == f else int(f)   # v < c  <=>  v <= t
        mode = NUM_LE
    elif op == 'le':
        t = int(f)                              # v <= floor(c)
        mode = NUM_LE
    elif op == 'gt':
        t = int(f) + 1                          # v > c  <=>  v >= t
        mode = NUM_GE
    else:  # ge
        t = int(f) if cf == f else int(f) + 1   # v >= ceil(c)
        mode = NUM_GE
    if mode == NUM_LE:
        if t >= I32MAX:
            return (NUM_TRUE, 0)
        if t < I32MIN:
            return (NUM_FALSE, 0)
    else:
        if t <= I32MIN:
            return (NUM_TRUE, 0)
        if t > I32MAX:
            return (NUM_FALSE, 0)
    return (mode, t)


def _time_shape(bounds):
    """A scan's time bounds as its device program takes them:
    ((lo_mode, hi_mode), (lo, hi)).  The modes are the program's static
    structure (and its cache key's part); the values are ARGUMENTS of
    the program ('tb_lo' / 'tb_hi' under the scan's prefix), so a
    resident server that builds one window after another (`dn build
    --after D --before D+1`, day after day) runs one compiled program,
    not one a window.

    A mode is None (no test), 'arg' (compare with the argument) or
    'never' (nothing passes).  Bounds may lie outside int32 (a
    far-future timeBefore as "unbounded" is a plausible idiom;
    jnp.int32(2208988800) raises on numpy>=2).  Uploaded ts values are
    exact-i32 (the eligibility check falls back otherwise), so an
    out-of-range bound resolves statically: vacuous or
    nothing-passes."""
    lo, hi = bounds
    lo_mode = hi_mode = None
    if lo is not None:
        lo = int(lo)
        lo_mode = 'never' if lo > I32MAX else \
            'arg' if lo > I32MIN else None
    if hi is not None:
        hi = int(hi)
        hi_mode = 'never' if hi <= I32MIN else \
            'arg' if hi <= I32MAX else None
    return (lo_mode, hi_mode), (lo if lo_mode == 'arg' else 0,
                                hi if hi_mode == 'arg' else 0)


def _clamp_to_bounds(minmax, time_args):
    """(min, max) of a timestamp column cut to the bounds that are the
    program's arguments; None where nothing is left."""
    (lo_mode, hi_mode), (lo, hi) = time_args
    mn, mx = minmax
    if lo_mode == 'arg':
        mn = max(mn, lo)
    if hi_mode == 'arg':
        mx = min(mx, hi - 1)
    return (mn, mx) if mn <= mx else None


class _KeyPlan(object):
    """Per-breakdown device plan + its growing window/capacity state."""

    __slots__ = ('kind', 'name', 'field', 'step', 'lo', 'cap',
                 'host_translate', 'column', 'window_set')

    def __init__(self, kind, name, field=None, step=None, column=None):
        self.kind = kind          # 'str' | 'p2' | 'lin'
        self.name = name
        self.field = field or name
        self.step = step
        self.column = column      # engine StringColumn for 'str'
        self.lo = 0
        self.cap = 8 if kind != 'p2' else 32
        self.host_translate = False
        self.window_set = False   # 'lin' window anchored to data yet?

    def sig(self):
        return (self.kind, self.lo, self.cap, self.step,
                self.host_translate)


class DeviceScan(VectorScan):
    """VectorScan whose eligible batches execute fully on the device.

    ESCALATE_RECORDS: batches are processed by the host engine until
    this many records have been seen (device dispatch + compile are not
    worth paying for CLI-sized inputs); 0 means device-first.

    REQUIRE_ACCELERATOR: when True the device path additionally
    requires a non-CPU backend (auto mode); forced mode (DN_ENGINE=jax)
    runs on whatever backend jax has, including the CPU test mesh.

    PROBATION_RECORDS: when nonzero, the first device batch (jit
    compile) is flushed, then this many device-processed records are
    timed and compared against the host rate observed before
    escalation; if the device is slower (e.g. a chip behind a slow
    transport, or a query shape XLA handles badly), the scan
    de-escalates back to the host engine permanently.  The backend
    probe AND this crossover check only ever run past
    ESCALATE_RECORDS, so small scans never touch the device plugin."""

    ESCALATE_RECORDS = 0
    REQUIRE_ACCELERATOR = False
    PROBATION_RECORDS = 0
    PROBATION_SECONDS = 0.25
    # whether the datasource should run the MT host executor and let
    # this scanner take the stream over mid-flight (auto mode only;
    # forced mode owns the stream from the first batch)
    AUTO_STREAM = False

    def __init__(self, query, time_field, pipeline, ds_filter=None):
        VectorScan.__init__(self, query, time_field, pipeline,
                            ds_filter=ds_filter)
        _SCAN_LEAKS.track(self)
        # input-key namespace: '' standalone; a DeviceScanStack of
        # several scans assigns 'm<i>_' so per-scan inputs (leaf
        # tables, translate tables, synth columns, base) coexist in
        # one merged inputs dict while parser-derived columns stay
        # shared across metrics
        self._pfx = ''
        # the time bounds as the program takes them: (modes, values)
        self._time_args = _time_shape(self.time_bounds) \
            if self.time_bounds is not None else None
        self._alone = None        # the stack of this scan alone
        self._records_seen = 0
        self._backend_ok = None
        self._host_records = 0
        self._host_rate = None
        self._t0 = None
        self._probation = None    # None=not started, tuple=timing, False=done
        self._disabled = False
        self._escalated = False
        self._probe_thread = None
        self._probe_result = None
        self.probe_status = None  # 'ok'/'refused'/'error'/'timeout'
        self._progress = None     # (bytes_done, bytes_total) from stream
        self._shadow_ctx = None   # set by enable_shadow (MT path)
        self._shadow = None
        self._sticky = None       # upload-profile state (_stage_device)
        self._sparse_cap = SPARSE_CAP0
        self._sparse_ub = 0       # unique-count upper bound this epoch
        self._pending_flush = []  # async-prefetched epochs (see
        self._prefetched = False  # _prefetch_flush)
        self._plans = None            # built lazily from the query
        self._epoch_sig = None
        self._programs = None
        self._kernels_logged = set()  # (program key, pallas) debug-logged
        self._acc = None              # device-resident (dense, first, cvec)
        self._acc_meta = None         # epoch ('caps', 'cols', 'ns')
        self._acc_batch = 0           # batches folded into the acc
        self._pipe = collections.deque()  # in-flight completion tokens
        self._leaf_list = []          # [(key, Leaf)] in stable order
        self._leaf_tables = {}        # leaf idx -> (host_len, device arr)
        self._ctabs = {}              # leaf idx -> device i8[16]
        self._trans_dev = {}          # plan name -> (host_len, device arr)
        self._num_plans = []
        self._counter_spec = None
        self._synth_names = None
        self._build_static()

    # -- static (per-query) plan -------------------------------------------

    def _build_static(self):
        """Decide, once, whether this query can have a device program
        at all, and precompute everything that doesn't depend on data.
        Deliberately touches NO jax state: backend availability is
        probed lazily on the first batch past ESCALATE_RECORDS (the
        first jax.devices() takes seconds on a chip, a price small
        host-only scans must not pay)."""
        synth_names = set(s['name'] for s in self.synthetic)
        plans = []
        for b in self.query.qc_breakdowns:
            name = b['name']
            if name in self.query.qc_bucketizers:
                bz = self.query.qc_bucketizers[name]
                if isinstance(bz, mod_query.P2Bucketizer):
                    kind, step = 'p2', None
                else:
                    step = bz.step
                    if not (isinstance(step, int) and
                            not isinstance(step, bool) and
                            1 <= step <= I32MAX):
                        self._disabled = True
                        return
                    kind = 'lin'
                if name in synth_names:
                    field = next(s['field'] for s in self.synthetic
                                 if s['name'] == name)
                    plans.append(_KeyPlan(kind, name, field='\0synth:' +
                                          name, step=step))
                else:
                    plans.append(_KeyPlan(kind, name, step=step))
            else:
                if name in synth_names:
                    # synthetic (date) field used as a plain string key:
                    # host path stringifies parsed seconds; rare — host
                    self._disabled = True
                    return
                plans.append(_KeyPlan('str', name,
                                      column=self.string_columns[name]))
        self._plans = plans
        self._synth_names = synth_names

        for pred in (self.ds_pred, self.user_pred):
            if pred is None:
                continue
            for key, leaf in pred.leaves.items():
                if key not in [k for k, _ in self._leaf_list]:
                    self._leaf_list.append((key, leaf))
        for _, leaf in self._leaf_list:
            self._num_plans.append(numeric_leaf_plan(leaf.op, leaf.const))

        # counters, in the exact order the host engine bumps them
        # (always=False counters are only bumped when nonzero, matching
        # the host's conditional bumps)
        spec = []
        if self.ds_pred is not None:
            s = self.ds_stage
            spec += [(s, 'ninputs', True), (s, 'nfailedeval', False),
                     (s, 'nfilteredout', False), (s, 'noutputs', True)]
        if self.user_pred is not None:
            s = self.user_stage
            spec += [(s, 'ninputs', True), (s, 'nfailedeval', False),
                     (s, 'nfilteredout', False), (s, 'noutputs', True)]
        if self.synthetic:
            s = self.synth_stage
            spec += [(s, 'ninputs', True), (s, 'undef', False),
                     (s, 'baddate', False), (s, 'noutputs', True)]
        if self.time_bounds is not None:
            s = self.time_stage
            spec += [(s, 'ninputs', True), (s, 'nfilteredout', False),
                     (s, 'noutputs', True)]
        spec.append((self.aggr.stage, 'ninputs', True))
        spec.append((self.aggr.stage, 'nnonnumeric', False))
        # records aggregated through the unbounded-cardinality path:
        # the host engine bumps this in _sparse_merge; the device
        # sparse program emits the same value (0 in dense mode).  The
        # counts can differ from a pure-host run only when the dense
        # budget decision itself straddles MAX_DENSE_SEGMENTS between
        # the host's per-batch radices and the device's pow2 caps.
        spec.append((self.aggr.stage, 'nspillrecords', False))
        self._counter_spec = spec

    # -- per-batch entry ---------------------------------------------------

    def _process(self, provider, weights, alive=None):
        """A lone scan is a stack of one: the batch goes to the device
        as a build's does, and a batch the device declines to the host
        engine after a flush, so that insertion order survives."""
        if self._alone is None:
            self._alone = DeviceScanStack([self])
        if self._alone.try_device(provider, weights, alive):
            return
        n = provider.n
        self._records_seen += n
        self._flush()
        self._host_records += n
        VectorScan._process(self, provider, weights, alive=alive)

    # once the stream is this far along, the accumulator-so-far is
    # compacted and its fetch issued ASYNC, overlapping the
    # device->host leg with the remaining parse/compute instead
    # of serializing it after the last batch
    PREFETCH_PROGRESS = 0.7

    def set_progress(self, bytes_done, bytes_total):
        """Stream-progress hook (the file datasource reports bytes
        consumed vs total): lets auto mode estimate remaining work
        before committing to a device switch, and triggers the one-time
        async flush prefetch late in the stream."""
        self._progress = (bytes_done, bytes_total)
        if not self._prefetched and self._acc is not None and \
                bytes_total > 0 and \
                bytes_done >= self.PREFETCH_PROGRESS * bytes_total:
            self._prefetched = True
            self._prefetch_flush()

    def _prefetch_flush(self):
        """Compact the current epoch on device and issue its fetch
        asynchronously; accumulation continues in a fresh accumulator
        and the result is drained (in order) at the next _flush."""
        acc = self._acc
        meta = self._acc_meta
        nbatches = self._acc_batch
        if acc is None:
            return
        try:
            sparse = bool(meta.get('sparse_cap'))
            if sparse:
                acc, live = self._merge_sparse(acc, meta, self._sparse_ub)
            with obs_metrics.leaf_stage('scan.dispatch'):
                if sparse:
                    cap = int(acc[0].shape[0])
                    k = min(cap, _pow2(max(live, 1)))
                    out = _sparse_program(cap, k,
                                          tuple(meta['caps']))(acc)
                elif meta['cols'] and \
                        meta['ns'] >= self.COMPACT_MIN_SEGMENTS:
                    k = min(int(acc[0].shape[0]), self.COMPACT_K)
                    out = _compact_program(int(acc[0].shape[0]), k)(acc)
                else:
                    return    # small fetch: nothing worth overlapping
                _issue_async(out)
        except Exception:
            LOG.debug('flush prefetch failed; staying synchronous')
            return
        # keep the acc referenced: a sparse prefetch sized by the ub
        # bound never refetches, but the dense speculative width can
        self._pending_flush.append((meta, nbatches, acc, out))
        self._acc = None
        self._acc_meta = None
        self._acc_batch = 0
        self._sparse_ub = 0
        self._pipe.clear()

    def _drain_pending(self):
        pending = self._pending_flush
        self._pending_flush = []
        for meta, nbatches, acc, out in pending:
            if nbatches:
                self.aggr.stage.bump_hidden('ndevicebatches', nbatches)
            cap = meta.get('sparse_cap')
            if cap:
                cols, w32, wof, cvec, stats = out
                compacted = True
                with obs_metrics.leaf_stage('scan.fetch'):
                    st = np.asarray(stats)
                    n = int(st[0])
                    k = int(cols[0].shape[0])
                    if n > k or bool(np.asarray(wof)):
                        # ub bound failed or i32 weight overflow:
                        # refetch
                        fetched = _sparse_fetch(acc, _pow2(max(n, 1)),
                                                meta['caps'])
                        if fetched is None:  # device fetch error: full
                            fetched = _sparse_full_result(
                                acc, meta['caps'])
                            compacted = False
                        cols_np, wsumf, cvec_np, st = fetched
                    else:
                        cols_np = [c[:n].astype(np.int64)
                                   for c in _fetch_arrays(cols)]
                        wsumf = np.asarray(w32)[:n].astype(np.float64)
                        cvec_np = np.asarray(cvec)
                if int(st[1]):
                    raise RuntimeError(
                        'device sparse aggregation overflowed its '
                        'resident set (cap=%d)' % cap)
                self._count_sparse_set(meta, int(st[0]))
                if compacted:
                    self.aggr.stage.bump_hidden('ncompactflush', 1)
                with obs_metrics.leaf_stage('scan.emit'):
                    self._emit_counters(cvec_np)
                    self._emit_cols(meta, cols_np, wsumf)
            else:
                cnt, segs, dense, cvec = out
                compacted = True
                with obs_metrics.leaf_stage('scan.fetch'):
                    n = int(np.asarray(cnt))
                    k = int(segs.shape[0])
                    if n > k:
                        fetched = _compact_fetch(acc, _pow2(n))
                        if fetched is None:  # device fetch error: full
                            fetched = _dense_full_result(acc)
                            compacted = False
                        segs_np, wsumf, cvec_np = fetched
                    else:
                        segs_np = np.asarray(segs)[:n].astype(np.int64)
                        wsumf = np.asarray(dense)[:n].astype(np.float64)
                        cvec_np = np.asarray(cvec)
                if compacted:
                    self.aggr.stage.bump_hidden('ncompactflush', 1)
                with obs_metrics.leaf_stage('scan.emit'):
                    self._emit_counters(cvec_np)
                    self._decode_emit(meta, segs_np, wsumf)

    def _emit_counters(self, cvec):
        for (stage, name, always), v in zip(self._counter_spec, cvec):
            v = int(v)
            if always or v:
                stage.bump(name, v)

    def _decode_emit(self, meta, segs, wsum):
        """Decode fused segment codes -> global per-column codes and
        emit (shared by the sync flush paths and the async drain)."""
        if len(segs) == 0:
            return
        self._emit_cols(meta, _decode_fused(segs, meta['caps']), wsum)

    def _emit_cols(self, meta, col_codes, wsum):
        """Per-column codes -> global codes (window offsets applied)
        -> the shared emit path."""
        if len(wsum) == 0:
            return
        gcols = []
        for (kind, lo), cc in zip(meta['cols'], col_codes):
            if kind == 'str':
                gcols.append(np.asarray(cc, dtype=np.int64))
            else:
                gcols.append(np.asarray(cc, dtype=np.int64) + lo)
        self._emit_unique(gcols, wsum)

    def note_external_batch(self, n):
        """A batch of n records was processed outside this scanner (the
        multithreaded host executor); counts toward escalation
        thresholds and the observed host rate."""
        if self._t0 is None:
            self._t0 = time.monotonic()
        self._records_seen += n
        self._host_records += n

    def take_over_now(self):
        """Whether the device path should take over the batch stream
        from the multithreaded host executor (auto mode integration;
        see datasource_file._scan_native)."""
        return (not self._disabled and
                self._records_seen > self._escalate_records() and
                self._engage_device())

    def _escalate_records(self):
        """The record threshold before the device path is considered;
        AutoDeviceScan lowers it when a persisted audition verdict
        already proved this query shape wins on a device."""
        return self.ESCALATE_RECORDS

    def _engage_device(self):
        """Forced mode: probe the backend synchronously on the first
        candidate batch (the caller asked for the device; blocking on
        its initialization is expected)."""
        if self._backend_ok is None and not self._probe_backend():
            return False
        return self._backend_ok

    def _probe_ok(self):
        """Pure backend-eligibility check (initializes the backend, no
        scan-state mutation) — the single definition shared by the
        synchronous (forced) and background (auto) probes."""
        from . import faults as mod_faults
        mod_faults.fire('device.probe')    # chaos: probe failure ->
        ok = backend_ready()               # clean host fallback
        if ok and self.REQUIRE_ACCELERATOR:
            from .ops import is_accelerator
            ok = is_accelerator()
        return bool(ok)

    def _probe_backend(self):
        """One-time lazy backend probe (first batch past the escalation
        threshold).  False permanently disables the device path.

        The probe — the scan's first device op — runs under a deadline
        (DN_DEVICE_PROBE_TIMEOUT), so a backend that never answers
        cannot hang `dn scan`.  Under DN_ENGINE=jax a probe that times
        out, errors or is refused FAILS the scan with the reason; in
        the other modes the scan finishes on the host engine, which
        computes identical results, and the reason survives in
        `probe_status` (and the probe-stage span)."""
        with obs_metrics.timed_stage('device_scan.probe') as sp:
            status, ok = run_with_deadline(self._probe_ok,
                                           probe_deadline_s(),
                                           'backend-probe')
            sp.set(status=status)
        if status == 'timeout':
            why = 'device backend unresponsive (no answer within ' \
                '%.0fs)' % probe_deadline_s()
            ok = False
        elif status == 'error':
            why = 'device backend failed to initialize: %r' % (ok,)
            ok = False
        elif not ok:
            why = 'no usable device backend'
        if not ok and engine_mode() == 'jax':
            # the user forced the device lane: failing is the answer,
            # not a host run that looks like a device run
            raise DNError('DN_ENGINE=jax: ' + why)
        if status == 'timeout':
            import sys
            sys.stderr.write('dn: warning: %s; falling back to the '
                             'host engine\n' % why)
        if ok:
            self.probe_status = 'ok'
        else:
            self.probe_status = status if status != 'ok' else 'refused'
        LOG.debug('backend probe', ok=ok, status=status,
                  records_seen=self._records_seen)
        self._backend_ok = ok
        if not ok:
            self._disabled = True
        return ok

    def _sync_device(self):
        """Block until every batch folded so far has executed (without
        fetching or emitting anything) — the timing barrier for
        probation measurements."""
        if self._acc is not None:
            jax, _ = get_jax()
            with obs_metrics.leaf_stage('scan.device_wait'):
                jax.block_until_ready(self._acc)

    def _after_device_batch(self, n):
        """Crossover probation: time a window of device batches against
        the host rate observed pre-escalation and de-escalate if the
        device loses.  The window is bounded by PROBATION_RECORDS *or*
        PROBATION_SECONDS, whichever trips first — a record-count-only
        window on a slow device path spends most of a scan measuring it
        (the round-3 scale cliff)."""
        if not self.PROBATION_RECORDS or self._probation is False:
            return
        now = time.monotonic()
        if self._probation is None:
            # first device batch: pin the host rate, sync out the jit
            # compile, and start the probation clock after it
            if self._host_records and now > self._t0:
                self._host_rate = self._host_records / (now - self._t0)
            self._sync_device()
            self._probation = (time.monotonic(), 0)
            return
        start, seen = self._probation
        seen += n
        if seen < self.PROBATION_RECORDS and \
                now - start < self.PROBATION_SECONDS:
            self._probation = (start, seen)
            return
        self._sync_device()
        elapsed = time.monotonic() - start
        rate = seen / elapsed if elapsed > 0 else float('inf')
        if self._host_rate is not None and rate < self._host_rate:
            self._disabled = True
            LOG.info('device de-escalated (lost probation)',
                     device_rate=_rate_field(rate),
                     host_rate=_rate_field(self._host_rate),
                     window_records=seen,
                     window_seconds=round(elapsed, 3))
            # a measured crossover loss is a verdict too: persist it so
            # the next identically-shaped run skips the whole detour
            # (auto mode overrides; forced mode has no probation)
            self._record_crossover(False, rate)
        else:
            LOG.debug('device passed probation',
                      device_rate=_rate_field(rate),
                      host_rate=_rate_field(self._host_rate))
        self._probation = False

    def _record_crossover(self, won, rate):
        """Hook: a probation-window crossover measurement concluded.
        The base scan keeps no persistent state; AutoDeviceScan
        persists the verdict in the audition cache."""

    def finish(self):
        sp = getattr(self, '_shadow', None)
        if sp is not None:
            sp.close()          # end of stream: release audition state
        self._alone = None      # it holds this scan: let both go
        self._flush()
        self._defer_final()
        return self.aggr

    # -- eligibility + input assembly --------------------------------------

    def _stage_device(self, provider, weights, alive, inputs):
        """Eligibility checks + device-input assembly for one batch,
        writing into the caller's `inputs` dict (shared across scans
        under DeviceScanStack: parser-derived columns use unprefixed
        keys so N metric scans upload them once; per-scan inputs carry
        self._pfx).  Returns the staged execution parameters
        (pn, profile, caps, ns, total_w) or None when this batch must
        take the host path.  Commits plan-state (windows/caps) and
        flushes on epoch flips as side effects — safe even if a sibling
        scan later fails staging, since the host path computes the same
        results regardless of plan state."""
        mn = provider.mn
        n = provider.n
        pfx = self._pfx

        w = np.asarray(weights, dtype=np.float64)
        if len(w) != n or not np.all(np.isfinite(w)) or \
                not np.all(w == np.floor(w)):
            return None
        total_w = float(np.abs(w).sum())
        if total_w >= 2 ** 31 or (len(w) and
                                  (w.min() < I32MIN or w.max() > I32MAX)):
            return None

        # Upload profile: static per-program flags that let the body
        # synthesize constant inputs on device instead of uploading
        # them — the H2D bytes per record are the device path's cost
        # floor when the host->device link is the narrow part.
        # Flags are STICKY toward the most general variant (an
        # observation can only widen them), so a scan recompiles at
        # most once per flag even when the data is heterogeneous —
        # a per-batch profile would retrace inside the probation /
        # audition timing windows and make the device look slow.
        sk = self._sticky
        if sk is None:
            sk = self._sticky = {'w1': True, 'gen_alive': True,
                                 'filter': {}, 'kvalid': {}}
        sk['w1'] = w1 = sk['w1'] and bool(np.all(w == 1.0))
        sk['gen_alive'] = gen_alive = sk['gen_alive'] and alive is None
        if gen_alive:
            inputs['nvalid'] = np.int32(n)
        else:
            inputs['alive'] = np.ones(n, dtype=bool) if alive is None \
                else np.asarray(alive, dtype=bool)
        if not w1:
            inputs['weights'] = w.astype(np.int32)

        # one-pass native batch statistics make the eligibility checks
        # O(1) numpy work per field (snapshot providers — the shadow
        # audition, MT workers — lack them and take the numpy path)
        src = provider.parser

        # per-batch memo on the SHARED provider: under DeviceScanStack
        # N metric scans stage against one provider, and each parser
        # accessor materializes a fresh array (ctypes copy) — fields
        # read by several metrics must pay that once, not N times
        memo = provider.__dict__.setdefault('_stage_memo', {})

        def _memo1(kind, f, fn):
            key = (kind, f)
            v = memo.get(key)
            if v is None:
                v = fn(f)
                memo[key] = v
            return v

        def _stats(f):
            fn = getattr(src, 'field_stats', None)
            return _memo1('stats', f, fn) if fn is not None else None

        def _widen(table, key, has_str, has_num, all_num):
            cur = table.get(key)
            if cur is None:
                cur = table[key] = [has_str, has_num, all_num]
            else:
                cur[0] = cur[0] or has_str
                cur[1] = cur[1] or has_num
                cur[2] = cur[2] and all_num
            return cur

        # dtype narrowing: per-record int columns upload as the
        # smallest dtype their observed range fits (dictionary codes
        # are tiny; values like latencies/status codes fit i16), with
        # the same sticky widening discipline — saves 2-4x of the H2D
        # bytes the profile didn't already eliminate.  The device
        # program upcasts to i32 after the transfer.
        dtypes = sk.setdefault('dtypes', {})

        def _narrow(key, arr, lo, hi):
            if 0 <= lo and hi <= 255:
                need = 1
            elif I16MIN <= lo and hi <= I16MAX:
                need = 2
            else:
                need = 3
            level = max(dtypes.get(key, need), need)
            dtypes[key] = level
            if level == 1:
                return arr.astype(np.uint8)
            if level == 2:
                return arr.astype(np.int16)
            return arr if arr.dtype == np.int32 \
                else arr.astype(np.int32)

        # filter fields: tags + string codes + exact-i32 numeric
        # values, each uploaded only when this scan has seen rows of
        # that kind in the field
        filter_profile = []
        for f in self.filter_fields:
            st = _stats(f)
            if st is not None:
                narr, i32ok, nmn_f, nmx_f, nnum, nstr = st
                if narr:
                    return None
                if nnum and not i32ok:
                    return None
                has_str, has_num, all_num = _widen(
                    sk['filter'], f, nstr > 0, nnum > 0, nnum == n)
                tags = _memo1('tags', f, src.tags_col) \
                    if not all_num else None
                strcodes = _memo1('str', f, src.strcodes_col) \
                    if has_str else None
                iv = _memo1('num', f, src.nums_i32) if has_num else None
                nrange = (int(nmn_f), int(nmx_f)) if nnum else (0, 0)
            else:
                tags, nums, strcodes = provider._field(f)
                if (tags == mn.TAG_ARRAY).any():
                    return None
                m = (tags == mn.TAG_INT) | (tags == mn.TAG_NUMBER)
                obs_num = bool(m.any())
                if obs_num:
                    nm = nums[m]
                    if not (np.all(np.isfinite(nm)) and
                            np.all(nm == np.floor(nm)) and
                            nm.min() >= I32MIN and nm.max() <= I32MAX):
                        return None
                has_str, has_num, all_num = _widen(
                    sk['filter'], f, bool((tags == mn.TAG_STRING)
                                          .any()), obs_num,
                    bool(m.all()))
                iv = None
                nrange = (0, 0)
                if has_num:
                    iv = np.zeros(n, dtype=np.int32)
                    if obs_num:
                        iv[m] = nums[m].astype(np.int64).astype(
                            np.int32)
                        nrange = (int(nums[m].min()),
                                  int(nums[m].max()))
            filter_profile.append((f, has_str, has_num, all_num))
            if not all_num:
                inputs['tags_' + f] = tags.astype(np.uint8, copy=False)
            if has_str and ('str_' + f) not in inputs:
                # -1 marks non-string rows (masked on device; any
                # index works), so the floor of the range is -1
                dlen = len(src.dictionary(f))
                inputs['str_' + f] = _narrow('str_' + f, strcodes,
                                             -1, dlen - 1)
            if has_num and ('num_' + f) not in inputs:
                inputs['num_' + f] = _narrow('num_' + f, iv, *nrange)

        # synthetic date fields: combined first-error + needed ts columns
        synth_vals = {}
        use_dstats = False
        if self.synthetic:
            dstats_fn = getattr(src, 'date_stats', None)
            first_ds = _memo1('dstats', self.synthetic[0]['field'],
                              dstats_fn) \
                if dstats_fn is not None else None
            use_dstats = first_ds is not None
            errs = None
            if use_dstats:
                # SHARED keys: under dstats the ts column is a pure
                # function of its source field ('tsf_<field>') and the
                # error chain of the ordered field list, so stacked
                # sibling scans reading the same date fields reuse one
                # upload instead of N prefixed copies
                terr_key = 'terr_' + '|'.join(
                    fc['field'] for fc in self.synthetic)
                for i, fc in enumerate(self.synthetic):
                    all_i32, nok = first_ds if i == 0 \
                        else _memo1('dstats', fc['field'], dstats_fn)
                    if nok and not all_i32:
                        return None
                    synth_vals[fc['name']] = _memo1(
                        'date', fc['field'], src.date_i32)
                errs = inputs.get(terr_key)
                if errs is not None and len(errs) != n:
                    # a sibling scan staged (and padded) it already;
                    # host-side uses need the unpadded batch view
                    errs = errs[:n]
                if errs is None:
                    for fc in self.synthetic:
                        err = _memo1('derr', fc['field'], src.date_err)
                        errs = err if errs is None else \
                            np.where(errs == 0, err, errs)
            else:
                terr_key = pfx + 'terr'
                for fc in self.synthetic:
                    vals, err = provider.date_column(fc['field'])
                    synth_vals[fc['name']] = vals
                    errs = err if errs is None else \
                        np.where(errs == 0, err, errs)
            ok = errs == 0
            sfield = {s['name']: s['field'] for s in self.synthetic}
            need = set()
            if self.time_bounds is not None:
                need.add('dn_ts')
                for mode, name, v in zip(self._time_args[0],
                                         ('tb_lo', 'tb_hi'),
                                         self._time_args[1]):
                    if mode == 'arg':
                        inputs[pfx + name] = np.int32(v)
            for p in self._plans:
                if p.field.startswith('\0synth:'):
                    need.add(p.field[len('\0synth:'):])
            for name in need:
                v = synth_vals[name]
                if use_dstats:
                    # already exact-i32 with error rows zeroed (skip
                    # when a sibling scan staged+padded it already)
                    if ('tsf_' + sfield[name]) not in inputs:
                        inputs['tsf_' + sfield[name]] = v
                    continue
                vo = v[ok]
                if len(vo) and not (np.all(np.isfinite(vo)) and
                                    np.all(vo == np.floor(vo)) and
                                    vo.min() >= I32MIN and
                                    vo.max() <= I32MAX):
                    return None
                inputs[pfx + 'ts_' + name] = np.where(ok, v, 0).astype(
                    np.int64).astype(np.int32)
            if terr_key not in inputs:
                inputs[terr_key] = errs

        # key columns: update windows/caps, assemble uploads
        new_caps = []
        pending = []  # deferred plan-state commits
        kvalid_profile = []   # plan names whose kvalid upload is skipped
        for p in self._plans:
            if p.kind == 'str':
                st = _stats(p.name)
                if st is not None:
                    all_str = st[5] == n
                    strcodes = None    # fetched only if needed below
                else:
                    tags, _, strcodes = provider._field(p.name)
                    all_str = bool((tags == mn.TAG_STRING).all())
                host = p.host_translate or not all_str
                if host:
                    codes = np.asarray(
                        provider.string_codes(p.name, p.column),
                        dtype=np.int64)
                    radix_now = len(p.column.dict.values)
                    inputs[pfx + 'key_' + p.name] = _narrow(
                        'key_' + p.name, codes, 0,
                        max(radix_now - 1, 0))
                else:
                    from .engine import _native_str_trans
                    trans = _native_str_trans(
                        p.column, provider.parser.dictionary(p.name))
                    cur = self._trans_dev.get(p.name)
                    if cur is None or cur[0] < len(trans):
                        jax, jnp = get_jax()
                        # never ship a zero-length table: XLA gather
                        # rejects slicing an empty operand (codes never
                        # reference the pad entries)
                        up = trans.astype(np.int32) if len(trans) \
                            else np.zeros(1, dtype=np.int32)
                        dev = jax.device_put(_pad_pow2(up))
                        self._trans_dev[p.name] = (len(trans), dev)
                    inputs[pfx + 'trans_' + p.name] = \
                        self._trans_dev[p.name][1]
                    if ('str_' + p.name) not in inputs:
                        # (a field that is both filter and breakdown
                        # reuses the filter loop's upload — one sticky
                        # key per physical input)
                        if strcodes is None:
                            strcodes = _memo1('str', p.name,
                                              src.strcodes_col)
                        dlen = len(provider.parser.dictionary(p.name))
                        inputs['str_' + p.name] = _narrow(
                            'str_' + p.name, strcodes, 0,
                            max(dlen - 1, 0))
                radix = len(p.column.dict.values)
                cap = max(p.cap, _pow2(max(radix, 1)))
                new_caps.append(cap)
                pending.append((p, cap, p.lo, host, True))
            else:
                if p.field.startswith('\0synth:'):
                    sname = p.field[len('\0synth:'):]
                    # window from real (err-free) timestamps only: the
                    # zero-filled error rows are dead and must not
                    # anchor the window at ordinal 0
                    sel = synth_vals[sname][ok]
                    minmax = (int(sel.min()), int(sel.max())) \
                        if len(sel) else None
                    if minmax is not None and self._time_args and \
                            sfield[sname] == sfield['dn_ts']:
                        # a column of the time filter's own field: a
                        # row outside the bounds is dead before its
                        # code is used, so the window need not hold it
                        # (a one-day build of a year's file keeps a
                        # day's segments, not the year's)
                        minmax = _clamp_to_bounds(minmax,
                                                  self._time_args)
                else:
                    st = _stats(p.name)
                    if st is not None and st[0] == 0 and st[5] == 0:
                        # no strings/arrays: the numeric rows ARE the
                        # valid rows, and min/max come from the stats
                        narr, i32ok, nmn, nmx, nnum, _ = st
                        if nnum and not i32ok:
                            return None
                        if ('kv_' + p.name) not in inputs:
                            inputs['kv_' + p.name] = _narrow(
                                'kv_' + p.name,
                                _memo1('num', p.name, src.nums_i32),
                                int(nmn) if nnum else 0,
                                int(nmx) if nnum else 0)
                        kv_skip = sk['kvalid'].get(p.name, True) and \
                            nnum == n
                        sk['kvalid'][p.name] = kv_skip
                        if kv_skip:
                            # every row numeric: no validity upload
                            kvalid_profile.append(p.name)
                        elif ('kvalid_' + p.name) not in inputs:
                            tags_k = _memo1('tags', p.name,
                                            src.tags_col)
                            inputs['kvalid_' + p.name] = \
                                (tags_k == mn.TAG_INT) | \
                                (tags_k == mn.TAG_NUMBER)
                        minmax = (int(nmn), int(nmx)) if nnum else None
                    else:
                        vals, valid = provider.numeric_column(p.name)
                        vv = vals[valid]
                        if len(vv) and not (np.all(np.isfinite(vv)) and
                                            np.all(vv == np.floor(vv))
                                            and vv.min() >= I32MIN and
                                            vv.max() <= I32MAX):
                            return None
                        if ('kv_' + p.name) not in inputs:
                            fill = int(vv[0]) if len(vv) else 0
                            v = np.where(valid, vals,
                                         fill).astype(np.int64)
                            inputs['kv_' + p.name] = _narrow(
                                'kv_' + p.name, v.astype(np.int32),
                                int(vv.min()) if len(vv) else 0,
                                int(vv.max()) if len(vv) else 0)
                        kv_skip = sk['kvalid'].get(p.name, True) and \
                            bool(valid.all())
                        sk['kvalid'][p.name] = kv_skip
                        if kv_skip:
                            kvalid_profile.append(p.name)
                        elif ('kvalid_' + p.name) not in inputs:
                            inputs['kvalid_' + p.name] = valid
                        minmax = (int(vv.min()), int(vv.max())) \
                            if len(vv) else None
                if p.kind == 'p2':
                    new_caps.append(p.cap)  # fixed [0, 32)
                    pending.append((p, p.cap, 0, False, True))
                    continue
                if minmax is not None:
                    omin = int(np.floor_divide(minmax[0], p.step))
                    omax = int(np.floor_divide(minmax[1], p.step))
                    if p.window_set:
                        lo = min(p.lo, omin)
                        hi = max(p.lo + p.cap - 1, omax)
                    else:
                        lo, hi = omin, omax
                    cap = max(p.cap, _pow2(hi - lo + 1))
                    wset = True
                    new_caps.append(cap)
                    pending.append((p, cap, lo, False, wset))
                else:
                    new_caps.append(p.cap)
                    pending.append((p, p.cap, p.lo, False,
                                    p.window_set))

        ns = 1
        for c in new_caps:
            ns *= c
        sparse = False
        if ns > MAX_DENSE_SEGMENTS:
            # high-cardinality: no dense accumulator fits.  Run the
            # SPARSE device program instead — fused i64 keys sort-merged
            # into a device-resident compacted set (keys/weights/first),
            # so the host only ever sees unique tuples.  The reference's
            # known failure mode was exactly this workload
            # (README.md:668-681).  Under a mesh every chip keeps a set
            # of its own, merged at the flush (_merge_sparse).  Excluded
            # when the fused key would overflow: per-column codes are
            # computed in i32 on device (and fetched dtype-narrowed), so
            # any single cap beyond 2^31 would wrap — host path instead
            if ns > (1 << 62) or max(new_caps) > (1 << 31):
                self._disabled = True
                return None
            sparse = True

        # commit plan-state changes; epoch flip rebuilds the program
        for p, cap, lo, host, wset in pending:
            p.cap, p.lo, p.host_translate = cap, lo, host
            p.window_set = wset
        sig = tuple(p.sig() for p in self._plans)
        if sig != self._epoch_sig:
            self._flush()
            self._epoch_sig = sig
            self._programs = None
        if self._time_args:
            # a bounded scan's window origins move with its bounds:
            # arguments of its program like them, not constants of it
            for p in self._plans:
                if p.kind == 'lin':
                    inputs[pfx + 'lo_' + p.name] = np.int32(p.lo)

        # the overflow guard runs AFTER any epoch-flip flush (a flush
        # resets the unique-count bound, which must then re-reserve
        # THIS batch or the bound undercounts by a batch)
        pn = self._padded_rows(n)
        if sparse and not self._sparse_guard(
                min(n, pn // self._mesh_shards())):
            return None

        # leaf outcome tables (grown host-side, resident on device)
        for i, (key, leaf) in enumerate(self._leaf_list):
            d = provider.parser.dictionary(leaf.field)
            table = leaf.table_for(d)
            cur = self._leaf_tables.get(i)
            if cur is None or cur[0] < len(table):
                jax, jnp = get_jax()
                up = np.ascontiguousarray(table) if len(table) \
                    else np.zeros(1, dtype=np.int8)
                dev = jax.device_put(_pad_pow2(up))
                self._leaf_tables[i] = (len(table), dev)
            inputs[pfx + 'tab_%d' % i] = self._leaf_tables[i][1]
            if i not in self._ctabs:
                jax, jnp = get_jax()
                ctab = np.zeros(16, dtype=np.int8)
                ctab[mn.TAG_MISSING] = ERROR
                ctab[mn.TAG_NULL] = leaf.outcome(None)
                ctab[mn.TAG_FALSE] = leaf.outcome(False)
                ctab[mn.TAG_TRUE] = leaf.outcome(True)
                ctab[mn.TAG_OBJECT] = leaf.outcome({})
                self._ctabs[i] = jax.device_put(ctab)
            inputs[pfx + 'ctab_%d' % i] = self._ctabs[i]

        if n < pn:
            pad = pn - n
            for k, v in list(inputs.items()):
                if isinstance(v, np.ndarray) and v.ndim == 1 and \
                        len(v) == n:
                    inputs[k] = np.concatenate(
                        [v, np.zeros(pad, dtype=v.dtype)])
            if not gen_alive:
                inputs['alive'][n:] = False

        profile = (w1, gen_alive, tuple(filter_profile),
                   tuple(kvalid_profile), use_dstats,
                   (self._sparse_cap if sparse else 0))
        return (pn, profile, tuple(new_caps), ns, total_w)

    def _padded_rows(self, n):
        """The capacity a batch of `n` records is padded to: stable
        (batches can overshoot BATCH_SIZE: the streamer only flushes
        between reads), from a floor that is auto-tuned from the
        measured H2D bandwidth so small shards stop uploading
        BATCH_SIZE worth of zeros per batch; under a mesh, rounded up
        so every shard gets an equal slice."""
        pn = self._pad_floor()
        while pn < n:
            pn <<= 1
        # the capacity only grows within a scan: a later, smaller batch
        # (the tail of a file) reuses the program already compiled
        # instead of compiling a second variant of everything — on the
        # chip a variant of the sparse program costs over a minute of
        # compilation, its dead padding rows next to nothing
        self._sticky['pn_floor'] = pn
        nsh = self._mesh_shards()
        return ((pn + nsh - 1) // nsh) * nsh

    def _pad_floor(self):
        """Smallest staged-batch capacity (a power of two, at most
        BATCH_SIZE).  Tuned once per scan (shared across a stack via
        the sticky dict) from the measured H2D bandwidth: padding a
        2k-record shard to BATCH_SIZE is free on a fast link but
        costs link time per batch on a slow one, so cap the padding
        waste at roughly one millisecond of
        upload (~the fixed dispatch cost).  DN_DEVICE_BATCH_FLOOR
        overrides the measurement; program caches key on the padded
        size, so a floor change only ever costs one extra trace."""
        sk = self._sticky
        if sk is None:
            return BATCH_SIZE
        fl = sk.get('pn_floor')
        if fl:
            return fl
        import os
        hi = BATCH_SIZE
        lo = min(4096, hi)
        fl = 0
        env = os.environ.get('DN_DEVICE_BATCH_FLOOR', '')
        if env:
            try:
                fl = int(env)
            except ValueError:
                fl = 0
        if fl <= 0:
            bw = sk.get('h2d_bw')
            if bw is None:
                bw = 0.0
                try:
                    jax, _ = get_jax()
                    buf = np.zeros(1 << 20, dtype=np.int8)
                    t0 = time.monotonic()
                    jax.block_until_ready(jax.device_put(buf))
                    dt = max(time.monotonic() - t0, 1e-9)
                    bw = float(buf.nbytes) / dt
                except Exception:
                    LOG.debug('h2d bandwidth probe failed')
                sk['h2d_bw'] = bw
            # rows whose upload fits in ~1 ms at ~48 uploaded
            # bytes/row (the staged i32/i8 column mix)
            fl = int(bw * 0.001 / 48.0) if bw else hi
        p = lo
        while p < fl and p < hi:
            p <<= 1
        fl = min(p, hi)
        sk['pn_floor'] = fl
        obs_metrics.set_gauge('device_batch_floor', fl)
        return fl

    def _sparse_guard(self, n):
        """Prevent resident-set overflow BEFORE folding a batch: track
        an upper bound on uniques (exact count at last check + records
        since); when this batch could overflow, sync-fetch the true
        count from the accumulator, and if still at risk flush the
        (correct-so-far) epoch and grow the capacity.  Under a mesh
        every chip has a set of its own: `n` is the most rows of the
        batch one chip can get, the bound is the fullest chip's, and
        the count read is the largest over the chips.  Returns False
        when the scan must take the host path instead (capacity
        ceiling: device permanently disabled for this scan)."""
        while True:
            cap = self._sparse_cap
            if self._sparse_ub + n <= cap:
                self._sparse_ub += n
                return True
            if self._acc is not None and len(self._acc) == 5:
                obs_metrics.inc('device_sparse_guard_syncs')
                with obs_metrics.leaf_stage('scan.fetch'):
                    nuniq = int(np.asarray(self._acc[4])[..., 0].max())
                if nuniq + n <= cap:
                    self._sparse_ub = nuniq + n
                    return True
            self._flush()
            if cap >= SPARSE_CAP_MAX:
                self._disabled = True
                LOG.info('sparse set capacity ceiling reached; '
                         'host path takes over', cap=cap)
                return False
            self._sparse_cap = cap * 4
            LOG.debug('sparse set grown', cap=self._sparse_cap)

    def _ensure_acc(self, acc_init, caps, ns, sparse_cap=0):
        if self._acc is None:
            self._acc = acc_init()
            self._acc_meta = {
                'caps': tuple(caps),
                'cols': [(p.kind, p.lo) for p in self._plans],
                'ns': ns,
                'sparse_cap': sparse_cap,
                'shards': self._mesh_shards(),
            }
            self._acc_batch = 0

    def _staged_programs(self, staged):
        """(progs, use_pallas) for a staged batch: this scan's part of
        the program DeviceScanStack composes."""
        pn, profile, caps, ns, total_w = staged
        pkey = (pn, profile)
        progs = self._programs.get(pkey) if self._programs else None
        if progs is None:
            progs = self._build_programs(caps, pn, profile)
            if self._programs is None:
                self._programs = {}
            self._programs[pkey] = progs
        from .ops import pallas_kernels as pk
        use_pallas = not profile[-1] and pk.should_use(ns, total_w)
        self._log_kernel(pkey, use_pallas, profile[-1], ns)
        return progs, use_pallas

    def _log_kernel(self, pkey, use_pallas, sparse_cap, ns):
        """One debug record per (program, kernel) of this scan naming
        the aggregation kernel the device runs and the mesh it runs
        over — how a forced run proves WHICH device program answered
        (chip_smoke.py reads it under LOG_LEVEL=debug)."""
        if (pkey, use_pallas) in self._kernels_logged:
            return
        self._kernels_logged.add((pkey, use_pallas))
        from .ops import pallas_kernels as pk
        mesh = self._device_mesh()
        merge = None
        if mesh:
            merge = 'allgather+sparse-fold' if sparse_cap else 'psum+pmin'
        LOG.debug('device aggregate kernel',
                  kernel='pallas-onehot' if use_pallas else
                  ('sparse-sort-merge' if sparse_cap else 'segment-sum'),
                  interpret=bool(use_pallas and pk.needs_interpret()),
                  segments=ns,
                  mesh_devices=int(mesh[0].devices.size) if mesh else 0,
                  merge=merge)

    def _note_dispatch(self, token, nbytes):
        """Pipeline bookkeeping for one dispatched batch: record
        whether the upload overlapped still-running device work (the
        previous batch's token not ready at dispatch time means the
        device was busy while this batch staged + uploaded), then
        bound the in-flight window by blocking on the token from
        `depth` dispatches back."""
        depth = pipeline_depth()
        q = self._pipe
        obs_metrics.inc('device_pipe_dispatches')
        if q and _acc_ready(q[-1]) is False:
            obs_metrics.inc('device_pipe_overlapped')
            obs_metrics.inc('device_h2d_overlapped_bytes', int(nbytes))
        q.append(token)
        if len(q) > depth:
            jax, _ = get_jax()
            with obs_metrics.leaf_stage('scan.device_wait'):
                while len(q) > depth:
                    jax.block_until_ready(q.popleft())

    # -- the device program -------------------------------------------------

    def _program_key(self, caps, n, profile):
        """Canonical static structure of the device program: two scans
        with equal keys trace to identical programs, so the jitted
        callables (and their XLA executables) are shared via
        _PROGRAM_CACHE.  `profile` is the batch's upload profile
        (which inputs are synthesized on device instead of uploaded);
        batches with different profiles use different cached
        variants."""
        # a bounded scan's linear windows start where its bounds do:
        # their origins are arguments ('lo_<name>'), not the key's
        plans = tuple((p.kind, p.name, p.field, p.step,
                       None if self._time_args and p.kind == 'lin'
                       else p.lo, p.host_translate)
                      for p in self._plans)
        leaves = tuple(
            (key, self._num_plans[i])
            for i, (key, _) in enumerate(self._leaf_list))
        return (
            n, tuple(caps), plans, leaves,
            jsv.json_stringify(self.ds_pred.ast)
            if self.ds_pred is not None else None,
            jsv.json_stringify(self.user_pred.ast)
            if self.user_pred is not None else None,
            # the bounds' static shape alone: their values are the
            # program's arguments (_time_shape)
            self._time_args[0] if self._time_args else None,
            # ordered (name, field) pairs: the traced body bakes in
            # field-derived input keys ('tsf_<field>') and an
            # order-dependent error chain ('terr_<f1|f2>'), so neither
            # the field mapping nor the order may collide in the cache
            tuple((s['name'], s['field']) for s in self.synthetic),
            len(self._counter_spec),
            self._mesh_key(),
            profile,
            # the traced body reads per-scan inputs under this prefix;
            # two structurally-identical scans in a DeviceScanStack
            # must not share a cached program
            self._pfx,
        )

    # -- mesh hooks (no-ops on the single-device path; the cluster
    # backend's MeshDeviceScan overrides them) ----------------------------

    def _device_mesh(self):
        """(Mesh, axis_name) to shard the per-record axis over, or None
        for single-device execution."""
        return None

    def _mesh_key(self):
        m = self._device_mesh()
        if m is None:
            return None
        mesh, axis = m
        return (axis, tuple(d.id for d in mesh.devices.flat))

    def _mesh_shards(self):
        """How many chips a batch's record axis is dealt over."""
        m = self._device_mesh()
        return int(m[0].devices.size) if m is not None else 1

    def _build_programs(self, caps, n, profile):
        key = self._program_key(caps, n, profile)
        cached = _PROGRAM_CACHE.get(key)
        if cached is not None:
            return cached
        progs = self._trace_programs(caps, n, profile)
        if len(_PROGRAM_CACHE) >= 64:
            # bounded: evict oldest (dict preserves insertion order);
            # re-tracing is cheap next to the XLA compile, which the
            # persistent compilation cache still remembers
            _PROGRAM_CACHE.pop(next(iter(_PROGRAM_CACHE)))
        _PROGRAM_CACHE[key] = progs
        return progs

    def _trace_programs(self, caps, n, profile):
        """What a fold of this scan is (dense, sparse, sparse on a
        mesh) and its empty accumulator: nothing here is jitted but
        the accumulator's constructor."""
        jax, jnp = get_jax()
        from . import native as mod_native
        mn = mod_native
        from .ops import pallas_kernels as pk

        w1, gen_alive, filter_profile, kvalid_skip, use_dstats, \
            sparse_cap = profile
        fprof = {f: (has_str, has_num, all_num)
                 for f, has_str, has_num, all_num in filter_profile}
        kvalid_skip = frozenset(kvalid_skip)

        # Freeze the per-plan statics NOW: the cached lambdas re-trace
        # whenever an input shape grows (e.g. a translate table crossing
        # a power of two), and by then the live _KeyPlan objects may
        # have mutated (window lo, host_translate) — the frozen copies
        # keep every retrace faithful to this program's cache key.
        _P = collections.namedtuple(
            '_P', 'kind name field step lo host_translate')
        plans = [_P(p.kind, p.name, p.field, p.step, p.lo,
                    p.host_translate) for p in self._plans]
        leaf_index = {key: i for i, (key, _) in
                      enumerate(self._leaf_list)}
        # leaf fields captured by value: the cached lambdas must not
        # close over `self` (a global cache entry would otherwise pin
        # the whole first scan instance — aggregator, dictionaries and
        # device tables included — for the life of the process)
        leaf_fields = [leaf.field for _, leaf in self._leaf_list]
        pfx = self._pfx
        # ts/terr keys mirror _stage_device: shared field-keyed
        # uploads under dstats, scan-private otherwise
        sfield = {s['name']: s['field'] for s in self.synthetic}
        if use_dstats:
            terr_key = 'terr_' + '|'.join(
                fc['field'] for fc in self.synthetic)

            def ts_key(name):
                return 'tsf_' + sfield[name]
        else:
            terr_key = pfx + 'terr'

            def ts_key(name):
                return pfx + 'ts_' + name
        num_plans = self._num_plans
        time_modes = self._time_args[0] if self._time_args else None
        has_synth = bool(self.synthetic)
        ds_ast = self.ds_pred.ast if self.ds_pred is not None else None
        user_ast = self.user_pred.ast if self.user_pred is not None \
            else None
        ns = 1
        for c in caps:
            ns *= c
        i32 = jnp.int32

        # mesh execution: the per-record axis shards over `maxis`, so
        # the body runs on bn = n / nshards rows per device and merges
        # (psum dense+counters, pmin global first-occurrence) before
        # the accumulator fold
        mesh_info = self._device_mesh()
        if mesh_info is not None:
            mesh, maxis = mesh_info
            nshards = int(mesh.devices.size)
            assert n % nshards == 0, (n, nshards)
            bn = n // nshards
        else:
            mesh = maxis = None
            nshards = 1
            bn = n

        def as_i32(x):
            # uploads arrive dtype-narrowed (u8/i16); compute in i32
            return x if x.dtype == jnp.int32 else x.astype(jnp.int32)

        def leaf_num_out(i, args, f):
            mode, t = num_plans[i]
            if mode == NUM_FALSE:
                return jnp.full((bn,), FALSE, dtype=jnp.int8)
            if mode == NUM_TRUE:
                return jnp.full((bn,), TRUE, dtype=jnp.int8)
            v = as_i32(args['num_' + f])
            tt = i32(t)
            if mode == NUM_EQ:
                hit = v == tt
            elif mode == NUM_NE:
                hit = v != tt
            elif mode == NUM_LE:
                hit = v <= tt
            else:
                hit = v >= tt
            return jnp.where(hit, jnp.int8(TRUE), jnp.int8(FALSE))

        def leaf_out(key, args):
            i = leaf_index[key]
            f = leaf_fields[i]
            has_str, has_num, all_num = fprof.get(f,
                                                  (True, True, False))
            if all_num:
                # every row numeric: tags/str uploads were skipped
                return leaf_num_out(i, args, f)
            tags = args['tags_' + f]
            out = args[pfx + 'ctab_%d' % i][tags]
            if has_str:
                # gather indices must be i32: narrowed i16 codes
                # overflow JAX's negative-index normalization once the
                # pow2-padded table exceeds 32767 entries
                out = jnp.where(tags == mn.TAG_STRING,
                                args[pfx + 'tab_%d' % i][as_i32(
                                    args['str_' + f])],
                                out)
            if not has_num:
                return out
            numm = (tags == mn.TAG_INT) | (tags == mn.TAG_NUMBER)
            return jnp.where(numm, leaf_num_out(i, args, f), out)

        def eval_ast(ast, args):
            if not ast:
                return jnp.full((bn,), TRUE, dtype=jnp.int8)
            op = next(iter(ast))
            if op in ('and', 'or'):
                outs = [eval_ast(sub, args) for sub in ast[op]]
                state = outs[0]
                stop = TRUE if op == 'and' else FALSE
                for o in outs[1:]:
                    state = jnp.where(state == stop, o, state)
                return state
            field, const = ast[op]
            key = (field, op, jsv.json_stringify(const))
            return leaf_out(key, args)

        def p2_int(v):
            x = jnp.maximum(v, i32(0))
            bl = jnp.zeros_like(v)
            for s in (16, 8, 4, 2, 1):
                big = x >= i32(1 << s)
                bl = bl + jnp.where(big, i32(s), i32(0))
                x = jnp.where(big, jnp.right_shift(x, i32(s)), x)
            bl = bl + jnp.where(x >= i32(1), i32(1), i32(0))
            return jnp.where(v < i32(1), i32(0), bl)

        def body(args, use_pallas):
            # global row index (for first-occurrence order and, when
            # the batch is dense, the synthesized alive mask)
            gidx = jax.lax.iota(jnp.int32, bn)
            if maxis is not None:
                gidx = gidx + jax.lax.axis_index(maxis).astype(
                    jnp.int32) * i32(bn)
            if gen_alive:
                # alive synthesized from the record count: rows past
                # nvalid are padding
                alive = gidx < args['nvalid']
            else:
                alive = args['alive']
            weights = None if w1 else args['weights']
            counters = []

            def isum(x):
                return jnp.sum(x, dtype=jnp.int32)

            for ast in (ds_ast, user_ast):
                if ast is None:
                    continue
                counters.append(isum(alive))
                out = eval_ast(ast, args)
                counters.append(isum(alive & (out == ERROR)))
                counters.append(isum(alive & (out == FALSE)))
                alive = alive & (out == TRUE)
                counters.append(isum(alive))

            if has_synth:
                counters.append(isum(alive))
                terr = args[terr_key]
                counters.append(isum(alive & (terr == 1)))   # UNDEF
                counters.append(isum(alive & (terr == 2)))   # BADDATE
                alive = alive & (terr == 0)
                counters.append(isum(alive))

            if time_modes is not None:
                counters.append(isum(alive))
                ts = args[ts_key('dn_ts')]
                lo_mode, hi_mode = time_modes
                ok = jnp.ones((bn,), dtype=bool)
                if 'never' in time_modes:
                    ok = ok & False
                if lo_mode == 'arg':
                    ok = ok & (ts >= args[pfx + 'tb_lo'])
                if hi_mode == 'arg':
                    ok = ok & (ts < args[pfx + 'tb_hi'])
                counters.append(isum(alive & ~ok))
                alive = alive & ok
                counters.append(isum(alive))

            counters.append(isum(alive))   # aggregator ninputs
            nnon = jnp.int32(0)
            codes = []
            for p in plans:
                if p.kind == 'str':
                    if p.host_translate:
                        codes.append(as_i32(args[pfx + 'key_' + p.name]))
                    else:
                        codes.append(
                            args[pfx + 'trans_' + p.name][as_i32(
                                args['str_' + p.name])])
                    continue
                if p.field.startswith('\0synth:'):
                    v = args[ts_key(p.field[len('\0synth:'):])]
                else:
                    if p.name not in kvalid_skip:
                        valid = args['kvalid_' + p.name]
                        nnon = nnon + isum(alive & ~valid)
                        alive = alive & valid
                    v = as_i32(args['kv_' + p.name])
                if p.kind == 'p2':
                    codes.append(p2_int(v))
                else:
                    codes.append(
                        jnp.floor_divide(v, i32(p.step)) -
                        (i32(p.lo) if time_modes is None
                         else args[pfx + 'lo_' + p.name]))
            counters.append(nnon)
            counters.append(isum(alive) if sparse_cap
                            else jnp.int32(0))   # nspillrecords
            cvec = jnp.stack(counters)

            if sparse_cap:
                # sparse mode: emit fused i64 keys + weights; the fold
                # sort-merges them into the resident compacted set
                i64 = jnp.int64
                fused = jnp.zeros((bn,), dtype=i64)
                for c, cap in zip(codes, caps):
                    fused = fused * i64(cap) + c.astype(i64)
                fused = jnp.where(alive, fused, i64(I64MAX))
                if w1:
                    wb = alive.astype(i64)
                else:
                    wb = jnp.where(alive, weights, i32(0)).astype(i64)
                return cvec, fused, wb, gidx

            def merge(dense, first, cvec):
                if maxis is None:
                    return dense, first, cvec
                return (jax.lax.psum(dense, maxis),
                        jax.lax.pmin(first, maxis),
                        jax.lax.psum(cvec, maxis))

            if not codes:
                if w1:
                    total = jnp.sum(alive, dtype=jnp.int32)
                else:
                    total = jnp.sum(
                        jnp.where(alive, weights, i32(0)),
                        dtype=jnp.int32)
                dense = total[None]
                first = jnp.zeros((1,), dtype=jnp.int32)
                return merge(dense, first, cvec)

            fused = jnp.zeros((bn,), dtype=jnp.int32)
            for c, cap in zip(codes, caps):
                fused = fused * i32(cap) + c
            fused = jnp.where(alive, fused, i32(ns))
            # global row index (gidx) so cross-shard pmin yields the
            # true first occurrence (host-engine insertion order)
            first = jax.ops.segment_min(gidx, fused,
                                        num_segments=ns + 1)[:ns]
            if use_pallas:
                wf = jnp.ones((bn,), dtype=jnp.float32) if w1 \
                    else weights.astype(jnp.float32)
                dense = pk.onehot_dense(
                    caps, bn, jnp.stack(codes), wf, alive,
                    interpret=pk.needs_interpret())
            else:
                if w1:
                    w = alive.astype(jnp.int32)
                else:
                    w = jnp.where(alive, weights, i32(0))
                dense = jax.ops.segment_sum(w, fused,
                                            num_segments=ns + 1)[:ns]
            return merge(dense, first, cvec)

        ncnt = len(self._counter_spec)
        acc_ns = max(ns, 1)

        def run_body(args, use_pallas):
            if mesh is None:
                return body(args, use_pallas)
            from jax.sharding import PartitionSpec as SP
            specs = record_specs(args, pfx, maxis)
            sargs = {k: args[k] for k in specs}
            return jax.shard_map(
                lambda a: body(a, use_pallas), mesh=mesh,
                in_specs=(specs,), out_specs=(SP(), SP(), SP()),
                check_vma=not use_pallas)(sargs)

        def fold(args, acc, use_pallas):
            """One batch folded into the device-resident accumulator:
            dense weights and counters add; the first-occurrence key
            takes a running min over (batch_base | row), which orders
            keys exactly as the host engine inserts them (batch
            submission order, then first row within the batch)."""
            dense, first, cvec = run_body(args, use_pallas)
            i64 = jnp.int64
            bfirst = jnp.where(
                first < I32MAX,
                args[pfx + 'base'] + first.astype(i64),
                i64(I64MAX))
            return (acc[0] + dense.astype(i64),
                    jnp.minimum(acc[1], bfirst),
                    acc[2] + cvec.astype(i64))

        def fold_sparse(args, acc, use_pallas):
            """Sparse fold (`use_pallas` is the folds' signature: this
            lane has no one-hot kernel): sort-merge the batch's fused
            i64 keys into the device-resident compacted set
            (kernels.sparse_fold).
            keys/first take the per-key min (first-occurrence order
            preserved exactly), weights sum, and the unique count rides
            along so the host pressure guard can read it without a full
            fetch.  Runs past the capacity are dropped; the sticky
            overflow flag makes that loud at flush (the host guard
            prevents it from ever tripping)."""
            cvec_b, fused, wb, gidx = body(args, False)
            i64 = jnp.int64
            first_b = jnp.where(fused != i64(I64MAX),
                                args[pfx + 'base'] + gidx.astype(i64),
                                i64(I64MAX))
            return sparse_fold(jax, jnp, acc, cvec_b, fused, wb, first_b)

        def fold_sparse_mesh(args, acc, use_pallas):
            """The same fold on every chip of the mesh: its shard of
            the batch into its own set (the accumulator's leaves carry
            the chips on a leading axis), and no collective.  `gidx`
            is the batch's global row, so a key's `first` is what one
            chip would have given it; the chips' sets meet at the
            flush (_merge_sparse)."""
            from jax.sharding import PartitionSpec as SP
            specs = record_specs(args, pfx, maxis)
            specs[pfx + 'base'] = SP()
            sargs = {k: args[k] for k in specs}

            def chip(a, acc1):
                out = fold_sparse(a, tuple(x[0] for x in acc1), False)
                return tuple(x[None] for x in out)

            sets = (SP(maxis),) * 5
            return jax.shard_map(chip, mesh=mesh, in_specs=(specs, sets),
                                 out_specs=sets)(sargs, acc)

        if sparse_cap:
            init_key = ('sparse', sparse_cap, ncnt, self._mesh_key())
            acc_init = _ACC_INIT_CACHE.get(init_key)
            if acc_init is None:
                def make_sparse_init(cap_, ncnt_, mesh_, maxis_):
                    jx, jn = get_jax()
                    lead, kw = (), {}
                    if mesh_ is not None:
                        from jax.sharding import NamedSharding, \
                            PartitionSpec
                        lead = (int(mesh_.devices.size),)
                        kw['out_shardings'] = NamedSharding(
                            mesh_, PartitionSpec(maxis_))
                    return jx.jit(lambda: (
                        jn.full(lead + (cap_,), I64MAX, dtype=jn.int64),
                        jn.zeros(lead + (cap_,), dtype=jn.int64),
                        jn.full(lead + (cap_,), I64MAX, dtype=jn.int64),
                        jn.zeros(lead + (ncnt_,), dtype=jn.int64),
                        jn.zeros(lead + (2,), dtype=jn.int64)), **kw)
                acc_init = make_sparse_init(sparse_cap, ncnt, mesh, maxis)
                if len(_ACC_INIT_CACHE) >= 64:
                    _ACC_INIT_CACHE.pop(next(iter(_ACC_INIT_CACHE)))
                _ACC_INIT_CACHE[init_key] = acc_init
            return _Programs(acc_init, fold_sparse if mesh is None
                             else fold_sparse_mesh)

        init_key = (acc_ns, ncnt)
        acc_init = _ACC_INIT_CACHE.get(init_key)
        if acc_init is None:
            def make_init(ns_, ncnt_):
                jx, jn = get_jax()
                return jx.jit(lambda: (
                    jn.zeros((ns_,), dtype=jn.int64),
                    jn.full((ns_,), I64MAX, dtype=jn.int64),
                    jn.zeros((ncnt_,), dtype=jn.int64)))
            acc_init = make_init(acc_ns, ncnt)
            if len(_ACC_INIT_CACHE) >= 64:
                _ACC_INIT_CACHE.pop(next(iter(_ACC_INIT_CACHE)))
            _ACC_INIT_CACHE[init_key] = acc_init
        return _Programs(acc_init, fold)

    # -- flush: fetch + ordered merge ---------------------------------------

    # accumulators at least this large are compacted ON DEVICE before
    # the fetch (argsort by first-occurrence, gather occurred segments)
    # — fetching a multi-MB dense array when a few thousand tuples
    # occurred is wasted device->host traffic (D2H bandwidth: not
    # measured on the current chip)
    COMPACT_MIN_SEGMENTS = 16384
    # speculative compacted-fetch width: one round trip when the
    # occurred count fits (the norm); a larger refetch otherwise
    COMPACT_K = 1 << 16

    def _flush(self):
        """Fetch the device accumulator (one round trip for the whole
        epoch: the copies are issued async and then awaited together)
        and merge it into the insertion-ordered Aggregator.  Any
        async-prefetched epochs drain first, preserving emission
        order."""
        if self._pending_flush:
            self._drain_pending()
        if self._acc is None:
            return
        acc = self._acc
        meta = self._acc_meta
        nbatches = self._acc_batch
        self._acc = None
        self._acc_meta = None
        self._acc_batch = 0
        self._pipe.clear()   # the fetch below syncs the whole epoch
        # engine telemetry: batches folded on the device this epoch
        # (programmatic — Stage.counters / the cluster tests — but
        # kept out of the --counters dump for golden byte parity)
        if nbatches:
            self.aggr.stage.bump_hidden('ndevicebatches', nbatches)
        sparse_ub = self._sparse_ub
        self._sparse_ub = 0

        if meta.get('sparse_cap'):
            self._flush_sparse(acc, meta, sparse_ub)
            return

        if not meta['cols']:
            with obs_metrics.leaf_stage('scan.fetch'):
                _issue_async(acc)
                cvec = np.asarray(acc[2])
                total = float(np.asarray(acc[0])[0])
            with obs_metrics.leaf_stage('scan.emit'):
                self._emit_counters(cvec)
                self.aggr.write_key((), self._weight(total))
            return

        segs = wsum = cvec = None
        with obs_metrics.leaf_stage('scan.fetch'):
            if meta['ns'] >= self.COMPACT_MIN_SEGMENTS:
                fetched = _compact_fetch(acc, self.COMPACT_K)
                if fetched is not None:
                    segs, wsum, cvec = fetched
                    self.aggr.stage.bump_hidden('ncompactflush', 1)
            if segs is None:
                segs, wsum, cvec = _dense_full_result(acc)
        with obs_metrics.leaf_stage('scan.emit'):
            self._emit_counters(cvec)
            # global codes for the shared emit path: device string
            # codes are already engine-dictionary codes; bucket codes
            # offset by the window origin give raw ordinals
            self._decode_emit(meta, segs, wsum)

    def _flush_sparse(self, acc, meta, sparse_ub):
        """Flush the sparse (high-cardinality) accumulator: the set is
        already compact, so fetch its occupied slots ordered by first
        occurrence (decoded + narrowed on device), sized by the
        epoch's unique-count upper bound."""
        acc, live = self._merge_sparse(
            acc, meta, min(sparse_ub, meta['sparse_cap']))
        k0 = _pow2(max(live, 1)) if live else self.COMPACT_K
        with obs_metrics.leaf_stage('scan.fetch'):
            fetched = _sparse_fetch(acc, k0, meta['caps'])
            if fetched is None:
                cols, wsum, cvec, stats = _sparse_full_result(
                    acc, meta['caps'])
            else:
                cols, wsum, cvec, stats = fetched
                self.aggr.stage.bump_hidden('ncompactflush', 1)
        if int(stats[1]):
            # the host pressure guard exists to make this unreachable;
            # if it ever trips, results are incomplete — fail loudly
            raise RuntimeError(
                'device sparse aggregation overflowed its resident set'
                ' (cap=%d); results would be incomplete'
                % meta['sparse_cap'])
        self._count_sparse_set(meta, int(stats[0]))
        with obs_metrics.leaf_stage('scan.emit'):
            self._emit_counters(cvec)
            self._emit_cols(meta, cols, wsum)

    def _merge_sparse(self, acc, meta, ub):
        """A mesh epoch's sets, one a chip, merged on the device into
        one set in the one-chip layout (replicated), which the fetch
        and the emit then take as they take a single chip's: the
        reduce phase of upstream's map -> reduce.  Every shape comes
        from the chips' own counts, read here: each chip's first `k`
        slots (k the fullest chip's live tuples, a power of two) are
        all-gathered and folded once more (`mesh.sparse_merge_program`)
        into an empty set that holds the sum of the counts.  Returns
        (set, bound on its live tuples): outside a mesh the set as it
        is and `ub`, the guard's bound, with nothing read."""
        if meta['shards'] == 1:
            return acc, ub
        from .parallel import mesh as mod_mesh
        mesh, axis = self._device_mesh()
        with obs_metrics.leaf_stage('scan.sparse_merge'):
            cap = meta['sparse_cap']
            live = np.minimum(np.asarray(acc[4])[:, 0], cap)
            k = min(cap, _pow2(max(int(live.max()), 1)))
            merged = mod_mesh.sparse_merge_program(
                mesh, axis, k, _pow2(max(int(live.sum()), 1)))(acc)
            tuples = int(np.asarray(merged[4])[0])
        obs_metrics.inc('device_sparse_merge_rows', int(live.sum()))
        obs_metrics.inc('device_sparse_merge_tuples', tuples)
        obs_metrics.inc('device_sparse_set_slots', cap)
        obs_metrics.inc('device_sparse_set_live', int(live.max()))
        return merged, tuples

    @staticmethod
    def _count_sparse_set(meta, live):
        """The set's fill at a flush, for a single chip (a mesh's is
        counted where its sets are merged)."""
        if meta['shards'] == 1:
            obs_metrics.inc('device_sparse_set_slots', meta['sparse_cap'])
            obs_metrics.inc('device_sparse_set_live',
                            min(live, meta['sparse_cap']))


# jitted flush-compaction programs, keyed by (acc_len, K)
_COMPACT_CACHE = {}


def _compact_program(acc_len, k):
    key = (acc_len, k)
    prog = _COMPACT_CACHE.get(key)
    if prog is not None:
        return prog
    jax, jnp = get_jax()

    def compact(acc):
        dense, first, cvec = acc
        cnt = jnp.sum(first < I64MAX).astype(jnp.int32)
        # ascending argsort puts occurred segments first, in exact
        # first-occurrence order (firsts are distinct: each global row
        # index belongs to one segment); I64MAX sentinels sort last
        order = jnp.argsort(first)[:k]
        occ = first[order] < I64MAX
        segs = jnp.where(occ, order.astype(jnp.int32), jnp.int32(-1))
        return cnt, segs, dense[order], cvec

    prog = jax.jit(compact)
    if len(_COMPACT_CACHE) >= 64:
        _COMPACT_CACHE.pop(next(iter(_COMPACT_CACHE)))
    _COMPACT_CACHE[key] = prog
    return prog


def _narrow_dtype(cap):
    if cap <= 256:
        return 'uint8'
    if cap <= 32768:
        return 'int16'
    return 'int32'


def _sparse_program(cap, k, caps):
    """Compacting fetch program for the sparse set: occupied slots
    ordered by first occurrence, with the fused keys DECODED to
    per-column codes on device and every output dtype-narrowed — so
    the device->host fetch ships the fewest bytes that can represent
    the result (plus an overflow flag that triggers the full-precision
    fallback for weight sums beyond i32)."""
    key = ('sparse', cap, k, caps)
    prog = _COMPACT_CACHE.get(key)
    if prog is not None:
        return prog
    jax, jnp = get_jax()

    def compact(acc):
        keys, wsum, first, cvec, stats = acc
        order = jnp.argsort(first)[:k]
        ks = keys[order]
        cols = []
        div = 1
        for cap_i in reversed(caps):
            c = (ks // jnp.int64(div)) % jnp.int64(cap_i)
            cols.append(c.astype(_narrow_dtype(cap_i)))
            div *= cap_i
        cols.reverse()
        ws = wsum[order]
        wof = jnp.any(ws > jnp.int64(I32MAX)) | \
            jnp.any(ws < jnp.int64(I32MIN))
        return tuple(cols), ws.astype(jnp.int32), wof, cvec, stats

    prog = jax.jit(compact)
    if len(_COMPACT_CACHE) >= 64:
        _COMPACT_CACHE.pop(next(iter(_COMPACT_CACHE)))
    _COMPACT_CACHE[key] = prog
    return prog


def _sparse_program_full(cap, k):
    """Full-precision fallback (i64 keys+weights): used when a weight
    sum overflows i32 (wof flag)."""
    key = ('sparse64', cap, k)
    prog = _COMPACT_CACHE.get(key)
    if prog is not None:
        return prog
    jax, jnp = get_jax()

    def compact(acc):
        keys, wsum, first, cvec, stats = acc
        order = jnp.argsort(first)[:k]
        return keys[order], wsum[order], cvec, stats

    prog = jax.jit(compact)
    if len(_COMPACT_CACHE) >= 64:
        _COMPACT_CACHE.pop(next(iter(_COMPACT_CACHE)))
    _COMPACT_CACHE[key] = prog
    return prog


def _note_h2d(nbytes):
    """Host->device transfer accounting (always-on counter)."""
    if nbytes:
        obs_metrics.inc('device_h2d_bytes', int(nbytes))


_PER_RECORD_KEYS = ('alive', 'weights', 'terr')
_PER_RECORD_PREFIXES = ('tags_', 'str_', 'num_', 'ts_', 'kv_', 'kvalid_',
                        'key_', 'tsf_', 'terr_')
_STACK_PFX = re.compile(r'm\d+_')    # DeviceScanStack's 'm<i>_'


def record_specs(args, pfx, axis):
    """The shard_map partition specs of one mesh scan's inputs, by
    key: the keys of `args` (a batch's staged inputs, under a
    DeviceScanStack every scan's in one dict) that are this scan's to
    read, each sharded over `axis` where _stage_device wrote a value a
    record, replicated where it wrote a lookup table or a scalar.  A
    scan reads the shared parser columns, which carry no prefix, and
    the keys under its own `pfx`; a sibling's 'm<j>_...' keys are left
    out.  What a key holds is told from its name with the scan's own
    prefix taken off, so a stacked scan's 'm1_key_x' is what a single
    scan's 'key_x' is.  The batch base (`pfx + 'base'`) is not listed:
    the dense fold reads it outside the shard_map."""
    from jax.sharding import PartitionSpec as SP
    specs = {}
    for k in args:
        if pfx and k.startswith(pfx):
            name = k[len(pfx):]
        elif pfx and _STACK_PFX.match(k):
            continue
        else:
            name = k
        if name == 'base':
            continue
        if name in _PER_RECORD_KEYS or \
                name.startswith(_PER_RECORD_PREFIXES):
            specs[k] = SP(axis)
        else:
            specs[k] = SP()   # lookup tables, nvalid: replicated
    return specs


def _upload_batch(inputs, mesh):
    """The scan.upload stage of one batch: its host arrays to the
    device (in place), counted in `device_h2d_bytes`; returns the
    byte count.  Under a mesh the shardings are the jit's to decide,
    so the arrays stay on the host and are only counted."""
    with obs_metrics.leaf_stage('scan.upload'):
        if mesh is None:
            nbytes = _upload_inputs(inputs)
        else:
            nbytes = sum(int(getattr(v, 'nbytes', 0) or 0)
                         for v in inputs.values()
                         if isinstance(v, np.ndarray))
    _note_h2d(nbytes)
    return nbytes


def _upload_inputs(inputs):
    """Issue async H2D transfers for the batch's host arrays, in
    place, and return the uploaded byte count.  jax.device_put returns
    immediately with the copy in flight, so by the time the jitted
    fold is dispatched its operands are already on the wire — this is
    what lets batch N+1's upload ride under batch N's execution
    instead of serializing at dispatch."""
    jax, _ = get_jax()
    nbytes = 0
    for k, v in list(inputs.items()):
        if isinstance(v, np.ndarray) and v.ndim:
            nbytes += int(v.nbytes)
            inputs[k] = jax.device_put(v)
    return nbytes


_PARALLEL_FETCH = {
    'enabled': None,    # None until env-resolved or probed
    'source': None,     # 'env' | 'probe'
    'probe_ms': None,
    'reason': None,     # why the probe disabled it (timeout/error)
}


def _reset_parallel_fetch():
    """Test seam: forget the memoized concurrent-fetch verdict."""
    _PARALLEL_FETCH.update(
        enabled=None, source=None, probe_ms=None, reason=None)


def _probe_parallel_fetch():
    """One concurrent D2H fetch of two tiny device arrays, verified
    byte-for-byte.  Plugins that serialize or deadlock concurrent
    transfers fail here (the caller wraps us in run_with_deadline), so
    the verdict is safe to memoize for the process lifetime."""
    import concurrent.futures as cf
    from .ops import get_jax
    jax, _ = get_jax()
    refs = [np.arange(256, dtype=np.int64) + i for i in range(2)]
    devs = [jax.device_put(r) for r in refs]
    for d in devs:
        d.block_until_ready()
    with cf.ThreadPoolExecutor(2) as ex:
        out = list(ex.map(np.asarray, devs))
    for ref, got in zip(refs, out):
        if not np.array_equal(ref, got):
            raise RuntimeError('concurrent fetch corrupted data')
    return True


def parallel_fetch_enabled():
    """Whether D2H fetches may run on a thread pool.  DN_PARALLEL_FETCH
    =1/0 overrides in either direction; otherwise the first call runs
    one guarded concurrent-fetch probe (deadline-armored — a plugin
    that wedges on concurrent transfers costs one short timeout, not a
    hang) and the verdict sticks for the process.  Callers reach this
    only after the backend is initialized, so the probe never triggers
    a cold backend bring-up."""
    if _PARALLEL_FETCH['enabled'] is not None:
        return _PARALLEL_FETCH['enabled']
    import os
    import time
    env = os.environ.get('DN_PARALLEL_FETCH', '')
    if env in ('0', '1'):
        _PARALLEL_FETCH.update(
            enabled=(env == '1'), source='env',
            probe_ms=None, reason=None)
    else:
        t0 = time.monotonic()
        status, res = run_with_deadline(
            _probe_parallel_fetch, min(probe_deadline_s(), 10.0),
            'parallel-fetch probe')
        ms = round((time.monotonic() - t0) * 1e3, 3)
        if status == 'ok':
            _PARALLEL_FETCH.update(
                enabled=True, source='probe', probe_ms=ms,
                reason=None)
        else:
            reason = ('probe timeout' if status == 'timeout'
                      else 'probe error: %s' % (res,))
            _PARALLEL_FETCH.update(
                enabled=False, source='probe', probe_ms=ms,
                reason=reason)
    return _PARALLEL_FETCH['enabled']


def parallel_fetch_doc():
    """Read-only /stats doc for the concurrent-fetch capability; never
    triggers the probe (enabled=None means not yet resolved)."""
    return dict(_PARALLEL_FETCH)


def _fetch_arrays(arrays):
    """np.asarray over several device arrays, on a small thread pool
    when the probed concurrent-fetch capability (or DN_PARALLEL_FETCH
    =1) allows it — concurrent transfers are not assumed safe on
    every backend, so the capability is probed once (gain on the
    current chip: not measured)."""
    arrays = list(arrays)
    if len(arrays) <= 1 or not parallel_fetch_enabled():
        out = [np.asarray(a) for a in arrays]
    else:
        import concurrent.futures as cf
        with cf.ThreadPoolExecutor(min(4, len(arrays))) as ex:
            out = list(ex.map(np.asarray, arrays))
    nbytes = sum(int(a.nbytes) for a in out)
    if nbytes:
        obs_metrics.inc('device_d2h_bytes', nbytes)
    return out


def _decode_fused(keys, caps):
    """Host-side fused-key decode (the fallback path)."""
    rem = keys.copy()
    cols = [None] * len(caps)
    for ci in range(len(caps) - 1, -1, -1):
        cols[ci] = rem % caps[ci]
        rem = rem // caps[ci]
    return cols


def _issue_async(arrays):
    for a in arrays:
        if isinstance(a, (tuple, list)):
            _issue_async(a)     # e.g. the sparse program's cols tuple
        elif hasattr(a, 'copy_to_host_async'):
            try:
                a.copy_to_host_async()
            except Exception:
                pass


def _sparse_full_result(acc, caps):
    """Full (uncompacted) fetch + host-side decode of a sparse
    accumulator — the fallback when the compacting fetch fails."""
    _issue_async(acc)
    keys = np.asarray(acc[0])
    wsums = np.asarray(acc[1])
    first = np.asarray(acc[2])
    cvec = np.asarray(acc[3])
    stats = np.asarray(acc[4])
    occurred = np.nonzero(first < I64MAX)[0]
    order = np.argsort(first[occurred], kind='stable')
    cols = _decode_fused(keys[occurred][order], caps)
    wsum = wsums[occurred][order].astype(np.float64)
    return cols, wsum, cvec, stats


def _dense_full_result(acc):
    """Full fetch of a dense accumulator in first-occurrence order —
    the fallback when the compacting fetch fails."""
    _issue_async(acc)
    dense = np.asarray(acc[0])
    first = np.asarray(acc[1])
    cvec = np.asarray(acc[2])
    occurred = np.nonzero(first < I64MAX)[0]
    order = np.argsort(first[occurred], kind='stable')
    segs = occurred[order]
    return segs, dense[segs].astype(np.float64), cvec


def _sparse_fetch(acc, k0, caps):
    """Fetch the sparse accumulator's occupied slots in exact
    first-occurrence order: (per-column code arrays i64, weights f64,
    cvec, stats).  One round trip when the unique count fits the
    speculative width."""
    cap = int(acc[0].shape[0])
    k = min(cap, k0)
    try:
        while True:
            cols, w32, wof, cvec, stats = \
                _sparse_program(cap, k, tuple(caps))(acc)
            _issue_async(list(cols) + [w32, cvec, stats])
            st = np.asarray(stats)
            n = int(st[0])
            if n > k:
                if k < cap:
                    k = min(cap, _pow2(n))
                    continue
                # n > capacity: genuine overflow — fetch what exists
                # and let the caller's stats[1] check raise loudly
                n = k
            if bool(np.asarray(wof)):
                keys, wsum, cvec, stats = \
                    _sparse_program_full(cap, k)(acc)
                kn = np.asarray(keys)[:n].astype(np.int64)
                return (_decode_fused(kn, caps),
                        np.asarray(wsum)[:n].astype(np.float64),
                        np.asarray(cvec), np.asarray(stats))
            fetched = _fetch_arrays(cols)
            wn = np.asarray(w32)[:n].astype(np.float64)
            return ([c[:n].astype(np.int64) for c in fetched],
                    wn, np.asarray(cvec), st)
    except Exception:
        LOG.debug('sparse compact fetch failed; full fetch')
        return None


def _compact_fetch(acc, k0):
    """Device-side compaction of a flush fetch: returns
    (segs i64[cnt] in first-occurrence order, weights f64[cnt], cvec)
    fetching O(occurred) bytes instead of O(ns), or None to take the
    full-fetch path.  One extra round trip only when more than k0
    segments occurred (then a pow2-sized refetch)."""
    acc_len = int(acc[0].shape[0])
    k = min(acc_len, k0)
    try:
        while True:
            cnt, segs, dense, cvec = _compact_program(acc_len, k)(acc)
            _issue_async((cnt, segs, dense, cvec))
            n = int(np.asarray(cnt))
            if n <= k:
                segs = np.asarray(segs)[:n].astype(np.int64)
                wsum = np.asarray(dense)[:n].astype(np.float64)
                return segs, wsum, np.asarray(cvec)
            k = min(acc_len, _pow2(n))
    except Exception:
        LOG.debug('compact fetch failed; full fetch')
        return None


class DeviceScanStack(object):
    """One device program per batch for the scans of one parse stream:
    the N metrics of a build, or a lone scan, which is a stack of one.
    The only place that stages, composes, uploads and dispatches.

    The reference's build fed one parse stream into N per-metric
    scanners (lib/datasource-file.js:403-427); the round-4 device build
    kept that shape — N separate DeviceScan programs per batch, each
    re-uploading the columns it needs.  This stack fuses them: every
    scan stages its inputs into ONE merged dict (parser-derived columns
    use shared keys, so a column read by several metrics crosses H2D
    once; per-scan inputs carry an 'm<i>_' prefix), and one combined
    jit folds the batch into every metric's device-resident accumulator
    in a single dispatch.  XLA sees all N pipelines in one module and
    CSEs the shared subcomputations (gathers on shared columns, date
    masks).  Builds amortize transfer over N metrics — the regime where
    the chip beats the host even through a slow transport (SURVEY §7.7:
    one pass, stacked metric programs).

    Scans keep their own accumulators/flush/emission; the stack only
    stages and dispatches, so per-scan results (and the index
    artifacts) are byte-identical to the host engine's.  On the
    cluster backend's mesh it is the same stack: each scan's fold is
    its own shard_map (record_specs picks its keys out of the merged
    dict), its merges (psum+pmin, or the sparse sets' all-gather at a
    flush) stay its own."""

    def __init__(self, scans):
        self.scans = list(scans)
        if len(self.scans) > 1:
            # shared sticky upload-profile state: widening decisions
            # apply to the shared physical inputs, so all scans must
            # agree.  (A stack of one leaves its scan's prefix and
            # sticky state as they are: the scan may be a larger
            # stack's, handed a batch that a sibling declined.)
            shared = {'w1': True, 'gen_alive': True, 'filter': {},
                      'kvalid': {}, 'dtypes': {}}
            for i, s in enumerate(self.scans):
                s._pfx = 'm%d_' % i
                s._sticky = shared
        self._nbatch = 0
        # (scan_idx, pn, profile) -> full program key: _program_key
        # json-stringifies predicate ASTs, too costly per batch
        self._pkey_memo = {}

    def process(self, provider, weights, alive):
        """Process one batch for every scan: the combined device
        program when every scan stages successfully, else the per-scan
        paths (each of which may still use its own device program or
        the host engine).  Exactly one of these runs per batch, so
        insertion order and results match the host engine's."""
        if not self.try_device(provider, weights, alive):
            for s in self.scans:
                s._process(provider, weights, alive=alive)

    def try_device(self, provider, weights, alive):
        """One batch to the device for every scan; False, with nothing
        folded or counted, when a gate or a scan's staging declines
        it."""
        n = provider.n
        for s in self.scans:
            if s._t0 is None:
                s._t0 = time.monotonic()
        if not (self._device_eligible(provider, n) and
                self._process_device(provider, weights, alive)):
            return False
        for s in self.scans:
            s._records_seen += n
            s._after_device_batch(n)
        return True

    def _device_eligible(self, provider, n):
        """The gates in front of staging: every scan past its
        escalation threshold with a backend that answers (a forced
        scan probes it here, once, under the probe deadline), and a
        batch of native columns."""
        for s in self.scans:
            # the escalation compare tests records_seen AFTER counting
            # this batch
            s._records_seen += n
            try:
                ok = (not s._disabled and
                      s._records_seen > s._escalate_records() and
                      s._engage_device())
            finally:
                s._records_seen -= n
            if not ok:
                return False
        if not isinstance(provider, NativeColumns):
            if engine_mode() == 'jax':
                raise DNError(
                    'DN_ENGINE=jax: the device scan needs the native '
                    'column parser and this batch came through the '
                    'Python record path (native/build/libdnparse.so '
                    'missing or DN_NATIVE=0; build it with '
                    '"make -C native")')
            return False
        return True

    def _process_device(self, provider, weights, alive):
        """Stage, upload and dispatch one batch; False when a scan
        cannot stage it (any exactness precondition: the host path
        then computes the same results)."""
        scans = self.scans
        inputs = {}
        with obs_metrics.leaf_stage('scan.stage'):
            staged = []
            for s in scans:
                st = s._stage_device(provider, weights, alive, inputs)
                if st is None:
                    return False
                staged.append(st)
            run = self._stacked_program(staged, inputs)
        nbytes = _upload_batch(inputs, scans[0]._device_mesh())
        with obs_metrics.leaf_stage('scan.dispatch'):
            accs, token = run(inputs, tuple(s._acc for s in scans))
        for s, acc in zip(scans, accs):
            s._acc = acc
            s._acc_batch += 1
            if s._acc_meta['sparse_cap']:
                obs_metrics.inc('device_sparse_fold_batches')
            if len(scans) > 1:
                # telemetry: this batch went through a program that
                # several scans share (kept out of --counters for
                # golden byte parity)
                s.aggr.stage.bump_hidden('nstackedbatches', 1)
        self._nbatch += 1
        scans[0]._note_dispatch(token, nbytes)
        if self._nbatch % SYNC_EVERY_BATCHES == 0:
            # periodic dispatch barrier (no fetch): hard backstop on
            # how far the host can race ahead of the device beyond the
            # pipeline window
            scans[0]._sync_device()
        return True

    def _stacked_program(self, staged, inputs):
        """The jitted program of one staged batch (every scan's
        accumulator made ready and its batch base written into
        `inputs` on the way)."""
        scans = self.scans
        pns = set(st[0] for st in staged)
        assert len(pns) == 1, pns    # same batch, same mesh => same pad

        parts = []
        key_parts = []
        for i, (s, st) in enumerate(zip(scans, staged)):
            pn, profile, caps, ns, total_w = st
            progs, use_pallas = s._staged_programs(st)
            s._ensure_acc(progs.acc_init, caps, ns,
                          sparse_cap=profile[-1])
            inputs[s._pfx + 'base'] = np.int64(s._acc_batch << 32)
            parts.append((progs.fold, use_pallas))
            # epoch sig covers window origins/host_translate, which
            # can change while caps stay the same
            mkey = (i, pn, profile, s._epoch_sig)
            pkey = self._pkey_memo.get(mkey)
            if pkey is None:
                pkey = s._program_key(caps, pn, profile)
                self._pkey_memo[mkey] = pkey
            key_parts.append((pkey, use_pallas))

        # jitted programs cache globally (like _PROGRAM_CACHE's traced
        # folds): every request constructs a fresh stack, and
        # re-tracing an N-metric program per build costs seconds
        ckey = tuple(key_parts)
        run = _STACK_CACHE.get(ckey)
        if run is None:
            jax, jnp = get_jax()
            folds = [p[0] for p in parts]
            ups = [p[1] for p in parts]
            on_mesh = scans[0]._device_mesh() is not None

            def stacked(args, accs):
                outs = tuple(f(args, a, u)
                             for f, a, u in zip(folds, accs, ups))
                # one fresh, non-donated completion token for the
                # whole batch, derived from the outputs.  Unlike the
                # (donated) accumulator leaves it never re-enters the
                # fold, so the pipeline can hold it and block on it
                # after later batches have consumed the accumulator
                # buffers (see DeviceScan._note_dispatch)
                if on_mesh:
                    # a token a scan, and for a sparse set (its leaves
                    # carry the chips on a leading axis) one a chip:
                    # summed into one scalar they would be a collective
                    # a batch, which the sparse fold keeps the sets
                    # free of
                    return outs, tuple(
                        jnp.sum(o[-1], axis=-1).astype(jnp.int32)
                        for o in outs)
                tok = jnp.int32(0)
                for o in outs:
                    tok = tok + jnp.sum(o[-1]).astype(jnp.int32)
                return outs, tok
            run = jax.jit(stacked, **_donate_kw())
            if len(_STACK_CACHE) >= 64:
                # bounded: evict oldest (dict preserves insertion
                # order); a server's distinct lone scans land here too
                _STACK_CACHE.pop(next(iter(_STACK_CACHE)))
            _STACK_CACHE[ckey] = run
        return run


class _ShadowProbe(object):
    """Background device audition: replays copies of recent batch
    snapshots through scratch DeviceScan instances (results discarded)
    to measure the REAL pipelined device rate — program compile
    included, which pre-warms the cache the live takeover will hit —
    while the MT host executor keeps owning the stream.  The first
    batch is warmup (compile); the rest run back-to-back with one
    trailing sync, matching production dispatch behavior."""

    COLLECT = 5      # 1 warmup + 4 measured batches

    def __init__(self, make_scans, make_provider, make_weights,
                 make_alive=None):
        self.make_scans = make_scans
        self.make_provider = make_provider
        self.make_weights = make_weights
        # production may pass a non-None alive mask (the build path's
        # shared datasource-filter eval); the replay must match, or the
        # staged profile (gen_alive) — and so the program cache key —
        # differs from what the takeover will run
        self.make_alive = make_alive or (lambda n: None)
        self.items = []
        self.rate = None
        self.failed = False
        self.done = False
        self.closed = False
        self._event = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def feed(self, snap, n):
        if self.done or self.closed or len(self.items) >= self.COLLECT:
            return
        self.items.append((snap, n))
        if len(self.items) >= self.COLLECT:
            self._event.set()

    def close(self):
        """End-of-stream / decision-made: wake the thread so it exits
        (failing fast on an incomplete collection) instead of holding
        batch snapshots for the wait timeout."""
        self.closed = True
        self._event.set()

    def _run(self):
        try:
            # batches arrive one per flush; collect-then-run so queue
            # gaps never pollute the rate measurement
            self._event.wait(timeout=600.0)
            items = self.items
            if self.closed or len(items) < 2:
                self.items = []
                self.failed = True
                return
            scans = self.make_scans()
            for s in scans:
                s._backend_ok = True
                # scratch scans: their results are discarded by design,
                # so an unflushed accumulator here is not lost work
                _SCAN_LEAKS.untrack(s)
            # the audition replays through the stack — the thing
            # production runs after a takeover, a scan's as a build's —
            # so the measured rate reflects it and the prewarmed
            # _STACK_CACHE
            stack = DeviceScanStack(scans)

            def run_one(snap, n):
                return stack._process_device(
                    self.make_provider(snap), self.make_weights(snap, n),
                    self.make_alive(n))

            if not run_one(*items[0]):       # warmup: trace + compile
                self.failed = True
                return
            for s in scans:
                s._sync_device()
            t0 = time.monotonic()
            seen = 0
            for snap, n in items[1:]:
                if not run_one(snap, n):
                    self.failed = True
                    return
                seen += n
            for s in scans:
                s._sync_device()
            elapsed = time.monotonic() - t0
            self.rate = seen / elapsed if elapsed > 0 else float('inf')
        except Exception:
            self.failed = True
        finally:
            self.items = []     # release the pinned snapshots
            self.done = True


class AutoDeviceScan(DeviceScan):
    """auto-mode DeviceScan: small scans stay on the host (device
    dispatch/compile latency dominates — the backend is not even
    probed below the threshold), large ones escalate to the device
    path mid-stream (host-processed batches were merged immediately,
    so insertion order is preserved), and a probation window
    de-escalates if the device turns out slower than the host
    (crossover detection).

    Unlike forced mode, auto NEVER blocks the stream on device
    initialization: the backend probe (which takes seconds on a
    chip) runs on a background thread while
    the host engine keeps scanning, and the switch happens only once
    (a) the probe has succeeded, (b) the stream's byte progress
    suggests enough work remains to amortize the program compile, and
    (c) — on the MT path — the device has WON a shadow audition:
    copies of live batches replayed through scratch DeviceScans on a
    background thread, so the measured pipelined device rate (compile
    pre-warmed for the real takeover) must beat the observed host rate
    by SHADOW_MARGIN before the stream is touched at all.  A host
    engine that is already faster is never disturbed."""

    ESCALATE_RECORDS = 1 << 19
    REQUIRE_ACCELERATOR = True
    PROBATION_RECORDS = 1 << 17
    AUTO_STREAM = True
    # minimum estimated remaining host-engine seconds to justify the
    # switch (covers compile + retrace + probation overhead)
    MIN_REMAINING_SECONDS = 3.0
    # without a size hint (stdin pipes), switch only deep into a stream
    UNKNOWN_SIZE_RECORDS = 4 << 20
    # shadow audition: take over only when the measured device rate
    # beats the observed host rate by this factor (hysteresis — a
    # near-tie is not worth the transition)
    SHADOW_MARGIN = 1.15
    # warm start: when the persisted audition cache says this query
    # shape already WON on a device, escalate much earlier (the
    # compile is in the XLA cache, the verdict is measured — the
    # half-million-record detour only re-pays overheads a previous
    # run already amortized).  The full shape+backend key still gates
    # the actual takeover, so a backend mismatch merely re-auditions.
    WARM_ESCALATE_RECORDS = 1 << 16
    WARM_MIN_REMAINING_SECONDS = 0.75

    def enable_shadow(self, make_scans, make_provider, make_weights,
                      make_alive=None):
        """MT-path integration: before the device may take the stream,
        it must win an audition on copies of live batches (fed via
        shadow_feed) against the observed host rate — so a host engine
        that is already faster is never disturbed at all."""
        self._shadow_ctx = (make_scans, make_provider, make_weights,
                            make_alive)

    def shadow_feed(self, snap, n):
        sp = self._shadow
        if sp is not None and not sp.done:
            sp.feed(snap, n)

    def _audition_shape(self):
        """The program-shaping query structure (breakdown plans,
        predicate ASTs, synthetic fields, time-boundedness) — the
        backend-independent half of the audition key."""
        plans = [(p.kind, p.name, p.field, p.step)
                 for p in (self._plans or [])]
        return jsv.json_stringify([
            plans,
            jsv.json_stringify(self.ds_pred.ast)
            if self.ds_pred is not None else None,
            jsv.json_stringify(self.user_pred.ast)
            if self.user_pred is not None else None,
            [[s['name'], s['field']] for s in self.synthetic],
            self.time_bounds is not None,
        ])

    def _audition_key(self):
        """Cache key of this scan's audition: the query shape plus the
        backend identity — the pair that determines which side wins on
        a given rig.  Initializes the backend (_backend_id), so only
        call it after the probe succeeded."""
        return self._audition_shape() + '@' + _backend_id()

    def _warm_hint(self):
        """Memoized shape-only audition-cache lookup — safe BEFORE the
        backend probe (no jax initialization): it only tunes how
        eagerly this scan escalates; the full shape+backend verdict
        still gates the takeover itself."""
        hint = getattr(self, '_warm_hint_memo', ())
        if hint == ():
            hint = audition_cache_shape_hint(self._audition_shape())
            self._warm_hint_memo = hint
        return hint

    def _escalate_records(self):
        if self._warm_hint() is True:
            return min(self.ESCALATE_RECORDS,
                       self.WARM_ESCALATE_RECORDS)
        return self.ESCALATE_RECORDS

    def _record_crossover(self, won, rate):
        audition_cache_put(self._audition_key(), won,
                           device_rate=rate,
                           host_rate=self._host_rate)

    def _engage_device(self):
        if self._escalated:
            return bool(self._backend_ok)
        if not self._worth_switching():
            # nothing to gain: don't even start the probe thread (its
            # backend initialization steals cycles from the MT host
            # pipeline on small machines)
            return False
        if self._backend_ok is None:
            if self._probe_thread is None:
                self._probe_thread = threading.Thread(
                    target=self._async_probe, daemon=True)
                self._probe_started = time.monotonic()
                self._probe_thread.start()
            result = self._probe_result
            if result is None:
                # wedge armor: a hung backend leaves the probe thread
                # stuck forever — the scan already runs on the host,
                # but give up (and say so) past the probe deadline so
                # the audition machinery stops waiting on it
                if time.monotonic() - self._probe_started > \
                        probe_deadline_s():
                    LOG.info('device backend probe exceeded deadline; '
                             'staying on host',
                             deadline_s=probe_deadline_s())
                    self._disabled = True
                return False     # still probing; host path continues
            self._probe_thread = None
            self._backend_ok = result
            if not result:
                self._disabled = True
                return False
        if not self._backend_ok:
            return False
        ctx = self._shadow_ctx
        if ctx is not None:
            sp = self._shadow
            if sp is None:
                # persisted verdict from a previous identically-shaped
                # run on this backend: skip the ~5-batch shadow-probe
                # warmup entirely (repeat CLI scans used to re-pay it
                # every invocation, which made auto decline the device
                # for every benchmark-sized job)
                cached = audition_cache_get(self._audition_key())
                if cached is False:
                    LOG.info('cached audition verdict: device loses; '
                             'staying on host')
                    self._disabled = True
                    return False
                if cached is True:
                    hr = self._current_host_rate()
                    if hr is not None:
                        self._host_rate = hr   # probation baseline
                    LOG.info('cached audition verdict: device wins; '
                             'taking over stream')
                else:
                    LOG.debug('device audition started',
                              records_seen=self._records_seen)
                    self._shadow = _ShadowProbe(*ctx)
                    return False
            else:
                if not sp.done:
                    return False
                if sp.failed or sp.rate is None:
                    LOG.info('device audition failed; staying on host')
                    self._disabled = True
                    return False
                hr = self._current_host_rate()
                if hr is not None and \
                        sp.rate < hr * self.SHADOW_MARGIN:
                    LOG.info('device lost audition; staying on host',
                             device_rate=_rate_field(sp.rate),
                             host_rate=_rate_field(hr),
                             margin=self.SHADOW_MARGIN)
                    audition_cache_put(self._audition_key(), False,
                                       device_rate=sp.rate,
                                       host_rate=hr)
                    self._disabled = True
                    return False
                audition_cache_put(self._audition_key(), True,
                                   device_rate=sp.rate, host_rate=hr)
                if hr is not None:
                    self._host_rate = hr   # probation baseline
                LOG.info('device won audition; taking over stream',
                         device_rate=_rate_field(sp.rate),
                         host_rate=_rate_field(hr))
        self._escalated = True
        LOG.info('escalated to device path',
                 records_seen=self._records_seen)
        return True

    def _current_host_rate(self):
        if self._t0 is None or not self._host_records:
            return None
        elapsed = time.monotonic() - self._t0
        return self._host_records / elapsed if elapsed > 0 else None

    def _async_probe(self):
        """Background backend probe; publishes a bool to
        _probe_result (single assignment, read by the stream
        thread)."""
        try:
            self._probe_result = self._probe_ok()
        except Exception:
            self._probe_result = False

    def _worth_switching(self):
        """Estimated remaining host-engine time exceeds the switch
        overhead.  Uses the stream's byte progress when available;
        falls back to a deep-stream record threshold.  A warm cached
        win lowers both bars: the compile and the measurement that the
        switch overhead pays for already happened in a previous run."""
        if self._t0 is None or not self._records_seen:
            return False
        elapsed = time.monotonic() - self._t0
        if elapsed <= 0:
            return False
        warm = self._warm_hint() is True
        rate = self._records_seen / elapsed
        prog = self._progress
        # the warm thresholds only ever LOWER the bar (min): a cached
        # win must never make auto more reluctant than a cold start
        if prog and prog[0] > 0 and prog[1] > 0:
            est_total = self._records_seen * (prog[1] / prog[0])
            remaining = max(0.0, est_total - self._records_seen)
            return remaining / rate >= (
                min(self.MIN_REMAINING_SECONDS,
                    self.WARM_MIN_REMAINING_SECONDS)
                if warm else self.MIN_REMAINING_SECONDS)
        return self._records_seen >= (
            min(self.UNKNOWN_SIZE_RECORDS, self.WARM_ESCALATE_RECORDS)
            if warm else self.UNKNOWN_SIZE_RECORDS)


def scan_class():
    """The scan implementation for the current engine mode: DeviceScan
    when a device backend should run the batch pipeline, else the host
    VectorScan.  (DN_ENGINE=jax forces the device path; auto uses it on
    accelerator backends for large inputs.)

    Initializes NO backend: auto mode routes on accelerator_likely()
    (pure env inspection), and the device classes probe the real
    backend lazily on the first batch past their escalation threshold —
    so a CLI scan over a small file never blocks on device
    start-up."""
    mode = engine_mode()
    if mode == 'jax':
        return DeviceScan
    if mode == 'auto' and accelerator_likely():
        return AutoDeviceScan
    # 'vector' pins the vectorized host engine (no device routing);
    # 'host' (handled upstream) pins the per-record reference path
    return VectorScan
