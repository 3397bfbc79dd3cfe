"""Device-offloaded index query: the stacked batch folded on the
device in one packed upload and one dispatch.

This module is the device engine behind the stacked index-query path
(index_query_stack.run_stacked): once the stacked batch exists, the
per-tuple weight sums are SURVEY §2.3's "index shards materialized as
dense bucket tensors merged via psum/scatter-add".  run_stacked
already holds the whole answer's input as two flat arrays, every row's
query-global segment id and its weight, and the fold takes them as
they are:

* **One packed pair.**  The segment ids and the i64 weights go into
  one i64[2, rows] array, padded to a row count from a fixed ladder
  (pad_rows: 4096, then times four up to 2^18, then times two); pad
  rows carry the last segment and weight 0.  No per-shard loop, no
  local codes, no translation tables: nothing is derived that the
  stack did not already compute.
* **One upload, one dispatch, one fetch.**  The pair rides into the
  jitted program (sums_program, keyed (rows, segments)) as its one
  argument, so the program's call carries the upload; the program is
  `segment_sum(pair[1], pair[0], num_segments)`; np.asarray fetches
  the i64[segments] accumulator once.  The ladder decides the
  compiles: a year of daily shards of a 400-tuple metric spans four
  row counts, a quarter of hourly shards served from rollups reaches
  2^20, and residency.prewarm compiles the six rungs up to there
  before the first request.
* **Residency.**  Inside a residency-armed `dn serve`
  (serve/residency.py) the folded accumulator pins under the content
  digest of its inputs (the PR 17 contract) and retires on the writer
  epoch, so an exact repeat skips the upload, the dispatch and the
  fetch.  Nothing else is pinned: a year's upload is at most 4 MB.
* **Audition-gated auto.**  The persisted audition cache
  (device_scan.dn_auditions.json) grows an `iq:` verdict family:
  under DN_ENGINE=auto the lane escalates to the device when a fresh
  verdict says the device won this query shape on this backend, and
  auditions (device vs host, timed, byte-compared) only where the
  backend is already warm — a cold `dn query` never pays backend init
  to ask.  DN_INDEX_DEVICE=1 forces the lane, =0 pins the host
  bincount; engine_mode()=jax engages it exactly as before.

Byte identity with the host path is the non-negotiable contract at
every cardinality: sums run in i64 (exact for the integer weights the
stacked gate admits), the audition path verifies equality before
persisting a win, and every structural refusal (overflowing dense
segments, wedged backend, jax unavailable) falls back to the host
bincount with the stacked path's ordering — `canonical_item_sort`
order included — untouched.
"""

import numpy as np

from .obs import metrics as obs_metrics

# sticky per-process device availability — SHARED with the legacy
# single-dispatch lane in index_query_stack (one verdict per process,
# whichever lane trips it first)
_DEVICE_STATE = {'ready': None, 'warned': False}

# the fold's programs keyed (rows, segments) — shared with the legacy
# single-dispatch lane and residency.prewarm
_SUMS_CACHE = {}

# per-process engagement snapshot for /stats (server.py reads it):
# dispatches/shards/rows since process start, last auto decision
_ENGAGE = {
    'dispatches': 0,
    'shards': 0,
    'rows': 0,
    'padded_rows': 0,
    'h2d_bytes': 0,
    'auditions': 0,
    'last_lane': None,
}


def _reset_device_state():
    """Test hook (shared with index_query_stack)."""
    _DEVICE_STATE['ready'] = None
    _DEVICE_STATE['warned'] = False


def _warn_device(reason):
    """The device lane cannot run.  Forced (DN_INDEX_DEVICE=1 or
    DN_ENGINE=jax) that is an error; a lane auto mode chose warns once
    and the host path answers."""
    from .engine import engine_mode, index_device_mode
    if index_device_mode() == '1' or engine_mode() == 'jax':
        from .errors import DNError
        raise DNError('device index-query lane unavailable (%s)'
                      % reason)
    if not _DEVICE_STATE['warned']:
        _DEVICE_STATE['warned'] = True
        import sys
        sys.stderr.write('dn: warning: device index-query lane '
                         'unavailable (%s); using host path\n' % reason)


def _reset_engagement():
    """Test hook: zero the per-process engagement snapshot."""
    for k in list(_ENGAGE):
        _ENGAGE[k] = None if k == 'last_lane' else 0


def _pow2(x, floor=8):
    p = floor
    while p < x:
        p <<= 1
    return p


# the row ladder: 4096, then times four up to 2^18, then times two.
# Four programs cover every batch of up to 262,144 rows (a year of
# daily shards of a 400-tuple metric is 145,000), and past that the
# padding's bytes matter more than one compile
ROW_FLOOR = 1 << 12
ROW_COARSE_TOP = 1 << 18
# what residency.prewarm compiles up to: a quarter of hourly shards of
# a 400-tuple metric, served from its rollups, is one batch of some
# 670,000 rows (a rollup shard holds its fine shards' rows, not their
# sum), so a server meets the two rungs past 2^18 in its first minute
ROW_PREWARM_TOP = 1 << 20
SEGMENT_FLOOR = 1 << 9


def pad_rows(n):
    """The ladder's row count for a batch of `n` rows."""
    p = ROW_FLOOR
    while p < n:
        p <<= 2 if p < ROW_COARSE_TOP else 1
    return p


def pad_segments(nuniq):
    """The accumulator's padded length: a power of two from 512, so
    that every aggregate of up to 512 tuples shares its row count's
    one program (the fetch is 8 bytes a segment)."""
    return _pow2(nuniq, SEGMENT_FLOOR)


def ladder(top=ROW_COARSE_TOP):
    """Every row count the ladder holds up to `top`: the four coarse
    rungs up to 2^18, or with ROW_PREWARM_TOP the six programs
    residency.prewarm compiles."""
    rows = [ROW_FLOOR]
    while rows[-1] < top:
        rows.append(pad_rows(rows[-1] + 1))
    return rows


# -- lane routing -----------------------------------------------------------

def _audition_key(nrows, nuniq):
    """Audition-cache key family for index queries: log2-bucketed
    (rows, uniques) — the two sizes that decide the fold's program —
    plus the backend identity the verdict was measured on (appended
    by the caller via _backend_id)."""
    lr = _pow2(max(nrows, 1)).bit_length() - 1
    lu = _pow2(max(nuniq, 1)).bit_length() - 1
    return 'iq:r%d:u%d' % (lr, lu)


def _audition_warm():
    """Whether an auto-mode audition may initialize/touch the backend
    here: only when the process already paid backend init (serve
    pre-warm, a prior scan) or a serve residency manager is armed.  A
    cold ad-hoc `dn query` never blocks on plugin bring-up just to
    ask a question the host path answers in milliseconds."""
    from .ops import backend_probed
    if backend_probed():
        return True
    from .serve import residency as mod_residency
    return mod_residency.active() is not None


def lane_decision(nrows, nuniq):
    """('device'|'audition'|'host') for this aggregation.  'device'
    executes with clean host fallback; 'audition' executes BOTH paths,
    byte-compares, times, and persists the verdict the next auto query
    routes on."""
    from .engine import engine_mode, index_device_mode
    mode = index_device_mode()
    if mode == '0':
        return 'host'
    eng = engine_mode()
    if eng == 'jax' or mode == '1':
        return 'device'
    if eng != 'auto':
        return 'host'            # host/vector pins stay host
    from . import device_scan as mod_ds
    hint = mod_ds.audition_cache_shape_hint(_audition_key(nrows,
                                                          nuniq))
    if hint is True:
        return 'device'
    if hint is None and _audition_warm():
        return 'audition'
    return 'host'


# -- the fold program -------------------------------------------------------

def sums_program(rows, segments):
    """Jitted i64[2, rows] (segment ids over weights) -> i64[segments]
    sums: the scatter-add that merges every shard's rows into the
    dense accumulator in one dispatch.  One argument, so a call with
    the host's packed pair is one upload."""
    prog = _SUMS_CACHE.get((rows, segments))
    if prog is None:
        from .ops import get_jax
        jax, _jnp = get_jax()

        def run(pair):
            return jax.ops.segment_sum(pair[1], pair[0],
                                       num_segments=segments)
        prog = jax.jit(run)
        if len(_SUMS_CACHE) >= 32:
            _SUMS_CACHE.pop(next(iter(_SUMS_CACHE)))
        _SUMS_CACHE[(rows, segments)] = prog
    return prog


def pack_pair(inv, weights, rows, segments):
    """The program's one argument: row 0 the segment ids, row 1 the
    weights as i64 (exact: the stacked gate admits integers only), pad
    rows on the last segment with weight 0."""
    n = len(inv)
    pair = np.empty((2, rows), dtype=np.int64)
    pair[0, :n] = inv
    pair[0, n:] = segments - 1
    pair[1, :n] = weights
    pair[1, n:] = 0
    return pair


def _residency():
    from .serve import residency as mod_residency
    return mod_residency.active()


def stats_doc():
    """Engagement snapshot for /stats' device section."""
    doc = dict(_ENGAGE)
    d = doc['dispatches']
    doc['shards_per_dispatch'] = round(doc['shards'] / d, 2) if d \
        else 0.0
    return doc


# -- execution --------------------------------------------------------------

def _device_fold(inv, weights, nuniq, shard_ctx):
    """The fold: the stacked batch packed once, uploaded and summed
    by one dispatch, fetched once.  Returns (the fetched i64[nuniq]
    accumulator as a host ndarray, the device array it came from, the
    padded row count).  Raises on any backend trouble — the caller
    owns fallback and the sticky state.  `shard_ctx` is (sids i64[n]
    ascending, the number of shards loaded) from the stacked path, or
    None (one anonymous shard)."""
    n = len(inv)
    rows, segments = pad_rows(n), pad_segments(nuniq)
    with obs_metrics.leaf_stage('index_fold.stage'):
        pair = pack_pair(inv, weights, rows, segments)
        if shard_ctx is None:
            nstaged = 1
        else:
            sid = shard_ctx[0]
            nstaged = 1 + int(np.count_nonzero(sid[1:] != sid[:-1]))
    obs_metrics.inc('index_fold_shards_staged', nstaged)
    obs_metrics.inc('index_fold_rows', n)
    obs_metrics.inc('index_fold_padded_rows', rows)
    # the call carries the upload: the pair is its one argument
    with obs_metrics.leaf_stage('index_fold.dispatch'):
        acc = sums_program(rows, segments)(pair)
    with obs_metrics.leaf_stage('index_fold.device_wait'):
        try:
            acc.block_until_ready()
        except AttributeError:
            pass
    with obs_metrics.leaf_stage('index_fold.fetch'):
        out = np.asarray(acc)[:nuniq]
    return out, acc, rows


def batched_sums(inv, weights, nuniq, shard_ctx=None, stage=None,
                 audition=False):
    """Per-tuple weight sums through the batched device engine, or
    None for the host bincount.  Exactness contract: i64 sums over the
    gate-admitted integer weights are bit-equal to the host path.
    The first device contact in the process runs under the probe
    deadline (device_scan.run_with_deadline): a wedged backend warns
    once and falls back instead of hanging `dn query`.  With
    `audition=True` both paths run, results are byte-compared, and
    the timed verdict persists to the audition cache for the next
    auto-mode query."""
    from .engine import MAX_DENSE_SEGMENTS
    if nuniq > MAX_DENSE_SEGMENTS or len(inv) == 0:
        return None
    st = _DEVICE_STATE
    if st['ready'] is False:
        # the verdict is sticky: a forced lane is refused again (an
        # error a request, never a quiet host answer after the first),
        # a lane auto chose has warned once
        _warn_device('found unusable earlier in this process')
        return None
    from .ops import get_jax
    if get_jax() is None:
        st['ready'] = False
        _warn_device('jax unavailable')
        return None

    res = _residency()
    rkey = repoch = None
    if res is not None:
        from . import index_query_mt as mod_iqmt
        from .serve import residency as mod_residency
        rkey = mod_residency.content_key(
            'iq-acc', (inv, weights.astype(np.int64)),
            (pad_segments(nuniq), nuniq))
        repoch = mod_iqmt.cache_epoch()
        pinned = res.get(rkey, repoch)
        if pinned is not None:
            _ENGAGE['last_lane'] = 'device'
            if stage is not None:
                stage.bump_hidden('index device sums', 1)
            return pinned.copy()

    import time as mod_time
    t0 = mod_time.monotonic()

    def compute():
        from .ops import backend_ready
        if not backend_ready():
            return None
        return _device_fold(inv, weights, nuniq, shard_ctx)

    if st['ready'] is None:
        from .device_scan import run_with_deadline, probe_deadline_s
        status, out = run_with_deadline(compute, probe_deadline_s(),
                                        'iq-device-batch')
        if status == 'timeout':
            st['ready'] = False
            _warn_device('backend unresponsive past the %.0fs probe '
                         'deadline' % probe_deadline_s())
            return None
        if status == 'error' or out is None:
            st['ready'] = False
            _warn_device('backend failed to initialize')
            return None
        st['ready'] = True
    else:
        try:
            out = compute()
        except Exception as e:
            st['ready'] = False
            _warn_device(repr(e))
            return None
        if out is None:
            st['ready'] = False
            _warn_device('backend failed to initialize')
            return None
    acc, dev_acc, rows = out
    device_s = mod_time.monotonic() - t0
    host = acc.astype(np.float64)

    h2d_bytes = 16 * rows                       # two i64 lanes
    _ENGAGE['dispatches'] += 1
    _ENGAGE['shards'] += shard_ctx[1] if shard_ctx is not None else 1
    _ENGAGE['rows'] += len(inv)
    _ENGAGE['padded_rows'] += rows
    _ENGAGE['h2d_bytes'] += h2d_bytes
    _ENGAGE['last_lane'] = 'device'
    if stage is not None:
        stage.bump_hidden('index device sums', 1)

    if audition:
        from . import device_scan as mod_ds
        t1 = mod_time.monotonic()
        ref = np.bincount(inv, weights=weights, minlength=nuniq)
        host_s = max(mod_time.monotonic() - t1, 1e-9)
        equal = np.array_equal(host, ref)
        rate_d = len(inv) / max(device_s, 1e-9)
        rate_h = len(inv) / host_s
        won = bool(equal and rate_d > rate_h)
        key = '%s@%s' % (_audition_key(len(inv), nuniq),
                         mod_ds._backend_id())
        mod_ds.audition_cache_put(key, won, device_rate=rate_d,
                                  host_rate=rate_h)
        _ENGAGE['auditions'] += 1
        if not equal:
            # never ship an inexact device result — and never trust
            # this lane again this process (exactness gate tripped)
            st['ready'] = False
            _warn_device('device/host sums mismatch (audition)')
            return None

    if res is not None:
        # pin the final device-side accumulator + its one fetched
        # copy: an exact repeat answers with zero transfer either way
        res.put(rkey, repoch, dev_acc, host, h2d_bytes=h2d_bytes)
        return host.copy()
    return host


def aggregate_weights(inv, weights, nuniq, stage=None,
                      shard_ctx=None):
    """The stacked path's aggregation seam: route to the batched
    device engine per lane_decision, host np.bincount otherwise —
    byte-identical either way."""
    lane = lane_decision(len(inv), nuniq)
    if lane != 'host':
        dense = batched_sums(inv, weights, nuniq,
                             shard_ctx=shard_ctx, stage=stage,
                             audition=(lane == 'audition'))
        if dense is not None:
            return dense
    _ENGAGE['last_lane'] = 'host'
    return np.bincount(inv, weights=weights, minlength=nuniq)
