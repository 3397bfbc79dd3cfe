"""Device-offloaded index query: batched shard tensors, on-device
scatter-add merge, residency-pinned hot columns.

This module is the device engine behind the stacked index-query path
(index_query_stack.run_stacked): once the stacked batch exists, the
per-tuple weight sums are SURVEY §2.3's "index shards materialized as
dense bucket tensors merged via psum/scatter-add" — and the cost of
moving bytes between host and device (not measured on the current
chip) dictates the rest of the shape:

* **Shard-batch staging.**  Rows arrive already perm-ordered by
  (shard, sort keys...), so each shard occupies one contiguous slice.
  Per shard we stage two pow2-padded i64 tensors — the LOCAL group
  code per row (first-occurrence rank of the row's aggregate tuple
  within the shard) and the integer weight — plus one tiny per-query
  translation table mapping local codes to the query-global segment
  ids.  Local codes are a pure function of (query plan, shard
  content): the slice order is the content-stable sort the stacked
  path already proves byte-parity for, and aggregate-tuple EQUALITY is
  content-determined even where global code values are not.  That is
  what makes the big tensors pinnable across queries whose global code
  space differs (a sliding year window re-keys every global id, but
  363 of 365 shard tensors are unchanged).
* **Slot-packed dispatches.**  Shards group by padded row count R and
  pack S-at-a-time (pow2 ladder, bounded by DN_INDEX_DEVICE_BATCH_ROWS
  and _MAX_SLOTS) into one jitted program: gather each slot's local
  codes through its translation row, then one segment_sum into the
  shared accumulator.  A 365-shard year query becomes a handful of
  device launches instead of 365 host group-bys, and the program cache
  stays O(log^2) on (S, R, T) like the scan path's pow2 ladders.
* **Device-resident fold, ONE fetch.**  The i64 accumulator rides
  device-resident through every dispatch as each jit's output fed
  into the next (psum-shaped fold, mesh-ready: under a sharded mesh
  the same program body folds partials with psum), so nothing but the
  final demuxed result ever rides the slow D2H path — np.asarray
  once, at the end.
* **Residency.**  Inside a residency-armed `dn serve`
  (serve/residency.py) the staged shard tensors pin in HBM keyed by
  (plan signature, shard integrity identity) — the integrity
  catalog's (size, crc32) when the tree has one, the handle cache's
  statkey otherwise — and retire on the same writer-epoch signal as
  every other pin, so a repeat dashboard query skips the H2D upload
  entirely.  The folded accumulator additionally pins under its
  content digest (the PR 17 contract), so an exact repeat skips the
  dispatches too.
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

import os

import numpy as np

from .obs import metrics as obs_metrics

# sticky per-process device availability — SHARED with the legacy
# single-dispatch lane in index_query_stack (one verdict per process,
# whichever lane trips it first)
_DEVICE_STATE = {'ready': None, 'warned': False}

# slot-packed fold programs keyed (nslots, prow, ptab, pu)
_FOLD_CACHE = {}

# per-process engagement snapshot for /stats (server.py reads it):
# dispatches/shards/rows since process start, last auto decision
_ENGAGE = {
    'dispatches': 0,
    'shards': 0,
    'rows': 0,
    'pinned_shard_hits': 0,
    'h2d_bytes': 0,
    'h2d_saved_bytes': 0,
    'auditions': 0,
    'last_lane': None,
}
_MAX_SLOTS = 64


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


def batch_rows():
    """DN_INDEX_DEVICE_BATCH_ROWS: padded-row budget per dispatch (how
    many shards pack into one launch).  Clamped to a sane floor so a
    misconfigured knob cannot serialize into per-shard dispatches."""
    try:
        v = int(os.environ.get('DN_INDEX_DEVICE_BATCH_ROWS',
                               str(1 << 20)))
    except ValueError:
        v = 1 << 20
    return max(v, 1 << 12)


# -- lane routing -----------------------------------------------------------

def _audition_key(nrows, nuniq):
    """Audition-cache key family for index queries: log2-bucketed
    (rows, uniques) — the two sizes that decide dispatch count and
    accumulator shape — plus the backend identity the verdict was
    measured on (appended by the caller via _backend_id)."""
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


# -- shard identity ---------------------------------------------------------

_CATALOG_DIR_MEMO = {}


def _shard_identity(path, statkey):
    """Residency identity for one shard file: the integrity catalog's
    (size, crc32) when the tree publishes one — content identity that
    survives a byte-identical republish — else the handle cache's
    (mtime_ns, size, ino) statkey.  None when neither exists (the
    shard then stages fresh every query, which is always correct)."""
    from . import integrity as mod_integrity
    d = os.path.dirname(os.path.abspath(path))
    for root in (d, os.path.dirname(d)):
        has = _CATALOG_DIR_MEMO.get(root)
        if has is None:
            has = os.path.exists(mod_integrity.catalog_path(root))
            _CATALOG_DIR_MEMO[root] = has
        if not has:
            continue
        try:
            cat = mod_integrity.cached_catalog(root)
        except Exception:
            break
        rel = os.path.relpath(os.path.abspath(path), root)
        ent = cat.get(rel)
        if ent is not None:
            return ('crc', rel, int(ent[0]), int(ent[1]))
    if statkey is not None:
        return ('stat',) + tuple(statkey)
    return None


def plan_signature(query):
    """Digest of everything that determines a shard's staged tensors
    GIVEN its content: the composed filter inputs, the breakdown
    specs (bucketizer parameters included — they live in the spec
    dicts), and the time window.  Two queries with equal signatures
    stage byte-identical (local, weight) tensors from an identical
    shard."""
    import hashlib
    h = hashlib.blake2b(digest_size=12)
    h.update(repr((query.qc_filter, query.qc_breakdowns,
                   query.qc_before, query.qc_after)).encode())
    return h.hexdigest()


# -- staging ----------------------------------------------------------------

def _stage_shard(inv_sl):
    """(local codes i64[n], ttable i64[nlocal], nlocal) for one
    shard's slice of the perm-ordered batch.  Local code = rank of the
    row's aggregate tuple in the shard's first-occurrence order —
    content-stable, so the padded tensor can pin across queries; the
    ttable maps local -> this query's global segment id."""
    lu, first, linv = np.unique(inv_sl, return_index=True,
                                return_inverse=True)
    order = np.argsort(first, kind='stable')
    rankmap = np.empty(len(lu), dtype=np.int64)
    rankmap[order] = np.arange(len(lu), dtype=np.int64)
    local = rankmap[linv.reshape(-1)]
    return local, lu[order], len(lu)


def _pad_slot(local, w, nlocal, prow):
    """Pow2-pad one shard's staged pair: pad rows carry the sentinel
    local code `nlocal`, whose ttable slot points at the accumulator's
    last segment with weight 0 — the same harmless-pad trick the
    legacy single-dispatch lane uses."""
    pl = np.full(prow, nlocal, dtype=np.int64)
    pl[:len(local)] = local
    pw = np.zeros(prow, dtype=np.int64)
    pw[:len(w)] = w
    return pl, pw


# -- the fold program -------------------------------------------------------

def _fold_program(nslots, prow, ptab, pu):
    """Jitted slot-packed scatter-add fold: `nslots` shard tensors of
    `prow` rows each gather their global segment ids through per-slot
    translation rows [ptab] and merge into the i64[pu] accumulator in
    ONE segment_sum.  The accumulator stays device-resident across
    dispatches by riding the jit output back into the next call — the
    psum-shaped fold.  Deliberately NOT donated: donating the
    accumulator buffer segfaults jaxlib 0.4.36's CPU client under the
    multi-device test mesh (flaky heap corruption on repeated
    donate-and-refeed), and the buffer is pu*8 bytes — there is
    nothing worth donating."""
    prog = _FOLD_CACHE.get((nslots, prow, ptab, pu))
    if prog is None:
        from .ops import get_jax
        jax, jnp = get_jax()

        def run(locs, ws, ttabs, acc):
            lmat = jnp.stack(locs)              # [S, prow]
            wmat = jnp.stack(ws)                # [S, prow]
            seg = jnp.take_along_axis(ttabs, lmat, axis=1)
            return acc + jax.ops.segment_sum(
                wmat.reshape(-1), seg.reshape(-1), num_segments=pu)
        prog = jax.jit(run)
        if len(_FOLD_CACHE) >= 32:
            _FOLD_CACHE.pop(next(iter(_FOLD_CACHE)))
        _FOLD_CACHE[(nslots, prow, ptab, pu)] = prog
    return prog


def _residency():
    from .serve import residency as mod_residency
    return mod_residency.active()


def _note_engagement(ndispatch, nshards, nrows, pinned_hits,
                     h2d_bytes, h2d_saved):
    _ENGAGE['dispatches'] += ndispatch
    _ENGAGE['shards'] += nshards
    _ENGAGE['rows'] += nrows
    _ENGAGE['pinned_shard_hits'] += pinned_hits
    _ENGAGE['h2d_bytes'] += h2d_bytes
    _ENGAGE['h2d_saved_bytes'] += h2d_saved


def stats_doc():
    """Engagement snapshot for /stats' device section."""
    doc = dict(_ENGAGE)
    d = doc['dispatches']
    doc['shards_per_dispatch'] = round(doc['shards'] / d, 2) if d \
        else 0.0
    return doc


# -- execution --------------------------------------------------------------

def _device_fold(inv, w64, nuniq, shard_ctx):
    """The staged, slot-packed, device-resident fold.  Returns the
    fetched i64[nuniq] accumulator (host ndarray).  Raises on any
    backend trouble — the caller owns fallback and the sticky state.
    `shard_ctx` is (sids i64[n] ascending, [(path, statkey)] per
    shard, query) from the stacked path, or None (single anonymous
    shard)."""
    from .ops import get_jax
    jax, _jnp = get_jax()
    pu = _pow2(nuniq)

    if shard_ctx is not None:
        sid, pairs, query = shard_ctx
    else:
        sid = np.zeros(len(inv), dtype=np.int64)
        pairs, query = [(None, None)], None
    nshards_total = (int(sid[-1]) + 1) if len(sid) else 0
    bounds = np.searchsorted(sid, np.arange(nshards_total + 1))

    res = _residency()
    repoch = plan = None
    if res is not None:
        from . import index_query_mt as mod_iqmt
        repoch = mod_iqmt.cache_epoch()
        if query is not None:
            plan = plan_signature(query)

    # stage every non-empty shard: pinned device tensors where
    # residency has them, fresh host arrays (uploaded per dispatch,
    # then pinned) otherwise.  One stage for the whole loop: a year's
    # query stages 365 shards
    staged = []                  # (prow, ttable, dev_local, dev_w)
    pinned_hits = 0
    h2d_bytes = 0
    h2d_saved = 0
    with obs_metrics.leaf_stage('index_fold.stage'):
        for s in range(nshards_total):
            lo, hi = int(bounds[s]), int(bounds[s + 1])
            if lo == hi:
                continue
            local, ttable, nlocal = _stage_shard(inv[lo:hi])
            prow = _pow2(hi - lo)
            key = None
            if plan is not None and s < len(pairs):
                ident = _shard_identity(*pairs[s]) \
                    if pairs[s][0] is not None else None
                if ident is not None:
                    key = ('iq-shard', plan, ident, prow)
                dev = res.get_device(key, repoch)
                if dev is not None:
                    staged.append((prow, ttable, nlocal, dev[0], dev[1]))
                    pinned_hits += 1
                    h2d_saved += prow * 16          # two i64 lanes
                    continue
            pl, pw = _pad_slot(local, w64[lo:hi], nlocal, prow)
            dl = jax.device_put(pl)
            dw = jax.device_put(pw)
            h2d_bytes += pl.nbytes + pw.nbytes
            if key is not None:
                res.put_device(key, repoch, (dl, dw),
                               nbytes=pl.nbytes + pw.nbytes)
            staged.append((prow, ttable, nlocal, dl, dw))
    obs_metrics.inc('index_fold_shards_staged', len(staged))

    if not staged:
        return np.zeros(nuniq, dtype=np.int64), None, 0, 0, 0, 0

    # pack by padded row count: pow2 slot ladder bounded by the
    # batch-rows budget, so a year of daily shards folds in a handful
    # of launches and the program cache stays O(log^2)
    ndispatch = 0
    with obs_metrics.leaf_stage('index_fold.dispatch'):
        groups = {}
        for st in staged:
            groups.setdefault(st[0], []).append(st)
        budget = batch_rows()
        acc = jax.device_put(np.zeros(pu, dtype=np.int64))
        for prow in sorted(groups):
            todo = groups[prow]
            smax = max(1, min(_MAX_SLOTS, budget // prow))
            i = 0
            while i < len(todo):
                s = 1
                while s * 2 <= min(smax, len(todo) - i):
                    s <<= 1
                chunk = todo[i:i + s]
                i += s
                ptab = _pow2(max(c[2] + 1 for c in chunk))
                ttabs = np.full((s, ptab), pu - 1, dtype=np.int64)
                for j, (_pr, tt, nl, _dl, _dw) in enumerate(chunk):
                    ttabs[j, :nl] = tt
                h2d_bytes += ttabs.nbytes
                prog = _fold_program(s, prow, ptab, pu)
                acc = prog(tuple(c[3] for c in chunk),
                           tuple(c[4] for c in chunk), ttabs, acc)
                ndispatch += 1
    with obs_metrics.leaf_stage('index_fold.device_wait'):
        try:
            acc.block_until_ready()
        except AttributeError:
            pass
    # ONE fetch: everything upstream stayed on the device
    with obs_metrics.leaf_stage('index_fold.fetch'):
        out = np.asarray(acc)[:nuniq]
    return out, acc, ndispatch, pinned_hits, h2d_bytes, h2d_saved


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

    w64 = weights.astype(np.int64)
    res = _residency()
    rkey = repoch = None
    if res is not None:
        from . import index_query_mt as mod_iqmt
        from .serve import residency as mod_residency
        rkey = mod_residency.content_key('iq-acc', (inv, w64),
                                         (_pow2(nuniq), nuniq))
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
        return _device_fold(inv, w64, nuniq, shard_ctx)

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
    acc, dev_acc, ndispatch, pinned_hits, h2d_bytes, h2d_saved = out
    device_s = mod_time.monotonic() - t0
    host = acc.astype(np.float64)

    nshards = len(shard_ctx[1]) if shard_ctx is not None else 1
    _note_engagement(ndispatch, nshards, len(inv), pinned_hits,
                     h2d_bytes, h2d_saved)
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

    if res is not None and dev_acc is not None:
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
