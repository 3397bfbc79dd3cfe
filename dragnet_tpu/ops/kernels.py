"""jit-compiled scan kernels: fused group-by segment-sum, the sparse
fold, a p2 bucketize.

Device kernels are 32-bit native: TPU has no native 64-bit integer path
(XLA's x64 rewrite rejects the s64 bitcasts that e.g. jnp.frexp emits),
and every quantity here fits 32 bits by construction — dictionary codes
and bucket ordinals are dense small ints, epoch seconds < 2^31, and
integer weights are exact in i32 (float weights use f32).  Exact p2/linear
bucketization happens host-side in the engine (numpy frexp on f64); the
device-side p2_bucketize here (log2 + boundary fix-up, TPU-compilable)
exists for fully-on-device pipelines.

Semantics contract (pinned by differential tests against aggr.py):

* p2: v < 1 -> 0; v >= 1 -> floor(log2 v) + 1   (DTrace quantize)
* predicate outcomes are ternary (FALSE/TRUE/ERROR); device_scan's
  program folds them with JS short-circuit rules
* fuse + segment-sum: mixed-radix composite key into a dense
  accumulator; partials merge by addition (psum across a mesh)
* sparse fold: a batch of fused i64 keys merged into a sorted,
  compacted resident set by one sort, a prefix sum and shifts — per
  key the exact i64 weight sum and the smallest first-occurrence index
"""

import functools

from . import get_jax

FALSE, TRUE, ERROR = 0, 1, 2
I64MAX = (1 << 63) - 1


def p2_bucketize(jnp, v):
    """f32 values -> i32 p2 bucket ordinals, exact at bucket boundaries.

    Uses log2 with a +-1 fix-up instead of frexp: frexp's exponent
    extraction lowers to a 64-bit bitcast that TPU's x64 rewrite cannot
    compile, while log2/exp2 on f32 are native.
    """
    e = jnp.floor(jnp.log2(jnp.maximum(v, 1.0))).astype('int32')
    pow_e = jnp.exp2(e.astype('float32'))
    e = jnp.where(pow_e > v, e - 1, e)
    e = jnp.where(pow_e * 2.0 <= v, e + 1, e)
    return jnp.where(v < 1, 0, e + 1).astype('int32')


@functools.lru_cache(maxsize=None)
def make_aggregate(radices, capacity, integer_weights=True):
    """Jitted (codes[ncols,cap] i32, weights[cap], alive[cap] bool) ->
    dense accumulator of size prod(radices).

    XLA lowers the segment-sum to a scatter-add.  Cached per shape so
    growing dictionaries only recompile when a radix grows.
    """
    jax, jnp = get_jax()
    num_segments = 1
    for r in radices:
        num_segments *= int(r)
    wdtype = 'int32' if integer_weights else 'float32'

    @jax.jit
    def agg(codes, weights, alive):
        fused = jnp.zeros((capacity,), dtype='int32')
        for i, r in enumerate(radices):
            fused = fused * jnp.int32(r) + codes[i]
        fused = jnp.where(alive, fused, num_segments)  # dead -> overflow
        w = jnp.where(alive, weights.astype(wdtype),
                      jnp.zeros((), dtype=wdtype))
        dense = jax.ops.segment_sum(w, fused,
                                    num_segments=num_segments + 1)
        return dense[:num_segments]

    return agg


def _block_cumsum(jnp, x):
    """Inclusive prefix sum of a long 1-D array: within rows of up to
    1024, then the row totals.  The same sums as `jnp.cumsum(x)`
    (wrapping integers), which the v5e compiler takes 56 s to compile
    for 1.2 M i64 and this 3 s."""
    n = x.shape[0]
    rows = x.reshape(-1, min(1024, n & -n))
    within = jnp.cumsum(rows, axis=1)
    total = within[:, -1]
    return (within + (jnp.cumsum(total) - total)[:, None]).reshape(n)


def sparse_fold(jax, jnp, acc, cvec_b, keys_b, w_b, first_b):
    """One batch merged into the sparse (high-cardinality) accumulator.

    acc = (keys, wsum, first, cvec, stats): `keys` ascending and
    distinct, padded with I64MAX; `wsum` (0 in the padding) and `first`
    (I64MAX in the padding) ride with them; stats = [nuniq, over].  The
    batch brings fused i64 keys (I64MAX on a dead row), i64 weights of
    either sign (0 on a dead row) and first-occurrence indices.

    A reduction over sorted runs with no scatter and no gather over the
    set (on the TPU those run one element at a time), and with one
    sort on one key (a sort is what this compiler compiles slowly: a
    second key or a second sort costs the cold build 20 s and more):

    1. sort the concatenation by key, `first` and the weight riding
       along: equal keys form a run, live runs first, the dead last;
    2. the run's smallest `first` reaches its last row by a doubling
       scan (min with the row 1, 2, 4, ... back while that row has the
       same key); a run is at most one resident row and the batch;
    3. an inclusive prefix sum of the weights (wrapping i64, so the
       differences below are exact whatever the signs);
    4. the last rows of the live runs move to the front, each left by
       the number of other rows before it, one bit of that number a
       stage: order is kept and no two of them meet, because the rows
       between two of them are never fewer than the difference of
       their moves.  That number is at most the batch's rows;
    5. runs tile the sorted rows, so a run's sum is its prefix less the
       previous run's, now its neighbour.

    Runs past the capacity fall off the slice; `over` keeps that loud
    (sticky), and `nuniq` counts every live run, kept or not."""
    keys0, wsum0, first0, cvec0, stats0 = acc
    i64 = jnp.int64
    cap = keys0.shape[0]
    top = i64(I64MAX)
    stages = [1 << b for b in range(int(keys_b.shape[0]).bit_length())]

    def back(x, s):             # x[i - s]; I64MAX before the first row
        return jnp.concatenate([jnp.full((s,), top, x.dtype), x[:-s]])

    def ahead(x, s):            # x[i + s]; 0 (False) past the last row
        return jnp.concatenate([x[s:], jnp.zeros((s,), x.dtype)])

    ks, fs, ws = jax.lax.sort(
        (jnp.concatenate([keys0, keys_b]),
         jnp.concatenate([first0, first_b]),
         jnp.concatenate([wsum0, w_b])),
        num_keys=1, is_stable=False)
    for s in stages:
        fs = jnp.where(back(ks, s) == ks,
                       jnp.minimum(fs, back(fs, s)), fs)
    live = jnp.concatenate(
        [ks[1:] != ks[:-1], jnp.ones((1,), dtype=bool)]) & (ks != top)
    nuniq = jnp.sum(live).astype(i64)
    rows = (ks, _block_cumsum(jnp, ws), fs)
    holes = _block_cumsum(jnp, (~live).astype(jnp.int32))
    for b, s in enumerate(stages):
        moves = live & ((holes >> b) & 1).astype(bool)
        comes = ahead(moves, s)
        rows = [jnp.where(comes, ahead(x, s), x) for x in rows]
        holes = jnp.where(comes, ahead(holes, s), holes)
        live = comes | (live & ~moves)
    keys1 = jnp.where(live, rows[0], top)[:cap]
    csum = rows[1][:cap]
    occupied = keys1 != top
    prev = jnp.concatenate([jnp.zeros((1,), dtype=i64), csum[:-1]])
    over = jnp.maximum(stats0[1], (nuniq > cap).astype(i64))
    return (keys1,
            jnp.where(occupied, csum - prev, i64(0)),
            jnp.where(occupied, rows[2][:cap], top),
            cvec0 + cvec_b.astype(i64),
            jnp.stack([nuniq, over]))
