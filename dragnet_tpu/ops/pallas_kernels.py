"""Pallas TPU kernel: fused group-by aggregation as a one-hot matmul.

The scan's aggregation (the reference's per-record skinner hash update,
lib/krill-skinner-stream.js -> skinner aggregator) is a segment-sum of
record weights into a dense accumulator.  XLA lowers
`jax.ops.segment_sum` to a scatter-add, which TPU executes poorly
(serialized updates); for the bounded-cardinality accumulators dragnet
queries produce (breakdown radix products, typically <= a few thousand
buckets), the TPU-idiomatic formulation is a *histogram matmul*:

    onehot[s, r] = (fused_key[r] == s)          # VPU compares
    dense[s]    += weights @ onehot[s, :]^T     # MXU reduction

Each (record-block x segment-block) tile builds its one-hot matrix in
VMEM and reduces it on the MXU with `dot_general`, accumulating into a
resident output block across the record-block grid axis (the innermost
grid dimension, so the output tile stays in VMEM).  No scatter, no
atomics, fully dense compute — exactly the shape the systolic array
wants.

Exactness: weights and partial sums are f32; the engine only routes
batches here when every weight is integral and the batch's total weight
is < 2^24, so all sums are exactly representable (the host/f64 path is
the fallback, same contract as the i32 segment-sum kernel in
kernels.py).

Grid-axis semantics (see /opt/skills/guides/pallas_guide.md): the last
grid dimension iterates innermost; an output BlockSpec whose index_map
ignores that dimension keeps its block resident in VMEM across those
steps, making grid = (segment_blocks, record_blocks) an accumulation
loop per segment tile.
"""

import functools

from . import get_jax

# Tile sizes: (BLOCK_R records) x (BLOCK_S segments) one-hot tiles.
# 512x512 f32 = 1 MB in VMEM per tile operand; lane-dim aligned (128).
BLOCK_R = 512
BLOCK_S = 512

# The one-hot formulation does records x segments work, so its cost
# grows linearly with the accumulator size while scatter's stays flat.
# Measured crossover on v5e: pallas 2.8ms vs scatter 11.5ms at 512
# segments (1M records), parity near 8k, scatter wins past that.
MAX_PALLAS_SEGMENTS = 4096


def _round_up(x, m):
    return ((x + m - 1) // m) * m


@functools.lru_cache(maxsize=None)
def _make_call(radices, capacity, interpret):
    """The pallas_call (plus its padded geometry) for a given
    radix/record-capacity shape.  Traceable: usable directly inside
    jit or a shard_map body."""
    j = get_jax()
    assert j is not None
    jax, jnp = j
    from jax.experimental import pallas as pl

    num_segments = 1
    for r in radices:
        num_segments *= int(r)
    s_pad = _round_up(max(num_segments, 1), BLOCK_S)
    r_pad = _round_up(max(capacity, 1), BLOCK_R)

    def kernel(fused_ref, w_ref, out_ref):
        i = pl.program_id(0)  # segment block (outer)
        k = pl.program_id(1)  # record block (inner, accumulating)

        @pl.when(k == 0)
        def _init():
            out_ref[...] = jnp.zeros_like(out_ref)

        fused = fused_ref[...]  # (1, BLOCK_R) i32
        w = w_ref[...]          # (1, BLOCK_R) f32
        # all constants explicitly 32-bit: the engine enables
        # jax_enable_x64, and weak-typed Python literals would become
        # f64/i64 — bitwidths Mosaic's vector layouts reject
        seg = jax.lax.broadcasted_iota(
            jnp.int32, (BLOCK_S, BLOCK_R), 0) + (
                i * jnp.int32(BLOCK_S)).astype(jnp.int32)
        onehot = jnp.where(seg == fused, jnp.float32(1.0),
                           jnp.float32(0.0))
        # (1, BLOCK_R) x (BLOCK_S, BLOCK_R) contracting the record dim
        # -> (1, BLOCK_S) on the MXU.  HIGHEST precision: the default
        # f32 matmul truncates operands to bf16 (8 mantissa bits),
        # which would silently round weights > 256 and break the exact-
        # sum contract
        partial = jax.lax.dot_general(
            w, onehot, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)
        out_ref[...] += partial

    # index maps derive the constant from a program id rather than using
    # a literal 0: under jax_enable_x64 a Python 0 traces as i64 and the
    # (i64, i32) return tuple fails Mosaic's type check
    call = pl.pallas_call(
        kernel,
        grid=(s_pad // BLOCK_S, r_pad // BLOCK_R),
        in_specs=[
            pl.BlockSpec((1, BLOCK_R), lambda i, k: (k - k, k)),
            pl.BlockSpec((1, BLOCK_R), lambda i, k: (k - k, k)),
        ],
        out_specs=pl.BlockSpec((1, BLOCK_S), lambda i, k: (i - i, i)),
        out_shape=jax.ShapeDtypeStruct((1, s_pad), jnp.float32),
        interpret=interpret,
    )
    return call, num_segments, s_pad, r_pad


def onehot_dense(radices, capacity, codes, weights, alive,
                 interpret=False):
    """Traced fused aggregate: (codes[ncols, capacity] i32,
    weights[capacity], alive[capacity] bool) -> dense f32 accumulator of
    prod(radices).  Call under jit or inside a shard_map body; partial
    accumulators merge by addition (psum)."""
    jax, jnp = get_jax()
    call, num_segments, s_pad, r_pad = _make_call(
        tuple(int(r) for r in radices), int(capacity), interpret)
    fused = jnp.zeros((capacity,), dtype='int32')
    for idx, r in enumerate(radices):
        fused = fused * jnp.int32(r) + codes[idx]
    fused = jnp.where(alive, fused, jnp.int32(s_pad))
    w = jnp.where(alive, weights.astype('float32'),
                  jnp.float32(0.0))
    pad = r_pad - capacity
    if pad:
        fused = jnp.pad(fused, (0, pad), constant_values=s_pad)
        w = jnp.pad(w, (0, pad))
    dense = call(fused[None, :], w[None, :])
    return dense[0, :num_segments]


@functools.lru_cache(maxsize=None)
def make_pallas_aggregate(radices, capacity, interpret=False):
    """Jitted form of onehot_dense — same contract as
    kernels.make_aggregate: dead records drop out, partials merge by
    addition."""
    jax, jnp = get_jax()

    @jax.jit
    def agg(codes, weights, alive):
        return onehot_dense(radices, capacity, codes, weights, alive,
                            interpret=interpret)

    return agg


def pallas_ok(num_segments):
    """Whether the one-hot matmul formulation is the right tool for
    this accumulator size."""
    return 0 < num_segments <= MAX_PALLAS_SEGMENTS


def available():
    """Pallas usable (importable and not disabled via DN_PALLAS=0)."""
    import os
    if os.environ.get('DN_PALLAS', '1') == '0':
        return False
    j = get_jax()
    if j is None:
        return False
    try:
        from jax.experimental import pallas  # noqa: F401
    except Exception:
        return False
    return True


def should_use(num_segments, total_weight):
    """The single routing gate for the one-hot kernel (engine and mesh
    both use this, so eligibility can never diverge between them):
    accumulator small enough for the matmul formulation, f32-exact
    total weight, pallas importable, and a backend Mosaic compiles for
    (interpret mode is a debugging emulator, not a production path —
    DN_PALLAS=force overrides for the CPU test mesh)."""
    import os
    if not pallas_ok(num_segments):
        return False
    if not (total_weight < 2 ** 24):
        return False
    if not available():
        return False
    if os.environ.get('DN_PALLAS') == 'force':
        return True
    from . import is_tpu_backend
    return is_tpu_backend()


def needs_interpret():
    """Mosaic only compiles for the TPU backend; others (the CPU test
    mesh) run the kernel in interpret mode."""
    from . import is_tpu_backend
    return not is_tpu_backend()
