"""Mesh-sharded aggregation kernels.

The scan reduce is a commutative-monoid merge (sum of weights per key
tuple), so distribution is: shard the record axis across mesh devices,
segment-sum locally, then all-reduce (psum) the dense accumulators over
ICI.  For large accumulators a reduce_scatter variant shards the segment
axis instead, leaving each device with a disjoint slice of the result —
the time-sharded index-build layout (each device owns whole time buckets,
no cross-device traffic until the final artifact merge).
"""

import functools

import numpy as np

from ..ops import get_jax


def local_devices():
    j = get_jax()
    if j is None:
        return []
    jax, _ = j
    return jax.devices()


def make_mesh(devices=None, axis='d'):
    """Mesh over the process-local devices: each process aggregates its
    own input partition on its own chips; cross-process merge happens at
    the points level (see cluster.py), so dictionary code spaces never
    need to align between hosts."""
    jax, _ = get_jax()
    from jax.sharding import Mesh
    if devices is None:
        devices = jax.local_devices()
    return Mesh(np.array(devices), (axis,))


def sharded_step(mesh, radices, per_device, scatter, integer_weights,
                 use_pallas=False, interpret=False):
    """The shard_map'd (codes[ncols, n], weights[n], alive[n]) -> dense
    aggregate over `mesh` (record axis sharded over 'd'): a local
    segment-sum (or the one-hot kernel) per device, merged by psum, or
    by psum_scatter when `scatter` leaves each device a disjoint slice
    of the buckets.  Unjitted, so callers choose the shardings."""
    jax, jnp = get_jax()
    from jax.sharding import PartitionSpec as P

    num_segments = 1
    for r in radices:
        num_segments *= int(r)
    wdtype = 'int32' if integer_weights else 'float32'

    if use_pallas:
        from ..ops import pallas_kernels as pk

        def local_step(codes, weights, alive):
            # fused one-hot matmul per shard (f32; caller guarantees
            # the total weight is f32-exact)
            return pk.onehot_dense(radices, per_device, codes,
                                   weights, alive, interpret=interpret)
    else:
        def local_step(codes, weights, alive):
            # codes: [ncols, per_device] i32; weights/alive: [per_device]
            fused = jnp.zeros((per_device,), dtype='int32')
            for i, r in enumerate(radices):
                fused = fused * jnp.int32(r) + codes[i]
            fused = jnp.where(alive, fused, num_segments)
            w = jnp.where(alive, weights.astype(wdtype),
                          jnp.zeros((), dtype=wdtype))
            dense = jax.ops.segment_sum(w, fused,
                                        num_segments=num_segments + 1)
            return dense[:num_segments]

    if scatter:
        def step(codes, weights, alive):
            dense = local_step(codes, weights, alive)
            # each device keeps a disjoint 1/ndev slice of the buckets
            return jax.lax.psum_scatter(dense, 'd', tiled=True)
        out_spec = P('d')
    else:
        def step(codes, weights, alive):
            dense = local_step(codes, weights, alive)
            return jax.lax.psum(dense, 'd')
        out_spec = P()

    # pallas_call does not annotate its outputs with mesh-axis
    # variance, so the vma check must be off for that path only
    return jax.shard_map(step, mesh=mesh,
                         in_specs=(P(None, 'd'), P('d'), P('d')),
                         out_specs=out_spec, check_vma=not use_pallas)


@functools.lru_cache(maxsize=64)
def sparse_merge_program(mesh, axis, k, cap_out):
    """Jitted merge of a mesh's sparse sets (device_scan's sparse lane:
    one set a chip, the chips on the leading axis of every leaf) into
    one set of `cap_out` slots in the one-chip layout, replicated.
    Each chip's first `k` slots are all-gathered over `axis` and
    folded by one more `kernels.sparse_fold` into an empty set: equal
    keys of different chips form a run of at most as many rows as
    there are chips, their weights add and the smallest `first` (a
    global row index) stays, which is the host engine's insertion
    order.  Counters add by psum, and a chip's overflow flag stays
    set.  The caller takes `k` and `cap_out` from the chips' own
    counts, so that no live slot is left behind and every key has a
    slot.  Its own module in a trace: `jit_sparse_merge`."""
    jax, jnp = get_jax()
    from jax.sharding import PartitionSpec as P
    from ..ops.kernels import I64MAX, sparse_fold

    def chip(acc):
        keys, wsum, first, cvec, stats = (x[0] for x in acc)
        i64 = jnp.int64
        empty = (jnp.full((cap_out,), I64MAX, dtype=i64),
                 jnp.zeros((cap_out,), dtype=i64),
                 jnp.full((cap_out,), I64MAX, dtype=i64),
                 jnp.zeros_like(cvec),
                 # (the flag summed, not pmax'ed: the TPU lowers only
                 # the sum of a 64-bit all-reduce)
                 jnp.stack([i64(0),
                            jnp.minimum(jax.lax.psum(stats[1], axis), 1)]))

        def gathered(x):
            return jax.lax.all_gather(x[:k], axis, tiled=True)

        return sparse_fold(jax, jnp, empty, jax.lax.psum(cvec, axis),
                           gathered(keys), gathered(wsum), gathered(first))

    def sparse_merge(acc):
        # every chip computes the same set from the same gathered
        # rows; an all_gather's result is not marked invariant, so the
        # replication check is off
        return jax.shard_map(chip, mesh=mesh, in_specs=((P(axis),) * 5,),
                             out_specs=(P(),) * 5, check_vma=False)(acc)

    return jax.jit(sparse_merge)


@functools.lru_cache(maxsize=None)
def _sharded_aggregate_cached(radices, per_device, ndev, scatter,
                              integer_weights, use_pallas=False):
    jax, _ = get_jax()
    mesh = make_mesh()
    assert len(mesh.devices.flat) == ndev
    interpret = False
    if use_pallas:
        from ..ops import pallas_kernels as pk
        interpret = pk.needs_interpret()
    return jax.jit(sharded_step(mesh, radices, per_device, scatter,
                                integer_weights, use_pallas,
                                interpret)), mesh


def sharded_aggregate(key_codes, radices, weights, alive, scatter=False):
    """Aggregate across all local mesh devices.

    key_codes: [ncols, n] int64 (host); weights: [n] f64; alive: [n] bool.
    Pads the record axis to a multiple of the device count (padding rows
    are dead) and returns the dense accumulator as numpy.
    """
    jax, jnp = get_jax()
    ndev = len(jax.local_devices())
    n = weights.shape[0]
    num_segments = 1
    for r in radices:
        num_segments *= int(r)
    if scatter and num_segments % ndev != 0:
        scatter = False

    # The i32 device kernel is exact only for integer weights whose
    # batch total fits; anything else takes the exact f64 host merge
    # (same guard as the single-device jax path in engine.py).
    int_w = bool(np.all(weights == np.floor(weights)))
    total = float(np.abs(weights).sum())
    if not (int_w and total < 2 ** 31):
        # exact-f64 host merge; cannot honor the per-device-slice
        # contract of the scatter variant
        assert not scatter, \
            'scatter=True requires int32-safe weights'
        fused = np.zeros(n, dtype=np.int64)
        for i in range(len(radices)):
            fused = fused * int(radices[i]) + key_codes[i]
        w = np.where(alive, weights, 0.0)
        return np.bincount(fused, weights=w, minlength=num_segments)

    pad = (-n) % ndev
    if pad:
        key_codes = np.pad(key_codes, ((0, 0), (0, pad)))
        weights = np.pad(weights, (0, pad))
        alive = np.pad(alive, (0, pad))

    per_device = (n + pad) // ndev
    # one-hot matmul path for small accumulators; scatter-based
    # segment-sum otherwise (single gate shared with engine.py)
    from ..ops import pallas_kernels as pk
    use_pallas = pk.should_use(num_segments, total)
    fn, mesh = _sharded_aggregate_cached(tuple(int(r) for r in radices),
                                         per_device, ndev, scatter, True,
                                         use_pallas)
    wdev = weights.astype(np.float32 if use_pallas else np.int32)
    out = fn(key_codes.astype(np.int32), wdev, alive)
    return np.asarray(out).astype(np.float64)
