"""The operations and bytes a kernel needs, computed from shapes and
exact counts: the numerator of every roofline share.  Kept with the
benchmark so that no later change can count its own work.

Copied arithmetic: dragnet_tpu/devbench.py's one-hot FLOPs
(`2 * padded_records * padded_segments`), for the cell that will run
the Mosaic one-hot kernel.
"""


def fold_bytes(h2d_bytes, d2h_bytes):
    """Bytes the segment-sum fold must move through HBM at the least:
    every staged column byte is written once by the transfer and read
    once by the fold, and every fetched result byte is written once and
    read once.  The accumulator's own traffic is left out (its size is
    the program's choice), so the share is a floor, never inflated."""
    return 2.0 * h2d_bytes + 2.0 * d2h_bytes


def onehot_flops(padded_records, padded_segments):
    """The one-hot MXU kernel: one multiply-add per record and
    segment."""
    return 2.0 * padded_records * padded_segments


def least_seconds(nbytes, flops, peak):
    """The roofline: the larger of bytes over HBM bandwidth and
    operations over peak FLOP/s."""
    return max(nbytes / peak['hbm_bytes_per_s'],
               flops / peak['bf16_flops_per_s'])


def bound(nbytes, flops, peak):
    """Which of the two bounds applies."""
    return 'memory' if nbytes / peak['hbm_bytes_per_s'] >= \
        flops / peak['bf16_flops_per_s'] else 'compute'
