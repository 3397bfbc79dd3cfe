"""The bytes the mesh's sparse merge needs, computed from exact counts:
the numerator of `sparse_merge_roofline.mesh`.  Kept with the
benchmark so that no later change can count its own work.

The merge (dragnet_tpu/parallel/mesh.py `sparse_merge_program`, module
`jit_sparse_merge` in a trace) all-gathers the chips' live slots and
folds them once more.  A slot is a key, a weight and a first-seen
index, 8 bytes each.  What it must move whatever the algorithm: of the
`rows` live slots that the chips hold together, a chip receives those
of the other chips over the interconnect, and its fold reads every row
once from HBM and writes it once.  The padding up to a power of two,
the empty set the rows are folded into and the sort's passes are the
program's choice and are left out, so the share is a floor.
"""

SLOT_BYTES = 24


def ici_bytes(rows, ndev):
    """Bytes that arrive at one chip over the interconnect."""
    return SLOT_BYTES * rows * (ndev - 1) / float(ndev)


def hbm_bytes(rows):
    """Bytes one chip's fold moves through HBM: read once, written
    once."""
    return 2.0 * SLOT_BYTES * rows


def least_seconds(rows, ndev, peak):
    """The roofline of one merge on one chip: the slower of the two
    transfers (they can overlap)."""
    return max(ici_bytes(rows, ndev) / peak['ici_bytes_per_s'],
               hbm_bytes(rows) / peak['hbm_bytes_per_s'])
