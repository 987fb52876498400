"""Grid/block-shape selection shared by the Pallas kernels.

Every kernel here tiles a dimension ``D`` with a block ``b`` and a grid
of ``D // b`` steps, which is only legal when ``b`` divides ``D``.  The
historical policy ``b = min(cap, D)`` silently violated that for legal
serving geometries — e.g. a 640-slot cache (a multiple of the 64-slot
growth granule) against the decode kernel's 512 cap, or llama3's
128256-entry vocab against the uncertainty kernel's 2048 cap.

The TPU adds a second rule: in a block's last two dims, the second-minor
(sublane) size must be a multiple of 8 and the minor (lane) size a
multiple of 128, unless the block spans the whole dim.  ``snap_block``
keeps the cap as an upper bound and snaps down to the largest divisor
that is a multiple of the tile ``align``; when no such divisor exists
(mamba2-780m's 50280-entry vocab has no multiple of 128 among its
divisors) the whole dim is the block.  ``tools/swarmlint``'s pallas-grid
probe sweeps every config's geometry through these choosers and fails
the build if a (dim, block) pair stops dividing or stops tiling.
"""
from __future__ import annotations

SUBLANE = 8     # second-minor block dim: a multiple of this, or the whole dim
LANE = 128      # minor block dim: a multiple of this, or the whole dim


def snap_block(dim: int, cap: int, align: int) -> int:
    """Largest divisor of ``dim`` that is <= ``cap`` and a multiple of
    ``align``; ``dim`` itself when there is none."""
    if dim <= 0:
        raise ValueError(f"cannot block a non-positive dim: {dim}")
    b = min(cap, dim) // align * align
    while b >= align:
        if dim % b == 0:
            return b
        b -= align
    return dim


def decode_blocks(T: int, bt: int = 512) -> int:
    """Time-tile (sublane rows) for ``decode_attention_pallas`` over a
    T-slot cache."""
    return snap_block(T, bt, SUBLANE)


def flash_blocks(S: int, T: int, bq: int = 256,
                 bk: int = 256) -> tuple[int, int]:
    """(query, key) tiles (sublane rows) for the flash-attention kernels."""
    return snap_block(S, bq, SUBLANE), snap_block(T, bk, SUBLANE)


def uncertainty_blocks(N: int, V: int, bn: int = 8,
                       bv: int = 2048) -> tuple[int, int]:
    """(row, vocab) tiles — sublane rows, lane columns — for
    ``uncertainty_pallas``."""
    return snap_block(N, bn, SUBLANE), snap_block(V, bv, LANE)
