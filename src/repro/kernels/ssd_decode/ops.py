"""Wrapper for the SSD decode-state update: Pallas on TPU, oracle elsewhere."""

from __future__ import annotations

import jax

from repro.kernels.ssd_decode import kernel as K
from repro.kernels.ssd_decode import ref as R


def ssd_state_update(pool, rows, layer, dA, dt, x, Bm, Cm, D, *,
                     force_pallas=False):
    """One SSD layer's decode step for every slot, on its rows of the
    stacked state pool (see ``kernel.ssd_state_update_pallas`` for shapes).
    Returns (y (B,H,P) f32, updated pool).  On TPU the kernel updates the
    pool in place; elsewhere the oracle gathers, steps and scatters the rows
    (sentinel ids dropped), bitwise the gathered serving path's math."""
    if jax.default_backend() == "tpu" or force_pallas:
        return K.ssd_state_update_pallas(
            pool, rows, layer, dA, dt, x, Bm, Cm, D,
            interpret=jax.default_backend() != "tpu")
    return R.ssd_state_update_ref(pool, rows, layer, dA, dt, x, Bm, Cm, D)
