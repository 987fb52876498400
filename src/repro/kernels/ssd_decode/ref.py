"""Pure-jnp oracle for the SSD decode-state kernel."""

from __future__ import annotations

import jax.numpy as jnp


def ssd_step(s, dA, dt, x, Bm, Cm, D):
    """One Mamba-2 decode step on gathered state rows, all in f32.

    s (B,H,P,N); dA, dt (B,H); x (B,H,P); Bm, Cm (B,G,N); D (H,).
    Returns (s_new (B,H,P,N), y (B,H,P)):
    ``s_new = dA * s + (x * dt) ⊗ B`` and ``y = s_new · C + D * x``."""
    rep = s.shape[1] // Bm.shape[1]
    xh = x * dt[..., None]
    Bh = jnp.repeat(Bm, rep, axis=1)                              # (B,H,N)
    Ch = jnp.repeat(Cm, rep, axis=1)
    s_new = s * dA[..., None, None] + xh[..., None] * Bh[:, :, None, :]
    y = jnp.einsum("bhpn,bhn->bhp", s_new, Ch)
    return s_new, y + x * D[:, None]


def ssd_state_update_ref(pool, rows, layer, dA, dt, x, Bm, Cm, D):
    """Gather-step-scatter oracle of ``ssd_state_update_pallas``: take the
    slots' rows of pool layer ``layer`` (ids clipped, as the gathered serving
    path reads them), step them, and write them back with sentinel ids
    (>= R) dropped.  Returns (y (B,H,P), updated pool (L,R,H,P,N))."""
    s = jnp.take(pool[layer], rows, axis=0, mode="clip")
    s_new, y = ssd_step(s, dA, dt, x, Bm, Cm, D)
    return y, pool.at[layer, rows].set(s_new, mode="drop")
