"""Flash-attention prefill kernel (causal/local GQA) — TPU target.

Online-softmax over KV blocks with VMEM scratch carry; MXU-aligned tiles
(bq, bk multiples of 128 at production shapes, head_dim 64-256).  Causal
runs skip fully-masked KV blocks (the grid still visits them, but the body
is ``pl.when``-gated so no MXU work is issued) and the output tile is
written at the last *needed* block — the same block-skipping that makes a
real TPU flash kernel ~2x over dense for causal.

TPU tiling: a block's last two dims must be multiples of (8, 128) or span
the whole array, so a one-head block of a (B, S, H, D) array is illegal.
The wrappers move heads in front — (B, H, S, D), one transpose of the
activations, O(S·H·D) against the kernel's O(S·T·H·D) — and tile
(rows, D).  Position vectors are reshaped to one block per leading index
(``(S/bq, bq, 1)`` columns for queries, ``(T/bk, 1, bk)`` rows for keys),
so every position block spans its array's last two dims.

Grid: (B, H, S/bq, T/bk), KV innermost.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import blocking

NEG_INF = -1e30


def _online_update(s, v, m_ref, l_ref, acc_ref):
    """Fold one (bq, bk) block of masked scores and its (bk, D) values into
    the running max / sum / accumulator scratch ((bq, 1), (bq, 1),
    (bq, D))."""
    m_old, l_old = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_old, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_old - m_new)
    l_ref[...] = l_old * corr + p.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _finish(o_ref, l_ref, acc_ref):
    out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
    o_ref[...] = out.astype(o_ref.dtype)


def _scratch(bq: int, D: int):
    return [pltpu.VMEM((bq, 1), jnp.float32),        # running max
            pltpu.VMEM((bq, 1), jnp.float32),        # running sum
            pltpu.VMEM((bq, D), jnp.float32)]        # output accumulator


def _head_major(q, k, v):
    """(B, S, H, D) -> (B, H, S, D) for q and (B, T, K, D) -> (B, K, T, D)
    for k/v: the layout whose (rows, D) tiles the TPU accepts."""
    return (q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3))


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                  *, bq: int, bk: int, nk: int, causal: bool,
                  window: int | None, scale: float):
    i = pl.program_id(2)
    j = pl.program_id(3)

    q_start = i * bq
    k_start = j * bk

    last_needed = nk - 1
    if causal:
        last_needed = jnp.minimum(nk - 1, (q_start + bq - 1) // bk)
    needed = j <= last_needed
    if window is not None:
        needed &= (k_start + bk - 1) >= (q_start - window + 1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(needed)
    def _body():
        q = q_ref[...].astype(jnp.float32)               # (bq, D)
        k = k_ref[...].astype(jnp.float32)               # (bk, D)
        v = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= qpos >= kpos
        if window is not None:
            mask &= qpos - kpos < window
        s = jnp.where(mask, s, NEG_INF)

        _online_update(s, v, m_ref, l_ref, acc_ref)

    @pl.when(j == last_needed)
    def _emit():
        _finish(o_ref, l_ref, acc_ref)


def _flash_positions_kernel(q_ref, k_ref, v_ref, qp_ref, kp_ref, o_ref,
                            m_ref, l_ref, acc_ref, *, bq: int, bk: int,
                            nk: int, causal: bool, window: int | None,
                            scale: float):
    """Positions-mode flash body: the causal/window masks come from explicit
    per-token position operands instead of grid offsets, so the kernel can
    attend a span over a whole live cache (continuation prefill: cache slots
    carry absolute positions, -1 = empty) or over ring layouts where slot
    order is not position order.  No block skipping — validity is dynamic,
    every KV block is visited and masked."""
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...].astype(jnp.float32)               # (bq, D)
    k = k_ref[...].astype(jnp.float32)               # (bk, D)
    v = v_ref[...].astype(jnp.float32)
    qp = qp_ref[...]                                 # (bq, 1) abs positions
    kp = kp_ref[...]                                 # (1, bk) -1 = empty slot
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    mask = jnp.broadcast_to(kp >= 0, (bq, bk))
    if causal:
        mask &= qp >= kp
    if window is not None:
        mask &= qp - kp < window
    _online_update(jnp.where(mask, s, NEG_INF), v, m_ref, l_ref, acc_ref)

    @pl.when(j == nk - 1)
    def _emit():
        _finish(o_ref, l_ref, acc_ref)


def flash_attention_positions_pallas(q: jax.Array, k: jax.Array,
                                     v: jax.Array, *, q_positions: jax.Array,
                                     kv_positions: jax.Array,
                                     causal: bool = True,
                                     window: int | None = None,
                                     bq: int = 256, bk: int = 256,
                                     interpret: bool = True) -> jax.Array:
    """q (B,S,H,D); k,v (B,T,K,D); q_positions (S,), kv_positions (T,)
    absolute positions shared across the batch (negative = inert padding /
    empty cache slot).  Returns (B,S,H,D)."""
    B, S, H, D = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    bq, bk = blocking.flash_blocks(S, T, bq, bk)
    assert S % bq == 0 and T % bk == 0
    nq, nk = S // bq, T // bk
    kern = functools.partial(
        _flash_positions_kernel, bq=bq, bk=bk, nk=nk, causal=causal,
        window=window, scale=D ** -0.5)
    out = pl.pallas_call(
        kern,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((None, None, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((None, None, bk, D),
                         lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((None, None, bk, D),
                         lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((None, bq, 1), lambda b, h, i, j: (i, 0, 0)),
            pl.BlockSpec((None, 1, bk), lambda b, h, i, j: (j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, bq, D),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        scratch_shapes=_scratch(bq, D),
        interpret=interpret,
        name="flash_attention_positions",
    )(*_head_major(q, k, v), q_positions.reshape(nq, bq, 1).astype(jnp.int32),
      kv_positions.reshape(nk, 1, bk).astype(jnp.int32))
    return out.transpose(0, 2, 1, 3)


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, window: int | None = None,
                           bq: int = 256, bk: int = 256,
                           interpret: bool = True) -> jax.Array:
    """q (B,S,H,D); k,v (B,T,K,D) with H = K*G. Returns (B,S,H,D)."""
    B, S, H, D = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    bq, bk = blocking.flash_blocks(S, T, bq, bk)
    assert S % bq == 0 and T % bk == 0
    kern = functools.partial(
        _flash_kernel, bq=bq, bk=bk, nk=T // bk, causal=causal,
        window=window, scale=D ** -0.5)
    out = pl.pallas_call(
        kern,
        grid=(B, H, S // bq, T // bk),
        in_specs=[
            pl.BlockSpec((None, None, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((None, None, bk, D),
                         lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((None, None, bk, D),
                         lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, bq, D),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        scratch_shapes=_scratch(bq, D),
        interpret=interpret,
        name="flash_attention",
    )(*_head_major(q, k, v))
    return out.transpose(0, 2, 1, 3)
