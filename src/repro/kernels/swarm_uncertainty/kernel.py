"""Fused uncertainty-probe kernel (paper Eq. 2-3) — the SWARM-LLM hot spot.

For every decoded position the gateway needs (i) the chosen-token
-p·log p term (Eq. 2), optionally full-distribution entropy, and (ii) the
top-k logit variance (Eq. 3).  Done naively that is 3 passes over the
(N, V) logits in HBM (softmax, gather, top_k) — V is up to 256k for the
assigned archs, so the probe is pure memory traffic.  This kernel streams
vocab blocks through VMEM once and keeps all running statistics
(online max / sum-exp / Σz·e^z / chosen logit / top-k buffer) in VMEM
scratch: a single HBM read of the logits, vocab-block tiles aligned to the
(8,128) VPU lanes (``blocking.uncertainty_blocks``).

Grid: (B, N/bn, V/bv), vocab innermost (sequential reduction on TPU).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import blocking

NEG_INF = -1e30


def _pop_max(work: jax.Array, cols: jax.Array):
    """Row max of work (R, C) and work with its FIRST occurrence masked.
    The first-occurrence column is a min over the lanes that hold the max
    (an argmax written with the reductions Mosaic lowers)."""
    cur = work.max(axis=1, keepdims=True)                  # (R, 1)
    first = jnp.where(work == cur, cols, work.shape[1]).min(axis=1,
                                                            keepdims=True)
    return cur, jnp.where(cols == first, NEG_INF, work)


def _select_topk(cand: jax.Array, k: int) -> jax.Array:
    """Row-wise top-k of cand (R, C) via k unrolled max+mask steps (no sort —
    Mosaic-friendly, exact under ties).  Returns (R, k), descending."""
    R, C = cand.shape
    cols = jax.lax.broadcasted_iota(jnp.int32, (R, C), 1)
    kcols = jax.lax.broadcasted_iota(jnp.int32, (R, k), 1)
    out = jnp.full((R, k), NEG_INF, jnp.float32)
    work = cand
    for i in range(k):
        cur, work = _pop_max(work, cols)
        out = jnp.where(kcols == i, cur, out)
    return out


def _merge_topk(a: jax.Array, b: jax.Array, k: int) -> jax.Array:
    """Top-k of the multiset union of two (R, k) rows — the running buffer
    and a block's own top-k — without concatenating along lanes."""
    R = a.shape[0]
    cols = jax.lax.broadcasted_iota(jnp.int32, (R, k), 1)
    out = jnp.full((R, k), NEG_INF, jnp.float32)
    for i in range(k):
        ca, a_rest = _pop_max(a, cols)
        cb, b_rest = _pop_max(b, cols)
        take_a = ca >= cb
        out = jnp.where(cols == i, jnp.maximum(ca, cb), out)
        a = jnp.where(take_a, a_rest, a)
        b = jnp.where(take_a, b, b_rest)
    return out


def _uncertainty_kernel(logits_ref, tokens_ref, h_ref, v_ref, hd_ref,
                        m_ref, l_ref, s_ref, chosen_ref, topk_ref,
                        *, k: int, bv: int, nv: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        s_ref[...] = jnp.zeros_like(s_ref)
        chosen_ref[...] = jnp.full_like(chosen_ref, NEG_INF)
        topk_ref[...] = jnp.full_like(topk_ref, NEG_INF)

    blk = logits_ref[...].astype(jnp.float32)          # (bn, bv)
    tok = tokens_ref[...]                              # (bn, 1)

    # --- online logsumexp (+ Σ z·e^z for distribution entropy) ---
    m_old, l_old, s_old = m_ref[...], l_ref[...], s_ref[...]
    m_new = jnp.maximum(m_old, blk.max(axis=1, keepdims=True))
    corr = jnp.exp(m_old - m_new)
    e = jnp.exp(blk - m_new)
    l_ref[...] = l_old * corr + e.sum(axis=1, keepdims=True)
    s_ref[...] = s_old * corr + (e * blk).sum(axis=1, keepdims=True)
    m_ref[...] = m_new

    # --- chosen-token logit (Eq. 2 numerator): one-hot lane select ---
    cols = j * bv + jax.lax.broadcasted_iota(jnp.int32, blk.shape, 1)
    hit = cols == tok
    val = jnp.where(hit, blk, 0.0).sum(axis=1, keepdims=True)
    chosen_ref[...] = jnp.where(hit.any(axis=1, keepdims=True), val,
                                chosen_ref[...])

    # --- running top-k merge (Eq. 3) ---
    topk_ref[...] = _merge_topk(topk_ref[...], _select_topk(blk, k), k)

    @pl.when(j == nv - 1)
    def _finalize():
        m, l, s = m_ref[...], l_ref[...], s_ref[...]
        log_l = jnp.log(jnp.maximum(l, 1e-30))
        logp = chosen_ref[...] - m - log_l
        p = jnp.exp(logp)
        h_ref[...] = -p * logp                             # Eq. 2 per-position
        hd_ref[...] = (log_l + m - s / jnp.maximum(l, 1e-30)) \
            / jnp.log(jnp.float32(nv * bv))                # full-dist entropy
        t = topk_ref[...]
        mean = t.mean(axis=1, keepdims=True)
        v_ref[...] = (t * t).mean(axis=1, keepdims=True) - mean * mean  # Eq. 3


def uncertainty_pallas(logits: jax.Array, tokens: jax.Array, *, k: int = 10,
                       bn: int = 8, bv: int = 2048,
                       interpret: bool = True):
    """logits (B,N,V), tokens (B,N) -> (h_token, v_topk, h_dist), each (B,N).

    Per-position operands and results ride as (B, N, 1) columns, so their
    (bn, 1) blocks span the array's minor dim — the TPU's tiling rule."""
    B, N, V = logits.shape
    bn, bv = blocking.uncertainty_blocks(N, V, bn, bv)
    assert N % bn == 0 and V % bv == 0, (N, bn, V, bv)
    grid = (B, N // bn, V // bv)
    kern = functools.partial(_uncertainty_kernel, k=k, bv=bv, nv=V // bv)
    col = pl.BlockSpec((None, bn, 1), lambda b, i, j: (b, i, 0))
    h, v, hd = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, bn, bv), lambda b, i, j: (b, i, j)),
            col,
        ],
        out_specs=[col, col, col],
        out_shape=[jax.ShapeDtypeStruct((B, N, 1), jnp.float32)] * 3,
        scratch_shapes=[
            pltpu.VMEM((bn, 1), jnp.float32),     # m
            pltpu.VMEM((bn, 1), jnp.float32),     # l
            pltpu.VMEM((bn, 1), jnp.float32),     # s = Σ z e^z
            pltpu.VMEM((bn, 1), jnp.float32),     # chosen logit
            pltpu.VMEM((bn, k), jnp.float32),     # top-k buffer
        ],
        interpret=interpret,
        name="swarm_uncertainty",
    )(logits, tokens.reshape(B, N, 1).astype(jnp.int32))
    return h[..., 0], v[..., 0], hd[..., 0]
