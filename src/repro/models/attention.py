"""GQA attention: chunked online-softmax (flash-equivalent in XLA) + decode.

The training/prefill path is a two-level ``lax.scan`` over (q blocks, kv
blocks) with a streaming softmax, so the compiled HLO never materialises the
(S, T) score matrix — the memory_analysis of the dry-run therefore reflects
flash-attention behaviour.  The Pallas kernel in ``repro.kernels.
flash_attention`` is the TPU-target implementation of the same math and is
validated against ``repro.kernels.flash_attention.ref`` (which in turn is
validated against this module in tests).

Decode attends one new token against a (possibly ring-buffered) KV cache.
"""

from __future__ import annotations

from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import constrain, data_shards, kernel_split
from repro.models import quant as Q
from repro.models.common import (ModelConfig, ParamDef, apply_rope,
                                 norm_def, normal_init, rmsnorm, rope_angles,
                                 zeros_init)

Array = jax.Array
NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def attn_defs(cfg: ModelConfig) -> dict:
    D, H, K, Dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    std_o = 0.02 / (2 * cfg.num_layers) ** 0.5
    defs = {
        "norm": norm_def(D),
        "wq": ParamDef((D, H, Dh), ("embed", "heads", "head_dim"), normal_init()),
        "wk": ParamDef((D, K, Dh), ("embed", "kv_heads", "head_dim"), normal_init()),
        "wv": ParamDef((D, K, Dh), ("embed", "kv_heads", "head_dim"), normal_init()),
        "wo": ParamDef((H, Dh, D), ("heads", "head_dim", "embed"), normal_init(std_o)),
    }
    if cfg.attn_bias:
        defs["bq"] = ParamDef((H, Dh), ("bias_heads", "head_dim"), zeros_init)
        defs["bk"] = ParamDef((K, Dh), ("kv_heads", "head_dim"), zeros_init)
        defs["bv"] = ParamDef((K, Dh), ("kv_heads", "head_dim"), zeros_init)
    return defs


# ---------------------------------------------------------------------------
# Chunked online-softmax attention (full sequence)
# ---------------------------------------------------------------------------

def chunked_attention(q: Array, k: Array, v: Array, *,
                      q_positions: Array, kv_positions: Array,
                      causal: bool, window: int | None,
                      q_block: int, kv_block: int) -> Array:
    """q (B,S,H,Dh); k,v (B,T,K,Dh); positions (S,)/(T,).  Returns (B,S,H,Dh).

    Streaming softmax in f32; GQA via head-group folding.  Wrapped in
    jax.checkpoint per q-block so training memory stays O(S * Dh).
    """
    B, S, H, Dh = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    qb = min(q_block, S)
    kb = min(kv_block, T)
    assert S % qb == 0 and T % kb == 0, (S, qb, T, kb)
    nq, nk = S // qb, T // kb
    scale = Dh ** -0.5

    qr = q.reshape(B, nq, qb, K, G, Dh).astype(jnp.bfloat16).swapaxes(0, 1)
    qpos = q_positions.reshape(nq, qb)
    kr = k.reshape(B, nk, kb, K, Dh).astype(jnp.bfloat16).swapaxes(0, 1)
    vr = v.reshape(B, nk, kb, K, Dh).astype(jnp.bfloat16).swapaxes(0, 1)
    kpos = kv_positions.reshape(nk, kb)

    @partial(jax.checkpoint, policy=jax.checkpoint_policies.nothing_saveable)
    def q_step(_, blk):
        qblk, qp = blk                          # (B,qb,K,G,Dh), (qb,)

        def kv_step(carry, kv):
            acc, m, l = carry                   # acc (B,K,G,qb,Dh) f32
            kblk, vblk, kp = kv
            s = jnp.einsum("bqkgd,bskd->bkgqs", qblk, kblk,
                           preferred_element_type=jnp.float32) * scale
            mask = jnp.ones((qb, kb), jnp.bool_)
            if causal:
                mask &= qp[:, None] >= kp[None, :]
            if window is not None:
                mask &= qp[:, None] - kp[None, :] < window
            mask &= kp[None, :] >= 0            # ring-buffer empty slots
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))      # (B,K,G,qb)
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(axis=-1)
            pv = jnp.einsum("bkgqs,bskd->bkgqd", p.astype(jnp.bfloat16), vblk,
                            preferred_element_type=jnp.float32)
            acc = acc * corr[..., None] + pv
            return (acc, m_new, l), None

        acc0 = jnp.zeros((B, K, G, qb, Dh), jnp.float32)
        m0 = jnp.full((B, K, G, qb), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, K, G, qb), jnp.float32)
        (acc, m, l), _ = jax.lax.scan(kv_step, (acc0, m0, l0), (kr, vr, kpos))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out.astype(q.dtype)        # (B,K,G,qb,Dh)

    _, outs = jax.lax.scan(q_step, None, (qr, qpos))
    # outs: (nq, B, K, G, qb, Dh) -> (B, S, H, Dh)
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(B, S, H, Dh)
    return out


def plain_attention(q: Array, k: Array, v: Array, *, q_positions, kv_positions,
                    causal: bool, window: int | None) -> Array:
    """Reference O(S*T)-memory attention (small shapes / oracle)."""
    B, S, H, Dh = q.shape
    K = k.shape[2]
    G = H // K
    qr = q.reshape(B, S, K, G, Dh)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qr.astype(jnp.float32),
                   k.astype(jnp.float32)) * (Dh ** -0.5)
    mask = jnp.ones((S, k.shape[1]), jnp.bool_)
    if causal:
        mask &= q_positions[:, None] >= kv_positions[None, :]
    if window is not None:
        mask &= q_positions[:, None] - kv_positions[None, :] < window
    mask &= kv_positions[None, :] >= 0
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p, v.astype(jnp.float32))
    return out.reshape(B, S, H, Dh).astype(q.dtype)


# ---------------------------------------------------------------------------
# Block forward (full sequence) and decode
# ---------------------------------------------------------------------------

class KVCache(NamedTuple):
    k: Array          # (B, T, K, Dh)
    v: Array          # (B, T, K, Dh)
    pos: Array        # (B, T) absolute positions of cached keys, -1 = empty
    # Quantized POOLS only (cache_quant engines): per-row f32 scales,
    # (N, L, K) parallel to k/v with the head_dim axis reduced away.  None
    # (the default) is an empty pytree node, so every bf16 cache — monolithic
    # caches, gathered views, delta buffers — keeps its exact pre-quant
    # structure, jit traces and sharding trees included.
    k_scale: Any = None
    v_scale: Any = None


def init_kv_cache(cfg: ModelConfig, batch: int, length: int, local: bool) -> KVCache:
    if local and cfg.window is not None:
        length = min(length, cfg.window)
    K, Dh = cfg.num_kv_heads, cfg.head_dim
    return KVCache(
        k=jnp.zeros((batch, length, K, Dh), cfg.dtype),
        v=jnp.zeros((batch, length, K, Dh), cfg.dtype),
        pos=jnp.full((batch, length), -1, jnp.int32),
    )


def _project_qkv(p: dict, x: Array, cfg: ModelConfig, positions: Array):
    """x (B,S,D), positions (B,S) -> q (B,S,H,Dh), k/v (B,S,K,Dh), roped."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))
    if cfg.attn_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    cos, sin = rope_angles(positions, cfg.head_dim, cfg.rope_theta)
    q = apply_rope(q, cos[..., None, :], sin[..., None, :])
    k = apply_rope(k, cos[..., None, :], sin[..., None, :])
    return q, k, v


def _prefill_attention(q: Array, k: Array, v: Array, *, q_positions: Array,
                       kv_positions: Array, causal: bool, window: int | None,
                       cfg: ModelConfig, mesh=None) -> Array:
    """Prefill-path attention with per-backend impl selection.

    ``cfg.attn_prefill_impl``: "chunked" = the XLA two-level-scan online
    softmax (the oracle); "flash" = the positions-mode Pallas flash kernel
    (interpret mode off-TPU); None = flash on TPU, chunked elsewhere —
    tier-1 CPU numerics are unchanged by default.  Training (``attn_block``)
    always uses the chunked path: impl selection is serving-only.  On a
    mesh the kernel runs under ``shard_map``, split by batch row and KV
    head (``kernel_split``).
    """
    impl = cfg.attn_prefill_impl
    if impl is None:
        impl = "flash" if jax.default_backend() == "tpu" else "chunked"
    if impl == "flash":
        from repro.kernels.flash_attention.ops import flash_attention_positions

        def kern(q, k, v, qp, kp):
            return flash_attention_positions(
                q, k, v, q_positions=qp, kv_positions=kp, causal=causal,
                window=window)
        if mesh is not None:
            b, h = kernel_split(mesh, q.shape[0], k.shape[2])
            kern = jax.shard_map(
                kern, mesh=mesh, out_specs=P(b, None, h),
                in_specs=(P(b, None, h),) * 3 + (P(), P()), check_vma=False)
        return kern(q, k, v, q_positions, kv_positions)
    return chunked_attention(q, k, v, q_positions=q_positions,
                             kv_positions=kv_positions, causal=causal,
                             window=window, q_block=cfg.attn_q_block,
                             kv_block=cfg.attn_kv_block)


def _attn_forward(p: dict, x: Array, cfg: ModelConfig, positions: Array,
                  local: bool, *, prefill: bool = False, mesh=None
                  ) -> tuple[Array, Array, Array]:
    """Shared full-sequence body -> (x + attn(x), k, v) — single source of
    truth for the training forward AND prefill so they cannot diverge.
    ``prefill=True`` routes through ``_prefill_attention`` (impl-selected);
    the default chunked path keeps training untouched."""
    h = rmsnorm(x, p["norm"], cfg.norm_eps)
    q, k, v = _project_qkv(p, h, cfg, positions)
    if prefill:
        out = _prefill_attention(
            q, k, v, q_positions=positions[0], kv_positions=positions[0],
            causal=cfg.causal, window=cfg.window if local else None, cfg=cfg,
            mesh=mesh)
    else:
        out = chunked_attention(
            q, k, v,
            q_positions=positions[0], kv_positions=positions[0],
            causal=cfg.causal, window=cfg.window if local else None,
            q_block=cfg.attn_q_block, kv_block=cfg.attn_kv_block)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return x + y, k, v


def attn_block(p: dict, x: Array, cfg: ModelConfig, *, local: bool,
               positions: Array | None = None) -> Array:
    """Pre-norm residual attention over a full sequence. x (B,S,D)."""
    B, S, D = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    out, _, _ = _attn_forward(p, x, cfg, positions, local)
    return out


def _scatter_kv(cache: KVCache, k: Array, v: Array, positions: Array,
                cfg: ModelConfig, local: bool, mesh, rules) -> KVCache:
    """Bulk-scatter roped span K/V into the cache at slot = position (ring
    slot = position % T for windowed layers).  Negative-position columns
    scatter out of bounds and are dropped."""
    B, S = positions.shape
    T = cache.k.shape[1]
    if local and cfg.window is not None and S > T:
        # ring buffer: only the last T POSITIONS survive a stepwise fill.
        # Mask by position rather than slicing columns — continuation spans
        # are right-padded, so the last T columns of a bucketed span are
        # not the last T real tokens (a column slice would drop recent real
        # K/V and keep padding).  Older positions scatter out of bounds;
        # their ring slots are overwritten by kept positions (every slot is
        # covered) or hold stale entries the window mask already excludes.
        pmax = positions.max(axis=1, keepdims=True)      # last real position
        positions = jnp.where(positions > pmax - T, positions, -1)
    slot = positions % T if (local and cfg.window is not None) else positions
    slot = jnp.where(positions >= 0, slot, T)
    b = jnp.arange(B)[:, None]
    kv_axes = ("act_batch", "act_kv_seq", "act_kv_heads", None)
    return KVCache(
        k=constrain(cache.k.at[b, slot].set(k.astype(cache.k.dtype),
                                            mode="drop"), kv_axes, mesh, rules),
        v=constrain(cache.v.at[b, slot].set(v.astype(cache.v.dtype),
                                            mode="drop"), kv_axes, mesh, rules),
        pos=cache.pos.at[b, slot].set(positions.astype(jnp.int32),
                                      mode="drop"),
    )


def attn_prefill(p: dict, x: Array, cache: KVCache, positions: Array,
                 cfg: ModelConfig, *, local: bool, continuation: bool = False,
                 mesh=None, rules=None) -> tuple[Array, KVCache]:
    """Prompt absorption: full-sequence attention + bulk KV-cache fill.

    x (B,S,D); positions (B,S) absolute positions, identical across the
    batch.  Negative positions are inert bucket padding: their K/V never
    enter the cache and attention masks them out, so a bucketed prefill is
    numerics-neutral per row.

    ``continuation=False`` (cold): requires a freshly initialised cache and
    a LEFT-padded span starting at position 0 — attention runs over the
    span's own K/V only (the fast path: no cache-length-sized reads).

    ``continuation=True`` (warm): the span is absorbed into an
    *already-populated* cache at offset positions (engine right-pads the
    span so padding never sits between the cached context and the new
    tokens).  The span K/V are scattered into the cache first, then the
    span queries attend over the **whole cache** — cached context and the
    span itself — with the same causal / window / empty-slot (pos = -1)
    masking the decode step uses, so a warm continuation reproduces
    cold-prefilling the concatenation (bitwise greedy tokens; logits to
    bf16 accumulation-order noise, see docs/RUNTIME.md).

    On-mesh (mesh/rules set) the refreshed KV cache is pinned to its
    logical-axis sharding so the bulk scatter does not un-shard it.
    """
    if not continuation:
        out, k, v = _attn_forward(p, x, cfg, positions, local, prefill=True,
                                  mesh=mesh)
        return out, _scatter_kv(cache, k, v, positions, cfg, local,
                                mesh, rules)

    h = rmsnorm(x, p["norm"], cfg.norm_eps)
    q, k, v = _project_qkv(p, h, cfg, positions)
    cache = _scatter_kv(cache, k, v, positions, cfg, local, mesh, rules)
    # queries over the full cache: empty slots carry pos = -1 and are masked
    # exactly like the decode step's mask (cache.pos rows are identical
    # across the batch — batched sessions absorb identical position grids)
    out = _prefill_attention(
        q, cache.k, cache.v,
        q_positions=positions[0], kv_positions=cache.pos[0],
        causal=cfg.causal, window=cfg.window if local else None, cfg=cfg,
        mesh=mesh)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return x + y, cache


def _decode_chunk_len(cfg: ModelConfig, length: int) -> int:
    """Static streaming-chunk length: ``cfg.attn_decode_block`` halved until
    it divides the cache length (local windows can be shorter than 64)."""
    cb = min(cfg.attn_decode_block, length)
    while length % cb:
        cb //= 2
    return max(cb, 1)


def _decode_stream_chunk(carry, qr: Array, k_c: Array, v_c: Array,
                         pos_c: Array, index: Array, cfg: ModelConfig,
                         local: bool, k_s: Array | None = None,
                         v_s: Array | None = None):
    """Online-softmax update for ONE (B, cb) KV chunk of a decode attend.

    Every decode layout — monolithic cache, gathered paged view, and the
    kernel-first block-table read — pushes its chunks through this exact
    function, so layouts that produce elementwise-equal chunk data are
    bitwise-identical by construction; only chunk *provenance* differs.

    ``k_s``/``v_s`` (B, cb, K) set = quantized pool chunk: ``k_c``/``v_c``
    hold RAW quantized rows (cast to comp dtype — int8/fp8 values are exact
    in bf16) and the dequant is fused here, where the accumulator already
    runs in f32: the k-scale lands on the post-QK scores (a per-(slot,head)
    constant factors out of the Dh contraction exactly) and the v-scale
    folds into the softmax weights before the PV contraction — no
    cache-shaped f32 dequant copy ever exists (the swarmlint
    ``quant-scale-drift`` contract).  With both None the trace is
    byte-identical to the pre-quantization one.
    """
    m, l, acc = carry                       # (B,K,G), (B,K,G), (B,K,G,Dh) f32
    # bf16 operands + f32 accumulation: never materialise an f32 cache copy
    s = jnp.einsum("bkgd,btkd->bkgt", qr.astype(cfg.comp_dtype), k_c,
                   preferred_element_type=jnp.float32) * (cfg.head_dim ** -0.5)
    if k_s is not None:
        s = s * k_s.transpose(0, 2, 1)[:, :, None, :]       # (B,K,1,cb)
    mask = (pos_c <= index[:, None]) & (pos_c >= 0)
    if local and cfg.window is not None:
        mask &= index[:, None] - pos_c < cfg.window
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m - m_new)
    l = l * corr + p.sum(axis=-1)
    if v_s is not None:
        p = p * v_s.transpose(0, 2, 1)[:, :, None, :]
    pv = jnp.einsum("bkgt,btkd->bkgd", p.astype(cfg.comp_dtype), v_c,
                    preferred_element_type=jnp.float32)
    acc = acc * corr[..., None] + pv
    return m_new, l, acc


def _decode_stream_init(B: int, cfg: ModelConfig):
    K, Dh = cfg.num_kv_heads, cfg.head_dim
    G = cfg.num_heads // K
    # swarmlint: ignore[dtype-drift] flash-style (m, l, acc) softmax
    # accumulators live one decode step, not in the cache; bf16 running
    # max/sum loses low bits vs the reference softmax
    return (jnp.full((B, K, G), NEG_INF, jnp.float32),
            jnp.zeros((B, K, G), jnp.float32),  # swarmlint: ignore[dtype-drift] see above: one-step softmax accumulator
            jnp.zeros((B, K, G, Dh), jnp.float32))  # swarmlint: ignore[dtype-drift] see above: one-step softmax accumulator


def _decode_stream_finish(carry, B: int, cfg: ModelConfig, mesh, rules) -> Array:
    _, l, acc = carry
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = out.reshape(B, 1, cfg.num_heads, cfg.head_dim)
    return constrain(out, ("act_batch", None, "act_heads", "act_head_dim"),
                     mesh, rules)


def _decode_attend(q: Array, k_lin: Array, v_lin: Array, pos_lin: Array,
                   index: Array, cfg: ModelConfig, local: bool,
                   mesh, rules) -> Array:
    """One query token against a slot-linear (B,T) K/V view — shared by the
    monolithic cache and the gathered paged view, so the two layouts cannot
    diverge numerically (paged == monolithic is bitwise by construction
    when the views are elementwise equal).  Streams the view in
    ``cfg.attn_decode_block`` chunks through the same online softmax the
    kernel-first block-table path uses (see ``_decode_stream_chunk``)."""
    B, Tl = pos_lin.shape
    K, Dh = cfg.num_kv_heads, cfg.head_dim
    G = cfg.num_heads // K
    qr = q.reshape(B, K, G, Dh)
    cb = _decode_chunk_len(cfg, Tl)
    nc = Tl // cb
    kr = k_lin.reshape(B, nc, cb, K, Dh).swapaxes(0, 1)
    vr = v_lin.reshape(B, nc, cb, K, Dh).swapaxes(0, 1)
    pr = pos_lin.reshape(B, nc, cb).swapaxes(0, 1)

    def step(carry, chunk):
        k_c, v_c, p_c = chunk
        return _decode_stream_chunk(carry, qr, k_c, v_c, p_c, index, cfg,
                                    local), None

    carry, _ = jax.lax.scan(step, _decode_stream_init(B, cfg), (kr, vr, pr))
    return _decode_stream_finish(carry, B, cfg, mesh, rules)


def attn_decode(p: dict, x: Array, cache: KVCache, index: Array,
                cfg: ModelConfig, *, local: bool, mesh=None, rules=None
                ) -> tuple[Array, KVCache]:
    """One-token decode. x (B,1,D); index (B,) absolute position of new token.

    On-mesh the one-row scatter and the attention contraction are pinned to
    the cache's logical-axis sharding, so a scanned decode keeps the KV
    cache sharded across steps (the scan carry would otherwise decay to
    whatever layout GSPMD propagates from the first step).
    """
    B = x.shape[0]
    T = cache.k.shape[1]
    h = rmsnorm(x, p["norm"], cfg.norm_eps)
    q, k_new, v_new = _project_qkv(p, h, cfg, index[:, None])
    slot = index % T if (local and cfg.window is not None) else index
    b = jnp.arange(B)
    kv_axes = ("act_batch", "act_kv_seq", "act_kv_heads", None)
    cache = KVCache(
        k=constrain(cache.k.at[b, slot].set(k_new[:, 0].astype(cache.k.dtype)),
                    kv_axes, mesh, rules),
        v=constrain(cache.v.at[b, slot].set(v_new[:, 0].astype(cache.v.dtype)),
                    kv_axes, mesh, rules),
        pos=cache.pos.at[b, slot].set(index.astype(jnp.int32)),
    )
    out = _decode_attend(q, cache.k, cache.v, cache.pos, index, cfg, local,
                         mesh, rules).astype(x.dtype)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return x + y, cache


# ---------------------------------------------------------------------------
# Paged (block-pool) variants: pool-shaped KVCache + per-slot block tables
# ---------------------------------------------------------------------------

PAGED_KV_AXES = ("act_pool", None, "act_kv_heads", None)
PAGED_SCALE_AXES = ("act_pool_scale", None, "act_kv_heads")


def init_paged_kv(cfg: ModelConfig, n_blocks: int, block_len: int,
                  cache_quant: str | None = None) -> KVCache:
    """Pool-shaped KV storage: k/v ``(n_blocks, block_len, K, Dh)``, pos
    ``(n_blocks, block_len)`` (-1 = empty).  Local-window layers share the
    same geometry — the window clamp happens at view time through the table
    slice, not in storage.  ``cache_quant`` set = k/v are stored int8/fp8
    with per-row f32 scales riding alongside (``quantize_rows(zeros)`` is
    ``(0, scale=0)``, so a zeroed quantized pool equals a scattered zeroed
    one)."""
    K, Dh = cfg.num_kv_heads, cfg.head_dim
    dt = cfg.dtype if cache_quant is None else Q.qdtype(cache_quant)

    def scale():
        # one alloc per field — aliasing k_scale/v_scale to one buffer
        # trips the donated pool-reset jit ("donate the same buffer twice")
        return (None if cache_quant is None
                else jnp.zeros((n_blocks, block_len, K), jnp.float32))  # swarmlint: ignore[dtype-drift] quant scales MUST be f32 (see quant-scale-drift); K floats per L*K*Dh-element block is noise
    return KVCache(
        k=jnp.zeros((n_blocks, block_len, K, Dh), dt),
        v=jnp.zeros((n_blocks, block_len, K, Dh), dt),
        pos=jnp.full((n_blocks, block_len), -1, jnp.int32),
        k_scale=scale(), v_scale=scale(),
    )


def paged_view(pool: KVCache, table: Array,
               view_dtype: Any = jnp.bfloat16) -> KVCache:
    """Gather a slot-linear ``(B, nb*L, ...)`` view of the pool through the
    block table.  With the same writes applied, the view is elementwise
    equal to the monolithic cache of length nb*L — which is what makes the
    whole paged serving path bitwise-identical to the monolithic one.
    Sentinel (out-of-range) table entries clip to the last pool block:
    garbage reads that only ever feed an empty serve slot's own row.

    A quantized pool gathers its scales alongside and dequantizes HERE, so
    the view is always a plain ``cfg``-dtype monolithic cache — this is the
    gathered-view parity oracle for the fused-dequant decode paths, and the
    only place pool rows are materialised dequantized."""
    B, nb = table.shape
    L = pool.k.shape[1]
    flat = table.reshape(-1)
    k = jnp.take(pool.k, flat, axis=0, mode="clip")
    v = jnp.take(pool.v, flat, axis=0, mode="clip")
    pos = jnp.take(pool.pos, flat, axis=0, mode="clip")
    if pool.k_scale is not None:
        k = Q.dequantize_rows(k, jnp.take(pool.k_scale, flat, axis=0,
                                          mode="clip"), view_dtype)
        v = Q.dequantize_rows(v, jnp.take(pool.v_scale, flat, axis=0,
                                          mode="clip"), view_dtype)
    return KVCache(k=k.reshape(B, nb * L, *k.shape[2:]),
                   v=v.reshape(B, nb * L, *v.shape[2:]),
                   pos=pos.reshape(B, nb * L))


def paged_scatter_blocks(pool: KVCache, table: Array, lin: KVCache,
                         lo: Array, hi: Array, *,
                         window: int | None = None) -> KVCache:
    """Write the blocks covering position range [lo, hi) of a slot-linear
    cache back into the pool through the table.

    ``lin`` is the (B, T) linear cache the monolithic compute produced off
    a ``paged_view`` gather; ``lo``/``hi`` (B,) bound the positions that
    dispatch wrote (prefill span, decode steps).  Only the covering blocks
    are scattered — O(tokens written), and a refcount-shared prefix block
    (always below ``lo``) is NEVER written through, which is the paged
    allocator's copy-on-write invariant.  ``window`` set = ring-buffer
    layer: the write range is mapped to ring slots (with wrap).  Sentinel
    (out-of-range) table entries drop, so empty serve slots scatter
    nothing."""
    N, L = pool.k.shape[0], pool.k.shape[1]
    B, T = lin.pos.shape
    nb = T // L
    tbl = table[:, :nb]
    jpos = jnp.arange(nb, dtype=jnp.int32)[None] * L        # block starts
    if window is None:
        touched = (jpos < hi[:, None]) & (jpos + L > lo[:, None])
    else:
        span = hi - lo
        s0 = lo % T
        s1 = s0 + jnp.minimum(span, T)
        touched = (((jpos < s1[:, None]) & (jpos + L > s0[:, None]))
                   | ((jpos + T < s1[:, None])
                      & (jpos + T + L > s0[:, None])))     # ring wrap
    dst = jnp.where(touched, tbl, N).reshape(-1)            # (B*nb,)
    kb = lin.k.reshape(B * nb, L, *lin.k.shape[2:])
    vb = lin.v.reshape(B * nb, L, *lin.v.shape[2:])
    pb = lin.pos.reshape(B * nb, L)
    if pool.k_scale is not None:
        # quantize-at-scatter: per-row scales over the written (covering)
        # blocks only; untouched blocks — shared COW prefixes included —
        # keep their existing q/scale pairs byte-for-byte.
        quant = "int8" if pool.k.dtype == jnp.int8 else "fp8"
        kb, ks = Q.quantize_rows(kb, quant)
        vb, vs = Q.quantize_rows(vb, quant)
        return pool._replace(
            k=pool.k.at[dst].set(kb, mode="drop"),
            v=pool.v.at[dst].set(vb, mode="drop"),
            pos=pool.pos.at[dst].set(pb, mode="drop"),
            k_scale=pool.k_scale.at[dst].set(ks, mode="drop"),
            v_scale=pool.v_scale.at[dst].set(vs, mode="drop"),
        )
    return pool._replace(
        k=pool.k.at[dst].set(kb.astype(pool.k.dtype), mode="drop"),
        v=pool.v.at[dst].set(vb.astype(pool.v.dtype), mode="drop"),
        pos=pool.pos.at[dst].set(pb, mode="drop"),
    )


def paged_scatter_delta(pool: KVCache, table: Array, delta: KVCache,
                        p0: Array, *, window: int | None = None) -> KVCache:
    """Scatter a dispatch's decode delta buffer (``init_decode_delta``) into
    the pool through the table — O(steps) slot writes per row, no
    slot-linear intermediate.  Ring layers (``window`` set) wrap slots mod
    the view length; when ``steps`` exceeds the ring length only the LAST
    ring-length delta rows are kept (earlier writes were superseded
    in-ring; dropping them statically avoids the undefined ordering of
    duplicate-index scatters).  Sentinel table entries and unwritten delta
    rows (pos = -1) drop.  The resulting pool is elementwise-equal to what
    the gathered-view path's ``paged_scatter_blocks`` writeback produces."""
    N, L = pool.k.shape[0], pool.k.shape[1]
    B, steps = delta.pos.shape
    Tl = table.shape[1] * L
    k, v, pos = delta.k, delta.v, delta.pos
    off = jnp.arange(steps, dtype=jnp.int32)
    if window is not None and steps > Tl:
        k, v, pos = k[:, -Tl:], v[:, -Tl:], pos[:, -Tl:]
        off = off[-Tl:]
        steps = Tl
    slot = p0[:, None] + off[None]
    if window is not None:
        slot = slot % Tl
    blk = jnp.take_along_axis(table, slot // L, axis=1)     # (B, steps)
    flat = jnp.where((blk < N) & (pos >= 0), blk * L + slot % L, N * L)
    flat = flat.reshape(-1)
    kf = pool.k.reshape(N * L, *pool.k.shape[2:])
    vf = pool.v.reshape(N * L, *pool.v.shape[2:])
    pf = pool.pos.reshape(N * L)
    if pool.k_scale is not None:
        # the delta buffer stays bf16 (O(B*steps), not worth shrinking);
        # quantize its rows here so the dispatch boundary — not the write
        # path — decides the pool representation, same per-row function the
        # gathered path's paged_scatter_blocks applies to the same rows.
        quant = "int8" if pool.k.dtype == jnp.int8 else "fp8"
        k, ks = Q.quantize_rows(k, quant)
        v, vs = Q.quantize_rows(v, quant)
        ksf = pool.k_scale.reshape(N * L, *pool.k_scale.shape[2:])
        vsf = pool.v_scale.reshape(N * L, *pool.v_scale.shape[2:])
        ksf = ksf.at[flat].set(ks.reshape(B * steps, *ks.shape[2:]),
                               mode="drop")
        vsf = vsf.at[flat].set(vs.reshape(B * steps, *vs.shape[2:]),
                               mode="drop")
        kf = kf.at[flat].set(k.reshape(B * steps, *k.shape[2:]), mode="drop")
        vf = vf.at[flat].set(v.reshape(B * steps, *v.shape[2:]), mode="drop")
        pf = pf.at[flat].set(pos.reshape(-1), mode="drop")
        return pool._replace(
            k=kf.reshape(pool.k.shape), v=vf.reshape(pool.v.shape),
            pos=pf.reshape(pool.pos.shape),
            k_scale=ksf.reshape(pool.k_scale.shape),
            v_scale=vsf.reshape(pool.v_scale.shape))
    kf = kf.at[flat].set(k.reshape(B * steps, *k.shape[2:]).astype(kf.dtype),
                         mode="drop")
    vf = vf.at[flat].set(v.reshape(B * steps, *v.shape[2:]).astype(vf.dtype),
                         mode="drop")
    pf = pf.at[flat].set(pos.reshape(-1), mode="drop")
    return pool._replace(k=kf.reshape(pool.k.shape),
                         v=vf.reshape(pool.v.shape),
                         pos=pf.reshape(pool.pos.shape))


def init_decode_delta(cfg: ModelConfig, batch: int, steps: int) -> KVCache:
    """Per-dispatch decode write buffer for the kernel-first path: row ``t``
    holds the K/V the dispatch's step ``t`` produced (pos -1 = unwritten).
    O(B * steps) — the scan carry no longer holds any cache-length state."""
    K, Dh = cfg.num_kv_heads, cfg.head_dim
    return KVCache(
        k=jnp.zeros((batch, steps, K, Dh), cfg.dtype),
        v=jnp.zeros((batch, steps, K, Dh), cfg.dtype),
        pos=jnp.full((batch, steps), -1, jnp.int32),
    )


def attn_decode_paged(p: dict, x: Array, pool: KVCache, table: Array,
                      delta: KVCache, index: Array, t: Array, p0: Array,
                      cfg: ModelConfig, *, local: bool, layer=None,
                      mesh=None, rules=None) -> tuple[Array, KVCache]:
    """One-token decode reading KV blocks IN PLACE through the block table.

    ``pool`` is the layer's block pool — a decode-scan *constant*, never
    materialised as a slot-linear view and never written here; ``table``
    (B, nb) is the block table, already sliced to the local window for
    windowed layers; ``delta`` holds this dispatch's decode writes (see
    ``init_decode_delta``); ``t`` is the scalar step number within the
    dispatch and ``p0`` (B,) the dispatch-start index (so index == p0 + t).

    The new token's K/V lands in delta row ``t`` first; each streamed pool
    chunk is then overlaid with the latest delta write per slot (ring slots
    for windowed layers), which makes the chunk data elementwise equal to
    the gathered-view path's slot-linear cache at step ``t`` — and the
    attend output bitwise equal, since both layouts stream through
    ``_decode_stream_chunk``.  On TPU the attend instead runs through the
    block-table Pallas kernel (``kernels/decode_attention``), validated
    against the gathered ref within tolerance.

    ``layer`` set = the pool leaves are repeat-stacked ``(R, N, L, ...)``
    (a stacked stage's scan constant) and ``layer`` is the stage scan's
    layer index: the gathers fold ``layer * N`` into their block ids
    instead of slicing a per-layer pool (which would copy the whole pool
    every decode step).

    Quantized pools (``pool.k_scale`` set) stream RAW int8/fp8 rows plus
    their per-row scale chunks and fuse the dequant into the accumulator
    (``_decode_stream_chunk``); the delta buffer stays bf16 and overlays
    with a unit scale.  Quantized-vs-gathered parity is budgeted, not
    bitwise: the fused path scales f32 scores where the oracle dequantizes
    rows to bf16 before the dot (see docs/RUNTIME.md "Quantized caches").
    """
    B = x.shape[0]
    stacked = layer is not None
    quantized = pool.k_scale is not None
    ksp = vsp = None
    if stacked:
        R, N, L = pool.k.shape[0], pool.k.shape[1], pool.k.shape[2]
        kp = pool.k.reshape((R * N,) + pool.k.shape[2:])
        vp = pool.v.reshape((R * N,) + pool.v.shape[2:])
        pp = pool.pos.reshape(R * N, L)
        if quantized:
            ksp = pool.k_scale.reshape((R * N,) + pool.k_scale.shape[2:])
            vsp = pool.v_scale.reshape((R * N,) + pool.v_scale.shape[2:])
        base = layer * N
    else:
        R, (N, L) = 1, (pool.k.shape[0], pool.k.shape[1])
        kp, vp, pp = pool.k, pool.v, pool.pos
        if quantized:
            ksp, vsp = pool.k_scale, pool.v_scale
        base = 0
    nb = table.shape[1]
    Tl = nb * L
    steps = delta.pos.shape[1]
    K, Dh = cfg.num_kv_heads, cfg.head_dim
    G = cfg.num_heads // K

    h = rmsnorm(x, p["norm"], cfg.norm_eps)
    q, k_new, v_new = _project_qkv(p, h, cfg, index[:, None])
    delta = KVCache(
        k=delta.k.at[:, t].set(k_new[:, 0].astype(delta.k.dtype)),
        v=delta.v.at[:, t].set(v_new[:, 0].astype(delta.v.dtype)),
        pos=delta.pos.at[:, t].set(index.astype(jnp.int32)),
    )
    qr = q.reshape(B, K, G, Dh)
    ring = local and cfg.window is not None

    if jax.default_backend() == "tpu":
        from repro.kernels.decode_attention.ops import paged_decode_attention
        # stacked pools fold the layer offset into the table ids; sentinel
        # entries (>= N) stay sentinels for the flat pool (>= R * N).
        tbl = (jnp.where(table < N, table + base, R * N + 7)
               if stacked else table)
        window = cfg.window if local else None

        def kern(qr, kp, vp, pp, tbl, index, dk, dv, dpos, p0, *scales):
            ks, vs = scales if quantized else (None, None)
            return paged_decode_attention(
                qr, kp, vp, pp, tbl, index, window=window, delta_k=dk,
                delta_v=dv, delta_pos=dpos, p0=p0, k_scale=ks, v_scale=vs)
        args = (qr, kp, vp, pp, tbl, index, delta.k, delta.v, delta.pos, p0)
        if quantized:
            args += (ksp, vsp)
        if mesh is not None:
            # a slot's table names pool blocks on any 'data' shard, so the
            # kernel needs the whole block dim on each device.  The pool is
            # split over 'data' (act_pool): with data > 1 every layer's pool
            # would be all-gathered onto every device each step.
            if data_shards(mesh) > 1:
                raise NotImplementedError(
                    "the paged decode kernel needs block tables that name "
                    "shard-local pool blocks to run on a mesh with data "
                    f"axes > 1 ({dict(mesh.shape)}); serve tensor-parallel "
                    "(serving_mesh(model_parallel=n_devices)) or with "
                    "attn_decode_impl='gather'")
            b, hd = kernel_split(mesh, B, K)
            pool_spec = P(None, None, hd)
            kern = jax.shard_map(
                kern, mesh=mesh, out_specs=P(b, hd), check_vma=False,
                in_specs=(P(b, hd), pool_spec, pool_spec, P(), P(b), P(b),
                          P(b, None, hd), P(b, None, hd), P(b), P(b))
                + (pool_spec,) * (2 if quantized else 0))
        out = kern(*args)
        out = constrain(out.reshape(B, 1, cfg.num_heads, Dh),
                        ("act_batch", None, "act_heads", "act_head_dim"),
                        mesh, rules)
    else:
        cb = _decode_chunk_len(cfg, Tl)
        nc = Tl // cb
        kp_flat = kp.reshape(R * N * L, K, Dh)
        vp_flat = vp.reshape(R * N * L, K, Dh)
        pp_flat = pp.reshape(R * N * L)
        if quantized:
            ksp_flat = ksp.reshape(R * N * L, K)
            vsp_flat = vsp.reshape(R * N * L, K)
        # gather each chunk at BLOCK granularity when the chunk is
        # block-aligned (whole (L, K, Dh) rows, same access pattern as
        # paged_view's one-shot gather — ~2x over a per-slot row gather on
        # CPU); fall back to per-slot rows otherwise.  Same elements either
        # way, so the streamed chunks stay bitwise-identical.
        block_granular = cb % L == 0

        def step(carry, xs_c):
            k_s = v_s = None
            if block_granular:
                blks = xs_c                       # (cb // L,) chunk's blocks
                sl = (blks[:, None] * L
                      + jnp.arange(L, dtype=jnp.int32)[None]).reshape(-1)
                # block-level clip matches paged_view's sentinel semantics
                tb = jnp.minimum(jnp.take(table, blks, axis=1), N - 1) + base
                k_c = jnp.take(kp, tb, axis=0).reshape(B, cb, K, Dh)
                v_c = jnp.take(vp, tb, axis=0).reshape(B, cb, K, Dh)
                p_c = jnp.take(pp, tb, axis=0).reshape(B, cb)
                if quantized:
                    k_s = jnp.take(ksp, tb, axis=0).reshape(B, cb, K)
                    v_s = jnp.take(vsp, tb, axis=0).reshape(B, cb, K)
            else:
                sl = xs_c                         # (cb,) this chunk's slots
                blk = (jnp.minimum(jnp.take(table, sl // L, axis=1), N - 1)
                       + base)
                flat = blk * L + (sl % L)[None]              # (B, cb)
                k_c = jnp.take(kp_flat, flat, axis=0)        # (B, cb, K, Dh)
                v_c = jnp.take(vp_flat, flat, axis=0)
                p_c = jnp.take(pp_flat, flat, axis=0)        # (B, cb)
                if quantized:
                    k_s = jnp.take(ksp_flat, flat, axis=0)   # (B, cb, K)
                    v_s = jnp.take(vsp_flat, flat, axis=0)
            if quantized:
                # raw quantized rows cast to the compute dtype (int8/fp8
                # values are exact in bf16); the scales ride as separate
                # chunk operands and are applied inside the accumulator
                k_c = k_c.astype(cfg.comp_dtype)
                v_c = v_c.astype(cfg.comp_dtype)
            # overlay this dispatch's own writes: latest delta row per slot.
            # The index math is cheap (B, cb) ints; the gathers + full-width
            # wheres are ~2x the chunk's own traffic, so they run under a
            # cond — most chunks hold no written slot and skip them.
            if ring:
                rel = (sl[None] - p0[:, None]) % Tl
                d = rel + Tl * ((t - rel) // Tl)
            else:
                d = sl[None] - p0[:, None]
            valid = (d >= 0) & (d <= t)

            def overlay(args):
                k_c, v_c, p_c, k_s, v_s = args
                dc = jnp.clip(d, 0, steps - 1)
                k_d = jnp.take_along_axis(delta.k, dc[..., None, None],
                                          axis=1)
                v_d = jnp.take_along_axis(delta.v, dc[..., None, None],
                                          axis=1)
                p_d = jnp.take_along_axis(delta.pos, dc, axis=1)
                if quantized:
                    # delta rows are real bf16 values: overlay them verbatim
                    # and neutralise the slot's scale to 1 — the fused
                    # dequant then leaves them untouched
                    k_s = jnp.where(valid[..., None], 1.0, k_s)
                    v_s = jnp.where(valid[..., None], 1.0, v_s)
                return (jnp.where(valid[..., None, None],
                                  k_d.astype(k_c.dtype), k_c),
                        jnp.where(valid[..., None, None],
                                  v_d.astype(v_c.dtype), v_c),
                        jnp.where(valid, p_d, p_c), k_s, v_s)

            k_c, v_c, p_c, k_s, v_s = jax.lax.cond(
                valid.any(), overlay, lambda a: a,
                (k_c, v_c, p_c, k_s, v_s))
            return _decode_stream_chunk(carry, qr, k_c, v_c, p_c, index,
                                        cfg, local, k_s, v_s), None

        xs = (jnp.arange(nb, dtype=jnp.int32).reshape(nc, cb // L)
              if block_granular
              else jnp.arange(Tl, dtype=jnp.int32).reshape(nc, cb))
        carry, _ = jax.lax.scan(step, _decode_stream_init(B, cfg), xs)
        out = _decode_stream_finish(carry, B, cfg, mesh, rules)

    y = jnp.einsum("bshk,hkd->bsd", out.astype(x.dtype),
                   p["wo"].astype(x.dtype))
    return x + y, delta
