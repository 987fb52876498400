"""GQA decode-attention kernel: one query token vs a (ring) KV cache.

Decode is bandwidth-bound: arithmetic intensity ≈ 2 flops/byte of cache.
The kernel streams KV blocks through VMEM once per batch row with every
KV head of the block resident, so cache bytes are read exactly once (vs
≥2x for the unfused softmax path).  Ring-buffer validity and the sliding
window are handled via the cached absolute positions.

TPU tiling: a block's last two dims must be multiples of (8, 128) or span
the whole array.  The caches are (.., T, K, D) with few KV heads, so a
one-head block is illegal; the wrappers instead view the heads as one
``K * D`` lane dim (a free reshape) and DMA whole ``(rows, K * D)`` tiles,
and the body walks the heads with static lane slices.  Position vectors
ride as ``(.., 1, rows)`` rows, and the per-row scalars (current index,
dispatch start, block table) are scalar-prefetched into SMEM.

Grid: (B, T/bt) monolithic, (B, nb + 1) paged; cache blocks innermost.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import blocking

NEG_INF = -1e30


def _attend_heads(q_ref, k, v, valid, m_ref, l_ref, acc_ref, *, K: int,
                  D: int, scale: float, k_s=None, v_s=None):
    """Online-softmax update of every KV head's (G, D) accumulator with one
    block of rows.  ``k``/``v`` (rows, K*D) raw cache rows; ``valid``
    (1, rows); ``k_s``/``v_s`` (rows, K) f32 dequant scales or None.  The
    scales multiply the block's rows in VMEM before the dots — a per-row
    constant factors out of the D contraction, so this equals scaling the
    scores and the softmax weights."""
    for h in range(K):
        q = q_ref[h].astype(jnp.float32)                  # (G, D)
        kh = k[:, h * D:(h + 1) * D].astype(jnp.float32)  # (rows, D)
        vh = v[:, h * D:(h + 1) * D].astype(jnp.float32)
        if k_s is not None:
            kh = kh * k_s[:, h:h + 1]                     # fused k dequant
            vh = vh * v_s[:, h:h + 1]                     # fused v dequant
        s = jax.lax.dot_general(q, kh, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(valid, s, NEG_INF)                  # (G, rows)
        m_old, l_old = m_ref[h], l_ref[h]                 # (G, 1)
        m_new = jnp.maximum(m_old, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_old - m_new)
        l_ref[h] = l_old * corr + p.sum(axis=1, keepdims=True)
        acc_ref[h] = acc_ref[h] * corr + jax.lax.dot_general(
            p, vh, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[h] = m_new


def _init_acc(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _emit(o_ref, l_ref, acc_ref):
    out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
    o_ref[...] = out.astype(o_ref.dtype)


def _scratch(K: int, G: int, D: int):
    return [pltpu.VMEM((K, G, 1), jnp.float32),      # running max
            pltpu.VMEM((K, G, 1), jnp.float32),      # running sum
            pltpu.VMEM((K, G, D), jnp.float32)]      # output accumulator


def _decode_kernel(idx_ref, q_ref, k_ref, v_ref, pos_ref, o_ref,
                   m_ref, l_ref, acc_ref,
                   *, K: int, D: int, nt: int, window: int | None,
                   scale: float):
    b = pl.program_id(0)
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        _init_acc(m_ref, l_ref, acc_ref)

    idx = idx_ref[b]                                  # current position
    pos = pos_ref[...]                                # (1, bt) cached abs pos
    valid = (pos >= 0) & (pos <= idx)
    if window is not None:
        valid &= idx - pos < window
    _attend_heads(q_ref, k_ref[...], v_ref[...], valid, m_ref, l_ref,
                  acc_ref, K=K, D=D, scale=scale)

    @pl.when(t == nt - 1)
    def _out():
        _emit(o_ref, l_ref, acc_ref)


def _paged_decode_kernel(tbl_ref, idx_ref, p0_ref, *refs, K: int, D: int,
                         L: int, nb: int, window: int | None, scale: float,
                         n_blocks: int, ring: bool, quantized: bool):
    """Streaming-softmax body over a slot's pool blocks plus the dispatch's
    delta write buffer.

    Grid (B, nb + 1): steps ``t < nb`` stream pool block ``table[b, t]``
    (the BlockSpec index maps dereference the scalar-prefetched table, so
    the DMA address is known before the body runs); the final step attends
    the delta rows — this dispatch's own decode writes, which never touch
    the pool mid-scan — and emits.  Pool-side masks: cached-position
    validity (pos in [0, idx], window), sentinel table entries
    (``table[b, t] >= n_blocks`` kills the whole block; its DMA is clamped
    to a real block and the data discarded), and *covered* slots — slots
    this dispatch has rewritten, whose live value is the delta row (for
    ring layers the pre-wrap value can still pass the window test when the
    view is shorter than the window, so position masking alone is not
    enough).  Delta-side masks: unwritten rows (pos -1), future rows
    (pos > idx), and for ring layers rows superseded in-ring by a later
    write to the same slot (pos <= idx - ring length).

    With ``quantized`` the pool operands are int8/fp8 and two extra f32
    scale refs ride after v (see ``_attend_heads``), so the dequantized
    cache never reaches HBM.  Delta rows stay bf16 and skip both."""
    if quantized:
        (q_ref, k_ref, v_ref, ks_ref, vs_ref, pos_ref,
         dk_ref, dv_ref, dpos_ref, o_ref, m_ref, l_ref, acc_ref) = refs
    else:
        (q_ref, k_ref, v_ref, pos_ref,
         dk_ref, dv_ref, dpos_ref, o_ref, m_ref, l_ref, acc_ref) = refs
    b = pl.program_id(0)
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        _init_acc(m_ref, l_ref, acc_ref)

    idx = idx_ref[b]                                  # current position
    p0 = p0_ref[b]                                    # dispatch start
    ring_len = nb * L
    attend = functools.partial(_attend_heads, q_ref, m_ref=m_ref,
                               l_ref=l_ref, acc_ref=acc_ref, K=K, D=D,
                               scale=scale)

    @pl.when(t < nb)
    def _pool_block():
        pos = pos_ref[...]                            # (1, L) cached abs pos
        valid = (pos >= 0) & (pos <= idx)
        if window is not None:
            valid &= idx - pos < window
        valid &= tbl_ref[b, t] < n_blocks             # sentinel entry
        sl = t * L + jax.lax.broadcasted_iota(jnp.int32, (1, L), 1)
        if ring:
            covered = (sl - p0) % ring_len <= idx - p0
        else:
            covered = (sl >= p0) & (sl <= idx)
        scales = ((ks_ref[...], vs_ref[...]) if quantized  # (L, K) f32
                  else (None, None))
        attend(k_ref[...], v_ref[...], valid & ~covered, k_s=scales[0],
               v_s=scales[1])

    @pl.when(t == nb)
    def _delta():
        dpos = dpos_ref[...]                          # (1, S) -1 = unwritten
        valid = (dpos >= 0) & (dpos <= idx)
        if window is not None:
            valid &= idx - dpos < window
        if ring:
            valid &= dpos > idx - ring_len            # superseded in-ring
        attend(dk_ref[...], dv_ref[...], valid)
        _emit(o_ref, l_ref, acc_ref)


def paged_decode_attention_pallas(q: jax.Array, k_pool: jax.Array,
                                  v_pool: jax.Array, pos_pool: jax.Array,
                                  table: jax.Array, index: jax.Array, *,
                                  window: int | None = None,
                                  k_scale: jax.Array | None = None,
                                  v_scale: jax.Array | None = None,
                                  delta_k: jax.Array | None = None,
                                  delta_v: jax.Array | None = None,
                                  delta_pos: jax.Array | None = None,
                                  p0: jax.Array | None = None,
                                  interpret: bool = True) -> jax.Array:
    """Paged-cache decode attention: the KV cache lives in a block pool
    (``k_pool``/``v_pool`` (N, L, K, D), ``pos_pool`` (N, L)) and each
    batch row reads it through a block table (B, nb) of pool block ids.

    The grid iterates (B, nb + 1) with the cache-block dim innermost, and
    the k/v/pos BlockSpec index maps dereference the scalar-prefetched
    table — ``table[b, t]`` picks the pool block to DMA — so the kernel
    streams exactly the slot's blocks through VMEM once per batch row,
    never materialising the gathered linear view the XLA path
    (``models.attention.paged_view``) builds.  Sentinel table entries
    (>= N, empty serve slots) are masked out wholesale; their DMA address
    is clamped in-range and the data discarded.

    ``delta_k``/``delta_v`` (B, S, K, D), ``delta_pos`` (B, S) and ``p0``
    (B,) carry the current dispatch's own decode writes (see
    ``models.attention.init_decode_delta``): the last grid step attends
    them, and pool slots the dispatch has rewritten — linear slots
    [p0, idx], ring slots for ``window`` layers, where the table is
    expected to be pre-sliced to the window so the ring length is the view
    length nb*L — are masked from the pool-side read.  Omitting the delta
    operands degrades to pure pool attention (a masked 1-row dummy rides
    the last grid step).

    With ``k_scale``/``v_scale`` (N, L, K) f32 the pool is quantized
    (int8/fp8) and the scale rows ride the same table-indexed DMA as
    their blocks; dequant is folded into the streaming softmax (see
    ``_attend_heads``), so HBM traffic per block stays at the quantized
    byte width plus one f32 scale per row and head.  Delta operands stay
    bf16 regardless."""
    B, K, G, D = q.shape
    N, L = k_pool.shape[0], k_pool.shape[1]
    nb = table.shape[1]
    quantized = k_scale is not None
    if delta_k is None:
        dt = jnp.bfloat16 if quantized else k_pool.dtype
        delta_k = jnp.zeros((B, 1, K, D), dt)
        delta_v = jnp.zeros((B, 1, K, D), dt)
        delta_pos = jnp.full((B, 1), -1, jnp.int32)
        p0 = index + 1                   # covers nothing, masks nothing
    S = delta_pos.shape[1]
    kern = functools.partial(_paged_decode_kernel, K=K, D=D, L=L, nb=nb,
                             window=window, scale=D ** -0.5, n_blocks=N,
                             ring=window is not None, quantized=quantized)

    def blk(b, t, tbl, idx, p0):
        # clamp: the delta step (t == nb) and sentinel entries still need an
        # in-range DMA address; their data is masked in the body
        return (jnp.minimum(tbl[b, jnp.minimum(t, nb - 1)], N - 1), 0, 0)

    def row(b, t, tbl, idx, p0):
        return (b, 0, 0)

    in_specs = [
        pl.BlockSpec((None, K, G, D), lambda b, t, *_: (b, 0, 0, 0)),
        pl.BlockSpec((None, L, K * D), blk),
        pl.BlockSpec((None, L, K * D), blk),
    ]
    operands = [q, k_pool.reshape(N, L, K * D), v_pool.reshape(N, L, K * D)]
    if quantized:
        in_specs += [pl.BlockSpec((None, L, K), blk),
                     pl.BlockSpec((None, L, K), blk)]
        operands += [k_scale, v_scale]
    in_specs += [
        pl.BlockSpec((None, 1, L), blk),
        pl.BlockSpec((None, S, K * D), row),
        pl.BlockSpec((None, S, K * D), row),
        pl.BlockSpec((None, 1, S), row),
    ]
    operands += [pos_pool.reshape(N, 1, L), delta_k.reshape(B, S, K * D),
                 delta_v.reshape(B, S, K * D), delta_pos.reshape(B, 1, S)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,            # block table, index, p0
        grid=(B, nb + 1),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, K, G, D),
                               lambda b, t, *_: (b, 0, 0, 0)),
        scratch_shapes=_scratch(K, G, D),
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, D), q.dtype),
        interpret=interpret,
        name="paged_decode_attention",
    )(table, index.astype(jnp.int32), p0.astype(jnp.int32), *operands)


def decode_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array,
                            pos: jax.Array, index: jax.Array, *,
                            window: int | None = None, bt: int = 512,
                            interpret: bool = True) -> jax.Array:
    """q (B,K,G,D); k,v (B,T,K,D); pos (B,T); index (B,). -> (B,K,G,D)."""
    B, K, G, D = q.shape
    T = k.shape[1]
    bt = blocking.decode_blocks(T, bt)
    assert T % bt == 0
    nt = T // bt
    kern = functools.partial(_decode_kernel, K=K, D=D, nt=nt, window=window,
                             scale=D ** -0.5)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,            # index
        grid=(B, nt),
        in_specs=[
            pl.BlockSpec((None, K, G, D), lambda b, t, idx: (b, 0, 0, 0)),
            pl.BlockSpec((None, bt, K * D), lambda b, t, idx: (b, t, 0)),
            pl.BlockSpec((None, bt, K * D), lambda b, t, idx: (b, t, 0)),
            pl.BlockSpec((None, None, 1, bt),
                         lambda b, t, idx: (b, t, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, K, G, D),
                               lambda b, t, idx: (b, 0, 0, 0)),
        scratch_shapes=_scratch(K, G, D),
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, D), q.dtype),
        interpret=interpret,
        name="decode_attention",
    )(index.astype(jnp.int32), q.reshape(B, K, G, D), k.reshape(B, T, K * D),
      v.reshape(B, T, K * D), pos.reshape(B, nt, 1, bt))
