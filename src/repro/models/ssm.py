"""Mamba-2 (SSD, state-space duality) block — chunked parallel form + decode.

Chunked SSD [arXiv:2405.21060, Listing 1], with the inter-chunk recurrence as
a ``lax.scan`` (linear memory in chunk count, and it reuses the same scan
machinery the rest of the stack compiles well).  Decode is the O(1) recurrent
step on a (B, H, P, N) f32 state — this is why mamba2 is the designated
long_500k swarm member (DESIGN.md §4).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain
from repro.kernels.ssd_decode import ref as ssd_ref
from repro.kernels.ssd_decode.ops import ssd_state_update
from repro.models.common import (ModelConfig, ParamDef, norm_def, normal_init,
                                 ones_init, rmsnorm, zeros_init)

Array = jax.Array


class SSMState(NamedTuple):
    ssd: Array     # (B, H, P, N) f32
    conv: Array    # (B, W-1, conv_dim)


def _dims(cfg: ModelConfig):
    d_inner = cfg.ssm_d_inner
    H = cfg.ssm_nheads
    P = cfg.ssm_head_dim
    G = cfg.ssm_ngroups
    N = cfg.ssm_state
    conv_dim = d_inner + 2 * G * N
    d_in_proj = 2 * d_inner + 2 * G * N + H
    return d_inner, H, P, G, N, conv_dim, d_in_proj


def ssd_defs(cfg: ModelConfig) -> dict:
    d_inner, H, P, G, N, conv_dim, d_in_proj = _dims(cfg)
    D = cfg.d_model

    def a_init(key, shape, dtype):
        # A in [1, 16] (mamba2 default) -> A_log
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)
                       ).astype(dtype)

    def dt_init(key, shape, dtype):
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                     * (jnp.log(0.1) - jnp.log(1e-3)) + jnp.log(1e-3))
        # inverse softplus
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)

    return {
        "norm": norm_def(D),
        "in_proj": ParamDef((D, d_in_proj), ("embed", "ssm_inner"), normal_init()),
        "conv_w": ParamDef((cfg.ssm_conv_width, conv_dim), ("conv_width", "ssm_inner"), normal_init()),
        "conv_b": ParamDef((conv_dim,), ("ssm_inner",), zeros_init),
        "A_log": ParamDef((H,), ("heads",), a_init, jnp.float32),
        "dt_bias": ParamDef((H,), ("heads",), dt_init, jnp.float32),
        "D_skip": ParamDef((H,), ("heads",), ones_init, jnp.float32),
        "gnorm": ParamDef((d_inner,), ("ssm_inner",), zeros_init),
        "out_proj": ParamDef((d_inner, D), ("ssm_inner", "embed"),
                             normal_init(0.02 / (2 * cfg.num_layers) ** 0.5)),
    }


def _split_proj(zxbcdt: Array, cfg: ModelConfig):
    d_inner, H, P, G, N, conv_dim, _ = _dims(cfg)
    z = zxbcdt[..., :d_inner]
    xBC = zxbcdt[..., d_inner:d_inner + conv_dim]
    dt = zxbcdt[..., -H:]
    return z, xBC, dt


def _causal_conv(xBC: Array, w: Array, b: Array, prev: Array | None = None,
                 tail_index: Array | None = None):
    """Depthwise causal conv1d. xBC (B,L,C); w (W,C); returns (out, new_tail).

    ``tail_index`` (B,) — number of *real* (non-padding) leading columns per
    row.  Default (None) takes the tail from the last W-1 columns, which is
    correct for LEFT-padded spans (real tokens at the end).  Continuation
    spans are RIGHT-padded (real tokens first, so the conv window of the
    first real token reaches into ``prev`` — the cached context tail — with
    no padding gap); there the tail must end at the last real input, i.e.
    padded-input columns [tail_index, tail_index + W - 2]."""
    B, L, C = xBC.shape
    W = w.shape[0]
    if prev is None:
        prev = jnp.zeros((B, W - 1, C), xBC.dtype)
    xpad = jnp.concatenate([prev, xBC], axis=1)
    out = jax.lax.conv_general_dilated(
        xpad, w[:, None, :].astype(xBC.dtype),
        window_strides=(1,), padding="VALID",
        dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=C)
    out = jax.nn.silu(out + b.astype(out.dtype))
    if W <= 1:
        tail = jnp.zeros((B, 0, C), xBC.dtype)
    elif tail_index is None:
        tail = xpad[:, -(W - 1):]
    else:
        idx = tail_index[:, None] + jnp.arange(W - 1, dtype=jnp.int32)[None]
        tail = jnp.take_along_axis(xpad, idx[..., None], axis=1)
    return out, tail


def _causal_conv_step(x: Array, w: Array, b: Array, prev: Array):
    """One-token depthwise causal conv (decode path). x (B,1,C); prev
    (B,W-1,C).  Same math as ``_causal_conv`` at L=1, but lowered as a
    window multiply+sum instead of ``conv_general_dilated``: the per-step
    conv op is pure overhead at L=1, and XLA CPU's SPMD partitioner
    miscompiles (native crash) the grouped conv when C is sharded over
    'model' while the batch dim is replicated — the sharded decode scan
    hits exactly that layout whenever B doesn't divide the 'data' axis."""
    B, _, C = x.shape
    W = w.shape[0]
    xpad = jnp.concatenate([prev, x], axis=1)            # (B, W, C)
    # f32 window accumulation, rounded back to the activation dtype before
    # bias+silu — the same numerics the conv lowering produces
    out = (xpad.astype(jnp.float32) * w.astype(jnp.float32)[None]).sum(
        axis=1, keepdims=True).astype(x.dtype)
    out = jax.nn.silu(out + b.astype(out.dtype))
    tail = xpad[:, 1:] if W > 1 else jnp.zeros((B, 0, C), x.dtype)
    return out, tail


def _segsum(a: Array) -> Array:
    """a (..., q) -> (..., q, q) with out[i,j] = sum a[j+1..i], -inf above diag."""
    q = a.shape[-1]
    cum = jnp.cumsum(a, axis=-1)
    d = cum[..., :, None] - cum[..., None, :]
    mask = jnp.tril(jnp.ones((q, q), bool))
    return jnp.where(mask, d, -jnp.inf)


def ssd_scan(x: Array, dt: Array, A: Array, Bm: Array, Cm: Array, chunk: int,
             init_state: Array | None = None):
    """Chunked SSD.

    x (B,L,H,P); dt (B,L,H) (post-softplus); A (H,) negative;
    Bm, Cm (B,L,G,N).  Returns y (B,L,H,P), final state (B,H,P,N) f32.
    """
    Bsz, L, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = H // G
    Q = min(chunk, L)
    assert L % Q == 0
    nc = L // Q

    xb = (x * dt[..., None]).astype(jnp.float32).reshape(Bsz, nc, Q, H, P)
    a = (dt * A[None, None, :]).astype(jnp.float32)           # (B,L,H) log-decay
    ab = a.reshape(Bsz, nc, Q, H).transpose(0, 3, 1, 2)       # (B,H,nc,Q)
    a_cum = jnp.cumsum(ab, axis=-1)
    Bb = Bm.astype(jnp.float32).reshape(Bsz, nc, Q, G, N)
    Cb = Cm.astype(jnp.float32).reshape(Bsz, nc, Q, G, N)

    def rep_heads(t):  # (B,nc,Q,G,N) -> (B,nc,Q,H,N)
        return jnp.repeat(t, rep, axis=3)

    Bh, Ch = rep_heads(Bb), rep_heads(Cb)

    # 1. intra-chunk (diagonal blocks)
    Ldec = jnp.exp(_segsum(ab))                               # (B,H,nc,Q,Q)
    scores = jnp.einsum("bclhn,bcshn->bhcls", Ch, Bh)         # (B,H,nc,Q,Q)
    y_diag = jnp.einsum("bhcls,bhcls,bcshp->bclhp", scores, Ldec, xb)

    # 2. per-chunk input states
    decay_states = jnp.exp(a_cum[..., -1:] - a_cum)           # (B,H,nc,Q)
    states = jnp.einsum("bclhn,bhcl,bclhp->bchpn", Bh, decay_states, xb)

    # 3. inter-chunk recurrence (scan over chunks)
    chunk_decay = jnp.exp(a_cum[..., -1])                     # (B,H,nc)

    def step(s, inp):
        st_c, dec_c = inp                                     # (B,H,P,N), (B,H)
        s_out = s                                             # state *entering* chunk
        s = s * dec_c[..., None, None] + st_c
        return s, s_out

    s0 = (init_state if init_state is not None
          else jnp.zeros((Bsz, H, P, N), jnp.float32))
    final, prev_states = jax.lax.scan(
        step, s0, (states.swapaxes(0, 1), chunk_decay.transpose(2, 0, 1)))
    prev_states = prev_states.swapaxes(0, 1)                  # (B,nc,H,P,N)

    # 4. state -> output contribution
    out_decay = jnp.exp(a_cum)                                # (B,H,nc,Q)
    y_off = jnp.einsum("bclhn,bchpn,bhcl->bclhp", Ch, prev_states, out_decay)

    y = (y_diag + y_off).reshape(Bsz, L, H, P)
    return y, final


def ssd_block(p: dict, x: Array, cfg: ModelConfig) -> Array:
    """Full-sequence mamba2 block. x (B,S,D)."""
    d_inner, H, P, G, N, conv_dim, _ = _dims(cfg)
    h = rmsnorm(x, p["norm"], cfg.norm_eps)
    zxbcdt = h @ p["in_proj"].astype(h.dtype)
    z, xBC, dt = _split_proj(zxbcdt, cfg)
    xBC, _ = _causal_conv(xBC, p["conv_w"], p["conv_b"])
    xs = xBC[..., :d_inner]
    Bm = xBC[..., d_inner:d_inner + G * N].reshape(*xs.shape[:2], G, N)
    Cm = xBC[..., d_inner + G * N:].reshape(*xs.shape[:2], G, N)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    y, _ = ssd_scan(xs.reshape(*xs.shape[:2], H, P), dt, A, Bm, Cm, cfg.ssm_chunk)
    y = y + xs.reshape(*xs.shape[:2], H, P).astype(jnp.float32) * p["D_skip"][:, None]
    y = y.reshape(*xs.shape[:2], d_inner)
    y = rmsnorm((y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype),
                p["gnorm"], cfg.norm_eps)
    return x + y @ p["out_proj"].astype(x.dtype)


def ssd_prefill(p: dict, x: Array, state: SSMState, positions: Array,
                cfg: ModelConfig, mesh=None, rules=None, *,
                continuation: bool = False) -> tuple[Array, SSMState]:
    """Prompt absorption: chunked SSD scan that also returns the carried
    (B,H,P,N) state and conv tail for decode.

    positions (B,S): negative positions are inert bucket padding — their
    conv input is zeroed and dt forced to 0, so the step decay is exp(0)=1
    and the input contribution x*dt vanishes; the carried state passes
    through untouched.  Cold spans are left-padded (last column real);
    ``continuation=True`` spans are RIGHT-padded — real tokens first, so
    the conv window crosses from ``state.conv`` (the cached context tail)
    straight into the new span with no padding gap, and the conv tail is
    taken at the last *real* column.  The recurrence itself is
    layout-agnostic: ``state.ssd`` folds in as the scan's initial state and
    padding steps pass it through exactly (decay 1, input 0), so the final
    state equals the state after the last real token either way.
    """
    d_inner, H, P, G, N, conv_dim, _ = _dims(cfg)
    B, S, _ = x.shape
    valid = (positions >= 0)[..., None]                      # (B,S,1)
    h = rmsnorm(x, p["norm"], cfg.norm_eps)
    zxbcdt = h @ p["in_proj"].astype(h.dtype)
    z, xBC, dt = _split_proj(zxbcdt, cfg)
    xBC = jnp.where(valid, xBC, 0)
    tail_index = (valid[..., 0].sum(axis=1).astype(jnp.int32)
                  if continuation else None)
    xBC, conv_tail = _causal_conv(xBC, p["conv_w"], p["conv_b"],
                                  prev=state.conv, tail_index=tail_index)
    xs = xBC[..., :d_inner]
    Bm = xBC[..., d_inner:d_inner + G * N].reshape(B, S, G, N)
    Cm = xBC[..., d_inner + G * N:].reshape(B, S, G, N)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    dt = jnp.where(valid, dt, 0.0)
    A = -jnp.exp(p["A_log"])
    y, s_final = ssd_scan(xs.reshape(B, S, H, P), dt, A, Bm, Cm,
                          cfg.ssm_chunk, init_state=state.ssd)
    y = y + xs.reshape(B, S, H, P).astype(jnp.float32) * p["D_skip"][:, None]
    y = y.reshape(B, S, d_inner)
    y = rmsnorm((y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype),
                p["gnorm"], cfg.norm_eps)
    state = SSMState(
        ssd=constrain(s_final, ("act_batch", "act_heads", None, None),
                      mesh, rules),
        conv=constrain(conv_tail, ("act_batch", None, "act_ssm_inner"),
                       mesh, rules))
    return x + y @ p["out_proj"].astype(x.dtype), state


def init_ssm_state(cfg: ModelConfig, batch: int) -> SSMState:
    d_inner, H, P, G, N, conv_dim, _ = _dims(cfg)
    return SSMState(
        # swarmlint: ignore[dtype-drift] the SSD state update decays per
        # token (dA * state + dBx); bf16 accumulation drifts over long
        # sequences and breaks paged-vs-monolithic bitwise parity
        ssd=jnp.zeros((batch, H, P, N), jnp.float32),
        conv=jnp.zeros((batch, cfg.ssm_conv_width - 1, conv_dim), cfg.dtype),
    )


def _decode_inputs(p: dict, x: Array, conv: Array, cfg: ModelConfig):
    """A decode step up to the state update: norm, in_proj and the conv
    step.  Returns (z, conv tail, the f32 operands of the state update:
    dA, dt (B,H), x (B,H,P), B, C (B,G,N), D (H,))."""
    d_inner, H, P, G, N, conv_dim, _ = _dims(cfg)
    B = x.shape[0]
    h = rmsnorm(x, p["norm"], cfg.norm_eps)
    zxbcdt = h @ p["in_proj"].astype(h.dtype)
    z, xBC, dt = _split_proj(zxbcdt, cfg)
    xBC, conv_tail = _causal_conv_step(xBC, p["conv_w"], p["conv_b"], conv)
    xs = xBC[:, 0, :d_inner]
    Bm = xBC[:, 0, d_inner:d_inner + G * N].reshape(B, G, N)
    Cm = xBC[:, 0, d_inner + G * N:].reshape(B, G, N)
    dt = jax.nn.softplus(dt[:, 0].astype(jnp.float32) + p["dt_bias"])  # (B,H)
    A = -jnp.exp(p["A_log"])
    dA = jnp.exp(dt * A[None, :])                                      # (B,H)
    step = (dA, dt, xs.reshape(B, H, P).astype(jnp.float32),
            Bm.astype(jnp.float32), Cm.astype(jnp.float32), p["D_skip"])
    return z, conv_tail, step


def _decode_output(p: dict, x: Array, y: Array, z: Array,
                   cfg: ModelConfig) -> Array:
    """A decode step after the state update: gate, norm, out_proj and the
    residual.  y (B,H,P) f32."""
    y = y.reshape(x.shape[0], 1, cfg.ssm_d_inner)
    y = rmsnorm((y * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype),
                p["gnorm"], cfg.norm_eps)
    return x + y @ p["out_proj"].astype(x.dtype)


def ssd_decode(p: dict, x: Array, state: SSMState, cfg: ModelConfig,
               mesh=None, rules=None) -> tuple[Array, SSMState]:
    """One-token decode. x (B,1,D).  On-mesh the carried (B,H,P,N) state is
    pinned ``(act_batch, act_heads)``-sharded across the decode scan."""
    z, conv_tail, step = _decode_inputs(p, x, state.conv, cfg)
    s_new, y = ssd_ref.ssd_step(state.ssd, *step)
    out = _decode_output(p, x, y, z, cfg)
    state = SSMState(
        ssd=constrain(s_new, ("act_batch", "act_heads", None, None),
                      mesh, rules),
        conv=constrain(conv_tail, ("act_batch", None, "act_ssm_inner"),
                       mesh, rules))
    return out, state


def ssd_decode_pooled(p: dict, x: Array, state: SSMState, rows: Array,
                      layer, cfg: ModelConfig) -> tuple[Array, SSMState]:
    """One-token decode whose SSD state stays in the pool.  ``state.ssd``
    is the pool leaf itself — (L, R, H, P, N) with ``layer`` the stage
    scan's layer index, or (R, H, P, N) with ``layer`` None — and
    ``state.conv`` the slots' (B, W-1, C) conv-tail rows.  The slots'
    state rows ``rows`` (B,) are stepped where they lie
    (``kernels/ssd_decode``; sentinel ids >= R write nothing); returns the
    block output and SSMState(updated pool, new conv tail)."""
    z, conv_tail, step = _decode_inputs(p, x, state.conv, cfg)
    pool = state.ssd if layer is not None else state.ssd[None]
    with jax.named_scope("decode.ssd_state"):
        y, pool = ssd_state_update(pool, rows, 0 if layer is None else layer,
                                   *step)
    pool = pool if layer is not None else pool[0]
    return _decode_output(p, x, y, z, cfg), SSMState(ssd=pool,
                                                     conv=conv_tail)
