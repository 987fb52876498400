"""Mixture-of-Experts FFN: capacity-based dispatch, EP-shardable.

Dispatch layout is *grouped*: tokens are reshaped into ``moe_groups`` groups
(one per data-parallel shard at the production mesh), each group dispatches
into its own (E, C_local) capacity buffer.  Scatter/gather indices then stay
aligned with the batch sharding, so SPMD keeps dispatch local to a (data,
model) shard pair — the only collectives are the ones real expert parallelism
needs (routed activations crossing the expert axis).

Supports DeepSeek-style shared experts (always-on dense branch) and top-k
renormalised softmax gating (top-1 == Switch, top-6 == DeepSeekMoE,
top-1+shared == Llama-4-Scout).

Three dispatch flavours share the router (``_route_topk``) and the sort-based
in-expert ranking (``_rank_in_expert``):

* ``moe_block`` — the training / full-forward path: flat token groups,
  capacity ``moe_capacity`` (tokens compete batch-wide; overflow drops).
* ``moe_prefill_block`` — the serving prefill path: **one dispatch group per
  prompt position**, so each group routes exactly the token set a stepwise
  ``decode_step`` would route, and fused prefill reproduces sequential
  absorption semantics by construction.  Inert bucket-padding tokens
  (negative positions) are *masked*: router logits forced to -inf, the
  assignment moved to a sentinel expert segment so it never consumes a
  capacity slot of a real expert, and the combine weight zeroed.  Per-group
  capacity defaults to the group size (drop-free => exact top-k); the
  ``moe_serve_capacity_factor`` config knob bounds it at scale.
* ``moe_decode_block`` — the serving decode path: the SAME per-position
  dispatch at S=1 (a one-token-column capacity buffer, constant shapes for
  the decode scan).  Sharing the dispatch structure is what makes fused
  prefill and stepwise absorption **bitwise identical** through MoE layers:
  XLA evaluates the batched dispatch einsums per group slice, so a position
  routed inside an (S, E, C, D) buffer produces the exact bits the same
  position routed alone would (verified; the alternative top-k weight
  gather — ``moe_decode_impl="gather"``, expert FLOPs k instead of E — is
  1 bf16 ulp off, enough to flip a greedy argmax on an exact tie).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from jax import shard_map as _shard_map

from repro.distributed.sharding import constrain
from repro.models import quant as Q
from repro.models.common import (ACTIVATIONS, ModelConfig, ParamDef, norm_def,
                                 normal_init, rmsnorm)
from repro.models.ffn import _mlp_body, mlp_defs

Array = jax.Array


def moe_defs(cfg: ModelConfig) -> dict:
    D, E, Fe = cfg.d_model, cfg.num_experts, cfg.expert_d_ff
    std_o = 0.02 / (2 * cfg.num_layers) ** 0.5
    defs = {
        "norm": norm_def(D),
        "router": ParamDef((D, E), ("embed", "experts"), normal_init()),
        "w_gate": ParamDef((E, D, Fe), ("experts", "embed", "expert_ffn"), normal_init()),
        "w_up": ParamDef((E, D, Fe), ("experts", "embed", "expert_ffn"), normal_init()),
        "w_down": ParamDef((E, Fe, D), ("experts", "expert_ffn", "embed"), normal_init(std_o)),
    }
    if cfg.num_shared_experts:
        shared = dict(mlp_defs(cfg, d_ff=cfg.num_shared_experts * cfg.expert_d_ff))
        shared.pop("norm")  # share the block norm
        defs["shared"] = shared
    return defs


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def moe_capacity(cfg: ModelConfig, tokens_per_group: int) -> int:
    c = int(tokens_per_group * cfg.top_k / cfg.num_experts * cfg.capacity_factor)
    return max(_round_up(c, 8), 8)


def moe_serve_capacity(cfg: ModelConfig, tokens_per_group: int) -> int:
    """Per-group capacity on the serving (prefill) path.

    Default (``moe_serve_capacity_factor=None``): the group size itself —
    a group can never overflow an expert, so serving routing is exact
    top-k (drop-free) and fused prefill matches stepwise absorption
    bitwise at the routing level.  With the factor set, capacity is
    bounded like the training dispatch (overflow tokens lose their slot),
    trading the exactness guarantee for an O(factor·k/E) smaller buffer
    at large serve batch sizes.
    """
    f = cfg.moe_serve_capacity_factor
    if f is None:
        return tokens_per_group
    c = int(tokens_per_group * cfg.top_k / cfg.num_experts * f)
    return max(min(_round_up(c, 8), tokens_per_group), 1)


_MASKED = -1e30          # "-inf" for masked router logits (softmax-safe)


def _route_topk(router: Array, h: Array, cfg: ModelConfig,
                valid: Array | None = None) -> tuple[Array, Array, Array]:
    """Top-k routing in f32: h (g,T,D) -> (gates (g,T,k), idx (g,T,k),
    probs (g,T,E)).  ``valid`` (g,T) masks inert tokens: their logits are
    forced to -inf (uniform probs, no NaN) — callers must also exclude
    them from capacity counts and zero their combine weights.
    """
    logits = jnp.einsum("gtd,de->gte", h.astype(jnp.float32),
                        router.astype(jnp.float32))
    if valid is not None:
        logits = jnp.where(valid[..., None], logits, _MASKED)
    probs = jax.nn.softmax(logits, axis=-1)                # (g,T,E)
    gates, idx = jax.lax.top_k(probs, cfg.top_k)           # (g,T,k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates, idx, probs


def _rank_in_expert(flat_e: Array) -> Array:
    """flat_e (g, A) expert ids -> (g, A) position of each assignment within
    its expert's arrival order.  Sort-based ranking: O(A log A) and O(A)
    memory; argsort is stable, so in-segment order == token order == the
    GShard cumsum semantics.  Segment starts come from a cummax over
    boundary markers (a vmapped searchsorted segfaulted XLA:CPU under
    512-way SPMD — see §Perf)."""
    groups, A = flat_e.shape
    sort_idx = jnp.argsort(flat_e, axis=1)                 # (g, A)
    sorted_e = jnp.take_along_axis(flat_e, sort_idx, axis=1)
    ar = jnp.arange(A)[None, :]
    is_new = jnp.concatenate(
        [jnp.ones((groups, 1), bool),
         sorted_e[:, 1:] != sorted_e[:, :-1]], axis=1)
    seg_start = jax.lax.cummax(jnp.where(is_new, ar, 0), axis=1)
    pos_sorted = ar - seg_start
    inv = jnp.argsort(sort_idx, axis=1)
    return jnp.take_along_axis(pos_sorted, inv, axis=1)    # (g, A)


def moe_block(p: dict, x: Array, cfg: ModelConfig, *,
              groups: int = 1, mesh=None, rules=None) -> tuple[Array, Array]:
    """x (B,S,D) -> (x + moe(x), aux_loss).  groups must divide B*S.

    Sharding note: the capacity buffer is kept REPLICATED over the model
    axis (constrained below) so the dispatch scatter and combine gather stay
    local to each (data, model) shard — if the buffer's E dim is
    model-sharded, XLA SPMD rewrites the 3-index scatter into dense
    select-updates with (A, D)-sized u32 index tensors (measured 58 GB of
    u32 on deepseek train_4k; §Perf iteration 3).  The expert einsums then
    contract against model-sharded weights and their outputs are constrained
    back to replicated — one (g,E,C,D)-sized all-gather per layer instead.
    """
    B, S, D = x.shape
    E, k = cfg.num_experts, cfg.top_k
    T = B * S
    assert T % groups == 0, (T, groups)
    Tg = T // groups
    C = moe_capacity(cfg, Tg)

    h = rmsnorm(x, p["norm"], cfg.norm_eps)
    hf = h.reshape(groups, Tg, D)

    # --- routing (f32) ---
    gates, idx, probs = _route_topk(p["router"], hf, cfg)      # (g,Tg,k)

    # load-balance aux loss (Switch): E * mean_e(frac_tokens_e * mean_prob_e)
    onehot_top1 = jax.nn.one_hot(idx[..., 0], E, dtype=jnp.float32)
    aux = E * jnp.mean(jnp.mean(onehot_top1, axis=1) * jnp.mean(probs, axis=1))

    # --- dispatch: position of each assignment within its expert ---
    flat_e = idx.reshape(groups, Tg * k)                       # (g, A)
    A = Tg * k
    if cfg.moe_impl == "cumsum":
        # GShard-style one-hot cumsum: materialises (g, A, E) int32 —
        # measured 100+ GB/device at deepseek train_4k; kept for the
        # hillclimb before/after (EXPERIMENTS.md §Perf iteration 1)
        oh = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)        # (g, A, E)
        pos = jnp.cumsum(oh, axis=1) - oh
        pos = jnp.take_along_axis(
            pos, flat_e[..., None], axis=-1)[..., 0]           # (g, A)
    else:
        pos = _rank_in_expert(flat_e)                          # (g, A)
    keep = pos < C
    # dropped assignments scatter to row C (then sliced off)
    e_idx = jnp.where(keep, flat_e, E - 1)
    c_idx = jnp.where(keep, pos, C)

    token_src = jnp.repeat(jnp.arange(Tg), k)                  # (A,)
    src = jnp.take(hf, token_src, axis=1).astype(h.dtype)      # (g, A, D)

    def _dispatch(src_l, e_l, c_l):
        gl = jnp.broadcast_to(jnp.arange(src_l.shape[0])[:, None], e_l.shape)
        b = jnp.zeros((src_l.shape[0], E, C + 1, D), src_l.dtype)
        return b.at[gl, e_l, c_l].set(src_l, mode="drop")[:, :, :C]

    def _combine(ob_l, e_l, c_l):
        gl = jnp.broadcast_to(jnp.arange(ob_l.shape[0])[:, None], e_l.shape)
        return ob_l[gl, e_l, jnp.minimum(c_l, C - 1)]

    # Dispatch/combine run under shard_map when the group dim divides the
    # batch axes: each (data, model) shard then executes a purely LOCAL
    # scatter/gather with (A,)-sized indices.  Left to SPMD propagation, the
    # 3-index scatter on an expert-sharded buffer gets rewritten into dense
    # select-updates with (A, D)-sized u32 index maps (measured 58 GB of u32
    # temps on deepseek train_4k; §Perf iterations 1-3).
    daxes = tuple(a for a in ("pod", "data") if mesh is not None
                  and a in mesh.shape)
    dp = 1
    for a in daxes:
        dp *= mesh.shape[a]
    use_smap = mesh is not None and daxes and groups % dp == 0
    if use_smap:
        from jax.sharding import PartitionSpec as P
        gspec = P(daxes if len(daxes) > 1 else daxes[0])
        smap = lambda f: _shard_map(f, mesh=mesh,
                                    in_specs=(gspec, gspec, gspec),
                                    out_specs=gspec)
        buf = smap(_dispatch)(src, e_idx, c_idx)
    else:
        buf = _dispatch(src, e_idx, c_idx)

    # --- expert compute (weights model-sharded over E) ---
    act = ACTIVATIONS[cfg.ffn_act]
    dt = h.dtype
    gate_h = jnp.einsum("gecd,edf->gecf", buf, p["w_gate"].astype(dt))
    up_h = jnp.einsum("gecd,edf->gecf", buf, p["w_up"].astype(dt))
    out_buf = jnp.einsum("gecf,efd->gecd", act(gate_h) * up_h,
                         p["w_down"].astype(dt))               # (g,E,C,D)
    out_buf = constrain(out_buf, ("act_batch", None, None, None), mesh, rules)

    # --- combine ---
    if use_smap:
        y = smap(_combine)(out_buf, e_idx, c_idx)              # (g,A,D)
    else:
        y = _combine(out_buf, e_idx, c_idx)
    w = (gates.reshape(groups, Tg * k) * keep).astype(jnp.float32)
    y = (y.astype(jnp.float32) * w[..., None]).reshape(groups, Tg, k, D).sum(2)

    if "shared" in p:
        y = y + _mlp_body(p["shared"], hf, cfg).astype(jnp.float32)

    return x + y.reshape(B, S, D).astype(x.dtype), aux


# ---------------------------------------------------------------------------
# Serving path: masked per-position prefill + exact top-k decode
# ---------------------------------------------------------------------------

def moe_prefill_block(p: dict, x: Array, cfg: ModelConfig, positions: Array,
                      *, mesh=None, rules=None) -> tuple[Array, Array]:
    """Capacity-aware MASKED dispatch for the fused serving prefill.

    x (B,S,D); positions (B,S) absolute positions — negative marks inert
    bucket padding.  Returns (x + moe(x), aux).

    One dispatch group **per span position**: group s routes exactly the
    B tokens a stepwise ``decode_step`` at position s would route, so
    per-group capacity (``moe_serve_capacity(cfg, B)``; default B itself,
    i.e. drop-free) and in-group arrival ranking reproduce sequential
    absorption semantics — the fused path and the stepwise oracle make
    identical routing decisions by construction.  Continuation prefill
    (``transformer.prefill(..., continuation=True)``) reuses this dispatch
    unchanged: routing depends only on the hidden states and the valid
    mask, never on the absolute position values, so a span absorbed at
    offset positions over a live cache routes exactly as the same span
    inside a cold prefill of the concatenation (fully-masked trailing
    padding groups route to the sentinel segment and combine to zero).

    Padding tokens are masked three ways so padded and unpadded prompts
    dispatch identically: (1) router logits forced to -inf (no NaN:
    softmax of an all-masked row is uniform); (2) their assignments move
    to a sentinel expert segment (id E) which — ``argsort`` being stable —
    sorts after every real expert, so a padding token never consumes a
    capacity slot of a real expert in its group; (3) their combine weight
    is zeroed.  Capacity buffers keep their expert dim replicated (see
    ``moe_block``'s sharding note); the group dim is the sequence, which
    ``act_moe_group``/``act_expert_cap`` pin unsharded so the scatter
    stays a cheap 3-index per-group scatter under SPMD.
    """
    B, S, D = x.shape
    E, k = cfg.num_experts, cfg.top_k
    C = moe_serve_capacity(cfg, B)

    h = rmsnorm(x, p["norm"], cfg.norm_eps)
    hf = h.swapaxes(0, 1)                                  # (S, B, D)
    valid = (positions >= 0).swapaxes(0, 1)                # (S, B)
    gates, idx, probs = _route_topk(p["router"], hf, cfg, valid=valid)

    A = B * k
    valid_a = jnp.repeat(valid, k, axis=1)                 # (S, A)
    flat_e = jnp.where(valid_a, idx.reshape(S, A), E)      # masked -> sentinel
    pos = _rank_in_expert(flat_e)
    keep = (pos < C) & valid_a
    e_idx = jnp.where(keep, flat_e, E - 1)
    c_idx = jnp.where(keep, pos, C)                        # dropped -> row C

    token_src = jnp.repeat(jnp.arange(B), k)               # (A,)
    src = jnp.take(hf, token_src, axis=1).astype(h.dtype)  # (S, A, D)
    gl = jnp.broadcast_to(jnp.arange(S)[:, None], e_idx.shape)
    buf = jnp.zeros((S, E, C + 1, D), src.dtype)
    buf = buf.at[gl, e_idx, c_idx].set(src, mode="drop")[:, :, :C]
    buf = constrain(buf, ("act_moe_group", None, "act_expert_cap", None),
                    mesh, rules)

    act = ACTIVATIONS[cfg.ffn_act]
    dt = h.dtype
    gate_h = jnp.einsum("gecd,edf->gecf", buf, p["w_gate"].astype(dt))
    up_h = jnp.einsum("gecd,edf->gecf", buf, p["w_up"].astype(dt))
    out_buf = jnp.einsum("gecf,efd->gecd", act(gate_h) * up_h,
                         p["w_down"].astype(dt))           # (S,E,C,D)
    out_buf = constrain(out_buf, ("act_moe_group", None, "act_expert_cap",
                                  None), mesh, rules)

    y = out_buf[gl, e_idx, jnp.minimum(c_idx, C - 1)]      # (S, A, D)
    w = (gates.reshape(S, A) * keep).astype(jnp.float32)
    y = (y.astype(jnp.float32) * w[..., None]).reshape(S, B, k, D).sum(2)
    if "shared" in p:
        y = y + _mlp_body(p["shared"], hf, cfg).astype(jnp.float32)
    y = y.swapaxes(0, 1)                                   # (B, S, D)

    # masked load-balance aux: padding excluded from both factors
    vf = valid.astype(jnp.float32)[..., None]              # (S, B, 1)
    cnt = jnp.maximum(vf.sum(), 1.0)
    top1 = jax.nn.one_hot(idx[..., 0], E, dtype=jnp.float32)
    aux = E * jnp.mean(((top1 * vf).sum((0, 1)) / cnt)
                       * ((probs * vf).sum((0, 1)) / cnt))
    return x + y.astype(x.dtype), aux


def _take_expert_rows(w, idx, dt):
    """Gather the k selected experts' weight rows.  Quantized weights
    (``quant.QTensor``) gather payload *and* scale rows and dequantize
    after the gather, so weight traffic stays k/E bytes as well as
    k/E FLOPs."""
    if isinstance(w, Q.QTensor):
        return w.take_rows(idx, dt)
    return jnp.take(w, idx, axis=0).astype(dt)


def moe_decode_block(p: dict, x: Array, cfg: ModelConfig, *,
                     mesh=None, rules=None) -> tuple[Array, Array]:
    """Constant-shape exact top-k dispatch for the decode step.

    x (B,1,D) — one token per sequence.  Default (``moe_decode_impl=
    "dispatch"``): reuse the per-position serving dispatch at S=1 — the
    buffer is one token column, shapes depend only on (B, k, C) so the
    decode-scan carry stays shape-stable, and because prefill uses the
    *same* dispatch einsums, fused prefill == stepwise absorption ==
    serve() bitwise through every MoE layer.  Drop-free by default
    (capacity = B), so serve()'s mixed-request slot batches — and the
    garbage its empty slots decode — can never perturb another slot's
    routing.

    ``moe_decode_impl="gather"`` instead gathers only the k selected
    experts' weight rows per token: expert FLOPs drop from E to k and
    weight traffic is 3·B·k·D·F_e (< the resident weights whenever
    B·k < E, the serving regime).  Numerically ~1 bf16 ulp off the
    dispatch einsums, so greedy parity with the stepwise oracle is no
    longer bit-guaranteed — an opt-in for large-E production decode
    (docs/RUNTIME.md).
    """
    B, S, D = x.shape
    if cfg.moe_decode_impl != "gather":
        return moe_prefill_block(p, x, cfg,
                                 jnp.zeros((B, S), jnp.int32),
                                 mesh=mesh, rules=rules)
    T = B * S                               # S == 1 on the decode path
    h = rmsnorm(x, p["norm"], cfg.norm_eps)
    hf = h.reshape(T, D)
    gates, idx, _ = _route_topk(p["router"], hf[None], cfg)
    gates, idx = gates[0], idx[0]                          # (T, k)

    dt = h.dtype
    wk = ("act_batch", "act_topk", None, "act_expert_ffn")
    wg = constrain(_take_expert_rows(p["w_gate"], idx, dt),
                   wk, mesh, rules)                        # (T,k,D,Fe)
    wu = constrain(_take_expert_rows(p["w_up"], idx, dt),
                   wk, mesh, rules)
    wd = constrain(_take_expert_rows(p["w_down"], idx, dt),
                   ("act_batch", "act_topk", "act_expert_ffn", None),
                   mesh, rules)                            # (T,k,Fe,D)

    act = ACTIVATIONS[cfg.ffn_act]
    gate_h = jnp.einsum("td,tkdf->tkf", hf, wg)
    up_h = jnp.einsum("td,tkdf->tkf", hf, wu)
    o = jnp.einsum("tkf,tkfd->tkd", act(gate_h) * up_h, wd)
    y = (o.astype(jnp.float32) * gates[..., None]).sum(1)  # (T, D)
    y = y.reshape(B, S, D)
    if "shared" in p:
        y = y + _mlp_body(p["shared"], h, cfg).astype(jnp.float32)
    return x + y.astype(x.dtype), jnp.zeros((), jnp.float32)
