"""Model assembler: embeddings -> staged (scanned) blocks -> LM head.

One code path serves every assigned architecture: dense GQA decoders, MoE
(shared+routed), Mamba-2 SSD, RG-LRU hybrids, bidirectional encoders and the
stub-fronted VLM/audio variants.  Layers run as ``lax.scan`` over stacked
params (per ``ModelConfig.stage_plan``) so an 80-layer 110B model lowers to a
compact HLO for the multi-pod dry-run.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import ShardingRules, constrain
from repro.models import attention, ffn, moe, rglru, ssm
from repro.models.common import (ModelConfig, ParamDef, Stage, abstract_tree,
                                 axes_tree, init_tree, norm_def, normal_init,
                                 rmsnorm)

Array = jax.Array


# ---------------------------------------------------------------------------
# Param definitions
# ---------------------------------------------------------------------------

def block_defs(cfg: ModelConfig, kind: tuple[str, str]) -> dict:
    mixer, f = kind
    out: dict[str, Any] = {}
    if mixer in ("attn", "attn_local"):
        out["mixer"] = attention.attn_defs(cfg)
    elif mixer == "rglru":
        out["mixer"] = rglru.rglru_defs(cfg)
    elif mixer == "ssd":
        out["mixer"] = ssm.ssd_defs(cfg)
    else:
        raise ValueError(mixer)
    if f == "mlp":
        out["ffn"] = ffn.mlp_defs(cfg)
    elif f == "moe":
        out["ffn"] = moe.moe_defs(cfg)
    elif f != "none":
        raise ValueError(f)
    return out


def model_defs(cfg: ModelConfig) -> dict:
    D, V = cfg.d_model, cfg.vocab_size
    defs: dict[str, Any] = {
        "embed": ParamDef((V, D), ("vocab", "embed"), normal_init()),
        "final_norm": norm_def(D),
    }
    stages = []
    for st in cfg.stage_plan():
        sdefs = {f"b{i}": block_defs(cfg, kind) for i, kind in enumerate(st.blocks)}
        if st.repeat > 1:
            sdefs = jax.tree.map(lambda d: d.with_leading(st.repeat), sdefs,
                                 is_leaf=lambda x: isinstance(x, ParamDef))
        stages.append(sdefs)
    defs["stages"] = stages
    if not cfg.tie_embeddings:
        defs["lm_head"] = ParamDef((D, V), ("embed", "vocab"), normal_init())
    return defs


def init_params(cfg: ModelConfig, key: jax.Array) -> dict:
    return init_tree(model_defs(cfg), key, cfg.dtype)


def abstract_params(cfg: ModelConfig) -> dict:
    return abstract_tree(model_defs(cfg), cfg.dtype)


def param_axes(cfg: ModelConfig) -> dict:
    return axes_tree(model_defs(cfg))


# ---------------------------------------------------------------------------
# Forward (full sequence: training / prefill)
# ---------------------------------------------------------------------------

def _apply_block(bp: dict, x: Array, cfg: ModelConfig, kind: tuple[str, str],
                 moe_groups: int, mesh, rules) -> tuple[Array, Array]:
    mixer, f = kind
    if mixer == "attn":
        x = attention.attn_block(bp["mixer"], x, cfg, local=False)
    elif mixer == "attn_local":
        x = attention.attn_block(bp["mixer"], x, cfg, local=True)
    elif mixer == "rglru":
        x = rglru.rglru_block(bp["mixer"], x, cfg)
    elif mixer == "ssd":
        x = ssm.ssd_block(bp["mixer"], x, cfg)
    aux = jnp.zeros((), jnp.float32)
    if f == "mlp":
        x = ffn.mlp_block(bp["ffn"], x, cfg)
    elif f == "moe":
        x, aux = moe.moe_block(bp["ffn"], x, cfg, groups=moe_groups,
                               mesh=mesh, rules=rules)
    x = constrain(x, ("act_batch", "act_seq", "act_embed"), mesh, rules)
    return x, aux


def _run_stage(sp: dict, x: Array, cfg: ModelConfig, stage: Stage,
               moe_groups: int, mesh, rules) -> tuple[Array, Array]:
    def body_once(x, layer_params):
        aux = jnp.zeros((), jnp.float32)
        for i, kind in enumerate(stage.blocks):
            x, a = _apply_block(layer_params[f"b{i}"], x, cfg, kind,
                                moe_groups, mesh, rules)
            aux = aux + a
        return x, aux

    if stage.repeat == 1:
        if cfg.remat:
            # match the scanned path's remat policy so unrolled slice models
            # (dry-run cost extrapolation) reproduce production recompute
            return jax.checkpoint(
                body_once,
                policy=jax.checkpoint_policies.nothing_saveable)(x, sp)
        return body_once(x, sp)

    def scan_body(carry, layer_params):
        x, aux = carry
        x, a = body_once(x, layer_params)
        return (x, aux + a), None

    if cfg.remat:
        scan_body = jax.checkpoint(
            scan_body, policy=jax.checkpoint_policies.nothing_saveable)
    (x, aux), _ = jax.lax.scan(scan_body, (x, jnp.zeros((), jnp.float32)), sp)
    return x, aux


def embed_inputs(params: dict, cfg: ModelConfig, batch: dict,
                 mesh=None, rules=None) -> Array:
    """batch may contain `tokens` (B,S), and/or `frontend_embeds` (B,F,D)."""
    parts = []
    if "frontend_embeds" in batch:
        parts.append(batch["frontend_embeds"].astype(cfg.comp_dtype))
    if "tokens" in batch and batch["tokens"] is not None:
        tok = batch["tokens"]
        emb = jnp.take(params["embed"], tok, axis=0).astype(cfg.comp_dtype)
        parts.append(emb)
    x = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
    return constrain(x, ("act_batch", "act_seq", "act_embed"), mesh, rules)


def forward(params: dict, cfg: ModelConfig, batch: dict, *,
            moe_groups: int = 1, mesh=None,
            rules: ShardingRules | None = None) -> tuple[Array, Array]:
    """Full-sequence forward -> (logits (B,S,V), moe_aux)."""
    x = embed_inputs(params, cfg, batch, mesh, rules)
    aux = jnp.zeros((), jnp.float32)
    for sp, stage in zip(params["stages"], cfg.stage_plan()):
        x, a = _run_stage(sp, x, cfg, stage, moe_groups, mesh, rules)
        aux = aux + a
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = jnp.einsum("bsd,dv->bsv", x, head.astype(x.dtype))
    logits = constrain(logits, ("act_batch", "act_seq", "act_vocab"), mesh, rules)
    return logits, aux


def loss_fn(params: dict, cfg: ModelConfig, batch: dict, *,
            moe_groups: int = 1, mesh=None,
            rules: ShardingRules | None = None,
            aux_coef: float = 0.01) -> tuple[Array, dict]:
    """Next-token (or masked-unit, for encoders) cross entropy."""
    logits, aux = forward(params, cfg, batch, moe_groups=moe_groups,
                          mesh=mesh, rules=rules)
    labels = batch["labels"]
    mask = batch.get("loss_mask")
    if mask is None:
        mask = jnp.ones(labels.shape, jnp.float32)
    # frontend tokens carry no labels; logits cover [frontend | text]
    if logits.shape[1] != labels.shape[1]:
        logits = logits[:, logits.shape[1] - labels.shape[1]:]
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    # fused iota-compare-select reduction instead of take_along_axis: a
    # gather on the vocab-sharded dim would force SPMD to all-gather the
    # full logits (measured: 52 GB/device on llama3-8b train_4k)
    vocab_iota = jax.lax.broadcasted_iota(jnp.int32, lf.shape, lf.ndim - 1)
    gold = jnp.sum(jnp.where(vocab_iota == labels[..., None], lf, 0.0), axis=-1)
    nll = (lse - gold) * mask
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = nll.sum() / denom + aux_coef * aux
    metrics = {"nll": nll.sum() / denom, "moe_aux": aux,
               "tokens": mask.sum()}
    return loss, metrics


# ---------------------------------------------------------------------------
# Decode (one token, cached state)
# ---------------------------------------------------------------------------

class LayerCache(NamedTuple):
    """Per-(stage, slot) cache. Exactly one field is used per mixer kind,
    except in the kernel-first decode carry, where an SSD layer's ``ssd``
    holds only the slots' conv tails and ``ssd_pool`` its whole state pool
    (``paged_decode_carry``)."""
    kv: Any = None
    rg: Any = None
    ssd: Any = None
    ssd_pool: Any = None


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> list:
    """Cache pytree parallel to params['stages'] (stacked over scan repeats)."""
    caches = []
    for stage in cfg.stage_plan():
        sc = {}
        for i, (mixer, _) in enumerate(stage.blocks):
            if mixer in ("attn", "attn_local"):
                c = LayerCache(kv=attention.init_kv_cache(
                    cfg, batch, max_len, local=(mixer == "attn_local")))
            elif mixer == "rglru":
                c = LayerCache(rg=rglru.init_rglru_state(cfg, batch))
            elif mixer == "ssd":
                c = LayerCache(ssd=ssm.init_ssm_state(cfg, batch))
            if stage.repeat > 1:
                c = jax.tree.map(
                    lambda a: jnp.broadcast_to(a, (stage.repeat,) + a.shape), c)
            sc[f"b{i}"] = c
        caches.append(sc)
    return caches


def cache_axes(cfg: ModelConfig) -> list:
    """Logical-axis tree parallel to ``init_cache`` (for decode shardings)."""
    kv = attention.KVCache(
        k=("act_batch", "act_kv_seq", "act_kv_heads", None),
        v=("act_batch", "act_kv_seq", "act_kv_heads", None),
        pos=("act_batch", "act_kv_seq"))
    rg = rglru.RGLRUState(h=("act_batch", "act_ssm_inner"),
                          conv=("act_batch", None, "act_ssm_inner"))
    sd = ssm.SSMState(ssd=("act_batch", "act_heads", None, None),
                      conv=("act_batch", None, "act_ssm_inner"))
    out = []
    for stage in cfg.stage_plan():
        sc = {}
        for i, (mixer, _) in enumerate(stage.blocks):
            if mixer in ("attn", "attn_local"):
                c = LayerCache(kv=kv)
            elif mixer == "rglru":
                c = LayerCache(rg=rg)
            else:
                c = LayerCache(ssd=sd)
            if stage.repeat > 1:
                c = jax.tree.map(lambda a: (None,) + a, c,
                                 is_leaf=lambda x: isinstance(x, tuple) and
                                 all(isinstance(e, (str, type(None))) for e in x))
            sc[f"b{i}"] = c
        out.append(sc)
    return out


def _cached_block(bp: dict, x: Array, cache: LayerCache, posarg: Array,
                  cfg: ModelConfig, kind: tuple[str, str],
                  mesh=None, rules=None, *, is_prefill: bool,
                  continuation: bool = False) -> tuple[Array, LayerCache]:
    """One block with cache update — shared by prefill (posarg = positions
    (B,S)) and decode (posarg = index (B,)), so both paths always run the
    same block structure.  Paged caches never reach this level: the engine
    gathers their slot-linear view first (``paged_gather``) and runs this
    exact monolithic body on it, which is what makes paged serving bitwise-
    identical by construction."""
    mixer, f = kind
    if mixer in ("attn", "attn_local"):
        if is_prefill:
            x, kv = attention.attn_prefill(
                bp["mixer"], x, cache.kv, posarg, cfg,
                local=(mixer == "attn_local"), continuation=continuation,
                mesh=mesh, rules=rules)
        else:
            x, kv = attention.attn_decode(
                bp["mixer"], x, cache.kv, posarg, cfg,
                local=(mixer == "attn_local"), mesh=mesh, rules=rules)
        cache = cache._replace(kv=kv)
    elif mixer == "rglru":
        if is_prefill:
            x, rg = rglru.rglru_prefill(bp["mixer"], x, cache.rg, posarg, cfg,
                                        mesh=mesh, rules=rules,
                                        continuation=continuation)
        else:
            x, rg = rglru.rglru_decode(bp["mixer"], x, cache.rg, cfg,
                                       mesh=mesh, rules=rules)
        cache = cache._replace(rg=rg)
    elif mixer == "ssd":
        if is_prefill:
            x, s = ssm.ssd_prefill(bp["mixer"], x, cache.ssd, posarg, cfg,
                                   mesh=mesh, rules=rules,
                                   continuation=continuation)
        else:
            x, s = ssm.ssd_decode(bp["mixer"], x, cache.ssd, cfg,
                                  mesh=mesh, rules=rules)
        cache = cache._replace(ssd=s)
    if f == "mlp":
        x = ffn.mlp_block(bp["ffn"], x, cfg)
    elif f == "moe":
        # serving-path MoE: per-position masked dispatch in prefill (posarg
        # is positions (B,S); negative = inert padding, excluded from the
        # per-group capacity counts), constant-shape exact top-k in decode —
        # both route exactly per-token, so fused == stepwise == serve.
        if is_prefill:
            x, _ = moe.moe_prefill_block(bp["ffn"], x, cfg, posarg,
                                         mesh=mesh, rules=rules)
        else:
            x, _ = moe.moe_decode_block(bp["ffn"], x, cfg,
                                        mesh=mesh, rules=rules)
    x = constrain(x, ("act_batch", "act_seq", "act_embed"), mesh, rules)
    return x, cache


def constrain_cache(cache: list, cfg: ModelConfig, mesh=None,
                    rules=None) -> list:
    """Pin every cache leaf to its logical-axis sharding (no-op off-mesh).

    Applied right after ``init_cache`` inside a jitted prefill and at the
    exit of cache-splicing helpers, so the KV / recurrent state stays
    ``act_batch``-sharded (with ``act_kv_seq``/``act_kv_heads`` claiming the
    'model' axis where divisible) across the whole decode scan instead of
    being re-laid-out by whatever GSPMD infers step to step.
    """
    if mesh is None:
        return cache
    from jax.sharding import NamedSharding

    from repro.distributed.sharding import DEFAULT_RULES, spec_for
    rules = rules or DEFAULT_RULES
    axes = (paged_cache_axes(cfg, quantized=cache_is_quantized(cache))
            if is_paged(cache) else cache_axes(cfg))

    def one(leaf, ax):
        spec = spec_for(leaf.shape, ax, mesh, rules.act_rules)
        return jax.lax.with_sharding_constraint(
            leaf, NamedSharding(mesh, spec))

    # cache leaves are arrays; flatten_up_to leaves the parallel logical-axis
    # tuples of ``cache_axes`` intact as the second argument
    return jax.tree.map(one, cache, axes)


def is_paged(cache) -> bool:
    """True for the paged cache pytree ``{"layers", "table", "rows"}``."""
    return isinstance(cache, dict)


def cache_is_quantized(cache) -> bool:
    """True when a paged cache's KV pools carry scale sidecar leaves.

    Structural (``is not None``), so it is trace-safe: quantization is part
    of the pytree structure, never a runtime value."""
    for sc in cache["layers"]:
        for c in sc.values():
            if c.kv is not None:
                return c.kv.k_scale is not None
    return False


def paged_cache(layers: list, table: Array, rows: Array) -> dict:
    """Assemble the paged cache pytree the serving phases thread through
    jit: the engine-wide pool arrays + this dispatch's block tables and
    state-row ids (see serving/cache_manager.py)."""
    return {"layers": layers, "table": table, "rows": rows}


def _cached_pass(params: dict, cfg: ModelConfig, tokens: Array, cache: list,
                 posarg: Array, is_prefill: bool,
                 mesh, rules, continuation: bool = False) -> tuple[Array, list]:
    """Embed -> staged cached blocks -> LM head, for prefill and decode."""
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.comp_dtype)
    x = constrain(x, ("act_batch", "act_seq", "act_embed"), mesh, rules)
    new_caches = []
    for sp, stage, sc in zip(params["stages"], cfg.stage_plan(), cache):
        def stage_body(x, lp, lc, stage=stage):
            ncs = {}
            for i, kind in enumerate(stage.blocks):
                x, ncs[f"b{i}"] = _cached_block(
                    lp[f"b{i}"], x, lc[f"b{i}"], posarg, cfg, kind,
                    mesh, rules, is_prefill=is_prefill,
                    continuation=continuation)
            return x, ncs

        if stage.repeat == 1:
            x, nsc = stage_body(x, sp, sc)
        else:
            x, nsc = jax.lax.scan(
                lambda x, layer: stage_body(x, *layer), x, (sp, sc))
        new_caches.append(nsc)
    return _serving_logits(params, cfg, x, mesh, rules), new_caches


def _serving_logits(params: dict, cfg: ModelConfig, x: Array, mesh,
                    rules) -> Array:
    """Final norm + LM head for prefill and decode, emitted in f32.

    The head contracts bf16 operands into f32 and keeps that result: bf16
    logits put many vocabulary entries on the same value, so greedy argmax
    would break exact ties by index, and the one-ulp noise of a sharded
    reduction order would pick a different token."""
    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = jnp.einsum("bsd,dv->bsv", x, head.astype(x.dtype),
                        preferred_element_type=jnp.float32)
    return constrain(logits, ("act_batch", "act_seq", "act_vocab"), mesh,
                     rules)


def prefill(params: dict, cfg: ModelConfig, tokens: Array, cache: list,
            positions: Array, *, continuation: bool = False, mesh=None,
            rules: ShardingRules | None = None) -> tuple[Array, list]:
    """Absorb a whole span in one pass, populating every layer cache.

    tokens (B,S) int32; positions (B,S) absolute positions (negative =>
    inert bucket padding, see the per-mixer prefill docstrings).  Returns
    (logits (B,S,V), cache) — the cache is ready for ``decode_step`` after
    the last real position.  Reuses the full-sequence mixers (chunked
    attention / associative scan / chunked SSD), so one jitted call replaces
    S sequential ``decode_step`` dispatches.

    ``continuation=False`` (cold): requires a FRESHLY INITIALISED cache and
    a LEFT-padded span starting at position 0; attention layers attend only
    over this span's K/V.

    ``continuation=True`` (warm): absorbs the span into an
    *already-populated* cache at offset positions.  The span must be
    RIGHT-padded (real tokens first) so the recurrent mixers' conv windows
    cross from the cached context tail straight into the new tokens;
    attention scatters the span K/V into the cache and attends over the
    whole cache.  Recurrent mixers fold the carried state into the scan in
    both modes — the flag only switches the attention read set and the
    conv-tail extraction.

    MoE layers run the capacity-aware masked serving dispatch
    (``moe.moe_prefill_block``) in both modes: one dispatch group per span
    position (offset positions included — routing depends only on the
    hidden states and the valid mask), padding tokens masked out of routing
    and capacity, so prefill makes the same routing decisions as S
    sequential ``decode_step`` calls and bucket padding is bitwise-neutral.
    """
    return _cached_pass(params, cfg, tokens, cache, positions, True,
                        mesh, rules, continuation=continuation)


def grow_cache(cfg: ModelConfig, cache: list, batch: int, new_len: int
               ) -> list:
    """Extend every KV-cache leaf to ``new_len`` slots (new slots empty:
    k/v zero, pos = -1).  Length-independent leaves (recurrent states, conv
    tails, window-clamped ring buffers that don't change size) pass through
    unchanged.  Used when a session outgrows the cache it was created with
    (multi-turn continuation, warm serve() admission into longer slots).
    ``new_len`` must be >= the current length."""
    tmpl = init_cache(cfg, batch, new_len)

    def one(t, c):
        if t.shape == c.shape:
            return c
        return jax.lax.dynamic_update_slice(t, c.astype(t.dtype),
                                            (0,) * c.ndim)

    return jax.tree.map(one, tmpl, cache)


# ---------------------------------------------------------------------------
# Paged block pool (serving/cache_manager.py owns the allocator)
# ---------------------------------------------------------------------------

def init_block_pool(cfg: ModelConfig, n_blocks: int, block_len: int,
                    n_rows: int, cache_quant: str | None = None) -> list:
    """Pool arrays for the paged cache, structure parallel to
    ``init_cache``: attention layers hold ``(n_blocks, block_len, ...)`` KV
    blocks, recurrent/conv layers hold ``(n_rows, ...)`` state rows (the
    same leaves as a batch-``n_rows`` monolithic state — rows are just
    pooled batch slots addressed by id).  ``cache_quant`` stores the KV
    blocks int8/fp8 with per-row f32 scale leaves riding alongside;
    recurrent/conv state rows ALWAYS stay bf16 — compounding recurrences
    drift under requantization (the same reason their f32 accumulator
    sites carry dtype-drift pragmas)."""
    pools = []
    for stage in cfg.stage_plan():
        sc = {}
        for i, (mixer, _) in enumerate(stage.blocks):
            if mixer in ("attn", "attn_local"):
                c = LayerCache(kv=attention.init_paged_kv(
                    cfg, n_blocks, block_len, cache_quant))
            elif mixer == "rglru":
                c = LayerCache(rg=rglru.init_rglru_state(cfg, n_rows))
            elif mixer == "ssd":
                c = LayerCache(ssd=ssm.init_ssm_state(cfg, n_rows))
            if stage.repeat > 1:
                c = jax.tree.map(
                    lambda a: jnp.broadcast_to(a, (stage.repeat,) + a.shape), c)
            sc[f"b{i}"] = c
        pools.append(sc)
    return pools


def paged_cache_axes(cfg: ModelConfig, quantized: bool = False) -> dict:
    """Logical-axis tree parallel to ``paged_cache(init_block_pool(...))``:
    the pool block/row dim shards over 'data' (``act_pool`` rule), block
    tables and row ids ride with the batch.  ``quantized`` adds the scale
    sidecar leaves (``act_pool_scale`` rule — same 'data' chain over the
    block dim) so the axes tree stays structurally parallel to a
    ``cache_quant`` pool."""
    scale = attention.PAGED_SCALE_AXES if quantized else None
    kv = attention.KVCache(k=attention.PAGED_KV_AXES,
                           v=attention.PAGED_KV_AXES,
                           pos=("act_pool", None),
                           k_scale=scale, v_scale=scale)
    rg = rglru.RGLRUState(h=("act_pool", "act_ssm_inner"),
                          conv=("act_pool", None, "act_ssm_inner"))
    sd = ssm.SSMState(ssd=("act_pool", "act_heads", None, None),
                      conv=("act_pool", None, "act_ssm_inner"))
    out = []
    for stage in cfg.stage_plan():
        sc = {}
        for i, (mixer, _) in enumerate(stage.blocks):
            if mixer in ("attn", "attn_local"):
                c = LayerCache(kv=kv)
            elif mixer == "rglru":
                c = LayerCache(rg=rg)
            else:
                c = LayerCache(ssd=sd)
            if stage.repeat > 1:
                c = jax.tree.map(lambda a: (None,) + a, c,
                                 is_leaf=lambda x: isinstance(x, tuple) and
                                 all(isinstance(e, (str, type(None))) for e in x))
            sc[f"b{i}"] = c
        out.append(sc)
    return {"layers": out, "table": ("act_batch", None),
            "rows": ("act_batch",)}


def _local_nb(cfg: ModelConfig, nb: int, block_len: int, mixer: str) -> int:
    """Blocks a layer's slot-linear view spans: the full table, clamped to
    the window for local-attention layers (mirrors ``init_kv_cache``'s
    ring-buffer clamp; the engine validates window % block_len == 0)."""
    if mixer == "attn_local" and cfg.window is not None:
        return min(nb, max(cfg.window // block_len, 1))
    return nb


def paged_gather(cfg: ModelConfig, cache: dict) -> list:
    """Materialise the slot-linear **monolithic** view of a paged cache.

    Per attention layer: gather the table's pool blocks into a
    (B, nb*L, ...) ``KVCache`` (window-clamped for local layers); per
    recurrent layer: gather the slot's state rows.  With the same writes
    applied, the result is elementwise-equal to the cache ``init_cache``
    would have produced — the engine runs the UNCHANGED monolithic
    prefill/decode bodies on it, which is what makes the paged runtime
    bitwise-identical by construction.  O(B * table length) per dispatch,
    and the pool stays OUT of the decode-scan carry (carrying the pool
    would cost O(pool) per step — measured 10x on the smoke decode)."""
    layers, table, rows = cache["layers"], cache["table"], cache["rows"]
    nb = table.shape[1]
    out = []
    for stage, sc in zip(cfg.stage_plan(), layers):
        ns = {}
        for i, (mixer, _) in enumerate(stage.blocks):
            c = sc[f"b{i}"]
            stacked = stage.repeat > 1
            if c.kv is not None:
                L = c.kv.k.shape[2 if stacked else 1]
                tbl = table[:, :_local_nb(cfg, nb, L, mixer)]

                def pv(kv, tb):
                    # quantized pools dequantize inside paged_view, so the
                    # gathered view is ALWAYS a plain cfg-dtype monolithic
                    # cache and the compute bodies below never see scales
                    return attention.paged_view(kv, tb, cfg.dtype)
                view = (jax.vmap(pv, in_axes=(0, None))(c.kv, tbl)
                        if stacked else pv(c.kv, tbl))
                c = LayerCache(kv=view)
            else:
                axis = 1 if stacked else 0
                c = jax.tree.map(
                    lambda a: jnp.take(a, rows, axis=axis, mode="clip"), c)
            ns[f"b{i}"] = c
        out.append(ns)
    return out


def paged_scatter_back(cfg: ModelConfig, cache: dict, lin: list,
                       lo: Array, hi: Array) -> list:
    """Write a dispatch's results back into the pool: the blocks covering
    the written position range [lo, hi) per row (``attention.
    paged_scatter_blocks`` — O(tokens written), shared prefix blocks are
    never touched) plus the slot's recurrent state rows.  Sentinel table /
    row ids (empty serve slots) drop their writes."""
    layers, table, rows = cache["layers"], cache["table"], cache["rows"]
    nb = table.shape[1]
    out = []
    for stage, sc, sl in zip(cfg.stage_plan(), layers, lin):
        ns = {}
        for i, (mixer, _) in enumerate(stage.blocks):
            c, l = sc[f"b{i}"], sl[f"b{i}"]
            stacked = stage.repeat > 1
            if c.kv is not None:
                L = c.kv.k.shape[2 if stacked else 1]
                tbl = table[:, :_local_nb(cfg, nb, L, mixer)]
                win = cfg.window if mixer == "attn_local" else None
                scat = lambda p, v: attention.paged_scatter_blocks(
                    p, tbl, v, lo, hi, window=win)
                kv = (jax.vmap(scat)(c.kv, l.kv) if stacked
                      else scat(c.kv, l.kv))
                c = c._replace(kv=kv)
            else:
                axis = 1 if stacked else 0

                def one(pool_leaf, lin_leaf, axis=axis):
                    idx = (slice(None), rows) if axis else rows
                    return pool_leaf.at[idx].set(
                        lin_leaf.astype(pool_leaf.dtype), mode="drop")
                c = jax.tree.map(one, c, l)
            ns[f"b{i}"] = c
        out.append(ns)
    return out


def _map_kv_pools(cfg: ModelConfig, layers: list, fn) -> list:
    """Apply ``fn(kv_pool, stacked)`` to every attention pool leaf group."""
    out = []
    for stage, sc in zip(cfg.stage_plan(), layers):
        ns = {}
        for name, c in sc.items():
            ns[name] = (c._replace(kv=fn(c.kv, stage.repeat > 1))
                        if c.kv is not None else c)
        out.append(ns)
    return out


def _map_state_pools(cfg: ModelConfig, layers: list, fn) -> list:
    """Apply ``fn(state_leaf, stacked)`` to every recurrent state leaf."""
    out = []
    for stage, sc in zip(cfg.stage_plan(), layers):
        ns = {}
        for name, c in sc.items():
            stacked = stage.repeat > 1
            if c.rg is not None:
                c = c._replace(rg=jax.tree.map(
                    lambda a: fn(a, stacked), c.rg))
            elif c.ssd is not None:
                c = c._replace(ssd=jax.tree.map(
                    lambda a: fn(a, stacked), c.ssd))
            ns[name] = c
        out.append(ns)
    return out


def reset_blocks(cfg: ModelConfig, layers: list, ids: Array) -> list:
    """Re-initialise pool blocks ``ids`` (n,) in every KV pool: k/v zeroed,
    pos = -1 (quantized pools also zero the blocks' scale rows — exactly
    what quantizing a zero row scatters, see ``quant.quantize_rows``).
    O(len(ids)) — this replaces ``grow_cache``'s whole-buffer copy for
    paged session growth.  State rows are untouched."""
    def one(kv, stacked):
        # leaf -> same leaf with blocks ``ids`` set to ``val``; every KV
        # pool leaf (k/v/pos/scales) has the block dim first (or second
        # when repeat-stacked)
        def z(a, val):
            return a.at[:, ids].set(val) if stacked else a.at[ids].set(val)
        kv = kv._replace(k=z(kv.k, 0), v=z(kv.v, 0), pos=z(kv.pos, -1))
        if kv.k_scale is not None:
            kv = kv._replace(k_scale=z(kv.k_scale, 0),
                             v_scale=z(kv.v_scale, 0))
        return kv
    return _map_kv_pools(cfg, layers, one)


def copy_blocks(cfg: ModelConfig, layers: list, src: Array,
                dst: Array) -> list:
    """Copy pool blocks ``src`` -> ``dst`` in every KV pool (the COW copy:
    O(blocks copied), at most the one partially filled tail block per
    diverging slot).  Scale sidecar leaves copy with their blocks — COW
    and prefix sharing never requantize."""
    def one(kv, stacked):
        def cp(a):
            return (a.at[:, dst].set(a[:, src]) if stacked
                    else a.at[dst].set(a[src]))
        kv = kv._replace(k=cp(kv.k), v=cp(kv.v), pos=cp(kv.pos))
        if kv.k_scale is not None:
            kv = kv._replace(k_scale=cp(kv.k_scale), v_scale=cp(kv.v_scale))
        return kv
    return _map_kv_pools(cfg, layers, one)


def reset_rows(cfg: ModelConfig, layers: list, ids: Array) -> list:
    """Zero recurrent/conv state rows ``ids`` in every state pool (a fresh
    row must equal the monolithic ``init_cache`` zero state bitwise)."""
    def one(leaf, stacked):
        return leaf.at[:, ids].set(0) if stacked else leaf.at[ids].set(0)
    return _map_state_pools(cfg, layers, one)


def copy_rows(cfg: ModelConfig, layers: list, src: Array, dst: Array) -> list:
    """Copy state rows ``src`` -> ``dst`` (state rows are rewritten every
    decode step, so forking a session copies them instead of sharing)."""
    def one(leaf, stacked):
        if stacked:
            return leaf.at[:, dst].set(leaf[:, src])
        return leaf.at[dst].set(leaf[src])
    return _map_state_pools(cfg, layers, one)


# ---------------------------------------------------------------------------
# Kernel-first paged decode: attention reads pool blocks in place
# ---------------------------------------------------------------------------

def ssd_pools_carried(cfg: ModelConfig, mesh=None) -> bool:
    """Whether the kernel-first decode carries each SSD layer's state pool
    whole and steps the slots' rows where they lie (``ssm.
    ssd_decode_pooled``): for configs with SSD layers, off-mesh and on a
    one-device mesh.  On a mesh of several devices the pool's rows split
    over 'data' and its heads over 'model', so the dispatch gathers the
    slots' state rows as the other recurrent layers do."""
    return (any(m == "ssd" for m, _ in cfg.layer_plan())
            and (mesh is None or mesh.size == 1))


def paged_decode_carry(cfg: ModelConfig, cache: dict, steps: int,
                       mesh=None) -> list:
    """Initial carry for the kernel-first decode scan: per attention layer an
    O(B * steps) delta write buffer (``attention.init_decode_delta``), per
    recurrent layer the O(B) gathered state rows.  Unlike the gathered-view
    path there is NO cache-length state in the carry — the KV pool itself is
    a closed-over scan constant that ``paged_decode_step`` reads in place.
    Where ``ssd_pools_carried``, an SSD layer gathers only its conv-tail
    rows and carries its state pool itself (``LayerCache.ssd_pool``), which
    the scan updates in place and the dispatch returns as the new pool."""
    layers, rows = cache["layers"], cache["rows"]
    B = cache["table"].shape[0]
    pooled = ssd_pools_carried(cfg, mesh)
    out = []
    for stage, sc in zip(cfg.stage_plan(), layers):
        ns = {}
        for i, (mixer, _) in enumerate(stage.blocks):
            c = sc[f"b{i}"]
            stacked = stage.repeat > 1
            if c.kv is not None:
                d = attention.init_decode_delta(cfg, B, steps)
                if stacked:
                    d = jax.tree.map(lambda a: jnp.broadcast_to(
                        a, (stage.repeat,) + a.shape), d)
                c = LayerCache(kv=d)
            else:
                axis = 1 if stacked else 0

                def take(a, axis=axis):
                    return jnp.take(a, rows, axis=axis, mode="clip")
                if pooled and c.ssd is not None:
                    c = LayerCache(ssd=c.ssd._replace(ssd=None,
                                                      conv=take(c.ssd.conv)),
                                   ssd_pool=c.ssd.ssd)
                else:
                    c = jax.tree.map(take, c)
            ns[f"b{i}"] = c
        out.append(ns)
    return out


def _paged_block_step(bp: dict, x: Array, pool_c: LayerCache,
                      delta_c: LayerCache, table: Array, rows: Array,
                      index: Array, t: Array, p0: Array, cfg: ModelConfig,
                      kind: tuple[str, str], mesh, rules, layer=None
                      ) -> tuple[Array, LayerCache]:
    """One kernel-first decode block: attention attends through the block
    table in place (pool never copied), recurrent mixers run the unchanged
    monolithic decode on their carried state rows, and an SSD layer whose
    carry holds its state pool steps the slots' rows in it.  In a stacked
    stage ``pool_c`` and ``delta_c.ssd_pool`` hold the whole repeat-stacked
    pools and ``layer`` the stage scan's layer index — attention folds it
    into its block gathers and the SSD update into its row address, so the
    stage scan never slices (copies) a per-layer pool."""
    mixer, f = kind
    if mixer in ("attn", "attn_local"):
        L = pool_c.kv.k.shape[2 if layer is not None else 1]
        tbl = table[:, :_local_nb(cfg, table.shape[1], L, mixer)]
        x, d = attention.attn_decode_paged(
            bp["mixer"], x, pool_c.kv, tbl, delta_c.kv, index, t, p0, cfg,
            local=(mixer == "attn_local"), layer=layer, mesh=mesh,
            rules=rules)
        delta_c = delta_c._replace(kv=d)
    elif mixer == "rglru":
        x, rg = rglru.rglru_decode(bp["mixer"], x, delta_c.rg, cfg,
                                   mesh=mesh, rules=rules)
        delta_c = delta_c._replace(rg=rg)
    elif mixer == "ssd" and delta_c.ssd_pool is not None:
        x, s = ssm.ssd_decode_pooled(
            bp["mixer"], x, delta_c.ssd._replace(ssd=delta_c.ssd_pool),
            rows, layer, cfg)
        delta_c = LayerCache(ssd=s._replace(ssd=None), ssd_pool=s.ssd)
    elif mixer == "ssd":
        x, s = ssm.ssd_decode(bp["mixer"], x, delta_c.ssd, cfg,
                              mesh=mesh, rules=rules)
        delta_c = delta_c._replace(ssd=s)
    if f == "mlp":
        x = ffn.mlp_block(bp["ffn"], x, cfg)
    elif f == "moe":
        x, _ = moe.moe_decode_block(bp["ffn"], x, cfg, mesh=mesh, rules=rules)
    x = constrain(x, ("act_batch", "act_seq", "act_embed"), mesh, rules)
    return x, delta_c


def paged_decode_step(params: dict, cfg: ModelConfig, tokens: Array,
                      cache: dict, delta: list, index: Array, t: Array,
                      p0: Array, *, mesh=None,
                      rules: ShardingRules | None = None
                      ) -> tuple[Array, list]:
    """Kernel-first ``decode_step``: tokens (B,1), index (B,) -> (logits,
    delta).  ``cache`` is the paged pool pytree, closed over as a scan
    CONSTANT — attention reads KV blocks in place through the block table
    and never materialises the slot-linear view; ``delta``
    (``paged_decode_carry``) collects the dispatch's writes; ``t`` is the
    step number within the dispatch, ``p0`` the dispatch-start index.
    Stacked stages run as a lax.scan over (params, delta, layer-index) —
    the SAME stage structure as ``_cached_pass``, which matters for bitwise
    parity: XLA fuses a scan body differently from a Python unroll
    (measured 1-ulp logit noise on the smoke config), so the kernel-first
    path presents the shared block ops inside an identical scan body.  The
    pool is NOT scan xs: slicing a per-layer pool per repeat would copy the
    whole pool every decode step, so the stacked pool stays closed over and
    attention folds the layer index into its block gathers
    (``attn_decode_paged(layer=...)``) — the outer decode scan carries no
    O(pool) state and the stage scan moves none.  The one exception is the
    SSD state pools of ``paged_decode_carry``'s ``LayerCache.ssd_pool``: the
    stage scan carries them whole (never as xs/ys, which would stack a
    copy) and each layer updates its slots' rows in them in place."""
    layers, table, rows = cache["layers"], cache["table"], cache["rows"]
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.comp_dtype)
    x = constrain(x, ("act_batch", "act_seq", "act_embed"), mesh, rules)
    new_delta = []
    for sp, stage, sc, dc in zip(params["stages"], cfg.stage_plan(), layers,
                                 delta):
        def stage_body(x, lp, d_c, li, stage=stage, sc=sc):
            nds = {}
            for i, kind in enumerate(stage.blocks):
                x, nds[f"b{i}"] = _paged_block_step(
                    lp[f"b{i}"], x, sc[f"b{i}"], d_c[f"b{i}"], table, rows,
                    index, t, p0, cfg, kind, mesh, rules, layer=li)
            return x, nds

        if stage.repeat == 1:
            x, ns = stage_body(x, sp, dc, None)
        else:
            def scan_body(carry, xs_l, stage_body=stage_body):
                x, pools = carry
                x, nds = stage_body(x, xs_l[0], _with_pools(xs_l[1], pools),
                                    xs_l[2])
                return (x, _pools(nds)), _with_pools(nds, {})

            (x, pools), ns = jax.lax.scan(
                scan_body, (x, _pools(dc)),
                (sp, _with_pools(dc, {}),
                 jnp.arange(stage.repeat, dtype=jnp.int32)))
            ns = _with_pools(ns, pools)
        new_delta.append(ns)
    return _serving_logits(params, cfg, x, mesh, rules), new_delta


def _pools(dc: dict) -> dict:
    """The whole-pool leaves (``LayerCache.ssd_pool``) of a stage's decode
    carry, by block name."""
    return {name: c.ssd_pool for name, c in dc.items()
            if c.ssd_pool is not None}


def _with_pools(dc: dict, pools: dict) -> dict:
    """A stage's decode carry with its whole-pool leaves taken from
    ``pools`` (by block name); ``{}`` clears them."""
    return {name: c._replace(ssd_pool=pools.get(name))
            for name, c in dc.items()}


def paged_scatter_decode(cfg: ModelConfig, cache: dict, delta: list,
                         p0: Array) -> list:
    """End-of-dispatch writeback for the kernel-first decode: scatter each
    attention layer's delta rows into their pool slots through the table
    (``attention.paged_scatter_delta`` — O(steps) writes per row) and each
    recurrent layer's carried state rows; an SSD state pool the scan carried
    whole (``LayerCache.ssd_pool``) is already updated and becomes the pool.
    Produces pools elementwise-equal to the gathered path's
    ``paged_scatter_back``; sentinel table entries / row ids drop."""
    layers, table, rows = cache["layers"], cache["table"], cache["rows"]
    nb = table.shape[1]
    out = []
    for stage, sc, dl in zip(cfg.stage_plan(), layers, delta):
        ns = {}
        for i, (mixer, _) in enumerate(stage.blocks):
            c, d = sc[f"b{i}"], dl[f"b{i}"]
            stacked = stage.repeat > 1
            if c.kv is not None:
                L = c.kv.k.shape[2 if stacked else 1]
                tbl = table[:, :_local_nb(cfg, nb, L, mixer)]
                win = cfg.window if mixer == "attn_local" else None
                scat = lambda p, v: attention.paged_scatter_delta(
                    p, tbl, v, p0, window=win)
                kv = (jax.vmap(scat)(c.kv, d.kv) if stacked
                      else scat(c.kv, d.kv))
                c = c._replace(kv=kv)
            else:
                axis = 1 if stacked else 0

                def one(pool_leaf, d_leaf, axis=axis):
                    idx = (slice(None), rows) if axis else rows
                    return pool_leaf.at[idx].set(
                        d_leaf.astype(pool_leaf.dtype), mode="drop")
                if d.ssd_pool is not None:
                    c = LayerCache(ssd=c.ssd._replace(
                        ssd=d.ssd_pool, conv=one(c.ssd.conv, d.ssd.conv)))
                else:
                    c = jax.tree.map(one, c, d)
            ns[f"b{i}"] = c
        out.append(ns)
    return out


def decode_step(params: dict, cfg: ModelConfig, tokens: Array, cache: list,
                index: Array, *, mesh=None,
                rules: ShardingRules | None = None
                ) -> tuple[Array, list]:
    """tokens (B,1) int32; index (B,) positions. -> (logits (B,1,V), cache).

    MoE layers use the constant-shape exact top-k dispatch
    (``moe.moe_decode_block``) — drop-free per-token routing, so batch
    composition (serve slots, garbage in empty slots) can never change
    another sequence's routing."""
    return _cached_pass(params, cfg, tokens, cache, index, False,
                        mesh, rules)
