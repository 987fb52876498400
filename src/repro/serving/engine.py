"""Inference engine: two-phase serving runtime + integrated probe.

The runtime is the paper's edge hot path (Sec. VI latency) restructured the
way production servers run it:

  * **prefill** — the whole prompt is absorbed in ONE jitted pass
    (``transformer.prefill``) that bulk-fills every layer cache, instead of
    S sequential ``decode_step`` dispatches;
  * **decode** — ``max_new`` steps run as a single ``lax.scan``; a full
    ``generate`` fuses prefill + scan + probe into ONE device call;
  * **continuous batching** — ``serve()`` streams requests through the
    vLLM-style ``ContinuousBatcher``: admit into free slots, prefill the
    slot, scan-decode over all slots, retire at stop token / max_new.

Prompt shapes are bucketed (left-padded to the next power of two) so
heterogeneous batches hit a handful of compilations; bucket padding uses
negative positions, which every mixer's prefill treats as inert, so bucketed
results are bitwise-identical to unbucketed ones.

The uncertainty probe (paper Sec. IV-B) is computed *inside* the decode scan
from the logits the engine already produces — difficulty estimation adds no
extra forward pass: the paper's probe SLM "is" the local SLM.

With a ``(data, model)`` mesh attached (``launch/mesh.py::serving_mesh``)
every phase runs SPMD-partitioned: parameters placed by the logical-axis
rules, caches and batch dims sharded on 'data', jitted entry points built
with explicit in/out shardings (docs/SHARDING.md).  A data-parallel mesh is
bitwise-identical to the single-device path; tensor parallelism reorders
the 'model'-sharded reductions, so its logits carry about one bf16 ulp and
greedy tokens agree except at near-ties.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import uncertainty as U
from repro.core.uncertainty import UncertaintyConfig
from repro.distributed import sharding as sh
from repro.models import transformer as T
from repro.models.common import ModelConfig
from repro.serving.cache_manager import EvictedSessionError, PagedHandle
from repro.serving.faults import FaultPlan, PoolExhaustedError
from repro.serving.scheduler import ContinuousBatcher, Request
from repro.serving.spans import Spans

Array = jax.Array

PAD = 0


def bucket_len(s: int, granularity: int = 512, floor: int = 8) -> int:
    """Shape bucket for prompt lengths: next power of two up to
    ``granularity``, then multiples of ``2 * granularity`` (keeps the
    chunked-attention / SSD block-divisibility asserts satisfied)."""
    if s <= floor:
        return floor
    if s <= granularity:
        return 1 << (s - 1).bit_length()
    g2 = 2 * granularity
    return -(-s // g2) * g2


# ---------------------------------------------------------------------------
# Jitted phases
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("cfg", "mesh", "rules"))
def _prefill_into(params, cfg: ModelConfig, prompts, s_orig, cache,
                  mesh=None, rules=None):
    """Cold prefill into a provided cache — a fresh monolithic cache or a
    paged cache whose blocks the pool just reset (identical contents, so
    the two entry points share one implementation).  prompts (B, Sb)
    left-padded to a bucket; s_orig = pre-bucket length.  Returns (first
    greedy token (B,), its logits (B,V) f32, filled cache).

    On-mesh (mesh + rules static args set) the cache is pinned to its
    logical-axis sharding before the prefill fills it, so the bulk KV
    scatter and the carried recurrent states come out sharded.
    """
    B, S = prompts.shape
    cache = T.constrain_cache(cache, cfg, mesh, rules)
    # columns left of the original padded prompt get negative positions and
    # are inert in every mixer; real columns keep positions 0..s_orig-1
    positions = jnp.broadcast_to(
        jnp.arange(S, dtype=jnp.int32)[None] - (S - s_orig), (B, S))
    with jax.named_scope("prefill.model"):
        logits, cache = T.prefill(params, cfg, prompts, cache, positions,
                                  mesh=mesh, rules=rules)
        last = logits[:, -1].astype(jnp.float32)
        last = sh.constrain(last, ("act_batch", "act_vocab"), mesh, rules)
    with jax.named_scope("prefill.sample"):
        cur = jnp.argmax(last, axis=-1).astype(jnp.int32)
    return cur, last, cache


@partial(jax.jit, static_argnames=("cfg", "max_len", "mesh", "rules"))
def _prefill_absorb(params, cfg: ModelConfig, prompts, s_orig, max_len: int,
                    mesh=None, rules=None):
    """Monolithic cold prefill: initialise a (B, max_len) cache and absorb
    the prompt into it (see ``_prefill_into``)."""
    B = prompts.shape[0]
    return _prefill_into(params, cfg, prompts, s_orig,
                         T.init_cache(cfg, B, max_len), mesh=mesh, rules=rules)


@partial(jax.jit, static_argnames=("cfg", "ucfg", "steps", "greedy",
                                   "with_logits", "mesh", "rules"))
def _decode_scan(params, cfg: ModelConfig, cur, last, cache, pos, rng,
                 ucfg: UncertaintyConfig, steps: int, greedy: bool,
                 with_logits: bool = True, mesh=None, rules=None):
    """``steps`` decode iterations as one lax.scan.

    cur (B,) token entering the span; last (B,V) its logits; pos (B,) its
    absolute position.  Emits the tokens/logits *entering* each step (so the
    first emitted token is the prefill argmax, matching the legacy stepwise
    loop) plus the per-position Eq. 2-3 uncertainty terms.  The streaming
    serve path passes with_logits=False so the (B, steps, V) stack is never
    materialised as a jit output.

    On-mesh, the per-step logits are pinned ``(act_batch, act_vocab)`` and
    every cache/state leaf is re-constrained inside the mixers, so the scan
    carry keeps its sharding across all ``steps`` instead of collapsing to
    whatever layout GSPMD infers for the loop body.
    """
    def body(carry, _):
        cur, last, cache, pos, rng = carry
        # Eq. 2-3 terms of the *emitted* token: cur was chosen from last
        with jax.named_scope("decode.uncertainty"):
            h, v = U.uncertainty_terms(last[:, None, :], cur[:, None], ucfg)
        with jax.named_scope("decode.sample"):
            rng, sub = jax.random.split(rng)
        with jax.named_scope("decode.model"):
            logits, cache = T.decode_step(params, cfg, cur[:, None], cache,
                                          pos, mesh=mesh, rules=rules)
            lg = logits[:, -1].astype(jnp.float32)
            lg = sh.constrain(lg, ("act_batch", "act_vocab"), mesh, rules)
        with jax.named_scope("decode.sample"):
            if greedy:
                nxt = jnp.argmax(lg, axis=-1)
            else:
                nxt = jax.random.categorical(sub, lg, axis=-1)
        out = (cur, h[:, 0], v[:, 0]) + ((last,) if with_logits else ())
        return (nxt.astype(jnp.int32), lg, cache, pos + 1, rng), out

    carry, outs = jax.lax.scan(body, (cur, last, cache, pos, rng),
                               length=steps)
    toks, h_per, v_per = (o.swapaxes(0, 1) for o in outs[:3])
    lgs = outs[3].swapaxes(0, 1) if with_logits else None
    return toks, lgs, h_per, v_per, carry


@partial(jax.jit, static_argnames=("cfg", "ucfg", "max_new", "max_len",
                                   "greedy", "mesh", "rules"))
def _generate_fused(params, cfg: ModelConfig, prompts, s_orig, rng,
                    ucfg: UncertaintyConfig, max_new: int, max_len: int,
                    greedy: bool, mesh=None, rules=None):
    """Whole generation — prefill, scanned decode and the Eq. 4 combine —
    as ONE device call (nested jits trace inline).

    Returns (tokens, logits, u, h_mean, v_mean, carry): the raw Eq. 2-3
    per-request means let callers re-average u over an extended generation,
    and the decode-scan carry (cur, last, cache, pos, rng) is the warm
    session state ``InferenceEngine.generate(..., return_state=True)``
    hands out."""
    B = prompts.shape[0]
    cur, last, cache = _prefill_absorb(params, cfg, prompts, s_orig, max_len,
                                       mesh=mesh, rules=rules)
    toks, lgs, h_per, v_per, carry = _decode_scan(
        params, cfg, cur, last, cache, jnp.broadcast_to(s_orig, (B,)), rng,
        ucfg, max_new, greedy, mesh=mesh, rules=rules)
    h, v = h_per.mean(-1), v_per.mean(-1)
    return toks, lgs, U.combine_terms(h, v, ucfg), h, v, carry


# ---------------------------------------------------------------------------
# Paged entry points: gather the slot-linear view of the block pool, run the
# UNCHANGED monolithic bodies on it, scatter only the written block range
# back (transformer.paged_gather / paged_scatter_back).  Three consequences:
#   * bitwise parity with the monolithic path by construction (the same
#     compiled math runs on an elementwise-equal cache);
#   * the decode-scan carry stays shape-stable and O(B * max_len) — the
#     pool never rides the carry (that costs O(pool) per step: XLA
#     re-materialises scan carries, measured 10x on the smoke decode);
#   * pool writes are O(tokens written), so refcount-shared prefix blocks
#     are physically never touched (the COW invariant).
# The pool arrays are DONATED into each dispatch — the engine commits the
# returned arrays to the CachePool immediately, so the old buffers are
# dead; on backends with donation support the scatter-back aliases in
# place instead of copying the pool.
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("cfg", "mesh", "rules"),
         donate_argnames=("cache",))
def _prefill_into_paged(params, cfg: ModelConfig, prompts, s_orig, cache,
                        mesh=None, rules=None):
    """Paged cold prefill: gather -> ``_prefill_into`` -> scatter blocks
    [0, s_orig) back.  Returns (cur, last, updated paged cache)."""
    B = prompts.shape[0]
    cache = T.constrain_cache(cache, cfg, mesh, rules)
    with jax.named_scope("prefill.cache_gather"):
        lin = T.paged_gather(cfg, cache)
    cur, last, lin = _prefill_into(params, cfg, prompts, s_orig, lin,
                                   mesh=mesh, rules=rules)
    with jax.named_scope("prefill.cache_write"):
        layers = T.paged_scatter_back(
            cfg, cache, lin, jnp.zeros((B,), jnp.int32),
            jnp.broadcast_to(s_orig, (B,)).astype(jnp.int32))
    return cur, last, T.paged_cache(layers, cache["table"], cache["rows"])


@partial(jax.jit, static_argnames=("cfg", "mesh", "rules"),
         donate_argnames=("cache",))
def _prefill_continue_paged(params, cfg: ModelConfig, prompts, s_orig, start,
                            cache, mesh=None, rules=None):
    """Paged continuation prefill: gather -> ``_prefill_continue`` ->
    scatter blocks [start, start + s_orig) back."""
    cache = T.constrain_cache(cache, cfg, mesh, rules)
    with jax.named_scope("prefill.cache_gather"):
        lin = T.paged_gather(cfg, cache)
    cur, last, lin = _prefill_continue(params, cfg, prompts, s_orig, start,
                                       lin, mesh=mesh, rules=rules)
    with jax.named_scope("prefill.cache_write"):
        layers = T.paged_scatter_back(cfg, cache, lin, start,
                                      start + s_orig)
    return cur, last, T.paged_cache(layers, cache["table"], cache["rows"])


def _decode_scan_kernel(params, cfg: ModelConfig, cur, last, cache, pos, rng,
                        ucfg: UncertaintyConfig, steps: int, greedy: bool,
                        with_logits: bool = True, mesh=None, rules=None):
    """Kernel-first paged decode chunk: attention reads KV blocks IN PLACE
    through the block table (``transformer.paged_decode_step``) — the
    O(B * S) slot-linear view is never materialised.  The scan carry holds
    only the O(B * steps) delta write buffers + O(B) recurrent state rows
    (``paged_decode_carry``); the pool rides the closure as a scan constant
    and receives one delta scatter at the end of the dispatch.  Sampling,
    rng-splitting and uncertainty ops mirror ``_decode_scan`` exactly, and
    the streamed chunk data equals the gathered view elementwise, so tokens
    AND logits are bitwise-identical to the gathered-view path."""
    cache = T.constrain_cache(cache, cfg, mesh, rules)
    p0 = pos
    with jax.named_scope("decode.cache_write"):
        delta0 = T.paged_decode_carry(cfg, cache, steps, mesh=mesh)

    def body(carry, t):
        cur, last, delta, pos_c, rng = carry
        with jax.named_scope("decode.uncertainty"):
            h, v = U.uncertainty_terms(last[:, None, :], cur[:, None], ucfg)
        with jax.named_scope("decode.sample"):
            rng, sub = jax.random.split(rng)
        with jax.named_scope("decode.model"):
            logits, delta = T.paged_decode_step(
                params, cfg, cur[:, None], cache, delta, pos_c, t, p0,
                mesh=mesh, rules=rules)
            lg = logits[:, -1].astype(jnp.float32)
            lg = sh.constrain(lg, ("act_batch", "act_vocab"), mesh, rules)
        with jax.named_scope("decode.sample"):
            if greedy:
                nxt = jnp.argmax(lg, axis=-1)
            else:
                nxt = jax.random.categorical(sub, lg, axis=-1)
        out = (cur, h[:, 0], v[:, 0]) + ((last,) if with_logits else ())
        return (nxt.astype(jnp.int32), lg, delta, pos_c + 1, rng), out

    carry, outs = jax.lax.scan(body, (cur, last, delta0, pos, rng),
                               jnp.arange(steps))
    cur2, last2, delta2, pos2, rng2 = carry
    with jax.named_scope("decode.cache_write"):
        layers = T.paged_scatter_decode(cfg, cache, delta2, p0)
    out_cache = T.paged_cache(layers, cache["table"], cache["rows"])
    toks, h_per, v_per = (o.swapaxes(0, 1) for o in outs[:3])
    lgs = outs[3].swapaxes(0, 1) if with_logits else None
    return toks, lgs, h_per, v_per, (cur2, last2, out_cache, pos2, rng2)


@partial(jax.jit, static_argnames=("cfg", "ucfg", "steps", "greedy",
                                   "with_logits", "impl", "mesh", "rules"),
         donate_argnames=("cache",))
def _decode_scan_paged(params, cfg: ModelConfig, cur, last, cache, pos, rng,
                       ucfg: UncertaintyConfig, steps: int, greedy: bool,
                       with_logits: bool = True, impl: str = "gather",
                       mesh=None, rules=None):
    """Paged decode chunk.  ``impl="kernel"`` (the serving default) runs the
    kernel-first in-place block-table path (``_decode_scan_kernel``);
    ``impl="gather"`` is the parity oracle: gather -> the monolithic
    ``_decode_scan`` -> scatter blocks [pos, pos + steps) back.  Carry
    mirrors ``_decode_scan`` with the paged cache pytree in the cache
    slot; both impls produce bitwise-identical tokens and logits."""
    if impl == "kernel":
        return _decode_scan_kernel(params, cfg, cur, last, cache, pos, rng,
                                   ucfg, steps, greedy,
                                   with_logits=with_logits, mesh=mesh,
                                   rules=rules)
    cache = T.constrain_cache(cache, cfg, mesh, rules)
    with jax.named_scope("decode.cache_gather"):
        lin = T.paged_gather(cfg, cache)
    toks, lgs, h_per, v_per, carry = _decode_scan(
        params, cfg, cur, last, lin, pos, rng, ucfg, steps, greedy,
        with_logits=with_logits, mesh=mesh, rules=rules)
    cur2, last2, lin2, pos2, rng2 = carry
    with jax.named_scope("decode.cache_write"):
        layers = T.paged_scatter_back(cfg, cache, lin2, pos, pos + steps)
    out_cache = T.paged_cache(layers, cache["table"], cache["rows"])
    return toks, lgs, h_per, v_per, (cur2, last2, out_cache, pos2, rng2)


@partial(jax.jit, static_argnames=("cfg", "ucfg", "max_new", "greedy",
                                   "impl", "mesh", "rules"),
         donate_argnames=("cache",))
def _generate_fused_paged(params, cfg: ModelConfig, prompts, s_orig, cache,
                          rng, ucfg: UncertaintyConfig, max_new: int,
                          greedy: bool, impl: str = "gather", mesh=None,
                          rules=None):
    """Paged sibling of ``_generate_fused``: the cache comes in as the
    paged pool + this request's block tables / state rows (freshly
    allocated and reset by the CachePool) instead of being initialised
    in-trace.

    ``impl="gather"``: one gather, the whole monolithic prefill + scanned
    decode, one scatter of blocks [0, s_orig + max_new).
    ``impl="kernel"``: the prefill still gathers (amortised over the whole
    span) and scatters [0, s_orig) back, but the decode scan reads blocks
    in place (``_decode_scan_kernel``) — no per-step slot-linear KV."""
    B = prompts.shape[0]
    cache = T.constrain_cache(cache, cfg, mesh, rules)
    lin = T.paged_gather(cfg, cache)
    cur, last, lin = _prefill_into(params, cfg, prompts, s_orig, lin,
                                   mesh=mesh, rules=rules)
    pos = jnp.broadcast_to(s_orig, (B,))
    if impl == "kernel":
        layers = T.paged_scatter_back(
            cfg, cache, lin, jnp.zeros((B,), jnp.int32),
            jnp.broadcast_to(s_orig, (B,)).astype(jnp.int32))
        cache = T.paged_cache(layers, cache["table"], cache["rows"])
        toks, lgs, h_per, v_per, carry = _decode_scan_kernel(
            params, cfg, cur, last, cache, pos, rng, ucfg, max_new, greedy,
            mesh=mesh, rules=rules)
    else:
        toks, lgs, h_per, v_per, scarry = _decode_scan(
            params, cfg, cur, last, lin, pos, rng,
            ucfg, max_new, greedy, mesh=mesh, rules=rules)
        cur2, last2, lin2, pos2, rng2 = scarry
        layers = T.paged_scatter_back(
            cfg, cache, lin2, jnp.zeros((B,), jnp.int32),
            jnp.broadcast_to(s_orig + max_new, (B,)).astype(jnp.int32))
        out_cache = T.paged_cache(layers, cache["table"], cache["rows"])
        carry = (cur2, last2, out_cache, pos2, rng2)
    h, v = h_per.mean(-1), v_per.mean(-1)
    return toks, lgs, U.combine_terms(h, v, ucfg), h, v, carry


@partial(jax.jit, static_argnames=("cfg", "ucfg", "max_new", "greedy",
                                   "impl", "mesh", "rules"),
         donate_argnames=("cache",))
def _generate_continue_paged(params, cfg: ModelConfig, prompts, s_orig,
                             start, cache, rng, ucfg: UncertaintyConfig,
                             max_new: int, greedy: bool,
                             impl: str = "gather", mesh=None, rules=None):
    """Paged sibling of ``_generate_continue``: continuation prefill +
    scanned decode, scatter of blocks [start, start + s_orig + max_new).
    ``impl="kernel"`` scatters the prefill span back and decodes in place
    through the block table (see ``_generate_fused_paged``)."""
    cache = T.constrain_cache(cache, cfg, mesh, rules)
    lin = T.paged_gather(cfg, cache)
    cur, last, lin = _prefill_continue(params, cfg, prompts, s_orig, start,
                                       lin, mesh=mesh, rules=rules)
    if impl == "kernel":
        layers = T.paged_scatter_back(cfg, cache, lin, start, start + s_orig)
        cache = T.paged_cache(layers, cache["table"], cache["rows"])
        toks, lgs, h_per, v_per, carry = _decode_scan_kernel(
            params, cfg, cur, last, cache, start + s_orig, rng, ucfg,
            max_new, greedy, mesh=mesh, rules=rules)
    else:
        toks, lgs, h_per, v_per, scarry = _decode_scan(
            params, cfg, cur, last, lin, start + s_orig, rng, ucfg, max_new,
            greedy, mesh=mesh, rules=rules)
        cur2, last2, lin2, pos2, rng2 = scarry
        layers = T.paged_scatter_back(cfg, cache, lin2, start,
                                      start + s_orig + max_new)
        out_cache = T.paged_cache(layers, cache["table"], cache["rows"])
        carry = (cur2, last2, out_cache, pos2, rng2)
    h, v = h_per.mean(-1), v_per.mean(-1)
    return toks, lgs, U.combine_terms(h, v, ucfg), h, v, carry


@partial(jax.jit, static_argnames=("cfg", "mesh", "rules"))
def _prefill_continue(params, cfg: ModelConfig, prompts, s_orig, start,
                      cache, mesh=None, rules=None):
    """Continuation prefill: absorb a new span into an already-populated
    cache.  prompts (B, Sb) RIGHT-padded to a bucket (real tokens first, so
    the recurrent conv windows cross from the cached context tail straight
    into the span); s_orig = pre-bucket span length; start (B,) the
    session's next absolute position.  Returns (first greedy token (B,),
    its logits (B,V) f32, the updated cache).

    On-mesh the incoming warm cache is re-pinned to its logical-axis
    sharding before the span is spliced in, so a cache handed across jit
    boundaries keeps the ``cache_axes`` placement of docs/SHARDING.md.
    """
    B, S = prompts.shape
    col = jnp.arange(S, dtype=jnp.int32)[None]
    # real columns at absolute positions start..start+s_orig-1; bucket
    # padding keeps negative positions => inert in every mixer
    positions = jnp.where(col < s_orig, start[:, None] + col, col - S)
    cache = T.constrain_cache(cache, cfg, mesh, rules)
    with jax.named_scope("prefill.model"):
        logits, cache = T.prefill(params, cfg, prompts, cache, positions,
                                  continuation=True, mesh=mesh, rules=rules)
        last = jax.lax.dynamic_slice_in_dim(logits, s_orig - 1, 1, axis=1)
        last = last[:, 0].astype(jnp.float32)
        last = sh.constrain(last, ("act_batch", "act_vocab"), mesh, rules)
    with jax.named_scope("prefill.sample"):
        cur = jnp.argmax(last, axis=-1).astype(jnp.int32)
    return cur, last, cache


@partial(jax.jit, static_argnames=("cfg", "ucfg", "max_new", "greedy",
                                   "mesh", "rules"))
def _generate_continue(params, cfg: ModelConfig, prompts, s_orig, start,
                       cache, rng, ucfg: UncertaintyConfig, max_new: int,
                       greedy: bool, mesh=None, rules=None):
    """Warm-path sibling of ``_generate_fused``: continuation prefill over a
    live cache + scanned decode, one device call.  Same outputs."""
    cur, last, cache = _prefill_continue(params, cfg, prompts, s_orig, start,
                                         cache, mesh=mesh, rules=rules)
    toks, lgs, h_per, v_per, carry = _decode_scan(
        params, cfg, cur, last, cache, start + s_orig, rng,
        ucfg, max_new, greedy, mesh=mesh, rules=rules)
    h, v = h_per.mean(-1), v_per.mean(-1)
    return toks, lgs, U.combine_terms(h, v, ucfg), h, v, carry


@partial(jax.jit, static_argnames=("cfg", "greedy", "mesh", "rules"))
def _step(params, cfg: ModelConfig, tokens, cache, index, rng, greedy: bool,
          mesh=None, rules=None):
    logits, cache = T.decode_step(params, cfg, tokens, cache, index,
                                  mesh=mesh, rules=rules)
    lg = logits[:, -1].astype(jnp.float32)
    if greedy:
        nxt = jnp.argmax(lg, axis=-1)
    else:
        nxt = jax.random.categorical(rng, lg, axis=-1)
    return nxt.astype(jnp.int32), lg, cache


@dataclasses.dataclass
class SessionState:
    """Warm cache handle: everything needed to continue a generation.

    Returned by ``InferenceEngine.generate(..., return_state=True)`` and by
    ``serve()`` for requests submitted with ``return_state=True``; accepted
    back by ``generate(..., state=...)`` and by warm ``serve()`` admissions
    (``Request.state``).  The handle is engine-specific — caches encode one
    model's layer plan and dtypes — and single-use by convention: continuing
    mutates nothing (JAX arrays are immutable) but the positions only make
    sense along one timeline, so fork via ``state_select`` if needed.

    * ``cache`` — layer-cache pytree (see ``transformer.init_cache``),
      populated through position ``pos - 1``.  On-mesh it carries the
      ``cache_axes`` shardings of docs/SHARDING.md.
    * ``pos`` (B,) int32 — next absolute write position per row.
    * ``cur`` (B,) int32 — the last sampled token, not yet absorbed or
      emitted (the decode scan's pending token): pure decode extension
      (``generate(None, k, state=...)``) resumes from it bitwise.
    * ``last`` (B,V) f32 — ``cur``'s logits.
    * ``max_len`` — static cache length (slots).
    * ``offset`` — host-side upper bound of ``pos`` (static int), used to
      size cache growth without a device sync.
    * ``rng`` — the decode scan's carried PRNG key (None when unavailable,
      e.g. serve()-extracted states whose sampling stream was shared
      across slots).  Pure decode extension resumes from it, so sampled
      (greedy=False) extension replays a longer generation bitwise too.
    * ``exact`` — False when the handle was captured off a slot that kept
      decoding past the request's stop token (mid-chunk retirement): the
      KV entries up to ``pos`` are still exact, but the pending
      ``cur``/``last`` and any recurrent-mixer state have absorbed
      post-stop garbage steps.  Such a handle only supports continuation
      prefill on attention-only models; anything else raises.

    On a paged engine (``InferenceEngine(paged=True)``), ``cache`` is not
    an array pytree but a :class:`~repro.serving.cache_manager.PagedHandle`
    — the session's block tables, state-row ids and the pool epoch.  The
    handle references pool storage by id, so it is O(table) on host memory,
    fan-out (``state_select`` / ``engine.fanout``) is a refcounted table
    copy, and growth appends blocks instead of copying the cache;
    ``max_len`` stays the *logical* capacity (what the monolithic engine
    would carry), which keeps paged dispatch shapes — and therefore
    numerics — bitwise-identical to the monolithic path even after the
    pool trims the physical tables to the covered length.  Paged handles
    stay registered with the pool until ``engine.release(state)`` or TTL
    eviction; reuse after that raises ``EvictedSessionError``.

    Unlike monolithic states (immutable array pytrees), a paged handle is
    a LIVE reference into the pool: continuing or extending it writes its
    blocks and state rows in place.  The single-use convention is
    therefore load-bearing on paged recurrent-mixer engines — extending
    the same handle twice gathers post-extension state rows the second
    time.  (On attention-only models a repeated greedy extension rewrites
    identical K/V, so benchmark-style reuse stays exact.)  Fork with
    ``state_select`` / ``fanout`` before extending if you need both
    timelines.
    """
    cache: Any
    pos: Any
    cur: Any
    last: Any
    max_len: int
    offset: int
    rng: Any = None
    exact: bool = True

    @property
    def batch(self) -> int:
        return int(self.pos.shape[0])


@dataclasses.dataclass
class InferenceEngine:
    """One swarm member: a model + its two-phase serving runtime.

    ``mesh`` (optional, from ``launch/mesh.py``) turns on the mesh-sharded
    runtime: parameters are placed by the logical-axis ``rules`` (default
    ``SERVE_RULES`` — weights replicated over 'data', tensor-parallel over
    'model'), the KV/recurrent caches and every batch dimension shard over
    'data', and the jitted prefill / scanned decode run with explicit in/out
    shardings so XLA partitions one program across the mesh.  Greedy tokens
    are the same as the single-device path; ``mesh=None`` (default) is
    bit-for-bit the unsharded engine.
    """
    name: str
    cfg: ModelConfig
    params: Any
    ucfg: UncertaintyConfig = dataclasses.field(default_factory=UncertaintyConfig)
    max_len: int = 128
    mesh: Any = None                    # jax.sharding.Mesh with (data, model)
    rules: Any = None                   # ShardingRules; default SERVE_RULES
    # paged block-pool cache manager (docs/RUNTIME.md "Paged caches &
    # prefix sharing"): KV lives in a fixed pool of block_len-sized blocks
    # addressed through per-slot block tables, session growth appends
    # blocks instead of grow_cache's whole-buffer copy, and absorbed
    # prefixes fan out to many slots copy-on-write.  Bitwise-identical to
    # the monolithic path (the gathered table view equals the monolithic
    # cache elementwise) as long as block_len divides the engine's cache
    # bucketing — the default 64 always does.
    paged: bool = False
    block_len: int = 64
    pool_blocks: int | None = None      # default: 16 full-length sessions
    pool_rows: int | None = None        # recurrent-state rows in the pool
    # paged decode-attention impl (docs/RUNTIME.md "Kernel-first decode"):
    # "kernel" reads KV blocks in place through the block table — no
    # per-step slot-linear gather; "gather" is the parity oracle (gather ->
    # monolithic decode -> scatter).  None = measured-best per backend
    # (kernel everywhere: bitwise-identical tokens+logits either way, and
    # the in-place read wins on both CPU and TPU — see benchmarks/
    # decode_microbench.py).
    attn_decode_impl: str | None = None
    # quantized serving (docs/RUNTIME.md "Quantized caches"):
    # ``cache_quant`` stores the paged pool's KV blocks int8/fp8 with
    # per-row f32 scales (requires paged=True; recurrent state rows stay
    # bf16) — correctness becomes *budgeted*: greedy tokens match bf16 on
    # the smoke workloads and logit error stays within the per-arch
    # budget, instead of bitwise.  ``weight_quant`` stores the serving
    # matmul weights (attention/MLP/MoE projections + untied lm_head) as
    # QTensors, dequantized on the fly at the matmul call sites; works on
    # monolithic and paged engines, on- and off-mesh.
    cache_quant: str | None = None
    weight_quant: str | None = None
    # persistent compilation cache: set to a directory to make every jit
    # this engine triggers write/read XLA executables there — a second
    # process constructing the same engine performs ZERO fresh compiles
    # for already-seen (config, bucket, mesh) cells (serve() cold start).
    # A set JAX_COMPILATION_CACHE_DIR wins over it (repro.compile_cache).
    compilation_cache_dir: str | None = None

    def __post_init__(self):
        if self.compilation_cache_dir is not None:
            from repro.compile_cache import enable_compilation_cache
            enable_compilation_cache(self.compilation_cache_dir)
        self._mesh_jits: dict = {}
        # host-side dispatch accounting: how many cold prefills, warm
        # continuation prefills and decode-only resumes this engine issued
        # (the gateway tests assert the probe's swarm round adds zero here),
        # plus grow_copy — whole-cache growth copies, always 0 when paged
        self.counters = {"prefill": 0, "prefill_continue": 0,
                         "decode_only": 0, "grow_copy": 0,
                         # failure-domain accounting (docs/RUNTIME.md
                         # "Failure semantics"): admission rounds deferred
                         # by famine backpressure, requests shed/expired,
                         # slot-failure requeues, and transparent cold
                         # re-prefills after a warm handle was evicted
                         "famine_deferred": 0, "shed": 0, "expired": 0,
                         "requeued": 0, "reprefill_cold": 0,
                         # kernel-first decode dispatches that stepped the
                         # SSD state rows in place in the pool
                         # (transformer.ssd_pools_carried)
                         "ssd_state_inplace": 0}
        # serve()'s phases per loop turn (serving/spans.py): serve.turn and
        # its children serve.admit, serve.prefill (one per admission),
        # serve.decode, serve.sync, serve.record and serve.retire
        self.spans = Spans()
        # warm continuation attends CHUNKED over the cache, which needs the
        # cache length divisible by the KV block once it exceeds one block
        # (cold prefill/decode never hit this: they chunk only the span)
        kvb = self.cfg.attn_kv_block
        if self.max_len > kvb and self.max_len % kvb:
            self.max_len = -(-self.max_len // kvb) * kvb
        self._recurrent = any(m in ("rglru", "ssd")
                              for m, _ in self.cfg.layer_plan())
        from repro.models import quant as Q
        Q.check_quant(self.cache_quant)
        Q.check_quant(self.weight_quant)
        if self.cache_quant is not None and not self.paged:
            raise ValueError(
                "cache_quant requires paged=True: quantization is per pool "
                "block (scales ride the block pool as a sidecar leaf); the "
                "monolithic cache stays bf16")
        if self.weight_quant is not None:
            self.params = Q.quantize_params(self.params, self.weight_quant)
        self.pool = None
        if self.paged:
            L = self.block_len
            if self.max_len % L:
                # whole-block tables AND kv-chunk divisibility (lcm)
                self.max_len = self._round_len(self.max_len)
            has_local = any(m == "attn_local"
                            for m, _ in self.cfg.layer_plan())
            if has_local and self.cfg.window is not None \
                    and self.cfg.window % L:
                raise ValueError(
                    f"paged cache: block_len={L} must divide the local-"
                    f"attention window {self.cfg.window} (the ring view is "
                    "assembled from whole blocks)")
            from repro.serving.cache_manager import CachePool
            if self.attn_decode_impl is None:
                self.attn_decode_impl = "kernel"
            if self.attn_decode_impl not in ("kernel", "gather"):
                raise ValueError(
                    f"attn_decode_impl must be 'kernel' or 'gather', got "
                    f"{self.attn_decode_impl!r}")
            n_blocks = self.pool_blocks or max(64, 16 * self.max_len // L)
            n_rows = self.pool_rows or max(
                16, n_blocks * L // max(self.max_len, 1))
            self.rules = self.rules or (sh.SERVE_RULES
                                        if self.mesh is not None else None)
            self.pool = CachePool(self.cfg, L, n_blocks, n_rows,
                                  cache_quant=self.cache_quant,
                                  mesh=self.mesh, rules=self.rules)
        self._ssd_inplace = int(self.paged
                                and self.attn_decode_impl == "kernel"
                                and T.ssd_pools_carried(self.cfg, self.mesh))
        if self.mesh is None:
            return
        self.rules = self.rules or sh.SERVE_RULES
        # explicit parameter placement: the logical-axis rules decide which
        # dims shard ('heads'/'ffn'/'vocab' over 'model'); the rest replicate.
        # Quantized weights mirror the axes tree over the QTensor leaves so
        # each payload row and its scale land on the same shard.
        axes = T.param_axes(self.cfg)
        if self.weight_quant is not None:
            axes = Q.quantize_param_axes(axes, self.params)
        self._param_sh = sh.tree_shardings(
            self.params, axes, self.mesh, self.rules)
        self.params = jax.device_put(self.params, self._param_sh)

    # ------------------------------------------------------------------
    # Sharded entry points (built lazily, cached per shape signature)
    # ------------------------------------------------------------------

    def _act_sh(self, shape, logical):
        return NamedSharding(self.mesh, sh.spec_for(
            shape, logical, self.mesh, self.rules.act_rules))

    def _cache_sh(self, cache_or_avals):
        specs = sh.tree_specs(cache_or_avals, T.cache_axes(self.cfg),
                              self.mesh, self.rules.act_rules)
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s), specs)

    def _fused_sharded(self, B: int, Sb: int, max_len: int, max_new: int,
                       greedy: bool):
        """jitted prefill+decode with explicit in/out shardings: params by
        rule, prompts/tokens/u sharded on 'data' (batch), logits on
        ('data' batch x 'model' vocab)."""
        key = ("fused", B, Sb, max_len, max_new, greedy)
        fn = self._mesh_jits.get(key)
        if fn is None:
            cfg, ucfg, mesh, rules = self.cfg, self.ucfg, self.mesh, self.rules

            def body(params, prompts, s_orig, rng):
                return _generate_fused(params, cfg, prompts, s_orig, rng,
                                       ucfg, max_new, max_len, greedy,
                                       mesh=mesh, rules=rules)

            rep = NamedSharding(mesh, P())
            fn = jax.jit(
                body,
                in_shardings=(self._param_sh,
                              self._act_sh((B, Sb), ("act_batch", None)),
                              rep, rep),
                out_shardings=self._gen_out_sh(B, max_new, max_len))
            self._mesh_jits[key] = fn
        return fn

    def _gen_out_sh(self, B: int, max_new: int, max_len: int):
        """Output shardings shared by the fused cold and warm generate:
        (tokens, logits, u, h_mean, v_mean, carry) with the decode-scan
        carry — the session state — placed exactly like the decode chunk's
        slot state (cache per ``cache_axes``, batch dims on 'data')."""
        b_sh = self._act_sh((B,), ("act_batch",))
        v_sh = self._act_sh((B, self.cfg.vocab_size),
                            ("act_batch", "act_vocab"))
        csh = self._cache_sh(
            jax.eval_shape(lambda: T.init_cache(self.cfg, B, max_len)))
        rep = NamedSharding(self.mesh, P())
        return (self._act_sh((B, max_new), ("act_batch", None)),
                self._act_sh((B, max_new, self.cfg.vocab_size),
                             ("act_batch", None, "act_vocab")),
                b_sh, b_sh, b_sh,
                (b_sh, v_sh, csh, b_sh, rep))

    def _cont_sharded(self, B: int, Sb: int, max_len: int, max_new: int,
                      greedy: bool):
        """jitted continuation prefill + decode with explicit in/out
        shardings; the warm cache comes in already placed per cache_axes."""
        key = ("cont", B, Sb, max_len, max_new, greedy)
        fn = self._mesh_jits.get(key)
        if fn is None:
            cfg, ucfg, mesh, rules = self.cfg, self.ucfg, self.mesh, self.rules

            def body(params, prompts, s_orig, start, cache, rng):
                return _generate_continue(params, cfg, prompts, s_orig,
                                          start, cache, rng, ucfg, max_new,
                                          greedy, mesh=mesh, rules=rules)

            rep = NamedSharding(mesh, P())
            csh = self._cache_sh(
                jax.eval_shape(lambda: T.init_cache(cfg, B, max_len)))
            fn = jax.jit(
                body,
                in_shardings=(self._param_sh,
                              self._act_sh((B, Sb), ("act_batch", None)),
                              rep, self._act_sh((B,), ("act_batch",)),
                              csh, rep),
                out_shardings=self._gen_out_sh(B, max_new, max_len))
            self._mesh_jits[key] = fn
        return fn

    def _decode_sharded(self, B: int, max_len: int, steps: int, greedy: bool):
        """jitted decode chunk over the serve slots, explicit in/out
        shardings for the slot state (cur/last/pos/cache)."""
        key = ("decode", B, max_len, steps, greedy)
        fn = self._mesh_jits.get(key)
        if fn is None:
            cfg, ucfg, mesh, rules = self.cfg, self.ucfg, self.mesh, self.rules
            csh = self._cache_sh(
                jax.eval_shape(lambda: T.init_cache(cfg, B, max_len)))
            rep = NamedSharding(mesh, P())
            b_sh = self._act_sh((B,), ("act_batch",))
            v_sh = self._act_sh((B, cfg.vocab_size),
                                ("act_batch", "act_vocab"))
            n_sh = self._act_sh((B, steps), ("act_batch", None))

            def body(params, cur, last, cache, pos, rng):
                toks, _, h, v, carry = _decode_scan(
                    params, cfg, cur, last, cache, pos, rng, ucfg, steps,
                    greedy, with_logits=False, mesh=mesh, rules=rules)
                return toks, h, v, carry

            fn = jax.jit(
                body,
                in_shardings=(self._param_sh, b_sh, v_sh, csh, b_sh, rep),
                out_shardings=(n_sh, n_sh, n_sh,
                               (b_sh, v_sh, csh, b_sh, rep)))
            self._mesh_jits[key] = fn
        return fn

    # ------------------------------------------------------------------
    def _round_len(self, need: int) -> int:
        """Bucket a cache length: multiples of 64, and — because warm
        continuation attends chunked over the *cache* — multiples of
        ``attn_kv_block`` once the cache outgrows a single KV chunk.
        Paged engines round to the lcm with ``block_len`` so whole-block
        tables NEVER break the KV-chunk divisibility invariant (a
        block_len that divides 64/attn_kv_block — the default 64 does —
        leaves the lengths, and therefore numerics, identical to the
        monolithic path)."""
        g = math.lcm(64, self.block_len) if self.paged else 64
        n = -(-need // g) * g
        kvb = self.cfg.attn_kv_block
        if n > kvb:
            gk = math.lcm(kvb, self.block_len) if self.paged else kvb
            n = -(-n // gk) * gk
        return n

    def _cache_len(self, s_bucket: int, max_new: int) -> int:
        need = s_bucket + max_new
        if need <= self.max_len:
            return self.max_len
        return self._round_len(need)        # bucket cache growth too

    def _bucket(self, prompts: np.ndarray) -> tuple[np.ndarray, int]:
        B, S = prompts.shape
        gran = max(self.cfg.attn_q_block, self.cfg.attn_kv_block)
        Sb = bucket_len(S, gran)
        if Sb == S:
            return prompts, S
        out = np.zeros((B, Sb), np.int32)
        out[:, Sb - S:] = prompts
        return out, S

    def _bucket_right(self, prompts: np.ndarray) -> tuple[np.ndarray, int]:
        """Bucket a continuation span: RIGHT-padded, so no padding sits
        between the cached context and the new tokens (the recurrent conv
        windows must cross that boundary contiguously)."""
        B, S = prompts.shape
        gran = max(self.cfg.attn_q_block, self.cfg.attn_kv_block)
        Sb = bucket_len(S, gran)
        if Sb == S:
            return prompts, S
        out = np.zeros((B, Sb), np.int32)
        out[:, :S] = prompts
        return out, S

    def _grown_cache(self, state: SessionState, need: int):
        """(cache, max_len) with at least ``need`` slots, growing the
        session's cache (empty new slots) when it is too short.  Monolithic
        growth is ``grow_cache``'s whole-buffer copy (counted in
        ``counters["grow_copy"]``); the paged path never comes through here
        — it appends reset blocks to the block table instead."""
        if need <= state.max_len:
            return state.cache, state.max_len
        new_len = self._round_len(need)
        self.counters["grow_copy"] += 1
        cache = T.grow_cache(self.cfg, state.cache, state.batch, new_len)
        if self.mesh is not None:
            cache = jax.device_put(cache, self._cache_sh(cache))
        return cache, new_len

    # ------------------------------------------------------------------
    # Paged-cache helpers (CachePool-backed sessions)
    # ------------------------------------------------------------------

    def _paged_dev_cache(self, tables: np.ndarray, rows: np.ndarray):
        """The paged cache pytree for one dispatch: engine pool arrays +
        this dispatch's block tables and state-row ids."""
        return T.paged_cache(self.pool.arrays,
                             jnp.asarray(np.asarray(tables, np.int32)),
                             jnp.asarray(np.asarray(rows, np.int32)))

    def _paged_grown(self, state: SessionState, need: int):
        """Paged sibling of ``_grown_cache``: extend the session's block
        tables to the dispatch length (appending freshly reset blocks,
        COW-copying any shared block in the write range) — no whole-cache
        copy, ever.  Returns (cache pytree, dispatch max_len).  The
        dispatch length follows the same formula as the monolithic path so
        paged and monolithic dispatch shapes (and numerics) match."""
        handle = state.cache
        self.pool.check(handle)
        disp = state.max_len if need <= state.max_len \
            else self._round_len(need)
        tables = self.pool.extend(handle, disp // self.block_len,
                                  np.asarray(state.pos))
        return self._paged_dev_cache(tables, handle.rows), disp

    def release(self, state: SessionState) -> None:
        """Return a paged session's blocks to the pool and invalidate the
        handle (no-op for monolithic states — they are plain arrays)."""
        if self.paged and isinstance(state.cache, PagedHandle):
            self.pool.release(state.cache)

    def evict_idle_sessions(self, ttl_s: float) -> int:
        """TTL sweep over registered paged sessions (see CachePool)."""
        return self.pool.evict_idle(ttl_s) if self.paged else 0

    # ------------------------------------------------------------------
    # Session durability: checkpoint/restore through training/checkpoint
    # ------------------------------------------------------------------

    def checkpoint_session(self, state: SessionState, ckpt_dir: str, *,
                           step: int = 0, keep: int = 3) -> str:
        """Persist a session to disk so a chat survives an engine restart.

        Writes through :mod:`repro.training.checkpoint` (atomic publish:
        npz shards + manifest, tmp-dir ``os.replace``), so a crash
        mid-save never corrupts the recoverable state.  The cache is
        saved in its slot-linear MONOLITHIC view — for a paged session
        the handle's blocks are gathered first — which makes checkpoints
        portable across engine representations: a session saved on a
        paged engine restores onto a monolithic one and vice versa.

        Exactness matches the gather/scatter round-trip: global-attention
        KV and recurrent state rows restore bitwise; a local-attention
        ring that has already wrapped (``pos > window``) is clamped to
        its window view.  Inexact handles (mid-chunk stop retirement)
        keep their ``exact=False`` flag through the round-trip.
        """
        from repro.training import checkpoint as ck
        self._state_kind_check(state)
        if self.paged:
            h = state.cache
            cov_len = int(h.tables.shape[1]) * self.block_len
            cache = T.paged_gather(
                self.cfg, self._paged_dev_cache(h.tables, h.rows))
        else:
            cov_len = int(state.max_len)
            cache = state.cache
        # the per-layer cache dicts hold None for state kinds a layer does
        # not carry — flatten to the real leaves (checkpoint shards are
        # arrays only) and rebuild the structure from init_cache on restore
        tree = {"cache": jax.tree_util.tree_leaves(cache),
                "pos": np.asarray(state.pos),
                "cur": np.asarray(state.cur),
                "last": np.asarray(state.last)}
        if state.rng is not None:
            tree["rng"] = np.asarray(state.rng)
        extra = {"kind": "session", "batch": int(state.batch),
                 "max_len": int(state.max_len), "offset": int(state.offset),
                 "cov_len": cov_len, "exact": bool(state.exact),
                 "paged": bool(self.paged), "has_rng": state.rng is not None,
                 # the saved linear view was dequantized by paged_gather, so
                 # the shards are always bf16 — but a quantized session's
                 # numerics are budgeted, not bitwise, and restoring it into
                 # a differently-represented cache would silently change the
                 # conversation's precision; record the representation so
                 # restore can refuse a mismatch (QuantMismatchError)
                 "cache_quant": self.cache_quant}
        return ck.save(ckpt_dir, step, tree, extra=extra, keep=keep)

    def restore_session(self, ckpt_dir: str,
                        step: int | None = None) -> SessionState:
        """Rebuild a checkpointed session on THIS engine (possibly a fresh
        process: the one that crashed).  Paged engines scatter the saved
        linear view into freshly allocated pool blocks/rows and register
        the handle; monolithic engines adopt the arrays directly.  The
        resumed chat continues bitwise where the round-trip is exact
        (see ``checkpoint_session``)."""
        import json
        import os

        from repro.training import checkpoint as ck
        if step is None:
            step = ck.latest_step(ckpt_dir)
            if step is None:
                raise FileNotFoundError(f"no session checkpoint in "
                                        f"{ckpt_dir!r}")
        with open(os.path.join(ckpt_dir, f"step_{step}",
                               "manifest.json")) as f:
            extra = json.load(f)["extra"]
        if extra.get("kind") != "session":
            raise ValueError(f"checkpoint at {ckpt_dir!r} step {step} is "
                             "not a session checkpoint")
        saved_q = extra.get("cache_quant")   # absent in old checkpoints
        if saved_q != self.cache_quant:
            from repro.serving.cache_manager import QuantMismatchError
            raise QuantMismatchError(
                f"session checkpoint at {ckpt_dir!r} step {step} was saved "
                f"from a cache_quant={saved_q!r} engine but this engine is "
                f"cache_quant={self.cache_quant!r}"
                + ("" if self.paged else " (monolithic)")
                + "; restoring would silently change the session's numeric "
                "precision — restore on a matching engine or re-absorb the "
                "conversation")
        B, cov_len = int(extra["batch"]), int(extra["cov_len"])
        ab_cache = jax.eval_shape(lambda: T.init_cache(self.cfg, B, cov_len))
        ab_leaves, cache_def = jax.tree_util.tree_flatten(ab_cache)
        abstract = {
            "cache": ab_leaves,
            "pos": np.zeros((B,), np.int32),
            "cur": np.zeros((B,), np.int32),
            "last": np.zeros((B, self.cfg.vocab_size), np.float32)}
        if extra.get("has_rng"):
            abstract["rng"] = np.zeros((2,), np.uint32)
        tree, _ = ck.restore(ckpt_dir, step, abstract)
        tree["cache"] = jax.tree_util.tree_unflatten(cache_def,
                                                     tree["cache"])
        pos = jnp.asarray(np.asarray(tree["pos"], np.int32))
        cur = jnp.asarray(np.asarray(tree["cur"], np.int32))
        last = jnp.asarray(np.asarray(tree["last"], np.float32))
        rng = tree.get("rng")
        if rng is not None:
            rng = jnp.asarray(np.asarray(rng, np.uint32))
        if self.paged:
            handle = self.pool.alloc(B, cov_len // self.block_len)
            dev = self._paged_dev_cache(handle.tables, handle.rows)
            layers = T.paged_scatter_back(
                self.cfg, dev, tree["cache"],
                jnp.zeros((B,), jnp.int32),
                jnp.full((B,), cov_len, jnp.int32))
            self.pool.commit(layers)
            cache, max_len = handle, int(extra["max_len"])
        else:
            cache = jax.tree.map(jnp.asarray, tree["cache"])
            if self.mesh is not None:
                cache = jax.device_put(cache, self._cache_sh(cache))
            # the monolithic invariant is cache length == max_len: a
            # paged-saved session arrives trimmed to its covered length
            max_len = int(extra["max_len"]) if not extra.get("paged") \
                else cov_len
        return SessionState(cache, pos, cur, last, max_len,
                            int(extra["offset"]), rng=rng,
                            exact=bool(extra["exact"]))

    def fanout(self, state: SessionState, n: int) -> SessionState:
        """Fan a batch-1 session out to ``n`` rows sharing its prefix.

        Paged: a refcounted block-table copy — full prefix blocks are
        shared read-only, the partially filled tail block is copy-on-write
        per row, state rows are copied; NO prefill or cache copy happens,
        so N sessions over one absorbed system prompt cost exactly one
        prefill.  Monolithic: falls back to duplicating the cache rows
        (``state_select`` with a repeated index) — correct, but O(n * len).
        """
        if state.batch != 1:
            raise ValueError(f"fanout needs a batch-1 state, got "
                             f"{state.batch}")
        return self.state_select(state, np.zeros((n,), np.int32))

    # ------------------------------------------------------------------
    def generate(self, prompts: np.ndarray | None, max_new: int, *,
                 greedy: bool = True, seed: int = 0,
                 state: SessionState | None = None,
                 return_state: bool = False) -> dict:
        """prompts (B, S) int32, LEFT-padded with PAD=0 (HF batched-decode
        convention, so the last absorbed position is always the prompt end).

        Jitted prefill + one scanned decode, fused into a single device
        call (SPMD-partitioned when the engine has a mesh).  Returns
        ``{"tokens": (B, max_new) int32, "u": (B,) Eq. 4 difficulty,
        "logits": (B, max_new, V) f32, "prompt_lengths": (B,),
        "h_mean"/"v_mean": (B,) raw Eq. 2-3 means}`` — the probe's
        generation *is* the local answer (paper Sec. IV-A), and the Eq. 2-3
        entropy/variance terms are computed on the scanned logits at zero
        extra forward passes.

        Session API (docs/RUNTIME.md, "Continuation prefill & session
        caches"):

        * ``return_state=True`` adds ``"state"``: a :class:`SessionState`
          warm-cache handle covering the prompt plus every emitted token.
        * ``state=<handle>`` continues that session: ``prompts`` is only
          the NEW span (turn t+1's user tokens), absorbed into the live
          cache by one continuation prefill — the cached context is never
          re-prefilled.  Greedy tokens are identical to cold-prefilling the
          concatenation.
        * ``state=<handle>`` with ``prompts=None`` is a pure decode
          extension: resume from the session's pending token and emit
          ``max_new`` more — bitwise the tokens a single longer generation
          would have produced next (zero prefill dispatches of any kind).

        MoE configs take this fused path too: prefill routes each position
        as its own dispatch group with masked (capacity-excluded) bucket
        padding, and decode uses the constant-shape exact top-k dispatch —
        the same routing decisions the stepwise loop makes, so greedy
        tokens match ``generate_stepwise`` (docs/RUNTIME.md, MoE routing).
        """
        rng = jax.random.PRNGKey(seed)
        if prompts is not None:
            prompts = np.asarray(prompts, np.int32)
        if state is not None and (prompts is None or prompts.shape[1] == 0):
            self._check_state(state, extension=True)
            return self._extend(max_new, state, greedy, rng, return_state)
        if state is not None:
            self._check_state(state, extension=False)
        B, S = prompts.shape
        handle = None
        if state is None:
            pb, s_orig = self._bucket(prompts)
            max_len = self._cache_len(pb.shape[1], max_new)
            self.counters["prefill"] += 1
            if self.paged:
                self.counters["ssd_state_inplace"] += self._ssd_inplace
                handle = self.pool.alloc(B, max_len // self.block_len)
                out = _generate_fused_paged(
                    self.params, self.cfg, jnp.asarray(pb),
                    jnp.int32(s_orig),
                    self._paged_dev_cache(handle.tables, handle.rows), rng,
                    self.ucfg, int(max_new), bool(greedy),
                    impl=self.attn_decode_impl,
                    mesh=self.mesh, rules=self.rules)
            elif self.mesh is not None:
                fn = self._fused_sharded(B, pb.shape[1], max_len,
                                         int(max_new), bool(greedy))
                out = fn(self.params, jnp.asarray(pb), jnp.int32(s_orig),
                         rng)
            else:
                out = _generate_fused(
                    self.params, self.cfg, jnp.asarray(pb),
                    jnp.int32(s_orig), rng, self.ucfg, int(max_new),
                    max_len, bool(greedy))
            offset = s_orig + max_new
        else:
            if state.batch != B:
                raise ValueError(f"state batch {state.batch} != prompt "
                                 f"batch {B}")
            pb, s_orig = self._bucket_right(prompts)
            need = state.offset + pb.shape[1] + max_new
            if self.paged:
                handle = state.cache
                cache, max_len = self._paged_grown(state, need)
            else:
                cache, max_len = self._grown_cache(state, need)
            self.counters["prefill_continue"] += 1
            if self.paged:
                self.counters["ssd_state_inplace"] += self._ssd_inplace
                out = _generate_continue_paged(
                    self.params, self.cfg, jnp.asarray(pb),
                    jnp.int32(s_orig), state.pos, cache, rng, self.ucfg,
                    int(max_new), bool(greedy),
                    impl=self.attn_decode_impl,
                    mesh=self.mesh, rules=self.rules)
            elif self.mesh is not None:
                fn = self._cont_sharded(B, pb.shape[1], max_len,
                                        int(max_new), bool(greedy))
                out = fn(self.params, jnp.asarray(pb), jnp.int32(s_orig),
                         state.pos, cache, rng)
            else:
                out = _generate_continue(
                    self.params, self.cfg, jnp.asarray(pb),
                    jnp.int32(s_orig), state.pos, cache, rng, self.ucfg,
                    int(max_new), bool(greedy))
            offset = state.offset + s_orig + max_new
        toks, lgs, u, h, v, carry = out
        res = {"tokens": np.asarray(toks),
               "u": np.asarray(u),
               "logits": lgs,
               "h_mean": np.asarray(h), "v_mean": np.asarray(v),
               "prompt_lengths": (prompts != PAD).sum(axis=1)}
        if self.paged:
            cur, last, cache, pos, crng = carry
            self.pool.commit(cache["layers"])
            if return_state:
                self.pool.trim(handle, -(-offset // self.block_len))
                res["state"] = SessionState(handle, pos, cur, last, max_len,
                                            offset, rng=crng)
            elif state is None:
                self.pool.release(handle)   # one-shot: blocks back now
            else:
                # continued session not handed back: keep only the covered
                # blocks until the caller reuses or releases the handle
                self.pool.trim(handle, -(-offset // self.block_len))
        elif return_state:
            cur, last, cache, pos, crng = carry
            res["state"] = SessionState(cache, pos, cur, last, max_len,
                                        offset, rng=crng)
        return res

    def _check_state(self, state: SessionState, *, extension: bool):
        """Refuse reuse an inexact handle can't support: one captured after
        a mid-chunk stop retirement has a corrupted pending token and (for
        recurrent mixers) a corrupted carried state — only continuation
        prefill on an attention-only model survives that (the prefill
        replaces cur/last and stale KV entries are masked/overwritten)."""
        self._state_kind_check(state)
        if state.exact:
            return
        if extension or self._recurrent:
            raise ValueError(
                "inexact session state (captured after a mid-chunk stop "
                "retirement in serve()): "
                + ("pure decode extension needs the pending token"
                   if extension else
                   "recurrent-mixer state absorbed post-stop steps")
                + "; re-serve with max_new-aligned retirement or an "
                  "attention-only model")

    def _state_kind_check(self, state: SessionState):
        """A paged engine only accepts PagedHandle-backed states (and vice
        versa — the cache representations are not interchangeable), and a
        paged handle must still be registered with the pool (released /
        TTL-evicted handles raise EvictedSessionError)."""
        got = isinstance(state.cache, PagedHandle)
        if got != self.paged:
            raise ValueError(
                f"session state is {'paged' if got else 'monolithic'} but "
                f"this engine is {'paged' if self.paged else 'monolithic'}")
        if self.paged:
            self.pool.check(state.cache)

    def absorb(self, prompts: np.ndarray, *,
               state: SessionState | None = None) -> SessionState:
        """Prefill-only: absorb a context into a (fresh or live) cache and
        return the session handle — no decode steps run.

        The returned state's pending token is the prefill argmax, so
        ``generate(None, n, state=eng.absorb(p))`` emits exactly the greedy
        tokens ``generate(p, n)`` would.  Use it to cache a shared context
        (system prompt, conversation so far) once and fan generations out
        of it; continuation over an absorb-only state is **bitwise**
        identical to cold-prefilling the concatenation (no decode-written
        K/V in between — see docs/RUNTIME.md on the numerics).
        With ``state`` given, the new span is absorbed on top (prefill-only
        multi-turn ingestion).
        """
        prompts = np.asarray(prompts, np.int32)
        B, S = prompts.shape
        handle = None
        if state is None:
            pb, s_orig = self._bucket(prompts)
            max_len = self._cache_len(pb.shape[1], 0)
            self.counters["prefill"] += 1
            if self.paged:
                handle = self.pool.alloc(B, max_len // self.block_len)
                cache = self._paged_dev_cache(handle.tables, handle.rows)
                cur, last, cache = _prefill_into_paged(
                    self.params, self.cfg, jnp.asarray(pb),
                    jnp.int32(s_orig), cache, mesh=self.mesh,
                    rules=self.rules)
            else:
                cur, last, cache = _prefill_absorb(
                    self.params, self.cfg, jnp.asarray(pb), jnp.int32(s_orig),
                    max_len, mesh=self.mesh, rules=self.rules)
            pos, offset = jnp.full((B,), s_orig, jnp.int32), s_orig
        else:
            if state.batch != B:
                raise ValueError(f"state batch {state.batch} != prompt "
                                 f"batch {B}")
            self._state_kind_check(state)
            pb, s_orig = self._bucket_right(prompts)
            need = state.offset + pb.shape[1]
            if self.paged:
                handle = state.cache
                cache, max_len = self._paged_grown(state, need)
            else:
                cache, max_len = self._grown_cache(state, need)
            self.counters["prefill_continue"] += 1
            fn = _prefill_continue_paged if self.paged else _prefill_continue
            cur, last, cache = fn(
                self.params, self.cfg, jnp.asarray(pb), jnp.int32(s_orig),
                state.pos, cache, mesh=self.mesh, rules=self.rules)
            pos, offset = state.pos + s_orig, state.offset + s_orig
        if self.paged:
            self.pool.commit(cache["layers"])
            self.pool.trim(handle, -(-offset // self.block_len))
            cache = handle
        return SessionState(cache, pos, cur, last, max_len, offset)

    def _extend(self, max_new: int, state: SessionState, greedy: bool,
                rng, return_state: bool) -> dict:
        """Decode-only continuation: emit ``max_new`` more tokens from the
        session's pending token — exactly the tokens a longer original
        generation would have produced next (bitwise; the decode scan is
        sequential, and the carried rng resumes the sampling stream, so
        this holds for greedy AND sampled decode — states without a
        carried rng, e.g. serve()-extracted ones, restart the stream from
        ``seed`` and are bitwise for greedy only)."""
        if self.paged:
            cache, max_len = self._paged_grown(state, state.offset + max_new)
        else:
            cache, max_len = self._grown_cache(state, state.offset + max_new)
        self.counters["decode_only"] += 1
        if state.rng is not None:
            rng = state.rng
        B = state.batch
        if self.paged:
            self.counters["ssd_state_inplace"] += self._ssd_inplace
            toks, lgs, h_per, v_per, carry = _decode_scan_paged(
                self.params, self.cfg, state.cur, state.last, cache,
                state.pos, rng, self.ucfg, int(max_new), bool(greedy),
                impl=self.attn_decode_impl,
                mesh=self.mesh, rules=self.rules)
        elif self.mesh is not None:
            toks, h_per, v_per, carry = self._decode_sharded(
                B, max_len, int(max_new), bool(greedy))(
                    self.params, state.cur, state.last, cache, state.pos,
                    rng)
            lgs = None
        else:
            toks, lgs, h_per, v_per, carry = _decode_scan(
                self.params, self.cfg, state.cur, state.last, cache,
                state.pos, rng, self.ucfg, int(max_new), bool(greedy))
        h, v = np.asarray(h_per).mean(-1), np.asarray(v_per).mean(-1)
        res = {"tokens": np.asarray(toks),
               "u": np.asarray(U.combine_terms(h, v, self.ucfg)),
               "logits": lgs, "h_mean": h, "v_mean": v,
               "prompt_lengths": np.zeros((B,), np.int64)}
        offset = state.offset + max_new
        if self.paged:
            cur, last, cache, pos, crng = carry
            self.pool.commit(cache["layers"])
            self.pool.trim(state.cache, -(-offset // self.block_len))
            if return_state:
                res["state"] = SessionState(state.cache, pos, cur, last,
                                            max_len, offset, rng=crng)
        elif return_state:
            cur, last, cache, pos, crng = carry
            res["state"] = SessionState(cache, pos, cur, last, max_len,
                                        offset, rng=crng)
        return res

    def state_select(self, state: SessionState, idx) -> SessionState:
        """Slice (or fan out — repeated indices are fine) a batched session
        handle to rows ``idx``.  Used by the gateway to hand the swarm
        round the probe's state for just the SWARM-routed queries.

        Monolithic: materialises the selected cache rows (O(rows * len)).
        Paged: a refcounted block-table copy + a state-row device copy —
        the probe -> swarm handoff becomes O(table), and shared blocks are
        protected by COW on the next write."""
        idx_np = np.asarray(idx, np.int32)
        idx = jnp.asarray(idx_np)
        if self.paged:
            self._state_kind_check(state)
            handle = self.pool.select(state.cache, idx_np)
            cache = handle
        else:
            axes = self._slot_batch_axes(state.max_len)
            cache = jax.tree.map(lambda s, ax: jnp.take(s, idx, axis=ax),
                                 state.cache, axes)
            if self.mesh is not None:
                cache = jax.device_put(cache, self._cache_sh(cache))
        return SessionState(cache, jnp.take(state.pos, idx),
                            jnp.take(state.cur, idx),
                            jnp.take(state.last, idx, axis=0),
                            state.max_len, state.offset,
                            rng=state.rng, exact=state.exact)

    # ------------------------------------------------------------------
    def generate_stepwise(self, prompts: np.ndarray, max_new: int, *,
                          greedy: bool = True, seed: int = 0) -> dict:
        """Legacy one-token-at-a-time absorption path (S + max_new jitted
        dispatches).  Kept as the parity oracle for ``generate`` and as the
        baseline for the prefill_vs_stepwise benchmark.

        The cache length is derived from the same ``_bucket`` shape
        ``generate`` uses (only the real S columns are absorbed — inert
        bucket columns would need decode-path negative-position support),
        so ``_step`` specialises per (B, bucket) instead of re-jitting for
        every exact (B, S) the parity tests and benchmarks throw at it."""
        prompts = np.asarray(prompts, np.int32)
        B, S = prompts.shape
        pb, _ = self._bucket(prompts)
        cache = T.init_cache(self.cfg, B, self._cache_len(pb.shape[1],
                                                          max_new))
        if self.mesh is not None:
            cache = jax.device_put(cache, self._cache_sh(cache))
        else:
            cache = jax.tree.map(jnp.asarray, cache)
        rng = jax.random.PRNGKey(seed)

        lengths = (prompts != PAD).sum(axis=1)
        nxt = None
        for t in range(S):
            tok = jnp.asarray(prompts[:, t:t + 1])
            # absorption always runs greedy, so _step's sampling branch is
            # never traced and needs no key; threading the live `rng`
            # through S calls would alias the decode stream's key
            nxt, last_logits, cache = _step(
                self.params, self.cfg, tok, cache,
                jnp.full((B,), t, jnp.int32), None, True,
                mesh=self.mesh, rules=self.rules)

        out_tokens = []
        out_logits = []
        cur = nxt
        for n in range(max_new):
            out_tokens.append(cur)
            out_logits.append(last_logits)
            rng, sub = jax.random.split(rng)
            cur, last_logits, cache = _step(
                self.params, self.cfg, cur[:, None], cache,
                jnp.full((B,), S + n, jnp.int32), sub, greedy,
                mesh=self.mesh, rules=self.rules)

        tokens = jnp.stack(out_tokens, axis=1)              # (B, N)
        logits = jnp.stack(out_logits, axis=1)              # (B, N, V)
        u = U.difficulty(logits, tokens, self.ucfg)         # (B,)
        return {"tokens": np.asarray(tokens),
                "u": np.asarray(u),
                "logits": logits,
                "prompt_lengths": np.asarray(lengths)}

    # ------------------------------------------------------------------
    # Streaming serve: continuous batching over fixed decode slots
    # ------------------------------------------------------------------

    def _slot_batch_axes(self, max_len: int):
        """Per-leaf batch axis of the cache pytree (stacked scan stages
        carry their repeat dim in front of batch)."""
        a1 = jax.eval_shape(lambda: T.init_cache(self.cfg, 1, max_len))
        a2 = jax.eval_shape(lambda: T.init_cache(self.cfg, 2, max_len))
        return jax.tree.map(
            lambda x, y: next(i for i, (p, q) in enumerate(zip(x.shape, y.shape))
                              if p != q), a1, a2)

    def _slot_insert(self):
        """Jitted cache splice, built once per engine (jit re-specialises on
        shapes by itself, so one closure covers every max_len/n_slots)."""
        fn = getattr(self, "_slot_insert_fn", None)
        if fn is None:
            axes = self._slot_batch_axes(self.max_len)
            cfg, mesh, rules = self.cfg, self.mesh, self.rules

            @jax.jit
            def fn(slots, one, i):
                out = jax.tree.map(
                    lambda s, o, ax: jax.lax.dynamic_update_index_in_dim(
                        s, jax.lax.index_in_dim(o, 0, ax, keepdims=False),
                        i, ax),
                    slots, one, axes)
                # keep the slot cache pinned to its logical-axis sharding so
                # the splice doesn't force a re-layout before the next chunk
                return T.constrain_cache(out, cfg, mesh, rules)
            self._slot_insert_fn = fn
        return fn

    def _slot_extract(self):
        """Jitted inverse of ``_slot_insert``: slice slot ``i`` out of the
        slot cache as a batch-1 cache (a retiring request's session state)."""
        fn = getattr(self, "_slot_extract_fn", None)
        if fn is None:
            axes = self._slot_batch_axes(self.max_len)

            @jax.jit
            def fn(slots, i):
                return jax.tree.map(
                    lambda s, ax: jax.lax.dynamic_slice_in_dim(s, i, 1, ax),
                    slots, axes)
            self._slot_extract_fn = fn
        return fn

    def serve(self, requests: Sequence[Request] | None = None, *,
              batcher: ContinuousBatcher | None = None, n_slots: int = 4,
              decode_chunk: int = 8, stop_token: int | None = None,
              greedy: bool = True, seed: int = 0,
              session_ttl_s: float | None = None,
              faults: FaultPlan | None = None,
              overload: str = "raise",
              step_time_ms: float | None = None) -> list[dict]:
        """Streaming entry point: requests flow through a ContinuousBatcher.

        Loop: admit queued requests into free slots (each admission is one
        jitted prefill that is spliced into the slot cache) -> one scanned
        decode chunk over ALL slots -> record tokens / retire finished slots
        (stop token or max_new) -> repeat until idle.  Requests are admitted
        mid-flight as slots free up, ordered earliest-deadline-first then by
        priority (``Request.deadline_ms`` / ``Request.priority``; FIFO among
        equals).

        Returns one dict per finished request: {"rid", "tokens", "u"},
        in completion order.  With ``greedy=True`` (default) tokens are
        bitwise-identical to ``generate`` on the same prompt.  MoE configs
        stream like any other: admission prefills route per position and
        the decode chunk routes exactly per token, so neither other
        requests in flight nor garbage in empty slots can perturb a
        request's expert routing.

        Session caches (docs/RUNTIME.md): a request with ``state`` set is
        admitted by ONE continuation prefill of its (new-span) prompt over
        the warm cache — the conversation so far is never re-absorbed.  A
        request with ``return_state=True`` gets ``"state"`` in its result
        dict, sliced out of the slot cache at retirement; the decode chunk
        is clamped to such a request's remaining budget so its slot state
        is captured exactly at its last step (a stop-token retirement
        mid-chunk still yields an exact KV cache — stale higher-position
        entries are masked and later overwritten — but the *recurrent*
        state of RG-LRU/SSD mixers would have absorbed the chunk's
        post-stop garbage steps; chunk-aligned retirement avoids that).

        Paged engines (docs/RUNTIME.md "Paged caches & prefix sharing"):
        slots reference the block pool through per-slot tables — admission
        asks the pool for blocks (requests that don't fit wait in the
        queue until retirements free blocks), a cold admission prefills
        straight into its blocks, a warm admission is a refcounted table
        copy off the session handle (shared prefix blocks are NOT copied;
        N requests carrying the SAME absorbed handle fan its prefix out
        with zero extra prefills), retirement returns blocks to the pool,
        and ``return_state`` hand-back is a table adoption trimmed to the
        covered length — no cache extraction copy.  ``session_ttl_s``
        evicts registered sessions idle past the TTL whenever the pool
        runs out of blocks (their handles raise on reuse).

        Failure semantics (docs/RUNTIME.md "Failure semantics"):

        * pool famine is *backpressure*, not a crash — admissions defer
          while anything is decoding; a hard wedge (nothing decoding,
          nothing admissible even after the TTL sweep) raises
          ``PoolExhaustedError`` with ``overload="raise"`` (default) or,
          with ``overload="shed"``, retires the least-urgent queued
          request marked ``shed=True`` and keeps going (the gateway's
          cloud path is the recourse for shed work);
        * a warm request whose handle was evicted is transparently
          re-admitted COLD (``Request.cold_prompt`` when provided, else
          its ``prompt``), counted in ``counters["reprefill_cold"]``;
          a pure decode-resume with no recoverable prompt retires shed;
        * ``faults`` injects execution failures (serving/faults.py):
          "pool"/famine defers one admission round, "session"/evict
          force-releases the next warm admission's handle, "slot"/fail
          kills the lowest active slot after the current chunk — its
          request is requeued and re-admitted off its still-valid warm
          handle (or cold);
        * ``step_time_ms`` arms the deadline clock: each decode step
          advances a simulated clock by that many ms (plus any injected
          "decode"/straggle delay) and requests whose ``deadline_ms``
          has passed retire ``shed=True`` — queued ones before taking a
          slot, active ones mid-decode with what they have.  ``None``
          (default) keeps deadlines as pure admission ordering.

        Every loop turn is recorded in ``self.spans`` (and, when a profiler
        runs, in its trace): ``serve.turn`` (counts: turn, admitted,
        active, steps, blocks_in_use) around ``serve.admit`` (queued), one
        ``serve.prefill`` per admission (rid, prompt, bucket, warm),
        ``serve.decode`` (steps), ``serve.sync`` (the read-back of the
        chunk's tokens and uncertainty terms), ``serve.record`` (tokens)
        and ``serve.retire`` (retired rids).
        """
        if (requests is None) == (batcher is None):
            raise ValueError("pass exactly one of requests / batcher")
        if batcher is None:
            batcher = ContinuousBatcher(n_slots)
            for r in requests:
                batcher.submit(r)
        if any(s is not None for s in batcher.slots):
            # a slot occupied before this call has no prefilled cache here —
            # decoding it would silently emit garbage
            raise ValueError("serve() requires an un-admitted batcher: "
                             "submit requests to the queue only")
        n_slots = batcher.n_slots

        pending = list(batcher.queue)
        if not pending:
            return []
        gran = max(self.cfg.attn_q_block, self.cfg.attn_kv_block)

        def _need(r: Request) -> int:
            # warm requests need room for the session so far + the new span
            off = r.state.offset if r.state is not None else 0
            sb = bucket_len(len(r.prompt), gran) if r.prompt else 0
            n = self._cache_len(off + sb, r.max_new)
            if r.cold_prompt:
                # the slot must also fit the cold-re-prefill fallback
                # (full conversation) should the warm handle be lost
                n = max(n, self._cache_len(
                    bucket_len(len(r.cold_prompt), gran), r.max_new))
            return max(n, r.state.max_len) if r.state is not None else n

        max_len = max(_need(r) for r in pending)

        paged = self.paged
        V = self.cfg.vocab_size
        cur = jnp.zeros((n_slots,), jnp.int32)
        last = jnp.zeros((n_slots, V), jnp.float32)
        pos = jnp.zeros((n_slots,), jnp.int32)
        if paged:
            nb = max_len // self.block_len
            # sentinel table/row ids: empty slots decode harmlessly — their
            # pool writes are dropped (out-of-range scatter) and their
            # reads clip, so they own no storage and can corrupt none
            slot_tables = np.full((n_slots, nb), self.pool.n_blocks,
                                  np.int32)
            slot_rows = np.full((n_slots,), self.pool.n_rows, np.int32)
            slot_run: list = [None] * n_slots      # owned (blocks, row)
            cache = None
        else:
            cache = T.init_cache(self.cfg, n_slots, max_len)
            cache = (jax.device_put(cache, self._cache_sh(cache))
                     if self.mesh is not None
                     else jax.tree.map(jnp.asarray, cache))
        if self.mesh is not None:
            # place the slot state by the activation rules up front: batch
            # on 'data', logits vocab on 'model', cache per cache_axes
            cur = jax.device_put(cur, self._act_sh(cur.shape, ("act_batch",)))
            last = jax.device_put(last, self._act_sh(
                last.shape, ("act_batch", "act_vocab")))
            pos = jax.device_put(pos, self._act_sh(pos.shape, ("act_batch",)))
        rng = jax.random.PRNGKey(seed)
        insert = self._slot_insert() if not paged else None

        acc: dict[int, list] = {}       # rid -> [sum_h, sum_v, n]
        states: dict[int, SessionState] = {}    # rid -> extracted state
        pos0: dict[int, int] = {}       # slot -> position at admission
        results: list[dict] = []
        extract = self._slot_extract() if not paged else None

        def drain():
            for req in batcher.drain_finished():
                h, v, n = acc.pop(req.rid, (0.0, 0.0, 0))
                d = max(n, 1)
                out = {"rid": req.rid,
                       "tokens": np.asarray(req.generated, np.int32),
                       "u": float(U.combine_terms(h / d, v / d, self.ucfg))}
                if req.shed:
                    out["shed"] = True
                if req.rid in states:
                    out["state"] = states.pop(req.rid)
                results.append(out)

        promised = [0]          # slots admitted this round, not yet funded

        def fits(r: Request) -> bool:
            # admission asks the pool: a cold request needs a full run +
            # a state row, a warm one at most a COW tail + the unshared
            # remainder (bounded by the same) — be conservative.  admit()
            # may fill several slots before the engine allocates, so count
            # the slots already promised this round; the batcher admits a
            # request exactly when its fits() returned True, so the
            # increment below tracks admissions one-for-one.
            ok = self.pool.can_alloc((promised[0] + 1) * nb,
                                     promised[0] + 1)
            if ok:
                promised[0] += 1
            return ok

        now_ms = 0.0

        for turn in self.spans.turns(lambda: not batcher.idle):
            turn.phase("serve.admit", queued=len(batcher.queue))
            if (faults is not None and batcher.queue
                    and faults.consume("pool") is not None):
                # injected famine: this admission round sees zero free
                # blocks.  Backpressure, not a crash — queued requests
                # simply wait the round out while anything active keeps
                # decoding; with nothing active we skip the (empty-slot)
                # dispatch entirely.
                self.counters["famine_deferred"] += len(batcher.queue)
                admitted = []
                if not batcher.active():
                    continue
            else:
                promised[0] = 0
                admitted = batcher.admit(fits=fits if paged else None)
                if paged and not admitted and not batcher.active() \
                        and batcher.queue:
                    # pool famine with nothing decoding: TTL-evict idle
                    # sessions to recover blocks — except the handles queued
                    # warm requests still reference — then retry once
                    if session_ttl_s is not None:
                        keep = {r.state.cache.sid for r in batcher.queue
                                if r.state is not None
                                and isinstance(r.state.cache, PagedHandle)}
                        self.pool.evict_idle(session_ttl_s, exclude=keep)
                    promised[0] = 0
                    admitted = batcher.admit(fits=fits)
                    if not admitted:
                        if overload == "shed" \
                                and batcher.shed_one() is not None:
                            # hard wedge: retire the least-urgent queued
                            # request with shed=True and keep serving —
                            # the caller reroutes shed work (cloud path)
                            self.counters["shed"] += 1
                            continue
                        raise PoolExhaustedError(
                            f"cache pool exhausted: "
                            f"{self.pool.blocks_in_use}/"
                            f"{self.pool.n_blocks} blocks "
                            f"({self.pool._famine_detail()}) held by "
                            f"{self.pool.live_sessions} sessions and no "
                            "slot can admit — grow pool_blocks, release "
                            "sessions, or pass session_ttl_s")
            turn.counts["admitted"] = len(admitted)
            for i in admitted:
                req = batcher.slots[i]
                pc = turn.phase("serve.prefill", rid=req.rid, warm=False,
                                prompt=0, bucket=0)
                st = req.state
                if (st is not None and faults is not None
                        and isinstance(st.cache, PagedHandle)
                        and faults.consume("session") is not None):
                    # injected forced eviction: the handle is genuinely
                    # released so the recovery below is the real path
                    self.release(st)
                if st is not None:
                    try:
                        self._check_state(st, extension=not req.prompt)
                    except EvictedSessionError:
                        # the session handle is gone (TTL sweep, forced
                        # eviction): transparently re-admit COLD from the
                        # full-conversation prompt instead of failing
                        req.state = st = None
                        if req.cold_prompt is not None:
                            req.prompt = list(req.cold_prompt)
                        if not req.prompt:
                            # decode-resume with nothing to re-prefill
                            req.done = True
                            req.shed = True
                            batcher.finished.append(req)
                            batcher.slots[i] = None
                            self.counters["shed"] += 1
                            continue
                        self.counters["reprefill_cold"] += 1
                pc["warm"] = st is not None
                if paged:
                    if st is not None:
                        # warm admission: the slot's table row shares the
                        # session's prefix blocks by reference (COW tail) —
                        # the handle itself is untouched, so many requests
                        # can fan out of one absorbed prefix
                        run, row = self.pool.admit_row(
                            st.cache, nb, int(np.asarray(st.pos)[0]))
                    else:
                        blocks, row = self.pool.alloc_run(nb)
                        run = blocks
                    slot_tables[i, :] = run
                    slot_rows[i] = row
                    slot_run[i] = (run, row)
                    c1g = self._paged_dev_cache(slot_tables[i:i + 1],
                                                slot_rows[i:i + 1])
                elif st is not None:
                    c1g, _ = self._grown_cache(st, max_len)
                if st is not None:
                    # warm admission: continuation-prefill only the new
                    # span — the conversation so far is NOT re-absorbed
                    if req.prompt:
                        p = np.asarray(req.prompt, np.int32)[None]
                        pb, s_orig = self._bucket_right(p)
                        pc["prompt"], pc["bucket"] = s_orig, pb.shape[1]
                        self.counters["prefill_continue"] += 1
                        fn = (_prefill_continue_paged if paged
                              else _prefill_continue)
                        c1, l1, k1 = fn(
                            self.params, self.cfg, jnp.asarray(pb),
                            jnp.int32(s_orig), st.pos, c1g,
                            mesh=self.mesh, rules=self.rules)
                        p0 = st.offset + s_orig
                    else:                      # pure decode resume
                        self.counters["decode_only"] += 1
                        c1, l1, k1 = st.cur, st.last, c1g
                        p0 = st.offset
                else:
                    p = np.asarray(req.prompt, np.int32)[None]
                    pb, s_orig = self._bucket(p)
                    pc["prompt"], pc["bucket"] = s_orig, pb.shape[1]
                    self.counters["prefill"] += 1
                    if paged:
                        c1, l1, k1 = _prefill_into_paged(
                            self.params, self.cfg, jnp.asarray(pb),
                            jnp.int32(s_orig),
                            self._paged_dev_cache(slot_tables[i:i + 1],
                                                  slot_rows[i:i + 1]),
                            mesh=self.mesh, rules=self.rules)
                    else:
                        c1, l1, k1 = _prefill_absorb(
                            self.params, self.cfg, jnp.asarray(pb),
                            jnp.int32(s_orig), max_len,
                            mesh=self.mesh, rules=self.rules)
                    p0 = s_orig
                if paged:
                    # admission prefilled straight into the slot's pool
                    # blocks — commit the pool, nothing to splice
                    if T.is_paged(k1):
                        self.pool.commit(k1["layers"])
                else:
                    cache = insert(cache, k1, i)
                cur = cur.at[i].set(c1[0])
                last = last.at[i].set(l1[0])
                pos = pos.at[i].set(p0)
                pos0[i] = p0

            # clamp the chunk so a return_state request's last step lands on
            # a chunk boundary — its slot state is then captured exactly.
            # Each distinct clamped size jits its own decode scan, but only
            # once per engine and only for sizes < decode_chunk that
            # return_state requests actually hit near retirement (bounded
            # by decode_chunk, not by the request mix).
            dc = turn.phase("serve.decode")
            chunk = min([int(decode_chunk)] +
                        [r.max_new - len(r.generated)
                         for _, r in batcher.active() if r.return_state])
            dc["steps"] = turn.counts["steps"] = chunk
            if paged:
                self.counters["ssd_state_inplace"] += self._ssd_inplace
                cache = self._paged_dev_cache(slot_tables, slot_rows)
                toks, _, h_per, v_per, carry = _decode_scan_paged(
                    self.params, self.cfg, cur, last, cache, pos, rng,
                    self.ucfg, chunk, bool(greedy), with_logits=False,
                    impl=self.attn_decode_impl,
                    mesh=self.mesh, rules=self.rules)
            elif self.mesh is not None:
                toks, h_per, v_per, carry = self._decode_sharded(
                    n_slots, max_len, chunk, bool(greedy))(
                        self.params, cur, last, cache, pos, rng)
            else:
                toks, _, h_per, v_per, carry = _decode_scan(
                    self.params, self.cfg, cur, last, cache, pos, rng,
                    self.ucfg, chunk, bool(greedy), with_logits=False)
            cur, last, cache, pos, rng = carry
            if paged:
                self.pool.commit(cache["layers"])
            turn.phase("serve.sync")
            toks_np = np.asarray(toks)
            h_np, v_np = np.asarray(h_per), np.asarray(v_per)

            rc = turn.phase("serve.record", tokens=0)
            slot_of = {r.rid: i for i, r in batcher.active()}
            turn.counts["active"] = len(slot_of)
            if paged:
                turn.counts["blocks_in_use"] = self.pool.blocks_in_use
            retired_at: dict[int, int] = {}
            for t in range(chunk):
                active = batcher.active()
                if not active:
                    break
                for i, req in active:
                    a = acc.setdefault(req.rid, [0.0, 0.0, 0])
                    a[0] += float(h_np[i, t])
                    a[1] += float(v_np[i, t])
                    a[2] += 1
                batcher.record_tokens(toks_np[:, t], stop_token)
                rc["tokens"] += len(active)
                for i, req in active:
                    if req.done:
                        retired_at.setdefault(req.rid, t)
            rt = turn.phase("serve.retire")
            for req in batcher.finished:        # retired this chunk
                i = slot_of.get(req.rid)
                if i is None:
                    continue
                want_state = req.return_state and req.rid not in states
                # a request whose last step is the chunk's last step (the
                # clamp guarantees this for max_new retirement) is captured
                # exactly; a stop-token retirement mid-chunk left the slot
                # decoding garbage -> the handle is marked inexact and only
                # supports continuation prefill on attention-only models
                end = pos0[i] + len(req.generated)
                exact = retired_at.get(req.rid) == chunk - 1
                if paged and slot_run[i] is not None:
                    blocks, row = slot_run[i]
                    if want_state:
                        # hand-back = table adoption, trimmed to the
                        # covered blocks — no cache extraction copy
                        handle = self.pool.adopt(
                            blocks, row, -(-end // self.block_len))
                        states[req.rid] = SessionState(
                            handle, jnp.full((1,), end, jnp.int32),
                            cur[i:i + 1], last[i:i + 1], max_len, end,
                            exact=exact)
                    else:
                        self.pool.free_blocks(blocks)
                        self.pool.free_rows(np.array([row]))
                    # repoint the slot at the sentinels: its garbage decode
                    # keeps running but writes are dropped from here on
                    slot_tables[i, :] = self.pool.n_blocks
                    slot_rows[i] = self.pool.n_rows
                    slot_run[i] = None
                elif want_state:
                    states[req.rid] = SessionState(
                        extract(cache, i), jnp.full((1,), end, jnp.int32),
                        cur[i:i + 1], last[i:i + 1], max_len, end,
                        exact=exact)

            def _free_slot(i: int):
                # drop a live slot's pool resources and repoint it at the
                # sentinels (its garbage decode keeps running, writes drop)
                if paged and slot_run[i] is not None:
                    blocks, row = slot_run[i]
                    self.pool.free_blocks(blocks)
                    self.pool.free_rows(np.array([row]))
                    slot_tables[i, :] = self.pool.n_blocks
                    slot_rows[i] = self.pool.n_rows
                    slot_run[i] = None
                pos0.pop(i, None)

            if step_time_ms is not None:
                # simulated wall clock for deadline expiry: decode steps
                # cost step_time_ms each, plus any injected straggle
                now_ms += chunk * float(step_time_ms)
                if faults is not None:
                    ev = faults.consume("decode")
                    if ev is not None:
                        now_ms += 1000.0 * float(ev.delay_s)
                for i, req in batcher.expire(now_ms):
                    self.counters["expired"] += 1
                    if i is not None:
                        _free_slot(i)

            if faults is not None and faults.consume("slot") is not None:
                # injected slot failure: the lowest active slot dies after
                # this chunk.  Its decode progress is lost; the request
                # goes back in the queue and re-admits off its warm handle
                # when that is still valid (continuation prefill), else
                # cold (the admission path handles the evicted case).
                act = batcher.active()
                if act:
                    i, req = act[0]
                    _free_slot(i)
                    acc.pop(req.rid, None)
                    batcher.requeue(i)
                    self.counters["requeued"] += 1
            rt["retired"] = tuple(r.rid for r in batcher.finished)
            drain()
        drain()
        return results

    # ------------------------------------------------------------------
    def token_count(self, prompts: np.ndarray, max_new: int) -> np.ndarray:
        return (np.asarray(prompts) != PAD).sum(axis=1) + max_new
