"""Plain float32 reference of a Llama-architecture decoder.

Written from the published equations (Touvron et al. 2023; the Hugging
Face ``LlamaForCausalLM``): pre-norm RMSNorm blocks, rotary position
embedding on the rotate-half layout, grouped-query attention with a causal
softmax, a SwiGLU MLP, a final RMSNorm and a head tied to the embedding.
No cache, no kernels, no batching across requests, no bucketing: one
sequence at a time, one layer at a time, every matmul at ``highest``
precision.  It imports nothing of the program under test.

Weights are read from the serving program's parameter tree, whose layout
is: ``embed (V, D)``, ``final_norm (D,)`` and, stacked over layers, under
``stages[0]["b0"]``: ``mixer.{norm, wq (D,H,Dh), wk (D,K,Dh), wv (D,K,Dh),
wo (H,Dh,D)}`` and ``ffn.{norm, w_gate (D,F), w_up (D,F), w_down (F,D)}``.
RMSNorm weights are stored zero-centred: the published weight is
``1 + stored``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rmsnorm(x, stored, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + stored.astype(F32))


def _rope(x, theta):
    """x (S, heads, Dh) at positions 0..S-1, rotate-half layout."""
    S, _, Dh = x.shape
    inv = theta ** (-jnp.arange(0, Dh, 2, dtype=F32) / Dh)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None]          # (S, Dh/2)
    cos = jnp.cos(jnp.concatenate([ang, ang], -1))[:, None]
    sin = jnp.sin(jnp.concatenate([ang, ang], -1))[:, None]
    x1, x2 = x[..., :Dh // 2], x[..., Dh // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def matmul(x, w):
    """x (..., in) @ w (in, out), in float32."""
    return x @ w.astype(F32)


@partial(jax.jit, static_argnames=("eps", "theta", "mm"))
def _layer(x, lp, eps: float, theta: float, mm):
    """One decoder layer over the whole sequence. x (S, D) f32; ``mm`` does
    every matmul with a weight."""
    with jax.default_matmul_precision("highest"):
        at, mlp = lp["mixer"], lp["ffn"]
        S, D = x.shape
        H, Dh = at["wq"].shape[1], at["wq"].shape[2]
        K = at["wk"].shape[1]
        h = _rmsnorm(x, at["norm"], eps)
        q = _rope(mm(h, at["wq"].reshape(D, H * Dh)).reshape(S, H, Dh), theta)
        k = _rope(mm(h, at["wk"].reshape(D, K * Dh)).reshape(S, K, Dh), theta)
        v = mm(h, at["wv"].reshape(D, K * Dh)).reshape(S, K, Dh)
        k = jnp.repeat(k, H // K, axis=1)          # head h reads kv h // G
        v = jnp.repeat(v, H // K, axis=1)
        s = jnp.einsum("qhk,thk->hqt", q, k) / jnp.sqrt(F32(Dh))
        causal = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(causal[None], s, -jnp.inf)
        o = jnp.einsum("hqt,thk->qhk", jax.nn.softmax(s, axis=-1), v)
        x = x + mm(o.reshape(S, H * Dh), at["wo"].reshape(H * Dh, D))
        h = _rmsnorm(x, mlp["norm"], eps)
        g = jax.nn.silu(mm(h, mlp["w_gate"]))
        x = x + mm(g * mm(h, mlp["w_up"]), mlp["w_down"])
    return x


def hidden_batch(params, conf: dict, tokens, mm=matmul) -> jax.Array:
    """Final normed hidden states (B, S, D) f32 of right-padded token rows,
    one row at a time (padding only follows a row's real tokens, and the
    causal mask keeps it from them).  ``mm`` does every matmul with a
    weight: float32 by default."""
    eps, theta = float(conf["rms_norm_eps"]), float(conf["rope_theta"])
    stage = params["stages"][0]["b0"]
    layers = [jax.tree.map(lambda a, i=i: a[i], stage)
              for i in range(int(conf["num_hidden_layers"]))]
    rows = []
    for row in jnp.asarray(tokens):
        x = jnp.take(params["embed"], row, axis=0).astype(F32)
        for lp in layers:
            x = _layer(x, lp, eps, theta, mm)
        rows.append(_rmsnorm(x, params["final_norm"], eps))
    return jnp.stack(rows)


def head(params, conf: dict):
    """(D, V) output head: the embedding, tied."""
    if not conf.get("tie_word_embeddings", False):
        raise ValueError("untied heads are not in this reference")
    return params["embed"].T
