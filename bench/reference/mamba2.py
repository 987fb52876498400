"""Plain float32 reference of a Mamba-2 language model, in recurrent form.

Written from the published equations (Dao and Gu 2024, "Transformers are
SSMs", section 7; the ``Mamba2`` block of ``state-spaces/mamba``): per
layer, pre-norm RMSNorm, one input projection split into ``z``, ``xBC`` and
``dt``; a depthwise causal convolution over ``xBC`` with SiLU; per head the
selective state-space recurrence

    h_t = exp(dt_t * A) * h_{t-1} + dt_t * x_t B_t^T,    y_t = h_t C_t + D x_t

with ``dt_t = softplus(dt_t + dt_bias)`` and ``A = -exp(A_log)``; a gated
RMSNorm ``norm(y * silu(z))`` over groups of ``d_inner / ngroups``; an output
projection and the residual.  A final RMSNorm and a head tied to the
embedding give the logits.  The recurrence runs token by token
(``lax.scan``), never in the chunked form the serving program uses; one
layer at a time; every matmul at ``highest`` precision.  It imports nothing
of the program under test.

Weights are read from the serving program's parameter tree: ``embed (V,
D)``, ``final_norm (D,)`` and, stacked over layers, under
``stages[0]["b0"]["mixer"]``: ``norm (D,), in_proj (D, 2*d_inner + 2*G*N +
H), conv_w (W, C), conv_b (C,), A_log (H,), dt_bias (H,), D_skip (H,),
gnorm (d_inner,), out_proj (d_inner, D)`` with ``C = d_inner + 2*G*N``.
RMSNorm weights are stored zero-centred: the published weight is ``1 +
stored``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rmsnorm(x, stored, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + stored.astype(F32))


def dims(conf: dict) -> dict:
    ssm = conf["ssm_cfg"]
    d_inner = ssm["expand"] * conf["d_model"]
    return {"d_inner": d_inner, "N": ssm["d_state"], "P": ssm["headdim"],
            "G": ssm["ngroups"], "W": ssm["d_conv"],
            "H": d_inner // ssm["headdim"]}


def matmul(x, w):
    """x (..., in) @ w (in, out), in float32."""
    return x @ w.astype(F32)


@partial(jax.jit, static_argnames=("d", "eps", "mm"))
def _layer(x, mp, d: tuple, eps: float, mm):
    """One Mamba-2 layer over right-padded sequences. x (B, S, D) f32;
    ``mm`` does every matmul with a weight."""
    d_inner, N, P, G, W, H = d
    with jax.default_matmul_precision("highest"):
        B, S, _ = x.shape
        h = _rmsnorm(x, mp["norm"], eps)
        zxbcdt = mm(h, mp["in_proj"])
        z = zxbcdt[..., :d_inner]
        xbc = zxbcdt[..., d_inner:2 * d_inner + 2 * G * N]
        dt = zxbcdt[..., 2 * d_inner + 2 * G * N:]
        # depthwise causal conv: out[t] = sum_j w[j] * in[t - (W-1) + j]
        w = mp["conv_w"].astype(F32)
        pad = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))
        conv = sum(w[j] * pad[:, j:j + S] for j in range(W))
        xbc = jax.nn.silu(conv + mp["conv_b"].astype(F32))
        xs = xbc[..., :d_inner].reshape(B, S, H, P)
        Bm = xbc[..., d_inner:d_inner + G * N].reshape(B, S, G, N)
        Cm = xbc[..., d_inner + G * N:].reshape(B, S, G, N)
        grp = jnp.arange(H) // (H // G)             # head -> its group
        Bh, Ch = Bm[:, :, grp], Cm[:, :, grp]          # (B, S, H, N)
        dt = jax.nn.softplus(dt + mp["dt_bias"].astype(F32))      # (B,S,H)
        A = -jnp.exp(mp["A_log"].astype(F32))

        def step(state, inp):
            x_t, dt_t, b_t, c_t = inp
            state = (jnp.exp(dt_t * A)[..., None, None] * state
                     + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None])
            return state, jnp.einsum("bhpn,bhn->bhp", state, c_t)

        s0 = jnp.zeros((B, H, P, N), F32)
        _, y = jax.lax.scan(step, s0, (xs.swapaxes(0, 1), dt.swapaxes(0, 1),
                                       Bh.swapaxes(0, 1), Ch.swapaxes(0, 1)))
        y = y.swapaxes(0, 1) + xs * mp["D_skip"].astype(F32)[:, None]
        y = (y.reshape(B, S, d_inner) * jax.nn.silu(z)).reshape(
            B, S, G, d_inner // G)
        y = _rmsnorm(y, mp["gnorm"].reshape(G, d_inner // G), eps)
        x = x + mm(y.reshape(B, S, d_inner), mp["out_proj"])
    return x


def hidden_batch(params, conf: dict, tokens, mm=matmul) -> jax.Array:
    """Final normed hidden states (B, S, D) f32 of right-padded token rows
    (padding only follows a row's real tokens, so it never reaches them).
    ``mm`` does every matmul with a weight: float32 by default."""
    eps = float(conf["norm_epsilon"])
    d = dims(conf)
    key = (d["d_inner"], d["N"], d["P"], d["G"], d["W"], d["H"])
    stage = params["stages"][0]["b0"]["mixer"]
    x = jnp.take(params["embed"], jnp.asarray(tokens), axis=0).astype(F32)
    for layer in range(int(conf["n_layer"])):
        mp = jax.tree.map(lambda a, i=layer: a[i], stage)
        x = _layer(x, mp, key, eps, mm)
    return _rmsnorm(x, params["final_norm"], eps)


def head(params, conf: dict):
    """(D, V) output head: the embedding, tied."""
    if not conf.get("tie_embeddings", False):
        raise ValueError("untied heads are not in this reference")
    return params["embed"].T
