"""Mamba-2 configurations on the serving program.

Maps a published ``state-spaces/mamba2`` config onto the program's
``ModelConfig``, draws the weights from a seed in the program's parameter
layout, and counts the model FLOPs of a decode step and of a prefill.
"""
from __future__ import annotations

from bench import counts
from bench.reference import mamba2 as reference
from bench.weights import draw


def padded_vocab(conf: dict) -> int:
    m = int(conf.get("pad_vocab_size_multiple", 1))
    return -(-int(conf["vocab_size"]) // m) * m


def model_config(conf: dict):
    from repro.models.common import ModelConfig
    s = conf["ssm_cfg"]
    return ModelConfig(
        name=conf["name"], family="ssm", num_layers=int(conf["n_layer"]),
        d_model=int(conf["d_model"]), num_heads=0, num_kv_heads=0,
        head_dim=0, d_ff=0, vocab_size=padded_vocab(conf),
        mixer_pattern=("ssd",), tie_embeddings=bool(conf["tie_embeddings"]),
        ssm_state=int(s["d_state"]), ssm_expand=int(s["expand"]),
        ssm_head_dim=int(s["headdim"]), ssm_ngroups=int(s["ngroups"]),
        ssm_conv_width=int(s["d_conv"]), ssm_chunk=int(s["chunk_size"]),
        norm_eps=float(conf["norm_epsilon"]))


def token_range(conf: dict) -> int:
    """Real token ids; the padding rows of the embedding are never drawn."""
    return int(conf["vocab_size"])


def _init(conf: dict):
    """``state-spaces/mamba`` initialisation: PyTorch's default
    uniform(+-1/sqrt(fan_in)) for the projections and the depthwise conv,
    the output projection rescaled by 1/sqrt(n_layer), A in [1, 16], dt
    log-uniform in [0.001, 0.1] floored at 1e-4, D = 1, embedding
    N(0, 0.02).  RMSNorm weights 1, drawn as 1 + N(0, 0.1) so that a norm
    the program skipped or misapplied shows."""
    d = reference.dims(conf)
    D, L = conf["d_model"], conf["n_layer"]
    return {"norm": ("normal", 0.1), "final_norm": ("normal", 0.1),
            "gnorm": ("normal", 0.1), "embed": ("normal", 0.02),
            "in_proj": ("uniform", D ** -0.5),
            "conv_w": ("uniform", d["W"] ** -0.5),
            "conv_b": ("uniform", d["W"] ** -0.5),
            "A_log": ("log_of_uniform", 1.0, 16.0),
            "dt_bias": ("inv_softplus_log_uniform", 1e-3, 0.1, 1e-4),
            "D_skip": ("const", 1.0),
            "out_proj": ("uniform", d["d_inner"] ** -0.5 / L ** 0.5)}


def make_params(conf: dict, mcfg, seed: int):
    """The served weights, drawn on the device in one jitted call."""
    from repro.models import transformer as T
    return draw(T.abstract_params(mcfg), _init(conf), seed)


def _token_flops(conf: dict) -> int:
    d = reference.dims(conf)
    return counts.mamba2_token_flops(
        d=conf["d_model"], d_inner=d["d_inner"], heads=d["H"],
        head_dim=d["P"], d_state=d["N"], groups=d["G"], conv=d["W"],
        layers=conf["n_layer"], vocab=padded_vocab(conf))


def decode_step_flops(conf: dict, contexts) -> float:
    """Model FLOPs of one decode step over the active slots (the state is
    the same size whatever the context)."""
    return _token_flops(conf) * len(contexts)


def prefill_flops(conf: dict, length: int) -> float:
    """Model FLOPs of admitting one prompt of ``length`` real tokens, in
    recurrent form: every position through every layer, the head once."""
    head = 2 * conf["d_model"] * padded_vocab(conf)
    return (_token_flops(conf) - head) * length + head


def kv_shape(conf: dict):
    """Mamba-2 holds no KV cache: the attention counters do not apply."""
    return None
