"""Llama-architecture configurations on the serving program.

Maps a published ``LlamaForCausalLM`` config onto the program's
``ModelConfig``, draws the weights from a seed in the program's parameter
layout, and counts the model FLOPs of a decode step and of a prefill.
"""
from __future__ import annotations

from bench import counts
from bench.reference import llama as reference
from bench.weights import draw


def model_config(conf: dict):
    from repro.models.common import ModelConfig
    heads = int(conf["num_attention_heads"])
    return ModelConfig(
        name=conf["name"], family="dense",
        num_layers=int(conf["num_hidden_layers"]),
        d_model=int(conf["hidden_size"]), num_heads=heads,
        num_kv_heads=int(conf["num_key_value_heads"]),
        head_dim=int(conf.get("head_dim",
                              conf["hidden_size"] // heads)),
        d_ff=int(conf["intermediate_size"]),
        vocab_size=int(conf["vocab_size"]),
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        rope_theta=float(conf["rope_theta"]),
        norm_eps=float(conf["rms_norm_eps"]))


def token_range(conf: dict) -> int:
    return int(conf["vocab_size"])


# Hugging Face Llama initialisation: every linear and the embedding
# N(0, initializer_range); RMSNorm weights 1, drawn here as 1 + N(0, 0.1)
# so that a norm the program skipped or misapplied shows.
def _init(conf: dict):
    std = float(conf.get("initializer_range", 0.02))
    return {"norm": ("normal", 0.1), "final_norm": ("normal", 0.1),
            "embed": ("normal", std), "wq": ("normal", std),
            "wk": ("normal", std), "wv": ("normal", std),
            "wo": ("normal", std), "w_gate": ("normal", std),
            "w_up": ("normal", std), "w_down": ("normal", std)}


def make_params(conf: dict, mcfg, seed: int):
    """The served bf16 weights, drawn on the device in one jitted call."""
    from repro.models import transformer as T
    return draw(T.abstract_params(mcfg), _init(conf), seed)


def decode_step_flops(conf: dict, contexts) -> float:
    """Model FLOPs of one decode step over active slots whose contexts
    (tokens attended, new one included) are ``contexts``."""
    shape = kv_shape(conf)
    per_tok = counts.dense_matmul_flops(
        d=conf["hidden_size"], heads=shape["heads"],
        kv_heads=shape["kv_heads"], head_dim=shape["head_dim"],
        ffn=conf["intermediate_size"], gated=True,
        layers=conf["num_hidden_layers"], vocab=conf["vocab_size"])
    attn = sum(counts.decode_attention_flops(
        c, heads=shape["heads"], head_dim=shape["head_dim"])
        for c in contexts) * shape["layers"]
    return per_tok * len(contexts) + attn


def prefill_flops(conf: dict, length: int) -> float:
    """Model FLOPs of admitting one prompt of ``length`` real tokens: every
    position through every layer, causal attention, the head once."""
    shape = kv_shape(conf)
    head = 2 * conf["hidden_size"] * conf["vocab_size"]
    per_tok = counts.dense_matmul_flops(
        d=conf["hidden_size"], heads=shape["heads"],
        kv_heads=shape["kv_heads"], head_dim=shape["head_dim"],
        ffn=conf["intermediate_size"], gated=True,
        layers=conf["num_hidden_layers"], vocab=conf["vocab_size"]) - head
    attn, _ = counts.flash_attention_cost(length, **shape)
    return per_tok * length + attn + head


def kv_shape(conf: dict) -> dict:
    """What the decode and flash attention counters need."""
    heads = conf["num_attention_heads"]
    return {"layers": conf["num_hidden_layers"], "heads": heads,
            "kv_heads": conf["num_key_value_heads"],
            "head_dim": conf.get("head_dim", conf["hidden_size"] // heads)}
