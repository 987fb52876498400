"""FLOP and byte counters against hand counts at smoke shapes."""
import math

import pytest

from bench import counts
from bench.families import llama, mamba2
from bench.tests import smoke


def test_peaks_table_and_unknown_device():
    assert counts.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    assert counts.peaks("TPU v5 lite")["hbm_bw"] == 819e9
    with pytest.raises(KeyError):
        counts.peaks("cpu")


def test_dense_matmul_flops_by_hand():
    # attention 48*3*16*2 + 48*1*16*2 = 6144, gated MLP 48*96*3 = 13824,
    # two layers, head 48*512; 2 FLOPs per multiply-add
    got = counts.dense_matmul_flops(d=48, heads=3, kv_heads=1, head_dim=16,
                                    ffn=96, gated=True, layers=2, vocab=512)
    assert got == 2 * ((6144 + 13824) * 2 + 48 * 512) == 129024


def test_dense_matmul_flops_match_the_served_parameters():
    """2 FLOPs per matmul weight per token: every parameter but the norms,
    with the tied embedding counted once, as the head."""
    import jax
    from repro.models import transformer as T
    conf = smoke._json(smoke.CONFIGS["llama"])
    tree = T.abstract_params(llama.model_config(conf))
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    weights = sum(math.prod(leaf.shape) for path, leaf in flat
                  if "norm" not in str(path[-1]))
    per_tok = llama.decode_step_flops(conf, [0]) - 0
    # a context of 0 tokens adds no attention FLOPs
    assert per_tok == 2 * weights


def test_decode_attention_by_hand():
    assert counts.decode_attention_flops(10, heads=3, head_dim=16) == 1920
    # k and v of 10 tokens, 1 kv head of 16; q and out of 3 heads; bf16;
    # two layers
    assert counts.decode_attention_bytes(
        10, layers=2, heads=3, kv_heads=1, head_dim=16) == \
        (2 * 10 * 16 + 2 * 3 * 16) * 2 * 2 == 1664


def test_flash_attention_by_hand():
    flops, nbytes = counts.flash_attention_cost(
        4, layers=2, heads=3, kv_heads=1, head_dim=16)
    assert flops == 4 * 3 * 16 * (4 * 5 // 2) * 2 == 3840
    assert nbytes == (2 * 3 + 2 * 1) * 16 * 4 * 2 * 2 == 2048


def test_mamba2_token_flops_by_hand():
    # d 64, d_inner 128, 8 heads of 16, state 16, 1 group, conv 4:
    # in_proj 64 x 296, out_proj 128 x 64, conv 4 x 160, state 8*16*16
    per_layer = 2 * 64 * 296 + 2 * 128 * 64 + 2 * 4 * 160 + 5 * 2048
    got = counts.mamba2_token_flops(d=64, d_inner=128, heads=8, head_dim=16,
                                    d_state=16, groups=1, conv=4, layers=2,
                                    vocab=512)
    assert got == per_layer * 2 + 2 * 64 * 512 == 197120
    conf = smoke._json(smoke.CONFIGS["mamba2"])
    assert mamba2.decode_step_flops(conf, [5, 9]) == 2 * got


def test_prefill_flops_count_the_head_once():
    conf = smoke._json(smoke.CONFIGS["llama"])
    one = llama.prefill_flops(conf, 1)
    two = llama.prefill_flops(conf, 2)
    head = 2 * 48 * 512
    attn1, _ = counts.flash_attention_cost(1, **llama.kv_shape(conf))
    attn2, _ = counts.flash_attention_cost(2, **llama.kv_shape(conf))
    assert two - one == (llama.decode_step_flops(conf, [0]) - head) \
        + attn2 - attn1


def test_roofline_takes_the_bound_that_binds():
    peak = counts.peaks("TPU v5 lite")
    assert counts.roofline_seconds(197e12, 0, peak) == pytest.approx(1.0)
    assert counts.roofline_seconds(0, 819e9, peak) == pytest.approx(1.0)
    assert counts.roofline_seconds(197e12, 2 * 819e9, peak) == \
        pytest.approx(2.0)
