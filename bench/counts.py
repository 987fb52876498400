"""What the work costs: published chip peaks, and the FLOPs and bytes each
kernel and each model step needs.

Counts are of what a kernel or step computes, from its shapes, never of
how an implementation happens to do it: padding, masked blocks, relayout
copies and recomputation are not counted.  A later kernel that computes
the same thing is read against the same count.
"""
from __future__ import annotations

# Published per-chip peaks, keyed by the device_kind JAX reports.  Source:
# Google Cloud documentation, "TPU v5e" (system architecture table): 197
# TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of
# inter-chip interconnect per chip over 4 ICI links (50 GB/s per link).
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes": 16e9, "hbm_bw": 819e9, "link_bw": 50e9},
}


def peaks(device_kind: str) -> dict:
    """Peaks of one chip of ``device_kind``; a device not in :data:`PEAKS`
    is an error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}: add them to PEAKS with their "
                       "source") from None


# ---------------------------------------------------------------------------
# Model steps (2 FLOPs per multiply-add)
# ---------------------------------------------------------------------------

def dense_matmul_flops(*, d: int, heads: int, kv_heads: int, head_dim: int,
                       ffn: int, gated: bool, layers: int,
                       vocab: int) -> int:
    """Matmul FLOPs per token of a dense decoder: q, k, v and output
    projections, the MLP, and the output head (the embedding lookup is a
    gather, not a matmul)."""
    attn = d * heads * head_dim * 2 + d * kv_heads * head_dim * 2
    mlp = d * ffn * (3 if gated else 2)
    return 2 * ((attn + mlp) * layers + d * vocab)


def decode_attention_flops(context: int, *, heads: int, head_dim: int) -> int:
    """One query token attending ``context`` tokens, one layer: q.k and
    p.v, each ``heads * head_dim`` multiply-adds per token attended."""
    return 4 * heads * head_dim * context


def mamba2_token_flops(*, d: int, d_inner: int, heads: int, head_dim: int,
                       d_state: int, groups: int, conv: int, layers: int,
                       vocab: int) -> int:
    """FLOPs of one Mamba-2 token in recurrent form: input and output
    projections, the depthwise conv, the state update (decay, input outer
    product, sum: 3 per state element) and its read-out (2 per element),
    and the output head."""
    conv_dim = d_inner + 2 * groups * d_state
    d_in_proj = 2 * d_inner + 2 * groups * d_state + heads
    state = heads * head_dim * d_state
    per_layer = (2 * d * d_in_proj + 2 * d_inner * d + 2 * conv * conv_dim
                 + 5 * state)
    return per_layer * layers + 2 * d * vocab


# ---------------------------------------------------------------------------
# Kernels
# ---------------------------------------------------------------------------

def decode_attention_bytes(context: int, *, layers: int, heads: int,
                           kv_heads: int, head_dim: int,
                           itemsize: int = 2) -> int:
    """HBM bytes one slot's decode attention needs in one step over all
    layers: the keys and values of its ``context`` tokens, its query in
    and its output out."""
    kv = 2 * context * kv_heads * head_dim
    qo = 2 * heads * head_dim
    return (kv + qo) * itemsize * layers


def flash_attention_cost(length: int, *, layers: int, heads: int,
                         kv_heads: int, head_dim: int,
                         itemsize: int = 2) -> tuple[int, int]:
    """(FLOPs, HBM bytes) of causal self-attention over one prompt of
    ``length`` real tokens, all layers: q.k and p.v over the causal
    triangle, length * (length + 1) / 2 pairs; q, k, v read and the output
    written once."""
    pairs = length * (length + 1) // 2
    flops = 4 * heads * head_dim * pairs
    io = (2 * heads + 2 * kv_heads) * head_dim * length * itemsize
    return flops * layers, io * layers


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """Least time the chip could take: whichever of compute and memory
    bounds."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bw"])
