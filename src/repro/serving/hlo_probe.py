"""HLO live-buffer accounting for the kernel-first decode path.

The gathered-view paged decode (``attn_decode_impl="gather"``) materialises
the O(B * S) slot-linear attention KV view every dispatch; the kernel-first
path must never allocate it.  These probes make that checkable: derive the
HLO type strings of every buffer the gathered view would create, lower the
decode-scan executable, and scan its HLO text for them.  Used by
``tests/test_kernel_decode.py`` and enforced in CI through
``benchmarks/decode_microbench.py --check-hlo``.

Likewise the kernel-first decode must step the SSD state where it lies:
``whole_pool_writes`` lists the instructions of a compiled dispatch that
write a whole buffer of the state pool's shape (a copy, a gather or
scatter, a dynamic-update-slice or a fusion of one), which
``tests/test_tpu_compile.py`` requires to be none.
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp

from repro.models import transformer as T

_HLO_DTYPE = {"bfloat16": "bf16", "float32": "f32", "float16": "f16"}


def slot_linear_kv_types(cfg, cache: dict, block_len: int) -> set[str]:
    """HLO type strings (e.g. ``bf16[3,128,3,64]``) of every attention
    k/v leaf the slot-linear gathered view would materialise for this
    paged cache — the O(B * S) buffers ``paged_gather`` creates and the
    kernel-first path must never allocate.  O(B) leaves (recurrent state
    rows, conv tails) are excluded: the kernel path still gathers those."""
    view_lens = {cache["table"].shape[1] * block_len}
    if cfg.window is not None:
        view_lens.add(cfg.window)               # local-attention ring view
    gathered = jax.eval_shape(lambda c: T.paged_gather(cfg, c), cache)
    out = set()
    for leaf in jax.tree_util.tree_leaves(gathered):
        if (leaf.ndim >= 4 and leaf.shape[-3] in view_lens
                and not jnp.issubdtype(leaf.dtype, jnp.integer)):
            dt = _HLO_DTYPE.get(leaf.dtype.name, leaf.dtype.name)
            out.add(f"{dt}[{','.join(map(str, leaf.shape))}]")
    return out


def decode_hlo(eng, impl: str, prompts, steps: int = 4) -> tuple[str, set]:
    """Compiled HLO text of the engine's decode-scan executable for the
    given impl, plus the slot-linear view types for its cache shape."""
    from repro.serving.engine import _decode_scan_paged

    st = eng.absorb(prompts)
    cache, _ = eng._paged_grown(st, st.offset + steps)
    lowered = _decode_scan_paged.lower(
        eng.params, eng.cfg, st.cur, st.last, cache, st.pos,
        jax.random.PRNGKey(0), eng.ucfg, steps, True, impl=impl)
    txt = lowered.compile().as_text()
    return txt, slot_linear_kv_types(eng.cfg, cache, eng.block_len)


def assert_no_slot_linear_kv(eng_gather, eng_kernel, prompts,
                             steps: int = 4) -> dict:
    """Probe-soundness + kernel-first assertion in one shot: the gather
    executable must CARRY the slot-linear view (else the probe is vacuous)
    and the kernel-first executable must NOT.  Returns the accounting dict
    for reporting; raises AssertionError on violation."""
    txt_g, types_g = decode_hlo(eng_gather, "gather", prompts, steps)
    txt_k, types_k = decode_hlo(eng_kernel, "kernel", prompts, steps)
    assert types_g == types_k and types_g, "probe derived no view types"
    present = sorted(t for t in types_g if t in txt_g)
    assert present, ("probe unsound: gather executable lacks the "
                     f"slot-linear view {sorted(types_g)}")
    leaked = sorted(t for t in types_k if t in txt_k)
    assert not leaked, (
        f"kernel-first decode still materialises the slot-linear KV view: "
        f"{leaked}")
    return {"view_types": sorted(types_g), "in_gather_hlo": present,
            "in_kernel_hlo": leaked}


# result producers that move no data: the pool passes through them as is
_PASS_THROUGH = {"parameter", "get-tuple-element", "bitcast", "custom-call"}


def whole_pool_writes(hlo_text: str, shape: tuple, dtype: str = "f32"
                      ) -> list[str]:
    """Instructions of a compiled HLO text whose result is a whole buffer
    of ``dtype[shape]`` and that write it: every producer but a parameter,
    a tuple element, a bitcast or a custom call (the in-place kernel,
    aliased to its operand)."""
    ty = re.escape(f"{dtype}[{','.join(map(str, shape))}]")
    pat = re.compile(rf"= {ty}{{[^}}]*}} ([\w-]+)\(")
    return [line.strip() for line in hlo_text.splitlines()
            if (m := pat.search(line)) and m.group(1) not in _PASS_THROUGH]
