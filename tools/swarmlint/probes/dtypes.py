"""``decode-dtype`` — the decode step must not widen its carried state.

Two checks per architecture (smoke config, abstract eval only):

* **carry stability** — ``jax.eval_shape(decode_step)``: every cache
  leaf must come back with the dtype it went in with.  A decode step
  that returns an f32-widened cache doubles resident memory on the
  *second* step and breaks monolithic/paged bitwise parity.
* **no f32 convert of cache-shaped values** — walk the decode jaxpr
  (including sub-jaxprs) for ``convert_element_type`` equations that
  produce float32 from an operand whose shape matches a cache leaf:
  converting the cache itself to f32 mid-step is drift even when the
  final carry dtype is correct.  (Softmax/logit f32 accumulation on
  activation shapes is fine and expected.)
"""
from __future__ import annotations

from typing import List

from ..report import Finding

PROBE_ID = "decode-dtype"

_ENGINE_PATH = "src/repro/serving/engine.py"


def _leaves_with_path(tree):
    import jax
    return jax.tree_util.tree_flatten_with_path(tree)[0]


def _jaxpr_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                yield from _jaxpr_eqns(sub)


def _sub_jaxprs(value):
    from jax._src.core import ClosedJaxpr, Jaxpr
    if isinstance(value, ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, Jaxpr):
        yield value
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _sub_jaxprs(v)


def check() -> List[Finding]:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import configs as C
    from repro.models import transformer as T

    findings: List[Finding] = []
    B = 2
    for arch in C.ARCH_IDS:
        cfg = C.get_smoke(arch)
        L = 64
        params = T.abstract_params(cfg)
        cache = jax.eval_shape(lambda: T.init_cache(cfg, B, L))
        tok = jax.ShapeDtypeStruct((B, 1), np.int32)
        idx = jax.ShapeDtypeStruct((B,), np.int32)

        def step(p, c, t, i):
            return T.decode_step(p, cfg, t, c, i)

        _, out_cache = jax.eval_shape(step, params, cache, tok, idx)
        in_leaves = _leaves_with_path(cache)
        out_leaves = _leaves_with_path(out_cache)
        for (path_in, leaf_in), (_, leaf_out) in zip(in_leaves, out_leaves):
            if leaf_in.dtype != leaf_out.dtype:
                findings.append(Finding(
                    PROBE_ID, _ENGINE_PATH, 0,
                    f"{arch}: decode_step widens cache leaf "
                    f"{jax.tree_util.keystr(path_in)} from "
                    f"{leaf_in.dtype} to {leaf_out.dtype}"))

        # f32 converts whose operand is cache-shaped
        bf16_shapes = {tuple(l.shape) for _, l in in_leaves
                       if l.dtype == jnp.bfloat16 or l.dtype == cfg.dtype}
        jaxpr = jax.make_jaxpr(step)(params, cache, tok, idx)
        for eqn in _jaxpr_eqns(jaxpr.jaxpr):
            if eqn.primitive.name != "convert_element_type":
                continue
            if eqn.params.get("new_dtype") != jnp.float32:
                continue
            for invar in eqn.invars:
                aval = getattr(invar, "aval", None)
                if aval is not None and tuple(aval.shape) in bf16_shapes \
                        and aval.dtype == cfg.dtype:
                    findings.append(Finding(
                        PROBE_ID, _ENGINE_PATH, 0,
                        f"{arch}: decode jaxpr converts a cache-shaped "
                        f"{aval.dtype}{list(aval.shape)} value to float32"))
                    break
    return findings
