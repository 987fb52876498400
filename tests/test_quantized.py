"""Quantized serving tests (ISSUE 10).

``cache_quant="int8"|"fp8"`` stores the paged pool's KV blocks quantized
with per-block-row f32 scales and fuses the dequant into the decode
accumulator.  The contract is BUDGETED parity, not bitwise: against the
bf16 paged engine (itself bitwise vs monolithic), the quantized engine
must produce IDENTICAL greedy token streams and logits within a
per-arch budget — for every mixer family, through the whole session
API (cold generate, COW fanout, TTL churn, checkpoint/restore), and in
both decode paths (chunked-softmax and the Pallas block-table kernel).
Recurrent/conv state rows stay bf16 (pure-SSM archs are EXACT under
cache_quant).  ``weight_quant`` rides the same scheme for matmul
weights; MoE weight quantization is routing-sensitive, so its greedy
parity is only asserted where routing cannot flip.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs as C
from repro.core.uncertainty import UncertaintyConfig
from repro.models import quant as Q
from repro.models import transformer as T
from repro.serving.cache_manager import (EvictedSessionError,
                                         QuantMismatchError)
from repro.serving.engine import InferenceEngine
from repro.serving.swarm import pad_prompts

ARCHS = {
    "attn": "smollm-135m",
    "rglru": "recurrentgemma-2b",
    "ssd": "mamba2-780m",
    "moe_shared_routed": "deepseek-moe-16b",
    "moe_interleaved": "llama4-scout-17b-a16e",
}

# Per-arch max |logit| deltas vs the bf16 paged engine (~4x headroom
# over measured: attn/rglru <= 0.004, moe_sr <= 0.009, moe_il <= 0.053;
# ssd is a pure-SSM arch — no KV pool — and must be EXACT).  Documented
# in docs/RUNTIME.md "Quantized caches".
BUDGET = {
    "attn": 0.02, "rglru": 0.02, "ssd": 0.0,
    "moe_shared_routed": 0.05, "moe_interleaved": 0.2,
}

BLOCK = 16
PROMPTS = [[3, 20, 195, 2], [3, 21, 196, 199, 2], [7, 9, 2]]
SPANS = [[11, 12, 2], [13, 2], [14, 15, 16, 2]]


def _engine(arch, name="eng", **kw):
    cfg = kw.pop("cfg", None)
    if cfg is None:
        cfg = dataclasses.replace(C.get_smoke(arch), vocab_size=512)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    return InferenceEngine(name, cfg, params,
                           UncertaintyConfig(mode="distribution"), **kw)


def _pair(arch, quant, **kw):
    cfg = dataclasses.replace(C.get_smoke(arch), vocab_size=512)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    ucfg = UncertaintyConfig(mode="distribution")
    base = InferenceEngine("bf16", cfg, params, ucfg, paged=True,
                           block_len=BLOCK)
    qeng = InferenceEngine(quant, cfg, params, ucfg, paged=True,
                           block_len=BLOCK, cache_quant=quant, **kw)
    return base, qeng


def _assert_budgeted(r0, r1, budget):
    np.testing.assert_array_equal(r0["tokens"], r1["tokens"])
    l0 = np.asarray(r0["logits"], np.float32)
    l1 = np.asarray(r1["logits"], np.float32)
    np.testing.assert_allclose(l0, l1, atol=max(budget, 1e-7), rtol=0)


class TestBudgetedParity:
    @pytest.mark.parametrize("quant", ["int8", "fp8"])
    @pytest.mark.parametrize("arch", sorted(ARCHS))
    def test_generate_greedy_and_logit_budget(self, arch, quant):
        """Cold fused generate: same greedy stream, logits in budget,
        every mixer family and both quant formats."""
        base, qeng = _pair(ARCHS[arch], quant)
        prompts = pad_prompts(PROMPTS)
        r0 = base.generate(prompts, 6)
        r1 = qeng.generate(prompts, 6)
        _assert_budgeted(r0, r1, BUDGET[arch])

    def test_warm_continuation_in_budget(self):
        """absorb -> continue -> decode-only extend through a quantized
        pool: the session API stays in budget end to end."""
        base, qeng = _pair(ARCHS["attn"], "int8")
        prompts, span = pad_prompts(PROMPTS), pad_prompts(SPANS)
        w0 = base.generate(span, 6, state=base.absorb(prompts),
                           return_state=True)
        w1 = qeng.generate(span, 6, state=qeng.absorb(prompts),
                           return_state=True)
        _assert_budgeted(w0, w1, BUDGET["attn"])
        e0 = base.generate(None, 4, state=w0["state"])
        e1 = qeng.generate(None, 4, state=w1["state"])
        np.testing.assert_array_equal(e0["tokens"], e1["tokens"])

    def test_bf16_default_stays_bitwise_vs_monolithic(self):
        """The quantization machinery must not perturb the unquantized
        path: cache_quant=None paged == monolithic, bitwise."""
        mono = _engine(ARCHS["attn"], "mono")
        paged = _engine(ARCHS["attn"], "paged", paged=True, block_len=BLOCK)
        assert paged.pool.cache_quant is None
        prompts = pad_prompts(PROMPTS)
        r0 = mono.generate(prompts, 6)
        r1 = paged.generate(prompts, 6)
        np.testing.assert_array_equal(r0["tokens"], r1["tokens"])
        np.testing.assert_array_equal(np.asarray(r0["logits"]),
                                      np.asarray(r1["logits"]))

    def test_cache_quant_requires_paged(self):
        with pytest.raises(ValueError, match="paged"):
            _engine(ARCHS["attn"], cache_quant="int8")
        with pytest.raises(ValueError, match="quantization mode"):
            _engine(ARCHS["attn"], paged=True, cache_quant="int4")


class TestQuantizedKernel:
    def _quant_pool_case(self, quant, B=3, K=2, G=4, D=16, N=14, L=8, nb=4,
                         seed=0):
        key = jax.random.PRNGKey(seed)
        ks = jax.random.split(key, 4)
        q = jax.random.normal(ks[0], (B, K, G, D), jnp.float32)
        k_pool = jax.random.normal(ks[1], (N, L, K, D), jnp.bfloat16)
        v_pool = jax.random.normal(ks[2], (N, L, K, D), jnp.bfloat16)
        kq, k_s = Q.quantize_rows(k_pool, quant)
        vq, v_s = Q.quantize_rows(v_pool, quant)
        table = jax.random.permutation(
            ks[3], np.arange(N))[:B * nb].reshape(B, nb).astype(jnp.int32)
        T_ = nb * L
        idx = jnp.asarray(np.linspace(T_ - 1, 3, B).astype(np.int32))
        lin = jnp.arange(T_)[None, :]
        pos_lin = jnp.where(lin <= idx[:, None], lin, -1).astype(jnp.int32)
        pos_pool = jnp.full((N, L), -1, jnp.int32)
        pos_pool = pos_pool.at[table.reshape(-1)].set(
            pos_lin.reshape(B * nb, L))
        return q, k_pool, v_pool, kq, vq, k_s, v_s, pos_pool, table, idx

    @pytest.mark.parametrize("window", [None, 16])
    @pytest.mark.parametrize("quant", ["int8", "fp8"])
    def test_pallas_fused_dequant_matches_oracle(self, quant, window):
        """Quantized block-table kernel (interpret mode) == the gathered
        dequantized-view oracle: the in-accumulator scale application is
        EXACT (a per-(slot,head) constant factors out of the Dh dot), so
        the two read strategies agree to f32 tolerance — and both sit
        within the format's error of the unquantized pool."""
        from repro.kernels.decode_attention.ops import paged_decode_attention
        (q, kp, vp, kq, vq, k_s, v_s, pp, table,
         idx) = self._quant_pool_case(quant)
        ref = paged_decode_attention(q, kq, vq, pp, table, idx,
                                     window=window, k_scale=k_s, v_scale=v_s)
        pal = paged_decode_attention(q, kq, vq, pp, table, idx,
                                     window=window, k_scale=k_s, v_scale=v_s,
                                     force_pallas=True)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(pal),
                                   atol=2e-6, rtol=1e-6)
        full = paged_decode_attention(q, kp, vp, pp, table, idx,
                                      window=window)
        tol = 0.02 if quant == "int8" else 0.12
        np.testing.assert_allclose(np.asarray(pal), np.asarray(full),
                                   atol=tol, rtol=0)

    @pytest.mark.parametrize("quant", ["int8", "fp8"])
    def test_pallas_quantized_with_delta_overlay(self, quant):
        """Delta rows stay bf16 and overlay quantized pool slots: kernel
        == oracle with the two-phase read active."""
        from repro.kernels.decode_attention.ops import paged_decode_attention
        (q, _, _, kq, vq, k_s, v_s, pp, table,
         idx) = self._quant_pool_case(quant)
        B, S = table.shape[0], 4
        key = jax.random.PRNGKey(7)
        dk = jax.random.normal(key, (B, S) + kq.shape[2:], jnp.bfloat16)
        dv = jax.random.normal(jax.random.fold_in(key, 1),
                               (B, S) + kq.shape[2:], jnp.bfloat16)
        dpos = (idx[:, None] - jnp.arange(S, dtype=jnp.int32)[None, ::-1])
        p0 = jnp.maximum(idx - S + 1, 0)
        kw = dict(k_scale=k_s, v_scale=v_s, delta_k=dk, delta_v=dv,
                  delta_pos=dpos, p0=p0)
        ref = paged_decode_attention(q, kq, vq, pp, table, idx, **kw)
        pal = paged_decode_attention(q, kq, vq, pp, table, idx,
                                     force_pallas=True, **kw)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(pal),
                                   atol=2e-6, rtol=1e-6)


class TestQuantizedPoolChurn:
    def test_cow_fanout_shared_blocks_and_scales_untouched(self):
        """COW on a quantized pool: divergent continuations never write
        through shared prefix blocks — payload OR scale leaves — and the
        fanned decode stays in budget vs the bf16 engine's fanout."""
        base, qeng = _pair(ARCHS["rglru"], "int8")   # all pool kinds
        ctx = pad_prompts(PROMPTS)[:1]
        st = qeng.absorb(ctx)
        shared = np.asarray(st.cache.tables[0])

        # rank below the block axis, per pool-leaf kind: k/v (N,L,K,Dh),
        # pos (N,L), scales (N,L,K) — int8 payloads break the dtype
        # heuristic the bf16 test uses, so key on the field name
        depth = {"k": 4, "v": 4, "pos": 2, "k_scale": 3, "v_scale": 3}

        def checksum():
            ids = jnp.asarray(shared)
            vals = []
            for sc in qeng.pool.arrays:
                for c in sc.values():
                    if c.kv is not None:
                        for fname, leaf in zip(c.kv._fields, c.kv):
                            if leaf is None:
                                continue
                            vals.append(np.asarray(jnp.take(
                                leaf, ids, axis=leaf.ndim - depth[fname])
                                .astype(jnp.float32)).copy())
            return vals

        # quantized pools actually carry scale leaves alongside k/v/pos
        assert all(c.kv is None or c.kv.k_scale is not None
                   for sc in qeng.pool.arrays for c in sc.values())
        before = checksum()
        spans = pad_prompts([[40 + k, 2] for k in range(4)], align="right")
        out = qeng.generate(spans, 6, state=qeng.fanout(st, 4))
        after = checksum()
        for b, a in zip(before, after):
            np.testing.assert_array_equal(b, a)
        assert qeng.pool.counters["cow_copies"] >= 1
        ref = base.generate(spans, 6, state=base.fanout(base.absorb(ctx), 4))
        _assert_budgeted(ref, out, BUDGET["rglru"])

    def test_ttl_eviction_and_cold_reprefill(self):
        """TTL-evicted quantized sessions free their blocks AND scale
        rows; a cold re-prefill of the same conversation lands on the
        recycled (reset) blocks and reproduces the same stream."""
        _, qeng = _pair(ARCHS["attn"], "int8")
        clock = [0.0]
        qeng.pool._clock = lambda: clock[0]
        prompts = pad_prompts(PROMPTS)
        st = qeng.absorb(prompts[:1])
        first = qeng.generate(None, 4, state=qeng.fanout(st, 1))
        clock[0] = 100.0
        assert qeng.evict_idle_sessions(ttl_s=50.0) >= 1
        assert qeng.pool.blocks_in_use == 0
        with pytest.raises(EvictedSessionError):
            qeng.generate(None, 2, state=st)
        st2 = qeng.absorb(prompts[:1])      # recycled blocks, reset scales
        again = qeng.generate(None, 4, state=qeng.fanout(st2, 1))
        np.testing.assert_array_equal(first["tokens"], again["tokens"])
        np.testing.assert_array_equal(np.asarray(first["logits"]),
                                      np.asarray(again["logits"]))

    def test_famine_message_reports_quantized_bytes(self):
        """Pool famine on a quantized engine names the quantized block
        bytes — capacity planning sees the real (reduced) footprint."""
        from repro.serving.cache_manager import PoolExhaustedError
        _, qeng = _pair(ARCHS["attn"], "int8", pool_blocks=8)
        bf16 = _engine(ARCHS["attn"], paged=True, block_len=BLOCK)
        assert qeng.pool.block_bytes < bf16.pool.block_bytes
        with pytest.raises(PoolExhaustedError, match=r"int8 blocks of"):
            qeng.pool.alloc(4, 16)


class TestQuantizedCheckpoint:
    def test_restore_round_trip_resumes_in_budget(self, tmp_path):
        """checkpoint -> fresh quantized engine -> restore: the saved
        linear view re-quantizes at scatter (scales recomputed over the
        same rows), and the resumed stream matches the unbroken session
        exactly."""
        _, qeng = _pair(ARCHS["attn"], "int8")
        prompts = pad_prompts(PROMPTS)
        r = qeng.generate(prompts, 4, return_state=True)
        unbroken = qeng.generate(None, 4, state=r["state"])
        qeng.checkpoint_session(r["state"], str(tmp_path), step=1)
        fresh = _engine(ARCHS["attn"], "fresh", paged=True, block_len=BLOCK,
                        cache_quant="int8")
        st = fresh.restore_session(str(tmp_path))
        resumed = fresh.generate(None, 4, state=st)
        np.testing.assert_array_equal(unbroken["tokens"], resumed["tokens"])
        np.testing.assert_allclose(
            np.asarray(unbroken["logits"], np.float32),
            np.asarray(resumed["logits"], np.float32),
            atol=BUDGET["attn"], rtol=0)

    @pytest.mark.parametrize("dst", ["mono", "bf16_paged", "fp8"])
    def test_representation_mismatch_raises_typed_error(self, tmp_path, dst):
        """A quantized checkpoint refuses to restore into ANY
        differently-represented engine (and vice versa): silent
        precision changes are an error, not a surprise."""
        _, qeng = _pair(ARCHS["attn"], "int8")
        r = qeng.generate(pad_prompts(PROMPTS), 3, return_state=True)
        qeng.checkpoint_session(r["state"], str(tmp_path), step=1)
        other = {
            "mono": dict(),
            "bf16_paged": dict(paged=True, block_len=BLOCK),
            "fp8": dict(paged=True, block_len=BLOCK, cache_quant="fp8"),
        }[dst]
        eng = _engine(ARCHS["attn"], dst, **other)
        with pytest.raises(QuantMismatchError, match="cache_quant='int8'"):
            eng.restore_session(str(tmp_path))

    def test_bf16_checkpoint_refused_by_quantized_engine(self, tmp_path):
        paged = _engine(ARCHS["attn"], "paged", paged=True, block_len=BLOCK)
        r = paged.generate(pad_prompts(PROMPTS), 3, return_state=True)
        paged.checkpoint_session(r["state"], str(tmp_path), step=1)
        _, qeng = _pair(ARCHS["attn"], "int8")
        with pytest.raises(QuantMismatchError, match="cache_quant=None"):
            qeng.restore_session(str(tmp_path))


class TestWeightQuant:
    @pytest.mark.parametrize("arch", ["attn", "rglru"])
    def test_dense_weight_quant_greedy_parity(self, arch):
        """int8 weights on dense archs: greedy stream identical, logits
        in (a slightly wider) budget.  Router/embed/norm/recurrent
        weights are exempt by design, so routing-free archs cannot
        flip."""
        cfg = dataclasses.replace(C.get_smoke(ARCHS[arch]), vocab_size=512)
        params = T.init_params(cfg, jax.random.PRNGKey(0))
        ucfg = UncertaintyConfig(mode="distribution")
        base = InferenceEngine("b", cfg, params, ucfg, paged=True,
                               block_len=BLOCK)
        w = InferenceEngine("w", cfg, params, ucfg, paged=True,
                            block_len=BLOCK, cache_quant="int8",
                            weight_quant="int8")
        r0 = base.generate(pad_prompts(PROMPTS), 6)
        r1 = w.generate(pad_prompts(PROMPTS), 6)
        _assert_budgeted(r0, r1, 2 * max(BUDGET[arch], 0.01))

    def test_weights_stored_quantized_on_device(self):
        _, _ = 0, 0
        eng = _engine(ARCHS["attn"], paged=True, block_len=BLOCK,
                      weight_quant="int8")
        leaves = jax.tree_util.tree_leaves(
            eng.params, is_leaf=lambda x: isinstance(x, Q.QTensor))
        qt = [l for l in leaves if isinstance(l, Q.QTensor)]
        assert qt, "no QTensor leaves after weight_quant"
        for t in qt:
            assert t.q.dtype == jnp.int8
            assert t.scale.dtype == jnp.float32
            assert t.scale.shape == t.q.shape[:-1]

    def test_moe_gather_impl_matches_dispatch_quantized(self):
        """gather-decode with QTensor experts: gathered rows dequantize
        AFTER the gather to the same values the dispatch einsums see, so
        the two impls agree to the pre-existing ~1 ulp einsum-order
        noise, now under quantized weights.  Both engines decode the
        dispatch engine's greedy tokens (teacher-forced through one-step
        session extensions), so every step's logits are compared over one
        shared history even where the noise would flip a near-tie."""
        cfg = dataclasses.replace(C.get_smoke(ARCHS["moe_shared_routed"]),
                                  vocab_size=512)
        params = T.init_params(cfg, jax.random.PRNGKey(0))
        ucfg = UncertaintyConfig(mode="distribution")
        kw = dict(paged=True, block_len=BLOCK, cache_quant="int8",
                  weight_quant="int8")
        disp = InferenceEngine("disp", cfg, params, ucfg, **kw)
        cfg_g = dataclasses.replace(cfg, moe_decode_impl="gather")
        gath = InferenceEngine("gath", cfg_g, params, ucfg, **kw)
        toks = disp.generate(pad_prompts(PROMPTS), 6)["tokens"]

        def forced_logits(eng):
            st = eng.absorb(pad_prompts(PROMPTS))
            out = []
            for t in range(toks.shape[1]):
                st = dataclasses.replace(st, cur=jnp.asarray(toks[:, t]))
                r = eng.generate(None, 1, state=st, return_state=True)
                out.append(np.asarray(r["logits"][:, 0], np.float32))
                st = r["state"]
            eng.release(st)
            return np.stack(out, axis=1)

        np.testing.assert_allclose(forced_logits(disp), forced_logits(gath),
                                   atol=0.02, rtol=0)


SHARDED_QUANT_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax, numpy as np
from repro import configs as C
from repro.core.uncertainty import UncertaintyConfig
from repro.models import transformer as T
from repro.serving.engine import InferenceEngine
from repro.serving.swarm import pad_prompts
from repro.launch.mesh import serving_mesh

PROMPTS = [[3, 20, 195, 2], [3, 21, 196, 199, 2], [7, 9, 2], [5, 6, 7, 2]]
mesh = serving_mesh(model_parallel=2)
cfg = dataclasses.replace(C.get_smoke("smollm-135m"), vocab_size=512)
params = T.init_params(cfg, jax.random.PRNGKey(0))
ucfg = UncertaintyConfig(mode="distribution")
base = InferenceEngine("b", cfg, params, ucfg, paged=True, block_len=16,
                       mesh=mesh)
q = InferenceEngine("q", cfg, params, ucfg, paged=True, block_len=16,
                    mesh=mesh, cache_quant="int8", weight_quant="int8")
prompts = pad_prompts(PROMPTS)
r0 = base.generate(prompts, 6)
r1 = q.generate(prompts, 6)
l0, l1 = np.asarray(r0["logits"], np.float32), np.asarray(r1["logits"],
                                                          np.float32)
# budgeted tie-aware: sharded reductions already carry ~1 ulp; compare
# the greedy prefix before any inside-budget tie flip
for b in range(r0["tokens"].shape[0]):
    mism = np.where(r0["tokens"][b] != r1["tokens"][b])[0]
    n = mism[0] if len(mism) else r0["tokens"].shape[1]
    np.testing.assert_array_equal(r0["tokens"][b, :n], r1["tokens"][b, :n])
    np.testing.assert_allclose(l0[b, :n], l1[b, :n], atol=0.05, rtol=0)
    if len(mism):
        top2 = np.sort(l0[b, mism[0]])[-2:]
        assert top2[1] - top2[0] <= 0.1, (b, mism[0], top2)
print("RESULT ok")
"""


def test_quantized_sharded_smoke():
    """Quantized pool + QTensor weights on the (4, 2) fake-device mesh:
    the scale sidecars shard with their pools (act_pool_scale) and the
    budgeted greedy parity holds under real partitioned reductions."""
    import subprocess
    import sys
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    proc = subprocess.run([sys.executable, "-c", SHARDED_QUANT_SCRIPT],
                          env=env, capture_output=True, text=True,
                          timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "RESULT ok" in proc.stdout, proc.stdout
