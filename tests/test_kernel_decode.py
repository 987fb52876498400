"""Kernel-first paged decode (ISSUE 6): engine-level parity + accounting.

The kernel-first serve path (``attn_decode_impl="kernel"``) must be
BITWISE-identical — tokens AND logits — to the gathered-view oracle
(``"gather"``) AND to the monolithic engine, for all three mixer families
and both MoE archs, cold / warm-continuation / decode-only, unsharded and
on the (1, 1) mesh; the real (4, 2) fake-device mesh runs tie-aware in a
subprocess like the other sharded suites.  On top of parity:

* the kernel-first decode executable provably never materialises the
  O(B * S) slot-linear attention KV view — HLO live-buffer accounting
  (the gathered-view executable DOES carry it, so the probe is sound);
* with ``compilation_cache_dir`` set, a second process constructing the
  same engine and running the same dispatch performs ZERO fresh XLA
  compiles — every executable comes off the persistent cache (entry
  thresholds are zeroed, so any fresh compile would write a new file).
"""

import dataclasses
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro import configs as C
from repro.core.uncertainty import UncertaintyConfig
from repro.models import transformer as T
from repro.serving.engine import InferenceEngine
from repro.serving.scheduler import Request
from repro.serving.swarm import pad_prompts

ARCHS = {
    "attn": "smollm-135m",
    "rglru": "recurrentgemma-2b",
    "ssd": "mamba2-780m",
    "moe_shared_routed": "deepseek-moe-16b",
    "moe_interleaved": "llama4-scout-17b-a16e",
}

BLOCK = 16
PROMPTS = [[3, 20, 195, 2], [3, 21, 196, 199, 2], [7, 9, 2]]
SPANS = [[11, 12, 2], [13, 2], [14, 15, 16, 2]]


def _triple(arch: str, **kw):
    cfg = dataclasses.replace(C.get_smoke(arch), vocab_size=512)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    ucfg = UncertaintyConfig(mode="distribution")
    mono = InferenceEngine("mono", cfg, params, ucfg)
    gather = InferenceEngine("gather", cfg, params, ucfg, paged=True,
                             block_len=BLOCK, attn_decode_impl="gather", **kw)
    kernel = InferenceEngine("kernel", cfg, params, ucfg, paged=True,
                             block_len=BLOCK, attn_decode_impl="kernel", **kw)
    return mono, gather, kernel


@pytest.fixture(scope="module", params=sorted(ARCHS))
def triple(request):
    return _triple(ARCHS[request.param])


def _assert_same(r0, r1, logits=True):
    np.testing.assert_array_equal(r0["tokens"], r1["tokens"])
    if logits:
        np.testing.assert_array_equal(np.asarray(r0["logits"]),
                                      np.asarray(r1["logits"]))


class TestKernelFirstParity:
    def test_generate_bitwise_triple(self, triple):
        """Cold fused generate: kernel == gather == mono, tokens AND
        logits AND uncertainty, every arch."""
        mono, gather, kernel = triple
        prompts = pad_prompts(PROMPTS)
        r0 = mono.generate(prompts, 6)
        rg = gather.generate(prompts, 6)
        rk = kernel.generate(prompts, 6)
        _assert_same(r0, rg)
        _assert_same(r0, rk)
        np.testing.assert_array_equal(r0["u"], rk["u"])

    def test_warm_continuation_and_extension_bitwise(self, triple):
        """absorb -> continue -> decode-only extend stays bitwise across
        all three decode layouts."""
        mono, gather, kernel = triple
        prompts, span = pad_prompts(PROMPTS), pad_prompts(SPANS)
        outs = []
        for eng in (mono, gather, kernel):
            w = eng.generate(span, 6, state=eng.absorb(prompts),
                             return_state=True)
            e = eng.generate(None, 4, state=w["state"])
            outs.append((w, e))
        for w, e in outs[1:]:
            _assert_same(outs[0][0], w)
            _assert_same(outs[0][1], e)

    def test_serve_bitwise(self, triple):
        """Streaming serve through the kernel-first slots == monolithic
        generate, token for token."""
        mono, _, kernel = triple
        prompts = pad_prompts(PROMPTS)
        res = mono.generate(prompts, 6)
        fin = kernel.serve([Request(rid=i, prompt=prompts[i].tolist(),
                                    max_new=6) for i in range(len(PROMPTS))],
                           n_slots=2, decode_chunk=4)
        assert len(fin) == len(PROMPTS)
        for r in fin:
            np.testing.assert_array_equal(r["tokens"],
                                          res["tokens"][r["rid"]])

    def test_mesh11_bitwise(self, triple):
        """Kernel-first + the degenerate (1,1) serving mesh == monolithic
        unsharded, bit for bit."""
        from repro.launch.mesh import serving_mesh
        mono, _, _ = triple
        sh = InferenceEngine("kernel-mesh", mono.cfg, mono.params, mono.ucfg,
                             paged=True, block_len=BLOCK,
                             attn_decode_impl="kernel", mesh=serving_mesh())
        prompts = pad_prompts(PROMPTS)
        r0 = mono.generate(prompts, 6)
        r1 = sh.generate(prompts, 6)
        _assert_same(r0, r1)


# ---------------------------------------------------------------------------
# HLO live-buffer accounting: the gathered-view decode executable carries
# the O(B * S) slot-linear attention KV view; the kernel-first one must not.
# (Probes live in repro.serving.hlo_probe, shared with the microbench/CI.)
# ---------------------------------------------------------------------------


class TestNoSlotLinearKV:
    @pytest.mark.parametrize("arch", ["attn", "rglru"])
    def test_kernel_first_drops_gathered_view(self, arch):
        from repro.serving.hlo_probe import assert_no_slot_linear_kv
        _, gather, kernel = _triple(ARCHS[arch])
        acct = assert_no_slot_linear_kv(gather, kernel, pad_prompts(PROMPTS))
        assert acct["view_types"] and not acct["in_kernel_hlo"]


# ---------------------------------------------------------------------------
# Persistent compilation cache: second process == zero fresh compiles
# ---------------------------------------------------------------------------

CACHE_SCRIPT = r"""
import sys
import numpy as np
from repro import configs as C
import dataclasses, jax
from repro.core.uncertainty import UncertaintyConfig
from repro.models import transformer as T
from repro.serving.engine import InferenceEngine
from repro.serving.swarm import pad_prompts

cfg = dataclasses.replace(C.get_smoke("smollm-135m"), vocab_size=512)
params = T.init_params(cfg, jax.random.PRNGKey(0))
eng = InferenceEngine("e", cfg, params, UncertaintyConfig(mode="distribution"),
                      paged=True, block_len=16,
                      compilation_cache_dir=sys.argv[1])
res = eng.generate(pad_prompts([[3, 20, 195, 2], [7, 9, 2], [5, 6, 2]]), 6,
                   return_state=True)
res2 = eng.generate(None, 4, state=res["state"])
np.save(sys.argv[2], res["tokens"])
print("RESULT ok")
"""


def test_second_process_compiles_nothing_fresh(tmp_path):
    """Entry-size/compile-time thresholds are zeroed, so EVERY fresh XLA
    compile persists a new cache file: an unchanged file set on the second
    run proves every executable (prefill, decode scan, uncertainty) came
    off the persistent cache — the serve() cold start is jit-free."""
    cache_dir = tmp_path / "xla-cache"
    env = dict(os.environ, PYTHONPATH=os.path.join(
        os.path.dirname(__file__), "..", "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)   # it would win over the arg

    def run(tag):
        out = tmp_path / f"toks-{tag}.npy"
        proc = subprocess.run(
            [sys.executable, "-c", CACHE_SCRIPT, str(cache_dir), str(out)],
            env=env, capture_output=True, text=True, timeout=600)
        assert proc.returncode == 0, proc.stderr[-3000:]
        assert "RESULT ok" in proc.stdout
        return np.load(out), sorted(os.listdir(cache_dir))

    toks1, files1 = run(1)
    assert files1                       # run 1 populated the cache
    toks2, files2 = run(2)
    assert files2 == files1, (len(files1), len(files2))
    np.testing.assert_array_equal(toks1, toks2)


def test_cache_env_var_wins_over_engine_arg(tmp_path):
    """A set JAX_COMPILATION_CACHE_DIR decides where executables go; the
    engine's compilation_cache_dir never overrides it."""
    env_dir, arg_dir = tmp_path / "from-env", tmp_path / "from-arg"
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(env_dir),
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    proc = subprocess.run(
        [sys.executable, "-c", CACHE_SCRIPT, str(arg_dir),
         str(tmp_path / "toks.npy")],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert os.listdir(env_dir)
    assert not arg_dir.exists() or not os.listdir(arg_dir)


def test_cache_dir_resolution(monkeypatch):
    """Environment first, then the caller's path, then one fixed,
    git-ignored directory in the checkout."""
    from repro import compile_cache as cc
    monkeypatch.setenv(cc.ENV_VAR, "/env/cache")
    assert cc.cache_dir("/arg/cache") == "/env/cache"
    monkeypatch.delenv(cc.ENV_VAR)
    assert cc.cache_dir("/arg/cache") == "/arg/cache"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert cc.cache_dir() == os.path.join(root, ".jax_cache")
    with open(os.path.join(root, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


# ---------------------------------------------------------------------------
# Multi-device sharded parity (subprocess — fake-device flag needs a fresh
# process, see test_prefill_parity.py)
# ---------------------------------------------------------------------------

KERNEL_SHARDED_SCRIPT = r"""
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
assert dict(mesh.shape) == {"data": 4, "model": 2}, mesh.shape
for arch in ("smollm-135m", "mamba2-780m"):
    cfg = dataclasses.replace(C.get_smoke(arch), vocab_size=512)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    ucfg = UncertaintyConfig(mode="distribution")
    base = InferenceEngine(arch, cfg, params, ucfg)
    engs = {impl: InferenceEngine(arch, cfg, params, ucfg, paged=True,
                                  block_len=16, pool_blocks=256, mesh=mesh,
                                  attn_decode_impl=impl)
            for impl in ("gather", "kernel")}
    prompts = pad_prompts(PROMPTS)
    r0 = base.generate(prompts, 6)
    rg = engs["gather"].generate(prompts, 6)
    rk = engs["kernel"].generate(prompts, 6)
    # kernel-first vs gathered-view on the SAME mesh: identical partitioned
    # reductions over elementwise-equal chunk streams -> exact
    np.testing.assert_array_equal(rg["tokens"], rk["tokens"])
    np.testing.assert_array_equal(np.asarray(rg["logits"]),
                                  np.asarray(rk["logits"]))
    # vs the single-device engine: tie-aware (sharded reductions carry
    # ~1 bf16 ulp, same noise class as the monolithic sharded path)
    l0, l1 = np.asarray(r0["logits"]), np.asarray(rk["logits"])
    for b in range(r0["tokens"].shape[0]):
        mism = np.where(r0["tokens"][b] != rk["tokens"][b])[0]
        n = mism[0] if len(mism) else r0["tokens"].shape[1]
        np.testing.assert_array_equal(r0["tokens"][b, :n],
                                      rk["tokens"][b, :n])
        np.testing.assert_allclose(l0[b, :n], l1[b, :n], atol=0.01, rtol=0)
        if len(mism):
            top2 = np.sort(l0[b, mism[0]])[-2:]
            assert top2[1] - top2[0] <= 0.02, (arch, b, mism[0], top2)
    print(arch, "ok", flush=True)
print("RESULT ok")
"""


def test_kernel_sharded_matches_gather_and_single_device():
    """Kernel-first on a real (data=4, model=2) fake-device mesh: exact
    parity with the gathered-view engine on the same mesh, tie-aware
    greedy parity with the single-device engine."""
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    proc = subprocess.run([sys.executable, "-c", KERNEL_SHARDED_SCRIPT],
                          env=env, capture_output=True, text=True,
                          timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "RESULT ok" in proc.stdout, proc.stdout
