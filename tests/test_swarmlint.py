"""Golden-violation fixtures for every swarmlint rule (ISSUE 9).

Each AST rule has (a) a minimal fixture that MUST flag and (b) a
near-miss that MUST NOT — the near-misses are the idioms the serving
stack actually uses (donate-and-rebind, split-and-rebind, static-arg
branches, cfg.dtype allocation), so these tests pin the rules' false-
positive behaviour, not just their recall.  Pragma handling
(``# swarmlint: ignore[rule-id] justification``) is covered for the
same fixtures, and the abstract-eval probes run against the real tree
(they must stay green — the CI gate).
"""

import json
import subprocess
import sys

import pytest

from tools.swarmlint.rules import run_ast_rules


def _lint(tmp_path, source, relpath="serving/mod.py"):
    path = tmp_path / relpath
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(source)
    return run_ast_rules([str(path)])


def _active(findings, rule):
    return [f for f in findings if f.rule == rule and not f.suppressed]


# ---------------------------------------------------------------------------
# donation-reuse

DONATE_HEADER = """
import jax
from functools import partial

@partial(jax.jit, donate_argnames=("cache",))
def step(x, cache):
    return x + 1, cache
"""


class TestDonationReuse:
    def test_flags_reuse_after_donation(self, tmp_path):
        src = DONATE_HEADER + """
def caller(x, cache):
    y, new_cache = step(x, cache)
    return y + cache.sum()          # cache buffer is gone
"""
        fs = _active(_lint(tmp_path, src), "donation-reuse")
        assert len(fs) == 1 and "cache" in fs[0].message

    def test_flags_reuse_in_later_statement(self, tmp_path):
        src = DONATE_HEADER + """
def caller(x, cache):
    y, new_cache = step(x, cache)
    z = y * 2
    commit(cache)                   # still dead
"""
        assert len(_active(_lint(tmp_path, src), "donation-reuse")) == 1

    def test_near_miss_rebind_same_statement(self, tmp_path):
        src = DONATE_HEADER + """
def caller(x, cache):
    y, cache = step(x, cache)       # donate-and-rebind idiom
    return y + cache.sum()
"""
        assert _active(_lint(tmp_path, src), "donation-reuse") == []

    def test_near_miss_rebind_in_loop(self, tmp_path):
        src = DONATE_HEADER + """
def caller(x, cache):
    for _ in range(4):
        x, cache = step(x, cache)
    return x, cache
"""
        assert _active(_lint(tmp_path, src), "donation-reuse") == []

    def test_flags_cross_iteration_reuse(self, tmp_path):
        src = DONATE_HEADER + """
def caller(x, cache):
    for _ in range(4):
        x, _new = step(x, cache)    # cache dead on iteration 2
    return x
"""
        assert len(_active(_lint(tmp_path, src), "donation-reuse")) >= 1

    def test_near_miss_undonated_function(self, tmp_path):
        src = """
import jax
from functools import partial

@partial(jax.jit, static_argnames=("n",))
def step(x, cache, n):
    return x + n, cache

def caller(x, cache):
    y, new_cache = step(x, cache, 2)
    return y + cache.sum()          # no donation: reuse is fine
"""
        assert _active(_lint(tmp_path, src), "donation-reuse") == []


class TestDonationDup:
    def test_flags_duplicate_and_unknown_and_static(self, tmp_path):
        src = """
import jax
from functools import partial

@partial(jax.jit, donate_argnames=("cache", "cache"))
def a(x, cache):
    return x, cache

@partial(jax.jit, donate_argnames=("bogus",))
def b(x, cache):
    return x, cache

@partial(jax.jit, static_argnames=("cfg",), donate_argnames=("cfg",))
def c(x, cfg):
    return x
"""
        fs = _active(_lint(tmp_path, src), "donation-dup")
        msgs = "\n".join(f.message for f in fs)
        assert len(fs) == 3
        assert "more than once" in msgs and "not a parameter" in msgs \
            and "static" in msgs

    def test_near_miss_clean_declaration(self, tmp_path):
        src = DONATE_HEADER
        assert _active(_lint(tmp_path, src), "donation-dup") == []


# ---------------------------------------------------------------------------
# global-rng

class TestGlobalRng:
    def test_flags_np_global_and_stdlib(self, tmp_path):
        src = """
import random
import numpy as np

def admit(xs):
    np.random.seed(0)
    random.shuffle(xs)
    return np.random.randint(0, 4)
"""
        fs = _active(_lint(tmp_path, src), "global-rng")
        assert len(fs) == 3

    def test_near_miss_seeded_generators(self, tmp_path):
        src = """
import numpy as np

def admit(xs, seed):
    rs = np.random.RandomState(seed)       # owned, seeded: fine
    g = np.random.default_rng(seed)
    return rs.randint(0, 4) + int(g.integers(0, 4))
"""
        assert _active(_lint(tmp_path, src), "global-rng") == []

    def test_near_miss_outside_serving_dirs(self, tmp_path):
        src = """
import numpy as np

def make_dataset():
    np.random.seed(0)                      # benchmarks etc: allowed
    return np.random.randn(4)
"""
        fs = _lint(tmp_path, src, relpath="training/data.py")
        assert _active(fs, "global-rng") == []


# ---------------------------------------------------------------------------
# key-reuse

class TestKeyReuse:
    def test_flags_key_reused_across_iterations(self, tmp_path):
        src = """
import jax

def gen(rng, steps):
    out = []
    for _ in range(steps):
        out.append(sample(rng))            # same key every step
    return out
"""
        fs = _active(_lint(tmp_path, src), "key-reuse")
        assert len(fs) == 1 and "rng" in fs[0].message

    def test_flags_key_consumed_twice_sequentially(self, tmp_path):
        src = """
import jax

def gen(rng):
    a = sample(rng)
    b = sample(rng)                        # second draw, same key
    return a, b
"""
        assert len(_active(_lint(tmp_path, src), "key-reuse")) == 1

    def test_near_miss_split_and_rebind(self, tmp_path):
        src = """
import jax

def gen(rng, steps):
    out = []
    for _ in range(steps):
        rng, sub = jax.random.split(rng)   # consume-and-rebind idiom
        out.append(sample(sub))
    return out
"""
        assert _active(_lint(tmp_path, src), "key-reuse") == []

    def test_near_miss_carry_rebind(self, tmp_path):
        src = """
import jax

def serve(seed, chunks):
    rng = jax.random.PRNGKey(seed)
    for _ in range(chunks):
        toks, carry = decode(rng)
        cur, rng = carry                   # rebound from the carry
    return toks
"""
        assert _active(_lint(tmp_path, src), "key-reuse") == []

    def test_near_miss_split_into_key_array(self, tmp_path):
        src = """
import jax

def fan_out(rng, n):
    keys = jax.random.split(rng, n)        # key ARRAY: rows used one-off
    return [sample(keys[i]) for i in range(n)]
"""
        assert _active(_lint(tmp_path, src), "key-reuse") == []


# ---------------------------------------------------------------------------
# tracer-leak

class TestTracerLeak:
    def test_flags_branch_on_traced_value(self, tmp_path):
        src = """
import jax
from functools import partial

@partial(jax.jit, static_argnames=("greedy",))
def step(x, greedy):
    if x.sum() > 0:                        # traced condition
        return x
    return -x
"""
        fs = _active(_lint(tmp_path, src), "tracer-leak")
        assert len(fs) == 1 and "if" in fs[0].message

    def test_flags_host_conversions(self, tmp_path):
        src = """
import jax
import numpy as np

@jax.jit
def step(x):
    n = int(x[0])                          # host sync
    y = np.asarray(x)                      # host materialisation
    z = x.item()                           # device sync
    return n + z, y
"""
        assert len(_active(_lint(tmp_path, src), "tracer-leak")) == 3

    def test_near_miss_static_branches(self, tmp_path):
        src = """
import jax
from functools import partial

@partial(jax.jit, static_argnames=("greedy", "cfg"))
def step(x, greedy, cfg):
    B, S = x.shape
    if greedy:                             # static arg
        return x
    if S > 4:                              # shape is static
        return x * 2
    if cfg.window is not None:             # static arg attribute
        return x * 3
    n = int(x.shape[0])                    # shape access, not a tracer
    return x + n
"""
        assert _active(_lint(tmp_path, src), "tracer-leak") == []

    def test_near_miss_unjitted_function(self, tmp_path):
        src = """
def host_loop(x):
    if x.sum() > 0:                        # not jitted: fine
        return x
    return -x
"""
        assert _active(_lint(tmp_path, src), "tracer-leak") == []


# ---------------------------------------------------------------------------
# dtype-drift

class TestDtypeDrift:
    def test_flags_f32_cache_alloc(self, tmp_path):
        src = """
import jax.numpy as jnp

def init_cache(cfg, batch):
    return jnp.zeros((batch, 4), jnp.float32)
"""
        fs = _active(_lint(tmp_path, src, relpath="models/m.py"),
                     "dtype-drift")
        assert len(fs) == 1 and "float32" in fs[0].message

    def test_flags_missing_dtype(self, tmp_path):
        src = """
import jax.numpy as jnp

def init_cache(cfg, batch):
    return jnp.zeros((batch, 4))           # defaults to f32
"""
        assert len(_active(_lint(tmp_path, src, relpath="models/m.py"),
                           "dtype-drift")) == 1

    def test_near_miss_cfg_dtype_and_ints(self, tmp_path):
        src = """
import jax.numpy as jnp

def init_cache(cfg, batch):
    k = jnp.zeros((batch, 4), cfg.dtype)
    pos = jnp.full((batch,), -1, jnp.int32)
    return k, pos
"""
        assert _active(_lint(tmp_path, src, relpath="models/m.py"),
                       "dtype-drift") == []

    def test_near_miss_non_init_function(self, tmp_path):
        src = """
import jax.numpy as jnp

def softmax_stream(x):
    acc = jnp.zeros(x.shape, jnp.float32)  # one-step accumulator: fine
    return acc
"""
        assert _active(_lint(tmp_path, src, relpath="models/m.py"),
                       "dtype-drift") == []


# ---------------------------------------------------------------------------
# quant-scale-drift

class TestQuantScaleDrift:
    def test_flags_narrow_scale_alloc(self, tmp_path):
        src = """
import jax.numpy as jnp

def grow_pool(n, L, K):
    k_scale = jnp.zeros((n, L, K), jnp.bfloat16)
    return k_scale
"""
        fs = _active(_lint(tmp_path, src, relpath="serving/m.py"),
                     "quant-scale-drift")
        assert len(fs) == 1 and "float32" in fs[0].message

    def test_flags_scale_cast_narrow(self, tmp_path):
        src = """
import jax.numpy as jnp

def pack(pool):
    return pool.k_scale.astype(jnp.bfloat16)
"""
        assert len(_active(_lint(tmp_path, src, relpath="models/m.py"),
                           "quant-scale-drift")) == 1

    def test_flags_f32_dequantize_rows(self, tmp_path):
        src = """
import jax.numpy as jnp
from repro.models.quant import dequantize_rows

def view(q, scale):
    return dequantize_rows(q, scale, jnp.float32)
"""
        fs = _active(_lint(tmp_path, src, relpath="serving/m.py"),
                     "quant-scale-drift")
        assert len(fs) == 1 and "accumulator" in fs[0].message

    def test_flags_manual_f32_dequant_multiply(self, tmp_path):
        src = """
import jax.numpy as jnp

def attend(q_rows, k_scale):
    k = q_rows.astype(jnp.float32) * k_scale[..., None]
    return k
"""
        assert len(_active(_lint(tmp_path, src, relpath="models/m.py"),
                           "quant-scale-drift")) == 1

    def test_near_miss_accumulator_fused_scale(self, tmp_path):
        # the sanctioned fused-dequant shape: scores already f32 from
        # preferred_element_type, scale applied WITHOUT an .astype(f32)
        src = """
import jax.numpy as jnp

def stream_chunk(s, k_s, v_s, p):
    s = s * k_s.transpose(0, 2, 1)[:, :, None, :]
    p = p * v_s.transpose(0, 2, 1)[:, :, None, :]
    return s, p
"""
        assert _active(_lint(tmp_path, src, relpath="models/m.py"),
                       "quant-scale-drift") == []

    def test_near_miss_f32_scale_alloc_and_cache_dtype_dequant(self, tmp_path):
        src = """
import jax.numpy as jnp
from repro.models.quant import dequantize_rows

def grow_pool(n, L, K, view_dtype):
    v_scale = jnp.zeros((n, L, K), jnp.float32)  # swarmlint: ignore[dtype-drift] scales are f32 by contract
    return v_scale

def view(q, scale, view_dtype):
    return dequantize_rows(q, scale, view_dtype)
"""
        assert _active(_lint(tmp_path, src, relpath="serving/m.py"),
                       "quant-scale-drift") == []

    def test_near_miss_outside_serving_dirs(self, tmp_path):
        src = """
import jax.numpy as jnp

def plot(q, scale):
    return q.astype(jnp.float32) * scale
"""
        assert _active(_lint(tmp_path, src, relpath="benchmarks/b.py"),
                       "quant-scale-drift") == []


# ---------------------------------------------------------------------------
# pragmas

class TestPragmas:
    FLAGGING = """
import jax.numpy as jnp

def init_cache(cfg, batch):
    return jnp.zeros((batch, 4), jnp.float32){pragma}
"""

    def test_same_line_pragma_suppresses(self, tmp_path):
        src = self.FLAGGING.format(
            pragma="  # swarmlint: ignore[dtype-drift] f32 accumulator")
        fs = _lint(tmp_path, src, relpath="models/m.py")
        assert _active(fs, "dtype-drift") == []
        sup = [f for f in fs if f.suppressed]
        assert len(sup) == 1 and sup[0].justification == "f32 accumulator"

    def test_standalone_pragma_suppresses_next_code_line(self, tmp_path):
        src = """
import jax.numpy as jnp

def init_cache(cfg, batch):
    # swarmlint: ignore[dtype-drift] recurrence drifts in bf16
    # (continuation comment lines are skipped)
    return jnp.zeros((batch, 4), jnp.float32)
"""
        fs = _lint(tmp_path, src, relpath="models/m.py")
        assert _active(fs, "dtype-drift") == []

    def test_pragma_without_justification_is_bad_and_inert(self, tmp_path):
        src = self.FLAGGING.format(pragma="  # swarmlint: ignore[dtype-drift]")
        fs = _lint(tmp_path, src, relpath="models/m.py")
        assert len(_active(fs, "dtype-drift")) == 1      # not suppressed
        assert len(_active(fs, "bad-pragma")) == 1

    def test_pragma_for_other_rule_does_not_suppress(self, tmp_path):
        src = self.FLAGGING.format(
            pragma="  # swarmlint: ignore[key-reuse] wrong rule id")
        fs = _lint(tmp_path, src, relpath="models/m.py")
        assert len(_active(fs, "dtype-drift")) == 1


# ---------------------------------------------------------------------------
# the real tree + the probes (the CI gate)

class TestRepoIsClean:
    def test_ast_rules_green_on_src(self):
        fs = [f for f in run_ast_rules(["src/repro"]) if not f.suppressed]
        assert fs == [], "\n".join(f"{f.location()} {f.rule} {f.message}"
                                   for f in fs)

    def test_every_suppression_has_a_justification(self):
        for f in run_ast_rules(["src/repro"]):
            if f.suppressed:
                assert f.justification, f.location()

    def test_cheap_probes_green(self):
        # shard-coverage walks config metadata; pallas-grid is pure python
        from tools.swarmlint.probes import run_probes
        fs = run_probes(only={"shard-coverage", "pallas-grid"})
        assert fs == [], "\n".join(f.message for f in fs)

    def test_pallas_grid_flags_untileable_blocks(self, monkeypatch):
        # the old divisor-only policy picks a 148-row key tile for a
        # 2368-slot cache: it divides, but the TPU refuses the block
        from repro.kernels import blocking
        from tools.swarmlint.probes import run_probes
        monkeypatch.setattr(blocking, "flash_blocks", lambda S, T: (
            blocking.snap_block(S, 256, 1), blocking.snap_block(T, 256, 1)))
        fs = run_probes(only={"pallas-grid"})
        assert any("T=2368" in f.message for f in fs), fs

    @pytest.mark.slow
    def test_abstract_probes_green(self):
        # decode-dtype eval-shapes every arch; donation-alias lowers the
        # paged entry points — slower, still device-free
        from tools.swarmlint.probes import run_probes
        fs = run_probes(only={"decode-dtype", "donation-alias"})
        assert fs == [], "\n".join(f.message for f in fs)

    def test_cli_json_output(self):
        out = subprocess.run(
            [sys.executable, "-m", "tools.swarmlint", "--no-probes",
             "--json"],
            capture_output=True, text=True)
        assert out.returncode == 0, out.stdout + out.stderr
        payload = json.loads(out.stdout)
        assert payload["counts"]["active"] == 0
        assert payload["counts"]["suppressed"] >= 5
