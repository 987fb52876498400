"""Sharded-lowering tests on a small fake-device mesh.

These run in a SUBPROCESS because the XLA host-device-count flag must be set
before jax initialises (and must NOT leak into the other tests, which assume
1 device).  Mirrors what launch/dryrun.py does at 512 devices.
"""

import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, jax.numpy as jnp
from repro import configs as C
from repro.models import transformer as T
from repro.training import optimizer as opt, train as TR
from repro.distributed import sharding as sh
from jax.sharding import AxisType

mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                     axis_types=(AxisType.Auto,) * 3)
out = {}
for arch in %ARCHS%:
    cfg = C.get_smoke(arch)
    abs_p = T.abstract_params(cfg)
    step = TR.build_train_step(cfg, opt.AdamWConfig(), mesh, moe_groups=4)
    batch = {}
    B, S = 8, 32
    if cfg.family in ("encoder", "audio"):
        batch["frontend_embeds"] = jax.ShapeDtypeStruct((B, S, cfg.d_model), jnp.bfloat16)
        st = S
    elif cfg.frontend == "vision_patches":
        F = cfg.frontend_tokens
        batch["frontend_embeds"] = jax.ShapeDtypeStruct((B, F, cfg.d_model), jnp.bfloat16)
        batch["tokens"] = jax.ShapeDtypeStruct((B, S - F), jnp.int32)
        st = S - F
    else:
        batch["tokens"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
        st = S
    batch["labels"] = jax.ShapeDtypeStruct((B, st), jnp.int32)
    batch["loss_mask"] = jax.ShapeDtypeStruct((B, st), jnp.float32)
    with mesh:
        compiled = step.lower(abs_p, opt.abstract_state(abs_p), batch).compile()
    ca = compiled.cost_analysis()
    out[arch] = {"flops": ca.get("flops", 0.0)}
print("RESULT " + json.dumps(out))
"""


@pytest.mark.parametrize("archs", [
    ["smollm-135m", "mamba2-780m"],
    ["recurrentgemma-2b", "deepseek-moe-16b"],
])
def test_multipod_lowering_smokes(archs):
    script = SCRIPT.replace("%ARCHS%", json.dumps(archs))
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")]
    assert line, proc.stdout
    res = json.loads(line[0][len("RESULT "):])
    for arch in archs:
        assert res[arch]["flops"] > 0


def test_spec_builder_divisibility():
    """Non-divisible dims fall back to replication, never crash.

    spec_for only consults mesh.shape, so a lightweight stand-in lets us
    test production-sized (16, 16) axes on a 1-device container.
    """
    from types import SimpleNamespace
    from jax.sharding import PartitionSpec as P
    from repro.distributed import sharding as sh
    mesh = SimpleNamespace(shape={"data": 16, "model": 16})
    # 9 heads / 3 embed: neither divides 16 -> fully replicated
    assert sh.spec_for((9, 3), ("heads", "embed"), mesh,
                       sh.PARAM_RULES) == P()
    # 32 heads / 64 embed: both shard
    assert sh.spec_for((32, 64), ("heads", "embed"), mesh,
                       sh.PARAM_RULES) == P("model", "data")
    # KV-cache priority: 8 kv heads can't take 'model', seq dim does
    spec = sh.spec_for((128, 4096, 8, 128),
                       ("act_batch", "act_kv_seq", "act_kv_heads", None),
                       mesh, sh.ACT_RULES)
    assert spec == P("data", "model")
    # ...and heads win over seq when they divide
    spec = sh.spec_for((128, 4096, 16, 128),
                       ("act_batch", "act_kv_seq", "act_kv_heads", None),
                       mesh, sh.ACT_RULES)
    assert spec == P("data", None, "model")


def test_dryrun_artifacts_if_present():
    """If the sweep has run, every runnable cell must be ok on both meshes."""
    from repro import configs as C
    d = os.path.join(os.path.dirname(__file__), "..", "experiments",
                     "dryrun")
    if not os.path.isdir(d) or len(os.listdir(d)) < 80:
        pytest.skip("dry-run sweep artifacts not present")
    bad = []
    for arch, shape, skip in C.cells(include_skipped=True):
        for mesh in ("single", "multi"):
            p = os.path.join(d, f"{arch}__{shape}__{mesh}.json")
            if not os.path.exists(p):
                bad.append((arch, shape, mesh, "missing"))
                continue
            rec = json.load(open(p))
            if skip is None and not rec.get("ok"):
                bad.append((arch, shape, mesh, rec.get("error", "?")[:80]))
            if skip is not None and "skipped" not in rec:
                bad.append((arch, shape, mesh, "should be skipped"))
    assert not bad, bad
