"""The serving path's Pallas kernels compile for a TPU v5e chip.

Interpret mode checks what a kernel computes, not whether the TPU's
compiler accepts its block shapes (last two block dims multiples of
(8, 128) or whole), so these tests compile the kernels for a described
v5e chip at the published widths of the configs the chip smoke serves.
No chip is needed: the TPU compiler runs here on a described topology.
The topology is described inside a fixture, never at import, so every
test worker collects the same tests and only the one that runs this file
loads the TPU library.
"""

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro import configs as C
from repro.core.uncertainty import UncertaintyConfig
from repro.distributed import sharding as sh
from repro.kernels.decode_attention.kernel import paged_decode_attention_pallas
from repro.kernels.flash_attention.kernel import (
    flash_attention_positions_pallas)
from repro.kernels.swarm_uncertainty.kernel import uncertainty_pallas
from repro.models import transformer as T
from repro.serving import engine as E

ARCHS = ["smollm-135m", "swarm-edge-1b"]
BLOCK_LEN = 64          # the engine's default pool block
POOL_BLOCKS = 96        # blocks per layer pool in the mesh compiles


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler log files
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A v5e device to compile for, with the persistent compilation cache
    off: a described-chip executable is written there but cannot be read
    back without the chip."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, sharding, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _heads(arch):
    cfg = C.get_config(arch)
    K = cfg.num_kv_heads
    return K, cfg.num_heads // K, cfg.head_dim


@pytest.mark.parametrize("quant", [None, "int8"])
@pytest.mark.parametrize("arch", ARCHS)
def test_paged_decode_compiles(one_chip, arch, quant):
    """The block-table decode kernel (delta overlay on, bf16 or int8 pool
    with f32 scale rows) at the arch's KV geometry."""
    K, G, D = _heads(arch)
    B, N, nb, S = 4, 64, 8, 8
    pool_dt = jnp.int8 if quant else jnp.bfloat16

    def fn(q, kp, vp, pp, tbl, idx, ks, vs, dk, dv, dpos, p0):
        return paged_decode_attention_pallas(
            q, kp, vp, pp, tbl, idx, k_scale=ks if quant else None,
            v_scale=vs if quant else None, delta_k=dk, delta_v=dv,
            delta_pos=dpos, p0=p0, interpret=False)

    text = _compiled_text(
        fn, one_chip, ((B, K, G, D), jnp.bfloat16),
        ((N, BLOCK_LEN, K, D), pool_dt), ((N, BLOCK_LEN, K, D), pool_dt),
        ((N, BLOCK_LEN), jnp.int32), ((B, nb), jnp.int32),
        ((B,), jnp.int32), ((N, BLOCK_LEN, K), jnp.float32),
        ((N, BLOCK_LEN, K), jnp.float32), ((B, S, K, D), jnp.bfloat16),
        ((B, S, K, D), jnp.bfloat16), ((B, S), jnp.int32), ((B,), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("S,T", [(8, 8), (512, 512), (64, 576)])
@pytest.mark.parametrize("arch", ARCHS)
def test_flash_positions_compiles(one_chip, arch, S, T):
    """Positions-mode flash prefill: the smallest prompt bucket, a cold
    512-token bucket, and a continuation span over a 576-slot cache."""
    K, G, D = _heads(arch)

    def fn(q, k, v, qp, kp):
        return flash_attention_positions_pallas(
            q, k, v, q_positions=qp, kv_positions=kp, interpret=False)

    text = _compiled_text(
        fn, one_chip, ((1, S, K * G, D), jnp.bfloat16),
        ((1, T, K, D), jnp.bfloat16), ((1, T, K, D), jnp.bfloat16),
        ((S,), jnp.int32), ((T,), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("arch", ["smollm-135m", "mamba2-780m", "llama3-8b"])
def test_uncertainty_compiles(one_chip, arch):
    """The probe kernel over the arch's whole vocabulary (mamba2-780m's
    50280 has no multiple of 128 among its divisors: one whole-row tile)."""
    V = C.get_config(arch).vocab_size

    def fn(logits, tokens):
        return uncertainty_pallas(logits, tokens, interpret=False)

    text = _compiled_text(fn, one_chip, ((4, 8, V), jnp.float32),
                          ((4, 8), jnp.int32))
    assert "tpu_custom_call" in text


def _decode_dispatch(topo, shape, monkeypatch):
    """Lower the engine's kernel-first paged decode dispatch for llama3-8b
    (published widths, cut to 2 layers) on a described (data, model) v5e
    mesh, its operands placed as the engine places them.  The program asks
    ``jax.default_backend()`` which kernel to run; here that is the CPU, so
    the test answers for the chip it compiles for."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.asarray(topo.devices).reshape(shape), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    rules = sh.SERVE_RULES
    cfg = dataclasses.replace(C.get_config("llama3-8b"), num_layers=2)
    B, nb, steps = 8, 4, 8

    def placed(tree, axes, act):
        specs = sh.tree_specs(tree, axes, mesh,
                              rules.act_rules if act else rules.param_rules)
        return jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(mesh, s)), tree, specs)

    def rep(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt,
                                    sharding=NamedSharding(mesh, P()))

    params = placed(T.abstract_params(cfg), T.param_axes(cfg), act=False)
    pool = jax.eval_shape(
        lambda: T.init_block_pool(cfg, POOL_BLOCKS, BLOCK_LEN, 16))
    pool = placed(pool, T.paged_cache_axes(cfg)["layers"], act=True)
    cache = T.paged_cache(pool, rep((B, nb), jnp.int32), rep((B,), jnp.int32))
    return E._decode_scan_paged.lower(
        params, cfg, rep((B,), jnp.int32), rep((B, cfg.vocab_size),
                                               jnp.float32),
        cache, rep((B,), jnp.int32), rep((2,), jnp.uint32),
        UncertaintyConfig(mode="distribution"), steps, True,
        with_logits=False, impl="kernel", mesh=mesh, rules=rules)


def test_paged_decode_tensor_parallel_keeps_pool_local(topo, one_chip,
                                                       monkeypatch):
    """On a (1, 4) mesh the decode kernel runs per KV-head shard: the
    compiled dispatch holds the kernel and moves no pool block between
    devices (an all-gather of the pool would show as a bf16 pool-shaped
    all-gather)."""
    text = _decode_dispatch(topo, (1, 4), monkeypatch).compile().as_text()
    assert "tpu_custom_call" in text
    pool = re.compile(rf"= bf16\[\d+,{POOL_BLOCKS},{BLOCK_LEN},\d+,128\]\S* "
                      r"all-gather\(")
    gathers = [l for l in text.splitlines() if pool.search(l)]
    assert not gathers, gathers[:2]


def test_paged_decode_refuses_data_parallel_mesh(topo, one_chip,
                                                 monkeypatch):
    """With data > 1 the kernel would need every layer's whole pool on
    every device each step; the dispatch refuses to build instead."""
    with pytest.raises(NotImplementedError, match="shard-local pool blocks"):
        _decode_dispatch(topo, (2, 2), monkeypatch)
