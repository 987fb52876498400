"""SSD decode-state kernel (``kernels/ssd_decode``), in interpret mode.

The kernel serves one SSD layer of one decode step for every slot: it reads
the slot's f32 state row where it lies in the stacked pool, steps it, writes
it back in place and emits ``y``.  Checked here on the CPU:

* it matches the oracle (``ref.ssd_step`` on gathered rows, the math
  ``ssm.ssd_decode`` runs) in the new state and in ``y``, at the smoke
  widths, with two groups, and at mamba2-780m's head geometry;
* it declares the pool aliased input to output and changes nothing but the
  live slots' rows of its layer;
* an idle slot's sentinel row id (``n_rows``) writes nowhere and is clipped
  onto no live row, row ``n_rows - 1`` included: in the kernel, in the CPU
  path, and through one ``serve()`` dispatch beside a live request;
* the engine counts the decode dispatches that step the state in place.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs as C
from repro.core.uncertainty import UncertaintyConfig
from repro.kernels.ssd_decode import ops
from repro.kernels.ssd_decode.kernel import ssd_state_update_pallas
from repro.kernels.ssd_decode.ref import ssd_state_update_ref, ssd_step
from repro.models import ssm
from repro.models import transformer as T
from repro.serving import engine as E
from repro.serving.scheduler import Request

# f32 state and readout: the kernel reduces y over N in another order than
# XLA's einsum, so y agrees to a few f32 ulps of its terms, not bitwise
TOL = dict(rtol=2e-5, atol=2e-5)

GEOMETRY = {
    # L, R, H, P, N, G, rows (R marks an idle slot)
    "smoke": (2, 5, 8, 16, 16, 1, [3, 0, 5, 2]),
    "groups": (2, 6, 8, 16, 16, 2, [6, 5, 1]),
    "mamba2-780m": (3, 6, 48, 64, 128, 1, [4, 6, 1, 5]),
    "one slot": (2, 3, 8, 16, 16, 1, [2]),
}


def _operands(L, R, H, P, N, G, rows, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    B = len(rows)
    return dict(
        pool=jax.random.normal(k[0], (L, R, H, P, N), jnp.float32),
        rows=jnp.asarray(rows, jnp.int32),
        dA=jax.random.uniform(k[1], (B, H), minval=0.5, maxval=1.0),
        dt=jax.random.uniform(k[2], (B, H), maxval=0.1),
        x=jax.random.normal(k[3], (B, H, P)),
        Bm=jax.random.normal(k[4], (B, G, N)),
        Cm=jax.random.normal(k[5], (B, G, N)),
        D=jax.random.normal(k[6], (H,)))


def _step_args(o):
    return o["dA"], o["dt"], o["x"], o["Bm"], o["Cm"], o["D"]


def _kernel(o, layer):
    return ssd_state_update_pallas(o["pool"], o["rows"], jnp.int32(layer),
                                   *_step_args(o), interpret=True)


@pytest.mark.parametrize("geometry", sorted(GEOMETRY))
def test_matches_oracle(geometry):
    """New state rows and ``y`` of the live slots match the oracle; an idle
    slot reads a fresh (zero) state."""
    L, R, H, P, N, G, rows = GEOMETRY[geometry]
    o = _operands(L, R, H, P, N, G, rows)
    layer = L - 1
    y, pool = _kernel(o, layer)
    y_ref, pool_ref = ssd_state_update_ref(o["pool"], o["rows"], layer,
                                           *_step_args(o))
    live = np.asarray(rows) < R
    np.testing.assert_allclose(np.asarray(y)[live],
                               np.asarray(y_ref)[live], **TOL)
    np.testing.assert_allclose(np.asarray(pool), np.asarray(pool_ref), **TOL)
    _, y_fresh = ssd_step(jnp.zeros((len(rows), H, P, N)), *_step_args(o))
    np.testing.assert_allclose(np.asarray(y)[~live],
                               np.asarray(y_fresh)[~live], **TOL)


def test_pool_updated_in_place():
    """The kernel aliases the pool operand to its pool output, and of the
    pool it changes only the live slots' rows of its own layer."""
    L, R, H, P, N, G, rows = GEOMETRY["smoke"]
    o = _operands(L, R, H, P, N, G, rows)
    jaxpr = jax.make_jaxpr(lambda o: _kernel(o, 1))(o)
    (call,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    pool_in = [i for i, v in enumerate(call.invars)
               if v.aval.shape == o["pool"].shape]
    assert tuple(call.params["input_output_aliases"]) == ((pool_in[0], 1),)
    _, pool = _kernel(o, 1)
    before, after = np.asarray(o["pool"]), np.asarray(pool)
    np.testing.assert_array_equal(after[0], before[0])
    stepped = sorted(r for r in rows if r < R)
    kept = [r for r in range(R) if r not in stepped]
    np.testing.assert_array_equal(after[1, kept], before[1, kept])
    assert all((after[1, r] != before[1, r]).any() for r in stepped)


@pytest.mark.parametrize("impl", ["kernel", "oracle"])
def test_sentinel_rows_write_nowhere(impl):
    """Idle slots (row id n_rows) beside one live slot leave every other
    row bitwise as it was, the last row n_rows - 1 among them."""
    L, R, H, P, N, G = 2, 5, 8, 16, 16, 1
    o = _operands(L, R, H, P, N, G, [R, 0, R])
    if impl == "kernel":
        _, pool = _kernel(o, 1)
    else:
        _, pool = ssd_state_update_ref(o["pool"], o["rows"], 1,
                                       *_step_args(o))
    before, after = np.asarray(o["pool"]), np.asarray(pool)
    np.testing.assert_array_equal(after[:, 1:], before[:, 1:])
    np.testing.assert_array_equal(after[0], before[0])
    assert (after[1, 0] != before[1, 0]).any()


def _smoke_cfg(vocab: int):
    return dataclasses.replace(C.get_smoke("mamba2-780m"), vocab_size=vocab)


def _ssd_leaves(layers) -> list:
    return [leaf for sc in layers for c in sc.values() if c.ssd is not None
            for leaf in c.ssd]


@pytest.mark.parametrize("impl,vocab", [("kernel", 320), ("oracle", 384)])
def test_serve_idle_slot_beside_live_request(impl, vocab, monkeypatch):
    """One serve() dispatch with an idle slot beside a live request: of
    every SSD pool leaf (state and conv tail) only the request's own row
    changes.  ``vocab`` keeps each variant's jitted programs its own."""
    if impl == "kernel":
        monkeypatch.setattr(ssm, "ssd_state_update", functools.partial(
            ops.ssd_state_update, force_pallas=True))
    cfg = _smoke_cfg(vocab)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    eng = E.InferenceEngine("ssd", cfg, params,
                            UncertaintyConfig(mode="distribution"),
                            paged=True, block_len=16, pool_rows=6)
    R = eng.pool.n_rows
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 64))
    planted = [sc if not any(c.ssd is not None for c in sc.values()) else
               {n: c._replace(ssd=jax.tree.map(
                   lambda a: jax.random.normal(next(keys), a.shape,
                                               a.dtype), c.ssd))
                for n, c in sc.items()}
               for sc in eng.pool.arrays]
    eng.pool.commit(planted)
    before = [np.asarray(a) for a in _ssd_leaves(planted)]
    fin = eng.serve([Request(rid=0, prompt=[3, 20, 195, 2], max_new=4)],
                    n_slots=2, decode_chunk=4)
    assert len(fin) == 1 and len(fin[0]["tokens"]) == 4
    after = [np.asarray(a) for a in _ssd_leaves(eng.pool.arrays)]
    changed = set()
    for b, a in zip(before, after):
        changed |= {r for r in range(R) if not np.array_equal(a[:, r],
                                                              b[:, r])}
    assert len(changed) == 1 and R - 1 not in changed, changed
    assert eng.counters["ssd_state_inplace"] == 1


def test_counter_counts_in_place_dispatches(monkeypatch):
    """``counters["ssd_state_inplace"]`` equals the decode dispatches of a
    kernel-first engine with SSD layers, and stays 0 for the gathered path
    and for a model without SSD layers."""
    calls = []
    real = E._decode_scan_paged

    def counted(*a, **kw):
        calls.append(kw.get("impl"))
        return real(*a, **kw)
    monkeypatch.setattr(E, "_decode_scan_paged", counted)
    prompts = [[3, 20, 195, 2], [7, 9, 2], [5, 6, 7, 8, 2]]
    ucfg = UncertaintyConfig(mode="distribution")
    mamba = _smoke_cfg(384)
    smollm = dataclasses.replace(C.get_smoke("smollm-135m"), vocab_size=384)
    for cfg, impl, inplace in ((mamba, "kernel", True),
                               (mamba, "gather", False),
                               (smollm, "kernel", False)):
        calls.clear()
        eng = E.InferenceEngine("e", cfg, T.init_params(
            cfg, jax.random.PRNGKey(0)), ucfg, paged=True, block_len=16,
            attn_decode_impl=impl)
        eng.serve([Request(rid=i, prompt=p, max_new=5 + 2 * i)
                   for i, p in enumerate(prompts)], n_slots=2,
                  decode_chunk=4)
        assert calls and all(c == impl for c in calls)
        assert eng.counters["ssd_state_inplace"] == (len(calls) if inplace
                                                     else 0)


def test_unstacked_stage_matches_gathered_path():
    """A one-layer model has an unstacked stage, whose state pool has no
    layer axis: the in-place path still matches the gathered path bit for
    bit, tokens and logits."""
    cfg = dataclasses.replace(_smoke_cfg(448), num_layers=1)
    assert cfg.stage_plan()[0].repeat == 1
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    ucfg = UncertaintyConfig(mode="distribution")
    prompts = np.asarray([[3, 20, 195, 2], [0, 7, 9, 2]], np.int32)
    out = {}
    for impl in ("kernel", "gather"):
        eng = E.InferenceEngine(impl, cfg, params, ucfg, paged=True,
                                block_len=16, attn_decode_impl=impl)
        out[impl] = eng.generate(prompts, 6)
    np.testing.assert_array_equal(out["kernel"]["tokens"],
                                  out["gather"]["tokens"])
    np.testing.assert_array_equal(np.asarray(out["kernel"]["logits"]),
                                  np.asarray(out["gather"]["logits"]))
