"""The plain references against the serving program, on the CPU at smoke
configurations: in float32 they agree with the program's own forward pass
(so they compute the same model), and through the harness they accept what
the timed ``serve()`` produced."""
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.tests import smoke


@pytest.mark.parametrize("family", ["llama", "mamba2"])
def test_reference_equals_program_forward_in_f32(family, monkeypatch):
    from repro.models import attention, transformer as T
    # the program's chunked attention rounds q, k, v and p to bf16 whatever
    # the model dtype; its plain oracle keeps them in f32
    monkeypatch.setattr(
        attention, "chunked_attention",
        lambda q, k, v, q_block, kv_block, **kw:
            attention.plain_attention(q, k, v, **kw))
    conf = smoke._json(smoke.CONFIGS[family])
    fam = harness.family(conf)
    mcfg = dataclasses.replace(fam.model_config(conf), dtype=jnp.float32)
    params = fam.make_params(conf, mcfg, 2**32 + 3)
    toks = np.random.default_rng(0).integers(
        0, fam.token_range(conf), (2, 64)).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want, _ = T.forward(params, mcfg, {"tokens": jnp.asarray(toks)})
    h = fam.reference.hidden_batch(params, conf, toks)
    got = jnp.einsum("bsd,dv->bsv", h, fam.reference.head(params, conf),
                     precision="highest")
    spread = float(jnp.std(want))
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4 * max(spread, 1.0)


@pytest.mark.parametrize("family", ["llama", "mamba2"])
def test_served_tokens_pass_the_reference(family):
    out = harness.run_cell(smoke.cell(family), 2**31 + 99, 2.0, False,
                           t_start=time.perf_counter(), on_chip=False,
                           log=lambda *_: None)
    assert out["failed"] == 0
    assert out["correct"], out["checks"]
