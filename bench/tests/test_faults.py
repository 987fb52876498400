"""A run with the timed path broken underneath must come out not correct.

Each test skips the harness's look for a chip (``on_chip=False``), plants
one fault in the serving program for the length of one run at smoke size
on the CPU, and drives the rest of the run: set-up, the open-loop window,
the comparison with the reference.  The faults a serving cell can have:

* a token altered where it is produced (the decode chunk's output);
* a decode step that returns its state unchanged (the chunk's cache and
  state writes never reach the pool).

Half of a training batch and the exchange between chips do not exist in
these one-chip serving cells.
"""
import time

import jax
import pytest

from bench import harness
from bench.tests import smoke

SEED = 2**32 + 17


def _run(family):
    jax.clear_caches()
    try:
        return harness.run_cell(smoke.cell(family), SEED, 2.0, False,
                                t_start=time.perf_counter(), on_chip=False,
                                log=lambda *_: None)
    finally:
        jax.clear_caches()


def _assert_each_number_fails(out):
    """Not correct, and by each compared number alone: a cell that compared
    only one of them would still catch the fault."""
    assert not out["correct"]
    for name in ("widest_logit_gap", "u_rel_error"):
        c = out["checks"][name]
        assert c["value"] > c["limit"], (name, c)


@pytest.mark.parametrize("family", ["llama", "mamba2"])
def test_sound_run_is_correct(family):
    assert _run(family)["correct"]


@pytest.mark.parametrize("family", ["llama", "mamba2"])
def test_altered_token_is_caught(family, monkeypatch):
    from repro.serving import engine as E
    real = E._decode_scan_paged
    vocab = smoke._json(smoke.CONFIGS[family])["vocab_size"]

    def altered(*a, **kw):
        toks, *rest = real(*a, **kw)
        return (toks.at[:, 3].set((toks[:, 3] + 1) % vocab), *rest)
    monkeypatch.setattr(E, "_decode_scan_paged", altered)
    _assert_each_number_fails(_run(family))


@pytest.mark.parametrize("family", ["llama", "mamba2"])
def test_state_left_unchanged_is_caught(family, monkeypatch):
    from repro.models import transformer as T
    monkeypatch.setattr(T, "paged_scatter_decode",
                        lambda cfg, cache, delta, p0: cache["layers"])
    _assert_each_number_fails(_run(family))
