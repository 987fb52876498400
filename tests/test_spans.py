"""serve()'s host spans and the named scopes of the jitted programs.

The span recorder (``serving/spans.py``) nests, links each span to its
parent, keeps a bounded ring and reports self time; ``serve()`` records one
``serve.turn`` per loop turn with its phases in order, one ``serve.prefill``
per admission, and counts that add up to what it served; the prefill and
decode programs carry their phase names in the op metadata of their HLO,
where the chip's profiler finds them.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs as C
from repro.models import transformer as T
from repro.serving import engine as E
from repro.serving import spans as S
from repro.serving.scheduler import ContinuousBatcher, Request

CHUNK = 4


def _tick(monkeypatch):
    """A clock that advances 10 ns per reading."""
    clock = iter(range(0, 10**9, 10))
    monkeypatch.setattr(S.time, "perf_counter_ns", lambda: next(clock))


def test_spans_nest_link_and_time(monkeypatch):
    _tick(monkeypatch)
    sp = S.Spans()
    with sp.span("serve.turn", turn=3) as c:
        with sp.span("serve.admit", queued=2):
            pass
        with sp.span("serve.decode") as d:
            with sp.span("inner"):
                pass
            d.counts["steps"] = 8
        c.counts["admitted"] = 1
    with sp.span("serve.turn", turn=4):
        pass
    got = {s.name + str(s.counts.get("turn", "")): s for s in sp}
    turn, admit = got["serve.turn3"], got["serve.admit"]
    decode, inner = got["serve.decode"], got["inner"]
    assert [s.name for s in sp] == ["serve.admit", "inner", "serve.decode",
                                    "serve.turn", "serve.turn"]
    assert turn.parent is None and got["serve.turn4"].parent is None
    assert admit.parent == decode.parent == turn.id
    assert inner.parent == decode.id
    assert turn.counts == {"turn": 3, "admitted": 1}
    assert decode.counts == {"steps": 8}
    # each span reads the clock once on entry and once on exit
    assert (turn.dur_ns, admit.dur_ns, decode.dur_ns, inner.dur_ns) == \
        (70, 10, 30, 10)
    own = S.self_ns(sp)
    assert own[turn.id] == 70 - 10 - 30
    assert own[decode.id] == 30 - 10
    assert own[inner.id] == own[admit.id] == 10


def test_phases_follow_one_another(monkeypatch):
    _tick(monkeypatch)
    sp = S.Spans()
    more = iter([True, True, False])
    for turn in sp.turns(lambda: next(more)):
        turn.phase("serve.admit", queued=1)["queued"] += 1
        with sp.span("inner"):
            pass
        turn.phase("serve.sync")
        turn.counts["steps"] = 8
    spans = list(sp)
    assert [s.name for s in spans] == ["inner", "serve.admit", "serve.sync",
                                       "serve.turn"] * 2
    inner, admit, sync, turn = spans[:4]
    assert turn.counts == {"turn": 0, "steps": 8}
    assert spans[7].counts == {"turn": 1, "steps": 8}
    assert admit.counts == {"queued": 2}
    assert admit.parent == sync.parent == turn.id and inner.parent == admit.id
    # a phase ends where the next begins; the last ends with its turn
    assert admit.end_ns < sync.start_ns and sync.end_ns < turn.end_ns
    assert (turn.dur_ns, admit.dur_ns, inner.dur_ns, sync.dur_ns) == \
        (70, 30, 10, 10)
    assert S.self_ns(spans)[turn.id] == 70 - 30 - 10


def test_span_ring_is_bounded(monkeypatch):
    monkeypatch.setattr(S, "CAPACITY", 5)
    sp = S.Spans()
    for k in range(12):
        with sp.span("serve.turn", turn=k):
            pass
    assert [s.counts["turn"] for s in sp] == [7, 8, 9, 10, 11]


def test_span_survives_an_exception():
    sp = S.Spans()
    with pytest.raises(ValueError):
        with sp.span("serve.turn", turn=0):
            with sp.span("serve.admit"):
                raise ValueError("wedged")
    assert [s.name for s in sp] == ["serve.admit", "serve.turn"]
    with sp.span("serve.turn", turn=1):
        pass
    assert list(sp)[-1].parent is None


def test_phase_survives_an_exception():
    sp = S.Spans()
    with pytest.raises(ValueError):
        for turn in sp.turns(lambda: True):
            turn.phase("serve.admit")
            raise ValueError("wedged")
    assert [s.name for s in sp] == ["serve.admit", "serve.turn"]
    assert list(sp)[0].parent == list(sp)[1].id
    with sp.span("serve.turn", turn=1):
        pass
    assert list(sp)[-1].parent is None


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(C.get_smoke("mamba2-780m"), vocab_size=512)
    return cfg, T.init_params(cfg, jax.random.PRNGKey(0))


class _Counting(ContinuousBatcher):
    """Counts serve()'s loop turns: it calls admit() once per turn."""

    def __init__(self, n_slots):
        super().__init__(n_slots)
        self.turns = 0

    def admit(self, fits=None):
        self.turns += 1
        return super().admit(fits)


@pytest.fixture(scope="module", params=[False, True], ids=["mono", "paged"])
def served(request, model):
    cfg, params = model
    eng = E.InferenceEngine("m", cfg, params, paged=request.param,
                            block_len=16, max_len=128)
    rng = np.random.default_rng(0)
    b = _Counting(3)
    for i in range(7):
        b.submit(Request(rid=i, prompt=rng.integers(3, 500, int(
            rng.integers(4, 40))).tolist(), max_new=int(rng.integers(3, 20))))
    out = eng.serve(batcher=b, decode_chunk=CHUNK)
    return eng, b, out


def test_serve_records_one_turn_per_loop_turn(served):
    eng, b, _ = served
    spans = list(eng.spans)
    turns = [s for s in spans if s.name == "serve.turn"]
    assert len(turns) == b.turns > 1
    assert [t.counts["turn"] for t in turns] == list(range(b.turns))
    assert all(s.parent is not None for s in spans if s.name != "serve.turn")
    for t in turns:
        kids = [s.name for s in spans if s.parent == t.id]
        n = t.counts["admitted"]
        assert kids == (["serve.admit"] + ["serve.prefill"] * n
                        + ["serve.decode", "serve.sync", "serve.record",
                           "serve.retire"])
        assert t.counts["steps"] == CHUNK


def test_serve_counts_add_up(served):
    eng, _, out = served
    spans = list(eng.spans)
    pre = [s.counts for s in spans if s.name == "serve.prefill"]
    gran = max(eng.cfg.attn_q_block, eng.cfg.attn_kv_block)
    assert sorted(c["rid"] for c in pre) == list(range(7))
    assert all(not c["warm"] and 0 < c["prompt"] <= c["bucket"]
               and c["bucket"] == E.bucket_len(c["prompt"], gran)
               for c in pre)
    assert sum(t.counts["admitted"] for t in spans
               if t.name == "serve.turn") == 7
    decoded = sum(len(r["tokens"]) for r in out)
    assert sum(s.counts["tokens"] for s in spans
               if s.name == "serve.record") == decoded
    # every active slot takes one token per decode step
    turns = [s.counts for s in spans if s.name == "serve.turn"]
    assert sum(s.counts["steps"] for s in spans
               if s.name == "serve.decode") == CHUNK * len(turns)
    assert decoded <= sum(c["steps"] * c["active"] for c in turns)
    retired = [r for s in spans if s.name == "serve.retire"
               for r in s.counts["retired"]]
    assert sorted(retired) == list(range(7))


def _scopes(lowered) -> tuple[set, list]:
    txt = lowered.as_text(dialect="hlo", debug_info=True)
    names = re.findall(r'op_name="([^"]+)"', txt)
    scopes = {m for n in names for m in re.findall(r"(?:decode|prefill)\.\w+",
                                                   n)}
    topk = [re.search(r'op_name="([^"]+)"', line).group(1)
            for line in txt.splitlines() if " topk(" in line]
    return scopes, topk


@pytest.fixture(scope="module")
def paged_engine(model):
    cfg, params = model
    return E.InferenceEngine("m", cfg, params, paged=True, block_len=16,
                             max_len=64)


@pytest.mark.parametrize("impl,want", [
    ("kernel", {"decode.uncertainty", "decode.model", "decode.sample",
                "decode.cache_write"}),
    ("gather", {"decode.uncertainty", "decode.model", "decode.sample",
                "decode.cache_gather", "decode.cache_write"})])
def test_decode_program_names_its_phases(paged_engine, impl, want):
    eng = paged_engine
    B, nb = 2, eng.max_len // eng.block_len
    cache = eng._paged_dev_cache(
        np.arange(B * nb, dtype=np.int32).reshape(B, nb),
        np.arange(B, dtype=np.int32))
    lowered = E._decode_scan_paged.lower(
        eng.params, eng.cfg, jnp.zeros((B,), jnp.int32),
        jnp.zeros((B, eng.cfg.vocab_size), jnp.float32), cache,
        jnp.zeros((B,), jnp.int32), jax.random.PRNGKey(0), eng.ucfg, CHUNK,
        True, with_logits=False, impl=impl)
    scopes, topk = _scopes(lowered)
    assert scopes == want
    # Eq. 3's top-k of the logits (a full sort of each row once the chip's
    # compiler lowers it) belongs to the uncertainty terms
    assert topk and all("decode.uncertainty/" in n for n in topk)


def test_prefill_program_names_its_phases(paged_engine):
    eng = paged_engine
    nb = eng.max_len // eng.block_len
    cache = eng._paged_dev_cache(np.arange(nb, dtype=np.int32)[None],
                                 np.zeros((1,), np.int32))
    lowered = E._prefill_into_paged.lower(
        eng.params, eng.cfg, jnp.zeros((1, 32), jnp.int32), jnp.int32(20),
        cache)
    scopes, topk = _scopes(lowered)
    assert scopes == {"prefill.model", "prefill.sample",
                      "prefill.cache_gather", "prefill.cache_write"}
    assert not topk
