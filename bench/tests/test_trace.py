"""The trace reduction on a trace recorded on the chip.

``data/trace_mamba2_chat.json`` holds three turns of ``serve()``'s loop of
the mamba2-780m chat cell on one TPU v5e, traced through the harness's
batcher: every program execution, every device operation not nested in
another (their union is the busy time of all of them), and the harness's
host spans.  Expected numbers were read off the recorded events by hand.
"""
import json
import os
import types

import pytest

from bench import readings, trace as tr
from bench.openloop import Chunk

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
ADMIT = {15: 41111076.0, 16: 215012624.0, 17: 398594420.0,
         18: 588875212.0}
DECODE_NS = (158217178.0, 158226172.0, 158229323.0)
PREFILL_NS = (5821080.0, 4266618.0, 4265018.0, 24224886.0)


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "trace_mamba2_chat.json")) as f:
        rows = json.load(f)
    return tr.reduce([tr.Event(*r) for r in rows])


def _run(trace, chunks):
    cell = types.SimpleNamespace(settings={"engine": {"decode_chunk": 8}},
                                 config={})
    return types.SimpleNamespace(trace=trace, chunks=chunks, cell=cell,
                                 trace_window_s=(ADMIT[18] - ADMIT[15]) / 1e9,
                                 peak=None, fam=None)


def test_turns_are_cut_at_the_admission_spans(recorded):
    assert recorded.turns == {k: (ADMIT[k], ADMIT[k + 1])
                              for k in (15, 16, 17)}


def test_programs_fall_in_their_turns(recorded):
    dec = recorded.modules(readings.DECODE_MODULE)
    assert [k for k, _ in dec] == [15, 16, 17]
    assert tuple(m.dur_ns for _, m in dec) == DECODE_NS
    pre = recorded.modules(readings.PREFILL_MODULE)
    assert [k for k, _ in pre] == [15, 16, 16, 17]
    assert tuple(m.dur_ns for _, m in pre) == PREFILL_NS


def test_busy_time_is_the_union_of_operations(recorded):
    assert recorded.busy_ns() == 514559465.0


def test_longest_idle_gaps_are_named_by_the_host(recorded):
    gaps = tr.idle_gaps(recorded, n=3)
    assert [round(s * 1e9) for _, s in gaps] == [4943574, 4386297, 2548762]
    assert all(isinstance(name, str) and name for name, _ in gaps)


def test_readers_on_the_recorded_turns(recorded):
    from bench.metrics import decode_step_ms, device_idle_share
    from bench.metrics import prefill_ms_per_ktok
    chunks = [Chunk(15, [(0, 100)]), Chunk(16, [(1, 50), (2, 50)]),
              Chunk(17, [(3, 1000)])]
    run = _run(recorded, chunks)
    assert decode_step_ms.read(run) == pytest.approx(
        sum(DECODE_NS) / 1e6 / 24)
    assert prefill_ms_per_ktok.read(run) == pytest.approx(
        sum(PREFILL_NS) / 1e6 / 1.2)
    assert device_idle_share.read(run) == pytest.approx(
        100 * (1 - 514559465.0 / (ADMIT[18] - ADMIT[15])))


def test_readers_find_nothing_without_a_trace():
    from bench.metrics import decode_step_ms, flash_attention_roofline
    run = _run(None, [])
    assert decode_step_ms.read(run) is None
    run.fam = types.SimpleNamespace(kv_shape=lambda conf: None)
    assert flash_attention_roofline.read(run) is None


def test_kernel_names_as_the_chip_writes_them():
    import re
    ops = ["%paged_decode_attention.8 = bf16[32,3,3,64]{3,2,1,0:T(4,128)(2,1)"
           "S(1)} custom-call(s32[32,",
           "%copy.216 = bf16[32,3,3,64]{3,0,2,1:T(8,128)(2,1)S(1)} copy("
           "bf16[32,3,3,64] %paged_decode_attention.8)"]
    hits = [bool(re.search(readings.DECODE_KERNEL, o)) for o in ops]
    assert hits == [True, False]
    assert tr.op_label(ops[0]) == \
        "%paged_decode_attention.8 = bf16[32,3,3,64] custom-call"
