"""The readings of the program's own phase and scope names, on traces
recorded on the chip and on a small profile written here.

``data/trace_mamba2_chat_spans.json`` holds three turns of ``serve()``'s
loop of the mamba2-780m chat cell on one TPU v5e (turns 87-89 of a traced
run, cut at the harness's ``bench.admit.<k>`` spans), in the same form as
``data/trace_mamba2_chat.json``: every program execution, every device
operation not nested in another, and the host spans, here both the
harness's and the program's ``serve.*`` spans.  The profiler started
inside turn 87, so its ``serve.turn`` span was not recorded; turns 88 and
89 were recorded whole.  Expected idle times were read off the recorded
events by hand, by cutting each turn at every span and operation boundary
and naming each idle piece by the innermost span around its midpoint.

``data/trace_mamba2_chat_scopes.json`` holds every device operation, nested
ones too, that overlaps ``serve.turn.88`` of the same run, with the op name
its event metadata carried (``names``/``op_names`` are tables the rows
index: start ns, duration ns, name, op name).
"""
import json
import os

import pytest
from jax._src.profiler import ProfileData

from bench import phases, trace as tr
from repro.serving.spans import Span

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TURN = {88: 171569046.0, 89: 196455925.0}
# idle ns inside the harness's turns 88-89 and inside the serve.turn spans
IDLE_NS = {
    "harness": {"serve.prefill": 19959859.0, "serve.sync": 10460832.0,
                "serve.record": 639466.0, "serve.decode": 121960.0,
                "serve.retire": 118630.0, "serve.turn": 99380.0,
                "none": 96150.0, "serve.admit": 27040.0},
    "program": {"serve.prefill": 14121684.0, "serve.sync": 7134466.0,
                "serve.record": 492526.0, "serve.decode": 121949.0,
                "serve.retire": 118630.0, "serve.turn": 99380.0,
                "serve.admit": 27040.0, "none": 0.0}}


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "trace_mamba2_chat_spans.json")) as f:
        rows = json.load(f)
    return tr.reduce([tr.Event(*r) for r in rows])


def test_turns_are_the_whole_serve_turn_spans(recorded):
    got = phases.turns(recorded)
    assert [t.name for t, _ in got] == ["serve.turn.88", "serve.turn.89"]
    assert [t.dur_ns for t, _ in got] == [TURN[88], TURN[89]]
    tail = ["serve.decode", "serve.sync", "serve.record", "serve.retire"]
    assert [[k.name for k in kids] for _, kids in got] == [
        ["serve.admit", "serve.prefill"] + tail,
        ["serve.admit"] + ["serve.prefill"] * 3 + tail]


@pytest.mark.parametrize("turns", ["harness", "program"])
def test_idle_time_is_split_by_the_innermost_phase(recorded, turns):
    trace = tr.Trace(recorded.devices, recorded.host, recorded.turns)
    if turns == "program":
        trace.turns = {k: (t.start_ns, t.end_ns) for k, (t, _) in
                       zip(TURN, phases.turns(recorded))}
    got = phases.idle_by_phase(trace)
    assert got == pytest.approx({k: v / 1e9
                                 for k, v in IDLE_NS[turns].items()})
    assert list(got)[:2] == ["serve.prefill", "serve.sync"]
    # all of the turns' idle time, and all of it owned but 0.3%
    lo = min(s for s, _ in trace.turns.values())
    hi = max(e for _, e in trace.turns.values())
    idle = sum(e - s for s, e in trace.turns.values()) - sum(
        min(e, hi) - max(s, lo) for s, e in trace.devices[0].busy
        if e > lo and s < hi)
    assert sum(got.values()) == pytest.approx(idle / 1e9)
    assert got["none"] / sum(got.values()) < 0.005


def test_slowest_turn_of_the_recorded_spans(recorded):
    got = phases.slowest_turn(phases.trace_spans(recorded))
    assert got.startswith("slowest turn 89: serve.turn 196.456 ms; "
                          "serve.admit 0.027 ms; serve.prefill 10.620 ms; ")
    assert "serve.sync 166.079 ms; " in got
    assert got.endswith("; turns over 1000 ms: 0 of 2")


def test_slowest_turn_of_a_ring_shows_its_counts():
    ring = [Span(0, "serve.turn", 0, 2_000_000_000, None, {"turn": 0}),
            Span(1, "serve.sync", 0, 1_500_000_000, 0, {}),
            Span(2, "serve.turn", 2_000_000_000, 2_100_000_000, None,
                 {"turn": 1, "admitted": 2}),
            Span(3, "serve.prefill", 2_000_000_000, 2_040_000_000, 2,
                 {"rid": 7, "prompt": 300, "bucket": 512})]
    assert phases.slowest_turn(ring, over_ms=50) == (
        "slowest turn 0: serve.turn 2000.000 ms; serve.sync 1500.000 ms; "
        "own 500.000 ms; turns over 50 ms: 2 of 2")
    ring[0] = Span(0, "serve.turn", 0, 10, None, {"turn": 0})
    assert phases.slowest_turn(ring) == (
        "slowest turn 1: serve.turn 100.000 ms (admitted=2); "
        "serve.prefill 40.000 ms (rid=7, prompt=300, bucket=512); "
        "own 60.000 ms; turns over 1000 ms: 0 of 2")
    assert phases.slowest_turn([]) == "slowest turn: no serve.turn span"


def test_scope_self_time_of_a_recorded_turn(recorded):
    with open(os.path.join(DATA, "trace_mamba2_chat_scopes.json")) as f:
        d = json.load(f)
    plane = d["plane"]
    ops = [tr.Event(plane, "XLA Ops", d["names"][n], float(s), float(dur))
           for s, dur, n, _ in d["ops"]]
    names = {(plane, d["names"][n]): d["op_names"][k]
             for _, _, n, k in d["ops"]}
    host = [e for e in recorded.host if e.name == "serve.turn.88"]
    trace = tr.reduce(ops + host)
    trace.turns = {88: (host[0].start_ns, host[0].end_ns)}
    got = phases.scope_self_s(trace, names)
    assert list(got)[:4] == ["decode.model", "decode.cache_write", "none",
                             "decode.uncertainty"]
    # Eq. 2-3's terms: 1.656 ms of each of the turn's 8 decode steps
    assert got["decode.uncertainty"] == pytest.approx(13.248456e-3)
    # self times add up to the operations not nested in another, which the
    # spans trace holds on its own
    top = [o for _, o in recorded.in_turns(recorded.devices[0].ops)
           if trace.turns[88][0] <= o.start_ns < trace.turns[88][1]]
    assert len(top) == 96
    assert sum(got.values()) == pytest.approx(
        sum(o.dur_ns for o in top) / 1e9)


_XSPACE = '''
planes {
  id: 1
  name: "/host:CPU"
  lines {
    id: 1
    name: "python3"
    timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 100000000 }
    events { metadata_id: 2 offset_ps: 50000000 duration_ps: 40000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "serve.turn.4" } }
  event_metadata { key: 2 value { id: 2 name: "serve.sync" } }
}
planes {
  id: 2
  name: "/device:TPU:0"
  lines {
    id: 1
    name: "XLA Ops"
    timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 10000000 duration_ps: 50000000 }
    events { metadata_id: 2 offset_ps: 20000000 duration_ps: 20000000 }
    events { metadata_id: 3 offset_ps: 70000000 duration_ps: 10000000 }
    events { metadata_id: 4 offset_ps: 200000000 duration_ps: 10000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%while.1 = while()"
    stats { metadata_id: 9 str_value: "jit(f)/while" } } }
  event_metadata { key: 2 value { id: 2 name: "%sort.6 = sort()"
    stats { metadata_id: 8 str_value: "x" }
    stats { metadata_id: 9 str_value: "jit(f)/while/decode.uncertainty/sort" } } }
  event_metadata { key: 3 value { id: 3 name: "%copy.2 = copy()" } }
  event_metadata { key: 4 value { id: 4 name: "%dot.3 = dot()"
    stats { metadata_id: 9 str_value: "jit(f)/decode.model/dot" } } }
  stat_metadata { key: 8 value { id: 8 name: "other" } }
  stat_metadata { key: 9 value { id: 9 name: "tf_op" } }
}
'''


def test_report_reads_a_profile(tmp_path):
    raw = ProfileData.text_proto_to_serialized_xspace(_XSPACE)
    assert phases.op_names(raw) == {
        ("/device:TPU:0", "%while.1 = while()"): "jit(f)/while",
        ("/device:TPU:0", "%sort.6 = sort()"):
            "jit(f)/while/decode.uncertainty/sort",
        ("/device:TPU:0", "%copy.2 = copy()"): "",
        ("/device:TPU:0", "%dot.3 = dot()"): "jit(f)/decode.model/dot"}
    path = tmp_path / "plugins" / "profile" / "x" / "h.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(raw)
    # the turn runs 1-101 us: the loop (30 us of its own), its sort (20)
    # and the copy (10) fall in it, the dot at 200 us does not; the device
    # idles 1-10 and 90-101 us under the turn alone, 60-70 and 80-90 under
    # serve.sync (50-90)
    assert phases.report(str(tmp_path)) == [
        "whole turns: 1",
        "device self time by scope, ms: none 0.040000, "
        "decode.uncertainty 0.020000",
        "device idle time by phase, ms: serve.turn 0.020000, "
        "serve.sync 0.020000, none 0.000000",
        "slowest turn 4: serve.turn 0.100 ms; serve.sync 0.040 ms; "
        "own 0.060 ms; turns over 1000 ms: 0 of 1"]
