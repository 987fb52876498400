"""Where ``serve()``'s time went, read from the serving program's own names.

The program marks its phases (``repro/serving/spans.py``): ``serve.turn.<k>``
around each turn of ``serve()``'s loop and, inside it, ``serve.admit``, one
``serve.prefill`` per admission, ``serve.decode``, ``serve.sync`` (the
read-back of the turn's tokens), ``serve.record`` and ``serve.retire``.  They
are kept in a ring on the engine (``InferenceEngine.spans``) and, while a
profiler runs, land in its trace as host spans.  Every device operation of
the jitted programs carries its named scope (``decode.uncertainty``,
``prefill.model``, ...) in its op name, which the trace keeps in the
``tf_op`` stat of the operation's event metadata.

For a profile of a serving process (``jax.profiler.trace(<dir>)`` around
``engine.serve(...)``)::

    python3 -m bench.phases <trace dir or .xplane.pb>

prints the device self time by named scope and the device's idle time by
the ``serve.*`` phase the host was in, both over the turns the trace holds
whole, and the slowest of those turns with its phases.
:func:`slowest_turn` reads the engine's ring the same way, in the serving
process itself.  On a program without these names the readings find
nothing to split by.
"""
from __future__ import annotations

import bisect
import os
import re
import sys

import bench

_PHASE = re.compile(r"^serve\.[a-z]+")
_TURN = re.compile(r"^serve\.turn\.(\d+)$")
_SCOPE = re.compile(r"(?:^|/)((?:decode|prefill)\.\w+)(?=/|$)")
OP_NAME_STAT = "tf_op"      # event-metadata stat that holds the op name


def turns(trace) -> list:
    """[(serve.turn span, [the serve.* spans inside it])] of every turn
    the trace holds whole: a span is recorded only if the profiler ran
    when it began and when it ended."""
    spans = sorted((e for e in trace.host if _PHASE.match(e.name)),
                   key=lambda e: (e.start_ns, -e.dur_ns))
    out = []
    for i, t in enumerate(spans):
        if _TURN.match(t.name):
            kids = []
            for e in spans[i + 1:]:
                if e.start_ns >= t.end_ns:
                    break
                kids.append(e)
            out.append((t, kids))
    return out


def _segments(host) -> list:
    """[(start, end, phase)] of the host's time under ``serve.*`` spans,
    each piece under its innermost span, in time order.  The spans of one
    thread nest, so a sweep over their ends keeps the innermost on top."""
    ends = []
    for ev in host:
        m = _PHASE.match(ev.name)
        if m:
            ends += [(ev.start_ns, 1, -ev.end_ns, m.group(0)),
                     (ev.end_ns, 0, 0.0, "")]
    segs, stack, t = [], [], None
    for at, opens, _, phase in sorted(ends):
        if stack and at > t:
            segs.append((t, at, stack[-1]))
        if opens:
            stack.append(phase)
        elif stack:
            stack.pop()
        t = at
    return segs


def idle_by_phase(trace) -> dict:
    """{serve.* phase: seconds} of the first device's idle time inside the
    trace's turns, each idle piece split exactly by the innermost
    ``serve.*`` span the host was in, "none" where it was in none; largest
    first."""
    if not trace.devices:
        return {}
    busy = trace.devices[0].busy
    segs = _segments(trace.host)
    starts = [s for s, _, _ in segs]
    out: dict[str, float] = {"none": 0.0}
    for ts, te in sorted(trace.turns.values()):
        idle, t = [], ts
        for s, e in busy[max(bisect.bisect_left(busy, (ts,)) - 1, 0):]:
            if s >= te:
                break
            if s > t:
                idle.append((t, s))
            t = max(t, e)
        if t < te:
            idle.append((t, te))
        for a, b in idle:
            covered = 0.0
            for s, e, phase in segs[max(bisect.bisect_right(starts, a) - 1,
                                        0):]:
                if s >= b:
                    break
                ov = min(b, e) - max(a, s)
                if ov > 0:
                    out[phase] = out.get(phase, 0.0) + ov
                    covered += ov
            out["none"] += b - a - covered
    return {k: v / 1e9 for k, v in sorted(out.items(), key=lambda kv: -kv[1])}


def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a varint,
    a memoryview for everything else."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} in an xplane")
        yield key >> 3, v


def op_names(raw: bytes) -> dict:
    """{(device plane, event name): op name} from a serialized ``XSpace``.

    ``ProfileData`` shows an event's own stats but not its metadata's, so
    this walks the message itself (``tsl/profiler/protobuf/xplane.proto``):
    planes (1) -> event_metadata (4) and stat_metadata (5) map entries
    (key 1, value 2); XEventMetadata name (2), stats (5); XStatMetadata
    name (2); XStat metadata_id (1), str_value (5).  Events are matched by
    name; a name that two operations with different op names share maps
    to ""."""
    out: dict = {}
    for f, plane in _fields(memoryview(raw)):
        if f != 1:
            continue
        name, metas, stat_names = "", [], {}
        for g, v in _fields(plane):
            if g == 2:
                name = bytes(v).decode()
            elif g == 4:
                metas.append(dict(_fields(v)).get(2, b""))
            elif g == 5:
                entry = dict(_fields(v))
                stat_names[entry.get(1, 0)] = bytes(
                    dict(_fields(entry.get(2, b""))).get(2, b"")).decode()
        if not name.startswith("/device:"):
            continue
        want = {k for k, v in stat_names.items() if v == OP_NAME_STAT}
        for meta in metas:
            ev_name, op = "", ""
            for g, v in _fields(meta):
                if g == 2:
                    ev_name = bytes(v).decode()
                elif g == 5:
                    stat = dict(_fields(v))
                    if stat.get(1, 0) in want and 5 in stat:
                        op = bytes(stat[5]).decode()
            key = (name, ev_name)
            out[key] = op if out.get(key, op) == op else ""
    return out


def _op_self_ns(ops) -> list:
    """[(op, self time)] of one device's operations: each one's duration
    less what the operations nested in it cover (a loop less its body)."""
    out, stack = [], []             # stack: [op, ns its children cover]
    for o in sorted(ops, key=lambda o: (o.start_ns, -o.dur_ns)):
        while stack and stack[-1][0].end_ns <= o.start_ns:
            done, kids = stack.pop()
            out.append((done, done.dur_ns - kids))
        if stack:
            stack[-1][1] += o.dur_ns
        stack.append([o, 0.0])
    out.extend((o, o.dur_ns - kids) for o, kids in reversed(stack))
    return out


def scope_self_s(trace, names: dict) -> dict:
    """{named scope: seconds} of device self time of the operations that
    start in the trace's turns, by the innermost named scope of each one's
    op name (``names``, from :func:`op_names`), averaged over the devices;
    operations outside any scope count under "none"; largest first."""
    tot: dict[str, float] = {}
    for d in trace.devices:
        inside = {id(o) for _, o in trace.in_turns(d.ops)}
        for o, ns in _op_self_ns(d.ops):
            if id(o) in inside:
                found = _SCOPE.findall(names.get((d.name, o.name), ""))
                k = found[-1] if found else "none"
                tot[k] = tot.get(k, 0.0) + ns
    n = max(len(trace.devices), 1)
    return {k: v / n / 1e9
            for k, v in sorted(tot.items(), key=lambda kv: -kv[1])}


def trace_spans(trace) -> list:
    """The ``serve.*`` spans of the turns the trace holds whole, as the
    ring's ``Span`` records (no counts: those live in the ring)."""
    from repro.serving.spans import Span
    out = []
    for t, kids in turns(trace):
        k = int(_TURN.match(t.name).group(1))
        tid = len(out)
        out.append(Span(tid, "serve.turn", int(t.start_ns), int(t.end_ns),
                        None, {"turn": k}))
        out += [Span(len(out) + j, e.name, int(e.start_ns), int(e.end_ns),
                     tid, {}) for j, e in enumerate(kids)]
    return out


def slowest_turn(spans, over_ms: float = 1000.0) -> str:
    """One line: the slowest ``serve.turn`` of ``spans`` (the engine's
    ring, or :func:`trace_spans`) with its counts, each phase's duration
    and counts in order, the turn's own time outside them, and how many
    turns took over ``over_ms``."""
    from repro.serving.spans import self_ns
    spans = list(spans)
    done = [s for s in spans if s.name == "serve.turn"]
    if not done:
        return "slowest turn: no serve.turn span"
    t = max(done, key=lambda s: s.dur_ns)
    n_over = sum(s.dur_ns > over_ms * 1e6 for s in done)

    def show(s):
        c = ", ".join(f"{k}={v}" for k, v in s.counts.items()
                      if k != "turn")
        return f"{s.name} {s.dur_ns / 1e6:.3f} ms" + (f" ({c})" if c else "")

    kids = [show(s) for s in spans if s.parent == t.id]
    return (f"slowest turn {t.counts.get('turn')}: {show(t)}; "
            + "; ".join(kids + [f"own {self_ns(spans)[t.id] / 1e6:.3f} ms"])
            + f"; turns over {over_ms:g} ms: {n_over} of {len(done)}")


def report(path: str) -> list[str]:
    """The three readings of one profile (a trace directory or its
    ``.xplane.pb``), over the ``serve.turn`` spans it holds whole."""
    from bench import trace as tr
    if os.path.isdir(path):
        path = tr.find_xplane(path)
    with open(path, "rb") as f:
        names = op_names(f.read())
    trace = tr.reduce(tr.load(path))
    trace.turns = {int(_TURN.match(t.name).group(1)): (t.start_ns, t.end_ns)
                   for t, _ in turns(trace)}

    def ms(d):
        return ", ".join(f"{k} {v * 1e3:.6f}" for k, v in d.items())

    return [f"whole turns: {len(trace.turns)}",
            f"device self time by scope, ms: {ms(scope_self_s(trace, names))}",
            f"device idle time by phase, ms: {ms(idle_by_phase(trace))}",
            slowest_turn(trace_spans(trace))]


if __name__ == "__main__":
    sys.path.insert(0, os.path.join(bench.ROOT, "src"))
    if len(sys.argv) != 2:
        sys.exit("usage: python3 -m bench.phases <trace dir or .xplane.pb>")
    print("\n".join(report(sys.argv[1])))
