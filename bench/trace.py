"""From a profiler trace to the numbers the per-layer metrics read.

The JAX profiler writes an ``.xplane.pb``; :func:`load` flattens it into
:class:`Event` rows (plane, line, name, start, duration), and
:func:`reduce` turns those into a :class:`Trace`:

* device operations, from the ``XLA Ops`` line of each ``/device:`` plane,
  and program executions, from its ``XLA Modules`` line;
* the busy time of each device (the union of its operation intervals) and
  the idle gaps between them;
* the harness's host spans (``bench.admit.<k>``, ``bench.tokens.<k>``),
  which cut the timeline into turns of ``serve()``'s loop: turn ``k`` runs
  from the start of ``bench.admit.<k>`` to the start of
  ``bench.admit.<k+1>``.  Every device operation a turn dispatches runs
  inside it, because the turn ends with a host sync on its decode chunk.

Operations and programs are found by the names they carry in the trace
(``bench/readings.py``): a program by its jitted function's name
(``jit__decode_scan_paged(<hash>)``), an operation by its HLO text, which
for a Pallas kernel starts with the jitted wrapper it sits in
(``%paged_decode_attention.8 = ... custom-call(...)``).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

_ADMIT = re.compile(r"^bench\.admit\.(\d+)$")


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, "
                           f"found {len(paths)}")
    return paths[0]


def load(path: str) -> list[Event]:
    """Every event of an ``.xplane.pb`` file."""
    from jax._src.profiler import ProfileData
    return [Event(plane.name, line.name, e.name, float(e.start_ns),
                  float(e.duration_ns))
            for plane in ProfileData.from_file(path).planes
            for line in plane.lines for e in line.events]


def _union(intervals) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


@dataclasses.dataclass
class Device:
    name: str
    ops: list                   # Events on "XLA Ops"
    modules: list               # Events on "XLA Modules"
    busy: list                  # merged (start, end) of ops


@dataclasses.dataclass
class Trace:
    devices: list
    host: list                  # Events on host planes
    turns: dict                 # k -> (start_ns, end_ns) of complete turns

    def busy_ns(self) -> float:
        """Busy time averaged over the devices."""
        if not self.devices:
            return 0.0
        return sum(sum(e - s for s, e in d.busy)
                   for d in self.devices) / len(self.devices)

    def in_turns(self, events) -> list:
        """The events that start inside a complete turn, with its index."""
        spans = sorted((s, e, k) for k, (s, e) in self.turns.items())
        out, i = [], 0
        for ev in sorted(events, key=lambda x: x.start_ns):
            while i < len(spans) and spans[i][1] <= ev.start_ns:
                i += 1
            if i < len(spans) and spans[i][0] <= ev.start_ns:
                out.append((spans[i][2], ev))
        return out

    def ops(self, pattern: str) -> list:
        """(turn, op) of every device op whose name matches."""
        rx = re.compile(pattern)
        return self.in_turns([o for d in self.devices for o in d.ops
                              if rx.search(o.name)])

    def modules(self, pattern: str) -> list:
        """(turn, program execution) of every program whose name matches."""
        rx = re.compile(pattern)
        return self.in_turns([m for d in self.devices for m in d.modules
                              if rx.search(m.name)])


def reduce(events: list[Event]) -> Trace:
    devs: dict[str, Device] = {}
    host = []
    for ev in events:
        if ev.plane.startswith("/device:"):
            d = devs.setdefault(ev.plane, Device(ev.plane, [], [], []))
            if ev.line == "XLA Ops":
                d.ops.append(ev)
            elif ev.line == "XLA Modules":
                d.modules.append(ev)
        elif ev.plane.startswith("/host:"):
            host.append(ev)
    devices = [d for d in devs.values() if d.ops]
    for d in devices:
        d.busy = _union((o.start_ns, o.end_ns) for o in d.ops)
    admits = sorted((int(m.group(1)), ev.start_ns) for ev in host
                    if (m := _ADMIT.match(ev.name)))
    turns = {k: (s, s2) for (k, s), (k2, s2) in zip(admits, admits[1:])
             if k2 == k + 1}
    return Trace(devices, host, turns)


def op_label(name: str) -> str:
    """A device op's event name is its HLO text; keep the op's name, its
    result shape and its kind: ``%copy.212 = bf16[46080,64,192] copy``."""
    head, _, rest = name.partition(" = ")
    shape = re.match(r"\(?([a-z0-9]+\[[0-9,]*\])", rest)
    kind = re.search(r"\}\s*([a-z][\w-]*)\(", rest)
    return " ".join(x for x in (head, "=" if rest else "",
                                shape.group(1) if shape else "",
                                kind.group(1) if kind else "") if x)


def top_ops(trace: Trace, n: int = 10) -> list:
    """[op, seconds] of the device operations that took most time, by op,
    averaged over the devices."""
    tot: dict[str, float] = {}
    for d in trace.devices:
        for o in d.ops:
            k = op_label(o.name)
            tot[k] = tot.get(k, 0.0) + o.dur_ns
    k = max(len(trace.devices), 1)
    return [[name, ns / k / 1e9] for name, ns in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def _host_label(trace: Trace, t_ns: float) -> str:
    """What the host was doing at ``t_ns``: the innermost host span that
    holds it, numbers stripped (``bench.admit.12`` -> ``bench.admit``)."""
    best = None
    for ev in trace.host:
        if ev.start_ns <= t_ns < ev.end_ns and (
                best is None or ev.start_ns >= best.start_ns):
            best = ev
    if best is None:
        return "host: no span"
    return re.sub(r"[.#]\d+$", "", best.name)


def idle_gaps(trace: Trace, n: int = 10) -> list:
    """[what the host was doing, seconds] of the longest gaps between
    device operations inside the traced window (first device)."""
    if not trace.devices:
        return []
    busy = trace.devices[0].busy
    gaps = [(e0, s1) for (_, e0), (s1, _) in zip(busy, busy[1:]) if s1 > e0]
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[_host_label(trace, (a + b) / 2), (b - a) / 1e9]
            for a, b in gaps[:n]]
