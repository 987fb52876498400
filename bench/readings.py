"""Shared arithmetic of the metric readers in ``bench/metrics/``.

Latencies are per request due inside the window (the pre-roll before it
is not scored), from its scheduled send time (open loop); a request that
did not finish counts as infinitely late.  Device times come
from the traced window, restricted to the turns of ``serve()``'s loop that
lie wholly inside it, so that each device program can be paired with the
host's record of what that turn admitted and decoded.
"""
from __future__ import annotations

import math

import numpy as np

# Names as they appear in the device trace today: a program by its jitted
# function (``jit__decode_scan_paged(<hash>)``), a Pallas kernel by the
# jitted wrapper its custom call sits in (``%paged_decode_attention.8 =
# ... custom-call(...)``, ``kernels/*/ops.py``).
PREFILL_MODULE = r"^jit__prefill_into_paged\("
DECODE_MODULE = r"^jit__decode_scan_paged\("
DECODE_KERNEL = r"^%paged_decode_attention(\.\d+)? = "
FLASH_KERNEL = r"^%flash_attention_positions(\.\d+)? = "


def p95(values):
    """95th percentile (linear interpolation); None where it is not finite
    (more than 5% of requests unfinished) or there are no values."""
    v = np.asarray(list(values), float)
    if not len(v):
        return None
    q = float(np.percentile(v, 95))
    return q if math.isfinite(q) else None


def finished(s) -> bool:
    return len(s.tokens) == s.max_new


def ttft_ms(run):
    return [(s.tokens[0] - s.due) * 1e3 if finished(s) else math.inf
            for s in run.due_in_window()]


def latency_ms(run):
    return [(s.tokens[-1] - s.due) * 1e3 if finished(s) else math.inf
            for s in run.due_in_window()]


def tpot_ms(run):
    return [(s.tokens[-1] - s.tokens[0]) / (len(s.tokens) - 1) * 1e3
            if finished(s) else math.inf
            for s in run.due_in_window() if s.max_new > 1]


def queue_wait_ms(run):
    """Waits of the requests due in the window before the profiler
    started, which it cannot have delayed (all of them in a run without
    it)."""
    until = run.trace_started or math.inf
    return [(s.admitted - s.due) * 1e3 if s.admitted is not None
            else math.inf for s in run.due_in_window() if s.due < until]


def by_turn(pairs) -> dict:
    """{turn: [events]} of (turn, event) pairs."""
    out: dict = {}
    for k, ev in pairs:
        out.setdefault(k, []).append(ev)
    return out


def seconds(events) -> float:
    return sum(e.dur_ns for e in events) / 1e9
