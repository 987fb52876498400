"""An open-loop ``ContinuousBatcher``: the benchmark's hook into ``serve()``.

``InferenceEngine.serve(batcher=...)`` calls ``admit`` once per loop turn
(before the turn's prefills and its decode chunk) and ``record_tokens``
once per decoded step after the chunk's host sync.  This subclass

* releases each request only once its scheduled send time has passed;
  with no slot busy it sleeps until the next one is due (``serve()`` would
  otherwise see an idle engine with a queue it cannot admit);
* stamps every request when it is due, admitted, and when each of its
  tokens reaches the host;
* keeps, per loop turn ("chunk"), the requests admitted and, per decoded
  step, the context length of every slot that was active;
* marks both calls as host spans (``bench.admit.<chunk>``,
  ``bench.tokens.<chunk>``) in the profiler's trace, so the trace
  reduction can tell which device work belongs to which chunk.

Only host clock readings are taken here; nothing touches the device.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np

from repro.serving.scheduler import ContinuousBatcher, Request


@dataclasses.dataclass
class Stamp:
    rid: int
    prompt_len: int
    max_new: int
    due: float                        # host clock, seconds
    admitted: float | None = None
    tokens: list = dataclasses.field(default_factory=list)   # arrival times


@dataclasses.dataclass
class Chunk:
    """One turn of ``serve()``'s loop: admissions, then a decode chunk."""
    index: int
    admitted: list                    # (rid, prompt_len) admitted this turn
    synced: float | None = None       # host clock when its tokens arrived
    steps: list = dataclasses.field(default_factory=list)
    # steps[t] = context lengths (tokens attended, new one included) of the
    # slots active at decode step t


class OpenLoopBatcher(ContinuousBatcher):
    """Admits queued requests no earlier than their due time.

    ``schedule`` is a list of ``traffic.Scheduled``; ``t0`` the host clock
    reading at which the window opens.  ``on_turn(now)`` (optional) is
    called at the top of every admission turn, before any span opens —
    the harness starts and stops the profiler there."""

    def __init__(self, n_slots: int, schedule, t0: float, on_turn=None,
                 clock=time.perf_counter):
        super().__init__(n_slots)
        self.clock = clock
        self.on_turn = on_turn
        self.stamps: dict[int, Stamp] = {}
        self.chunks: list[Chunk] = []
        self.oversleep: list[float] = []   # how late the generator woke
        self._ctx = [0] * n_slots          # tokens in each slot's cache
        for s in schedule:
            self.stamps[s.rid] = Stamp(s.rid, len(s.prompt), s.max_new,
                                       t0 + s.due_s)
            self.submit(Request(rid=s.rid, prompt=list(s.prompt),
                                max_new=s.max_new))

    def _due(self, now: float) -> int:
        """How many queued requests are due at ``now`` (queue is sorted)."""
        n = 0
        for r in self.queue:
            if self.stamps[r.rid].due > now:
                break
            n += 1
        return n

    def admit(self, fits=None) -> list[int]:
        now = self.clock()
        if self.on_turn is not None:
            self.on_turn(now)
        k = len(self.chunks)
        with jax.profiler.TraceAnnotation(f"bench.admit.{k}"):
            if self.queue and not self.active() and not self._due(now):
                due = self.stamps[self.queue[0].rid].due
                time.sleep(max(0.0, due - self.clock()))
                now = self.clock()
                self.oversleep.append(now - due)
            admitted, turn = [], []
            for i in range(self.n_slots):
                if self.slots[i] is not None or not self._due(now):
                    continue
                r = self.queue[0]
                if fits is not None and not fits(r):
                    break
                self.slots[i] = self.queue.pop(0)
                st = self.stamps[r.rid]
                st.admitted = now
                self._ctx[i] = st.prompt_len
                admitted.append(i)
                turn.append((r.rid, st.prompt_len))
            self.chunks.append(Chunk(k, turn))
        return admitted

    def record_tokens(self, tokens: np.ndarray, stop_token=None):
        now = self.clock()
        chunk = self.chunks[-1]
        if chunk.synced is None:
            chunk.synced = now
        with jax.profiler.TraceAnnotation(f"bench.tokens.{chunk.index}"):
            active = self.active()
            chunk.steps.append([self._ctx[i] + 1 for i, _ in active])
            for i, r in active:
                self._ctx[i] += 1
                self.stamps[r.rid].tokens.append(now)
            super().record_tokens(tokens, stop_token)
