"""One run of one cell: set-up, an open-loop window through ``serve()``,
the metrics, and the check against the plain reference.

Everything a cell needs is found by name: the cell ``<workload>`` of
``BENCHMARK.json`` names a configuration (its ``file``) and a traffic mix
(``bench/traffic/<traffic>.json``); ``bench/cells/<workload>.json`` holds
the cell's fixed offered rate, engine settings and correctness limit; the
configuration's ``family`` names ``bench/families/<family>.py``; each
metric is read by ``bench/metrics/<metric>.py``.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time
import types

import numpy as np

from bench import ROOT

BENCH = os.path.join(ROOT, "bench")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


class NoChip(RuntimeError):
    """JAX sees no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    settings: dict              # bench/cells/<name>.json
    end_to_end: list            # metric entries of BENCHMARK.json
    per_layer: list


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str) -> Cell:
    spec = _json(os.path.join(ROOT, "BENCHMARK.json"))
    w = next((w for w in spec["workloads"] if w["name"] == workload), None)
    if w is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    c = next(c for c in spec["configs"] if c["name"] == w["config"])

    def mine(m):
        return workload in m.get("workloads", [workload])
    return Cell(
        name=workload, chips=int(w["chips"]),
        config=_json(os.path.join(ROOT, c["file"])),
        traffic=_json(os.path.join(BENCH, "traffic", w["traffic"] + ".json")),
        settings=_json(os.path.join(BENCH, "cells", workload + ".json")),
        end_to_end=[m for m in spec["end_to_end"] if mine(m)],
        per_layer=[m for m in spec["per_layer"] if mine(m)])


def family(conf: dict):
    return importlib.import_module(f"bench.families.{conf['family']}")


def reader(metric: str):
    """``bench/metrics/<metric>.py``'s ``read``."""
    path = os.path.join(BENCH, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def check_devices(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise NoChip("JAX finds no accelerator")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    return devs[:chips]


@dataclasses.dataclass
class Run:
    """What the metric readers read."""
    cell: Cell
    fam: object
    seconds: float
    t0: float                   # host clock when the window opened
    setup_s: float
    stamps: dict                # rid -> openloop.Stamp, pre-roll included
    chunks: list                # openloop.Chunk per serve() turn
    served: dict                # rid -> served tokens
    u: dict                     # rid -> difficulty score serve() returned
    schedule: dict              # rid -> traffic.Scheduled
    peak: dict | None           # chip peaks (None off the chip)
    trace: object | None        # trace.Trace of the traced run
    trace_window_s: float | None = None
    trace_started: float | None = None  # host clock when profiling began

    def due_in_window(self) -> list:
        """Stamps of the requests due inside the window: the ones scored."""
        end = self.t0 + self.seconds
        return [s for s in self.stamps.values() if self.t0 <= s.due < end]


class Tracer:
    """Starts the profiler ``start_s`` into the window and stops it
    ``length_s`` later, from the batcher's turn hook."""

    def __init__(self, directory: str, start_s: float, length_s: float):
        self.dir, self.start_s, self.length_s = directory, start_s, length_s
        self.t0 = None
        self.on = self.done = False
        self.started = self.stopped = None
        self.start_cost = self.stop_cost = 0.0

    def on_turn(self, now: float):
        import jax
        from jax._src.profiler import ProfileOptions
        if not self.on and not self.done and now >= self.t0 + self.start_s:
            opts = ProfileOptions()
            opts.python_tracer_level = 0      # host spans only
            opts.enable_hlo_proto = False
            t = time.perf_counter()
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.on, self.started = True, time.perf_counter()
            self.start_cost = self.started - t
        elif self.on and now >= self.started + self.length_s:
            self.stop()

    def stop(self):
        import jax
        if self.on:
            self.stopped = time.perf_counter()
            jax.profiler.stop_trace()
            self.stop_cost = time.perf_counter() - self.stopped
            self.on, self.done = False, True


class CompileCounter:
    """Counts compilations (and compile-cache loads) while armed."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax
        self.armed, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.armed and event in self.EVENTS:
            self.count += 1


def warm_up(engine, n_slots: int, lengths: list, decode_chunk: int):
    """Compile (or load) every program the window will run: one admission
    per prompt bucket the schedule uses (``lengths``, one prompt length in
    each), every slot admitted at least once, and the decode chunk."""
    from bench import openloop, traffic
    n = max(n_slots, len(lengths))
    sched = [traffic.Scheduled(i, 0.0, [1] * lengths[i % len(lengths)], 1)
             for i in range(n)]
    b = openloop.OpenLoopBatcher(n_slots, sched, time.perf_counter())
    engine.serve(batcher=b, decode_chunk=decode_chunk, greedy=True)


@dataclasses.dataclass
class Served:
    """A cell's model and engine, set up and warm."""
    fam: object
    mcfg: object
    params: object
    engine: object
    n_slots: int
    chunk: int
    phases: dict                # set-up seconds: weights, engine, warm_up

    def fresh_engine(self):
        """Drop the engine (its pool) and build another, same sizes."""
        from repro.serving.engine import InferenceEngine
        e = self.engine
        kw = dict(paged=True, block_len=e.block_len, max_len=e.max_len,
                  pool_blocks=e.pool.n_blocks, pool_rows=e.pool.n_rows)
        e.pool = None
        self.engine = None
        gc.collect()
        self.engine = InferenceEngine(e.name, self.mcfg, self.params, **kw)
        return self.engine


def set_up(cell: Cell, seed: int, seconds: float,
           rate: float | None = None) -> Served:
    """Weights from the seed, the engine, and every program a run of
    ``seconds`` at ``rate`` (the cell's, by default) will run compiled or
    loaded from the cache."""
    import jax
    from repro.compile_cache import enable_compilation_cache
    from repro.serving.engine import InferenceEngine, bucket_len

    from bench import traffic

    enable_compilation_cache()
    conf, mix, st = cell.config, cell.traffic, cell.settings
    eng_s = st["engine"]
    fam = family(conf)
    mcfg = fam.model_config(conf)
    t = time.perf_counter()
    params = jax.block_until_ready(fam.make_params(conf, mcfg, seed))
    phases = {"weights": time.perf_counter() - t}
    t = time.perf_counter()
    gran = max(mcfg.attn_q_block, mcfg.attn_kv_block)
    max_prompt, max_answer = traffic.longest_need(mix)
    n_slots = int(eng_s["n_slots"])
    block_len = int(eng_s["block_len"])
    chunk = int(eng_s["decode_chunk"])
    # every slot's table covers the longest request the mix can draw, and
    # the pool holds every slot at that length, so nothing waits for blocks
    max_len = InferenceEngine._round_len(
        types.SimpleNamespace(paged=True, block_len=block_len, cfg=mcfg),
        bucket_len(max_prompt, gran) + max_answer)
    engine = InferenceEngine(
        conf["name"], mcfg, params, paged=True, block_len=block_len,
        max_len=max_len, pool_blocks=n_slots * (max_len // block_len),
        pool_rows=n_slots)
    phases["engine"] = time.perf_counter() - t
    t = time.perf_counter()
    buckets = traffic.bucket_set(mix, float(rate or st["rate_per_s"]),
                                 seconds, float(st["preroll_s"]),
                                 lambda p: bucket_len(p, gran))
    warm_up(engine, n_slots, [min(b, max_prompt) for b in buckets], chunk)
    phases["warm_up"] = time.perf_counter() - t
    return Served(fam, mcfg, params, engine, n_slots, chunk, phases)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, on_chip: bool = True, log=print,
             checker=None) -> dict:
    """One run.  Returns the result object (without printing it).
    ``checker(run, params, mcfg, seed)`` replaces the comparison with the
    reference (``check.run_check``)."""
    import jax

    from bench import check, openloop, traffic, trace as tr
    from bench.counts import peaks

    devices = check_devices(cell.chips) if on_chip else jax.devices()[:1]
    st = cell.settings
    sv = set_up(cell, seed, seconds)
    fam, mcfg, params, engine = sv.fam, sv.mcfg, sv.params, sv.engine
    n_slots, chunk = sv.n_slots, sv.chunk
    preroll = float(st["preroll_s"])
    sched = traffic.schedule(cell.traffic, float(st["rate_per_s"]), seconds,
                             seed, fam.token_range(cell.config), preroll)
    counter = CompileCounter()

    tracer = None
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        tracer = Tracer(TRACE_DIR, float(st["trace_start_s"]),
                        float(st["trace_seconds"]))
    # the schedule starts with the pre-roll; the window opens at t0
    t_first = time.perf_counter() + 0.01
    t0 = t_first + preroll
    if tracer is not None:
        tracer.t0 = t0
    batcher = openloop.OpenLoopBatcher(
        n_slots, sched, t0, on_turn=tracer.on_turn if tracer else None)
    setup_s = t_first - t_start
    # no collector pauses while serving: what set-up left is frozen
    gc.collect()
    gc.freeze()
    gc.disable()
    counter.armed = True
    try:
        results = engine.serve(batcher=batcher, decode_chunk=chunk,
                               greedy=True, stop_token=None)
    finally:
        t_end = time.perf_counter()
        counter.armed = False
        gc.enable()
        gc.unfreeze()
    if tracer is not None:
        tracer.stop()
    served = {r["rid"]: np.asarray(r["tokens"]) for r in results}
    u = {r["rid"]: r["u"] for r in results}

    late = [x * 1e3 for x in batcher.oversleep]
    log("info: set-up " + ", ".join(f"{k} {v:.3f} s"
                                    for k, v in sv.phases.items())
        + f"; process start to set-up {setup_s - sum(sv.phases.values()):.3f}"
        " s")
    syncs = [c.synced for c in batcher.chunks
             if c.synced is not None and t0 <= c.synced <= t0 + seconds]
    turn = np.diff(syncs) * 1e3 if len(syncs) > 1 else np.zeros(1)
    log(f"info: pre-roll {preroll} s, window {seconds} s, {len(sched)} "
        f"requests sent, drain ended {t_end - t0 - seconds:.3f} s after "
        f"the window; generator lateness max {max(late, default=0.0):.3f} "
        f"ms mean {(sum(late) / len(late)) if late else 0.0:.3f} ms over "
        f"{len(late)} idle waits; compiles in window {counter.count}; "
        f"turns in window {len(turn)}, host time between syncs median "
        f"{np.median(turn):.3f} ms max {turn.max():.3f} ms")

    mem = None
    if on_chip:
        mem = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                  for d in devices)
    # free the program's state before the reference runs
    engine.pool = None
    del engine, results, sv
    gc.collect()

    run = Run(cell=cell, fam=fam, seconds=seconds, t0=t0, setup_s=setup_s,
              stamps=batcher.stamps, chunks=batcher.chunks, served=served, u=u,
              schedule={s.rid: s for s in sched},
              peak=peaks(devices[0].device_kind) if on_chip else None,
              trace=None)
    breakdown = None
    if tracer is not None and tracer.done:
        run.trace = tr.reduce(tr.load(tr.find_xplane(TRACE_DIR)))
        run.trace_window_s = tracer.stopped - tracer.started
        run.trace_started = tracer.started - tracer.start_cost
        log(f"info: profiler started {tracer.started - t0:.3f} s into the "
            f"window (start took {tracer.start_cost:.3f} s), traced "
            f"{run.trace_window_s:.3f} s (stop took {tracer.stop_cost:.3f} "
            f"s), {len(run.trace.turns)} whole turns")
        breakdown = {"device_ops": tr.top_ops(run.trace),
                     "idle_gaps": tr.idle_gaps(run.trace)}
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    failed = sum(1 for s in batcher.stamps.values()
                 if len(s.tokens) != s.max_new
                 or len(served.get(s.rid, ())) != s.max_new)
    checks = (checker or check.run_check)(run, params, mcfg, seed)
    checks["failed_requests"] = {"value": failed, "limit": 0}
    correct = all(c["value"] is not None and c["value"] <= c["limit"]
                  for c in checks.values())

    metrics = {}
    if on_chip:
        for m in (cell.per_layer if trace else cell.end_to_end):
            v = reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    out = {"correct": bool(correct), "attempted": len(sched),
           "failed": failed, "metrics": metrics, "device": device}
    if run.trace is not None and on_chip:
        device["busy_s"] = run.trace.busy_ns() / 1e9
        device["window_s"] = run.trace_window_s
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def main(argv=None, t_start: float | None = None) -> int:
    import argparse
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    try:
        out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                       t_start=t_start)
    except NoChip as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0
