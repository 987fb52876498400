"""Smoke-size cells for CPU tests: the benchmark's own harness and chat
traffic (the same lengths, so the same prompt buckets and slot tables),
with each model cut to smoke widths and depth and a lower offered rate,
so that a run takes seconds on the CPU."""
from __future__ import annotations

import json
import os

from bench import harness

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CONFIGS = {"llama": "smollm-smoke.json", "mamba2": "mamba2-smoke.json"}
# limits at smoke size, from readings taken like the cells' (bench/control.py
# on the CPU, seeds 1, 2, 3, 5, 6, 2**33 + 7).  Widest gap: llama program
# 0.026-0.082, int8 control 0.16-0.42; mamba2 program 0.0014-0.0047,
# control 0.0066-0.020.  u relative error: llama program 0.0018-0.0047,
# control 0.0053-0.013; mamba2 program 0.00014-0.00026, control
# 0.00034-0.0014.  Smoke widths separate program and control less than
# the cells' widths do; these limits serve the rehearsal and the faults.
LIMITS = {"llama": {"widest_logit_gap": 0.15, "u_rel_error": 0.02},
          "mamba2": {"widest_logit_gap": 0.006, "u_rel_error": 0.001}}


def _json(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


def cell(family: str) -> harness.Cell:
    spec = harness._json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    return harness.Cell(
        name=f"{family}-smoke.chat", chips=1, config=_json(CONFIGS[family]),
        traffic=harness._json(os.path.join(harness.BENCH, "traffic",
                                           "chat.json")),
        settings={"rate_per_s": 4, "preroll_s": 1, "trace_start_s": 0.5,
                  "trace_seconds": 1,
                  "engine": {"n_slots": 4, "decode_chunk": 8,
                             "block_len": 64},
                  "check": {"sample": 6, **LIMITS[family]}},
        end_to_end=spec["end_to_end"], per_layer=spec["per_layer"])
