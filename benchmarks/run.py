"""Benchmark harness — one function per paper table + microbenchmarks.

Prints ``name,us_per_call,derived`` CSV.  Set BENCH_FAST=1 for a quick pass
(fewer training steps for the study tables), BENCH_FORCE=1 to ignore the
cached study results.
"""

from __future__ import annotations

import os


def main() -> None:
    rows: list[tuple[str, object, object]] = []

    # --- microbenchmarks -------------------------------------------------
    from benchmarks import micro
    rows += [(n, round(us, 1), d) for n, us, d in micro.bench_all()]

    # --- paper tables (III, IV, V) ---------------------------------------
    from benchmarks import tables
    fast = os.environ.get("BENCH_FAST") == "1"
    force = os.environ.get("BENCH_FORCE") == "1"
    res = tables.cached_study(train_steps=120 if fast else 300, force=force)
    rows += tables.emit_rows(res)

    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us},{derived}")


if __name__ == "__main__":
    main()
