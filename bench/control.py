"""The control on the chip: the plain reference computed one precision
below the configuration's bf16 (in int8, ``bench/check.py::int8_matmul``)
takes the program's place in a cell's comparison, and has to come out not
correct against the cell file's own limits.

    python3 -m bench.control --workload <name> --seeds 1,2,3 [--seconds 15]

For each seed, one run of the cell as ``bench/run.py`` makes it (fresh
weights, the cell's pre-roll and load, a window of ``--seconds``, the same
sample), compared through ``check.run_check`` with the control's numbers
in the program's place.  Per seed it prints a line with the program's own
numbers on the same sample (the lower readings of a limit) and then the
run's result line, whose ``correct`` is false where the control fails a
limit.  The window needs only to be long enough to finish the mix's
longest requests and to hold as many requests as a run samples.  Exits 0
when every seed's control came out not correct.
"""
import bench

bench.prepare_process()

import json  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
from functools import partial  # noqa: E402


def run_seeds(cell, seeds, seconds: float, *, on_chip: bool = True,
              log=print) -> list:
    """One control run per seed; returns each run's result object."""
    from bench import check, harness
    outs = []
    for seed in seeds:
        out = harness.run_cell(
            cell, seed, seconds, False, t_start=time.perf_counter(),
            on_chip=on_chip, log=log,
            checker=partial(check.run_check, control=True,
                            log=lambda m, s=seed: log(f"seed {s}: {m}")))
        log(json.dumps({"seed": seed, **out}))
        outs.append(out)
    return outs


def main() -> int:
    import argparse

    from bench import harness
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args()
    outs = run_seeds(harness.load_cell(args.workload),
                     [int(s) for s in args.seeds.split(",")], args.seconds)
    return 0 if not any(o["correct"] for o in outs) else 1


if __name__ == "__main__":
    sys.exit(main())
