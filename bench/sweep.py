"""Find a cell's knee on the chip: the highest offered rate at which the
queue does not grow over the window.

    python3 -m bench.sweep --workload <name> --rates 0.5,1,2 --seconds 40

One process sets the cell up once, then offers each rate in turn through
the timed path, on a fresh engine each time: the cell's pre-roll, then a
window of ``--seconds``, which should be several times as long as a
request stays in the engine.  At the close of each window it stops (it
does not drain: only the growth of the queue matters here) and prints,
per rate: requests due in the window and admitted, the backlog (due, not
yet admitted) in the window's first and last quarters, the queue wait of
admitted requests in both halves, and the tokens delivered per second.  A rate whose backlog and wait keep rising
through the window is past the knee.  The cell file then fixes its rate
at about four fifths of the knee.
"""
import bench

bench.prepare_process()

import json  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402


class WindowClosed(Exception):
    pass


def offer(served, cell, rate: float, seconds: float, seed: int) -> dict:
    """One rate for one window; returns its readings."""
    import numpy as np

    from bench import openloop, traffic
    preroll = float(cell.settings["preroll_s"])
    sched = traffic.schedule(cell.traffic, rate, seconds, seed,
                             served.fam.token_range(cell.config), preroll)
    backlog = []                   # (t, due and not admitted)

    def on_turn(now):
        t = now - t0
        if t > seconds:
            raise WindowClosed
        due = sum(1 for s in b.stamps.values()
                  if s.due <= now and s.admitted is None)
        backlog.append((t, due))

    t0 = time.perf_counter() + 0.01 + preroll
    b = openloop.OpenLoopBatcher(served.n_slots, sched, t0, on_turn=on_turn)
    try:
        served.engine.serve(batcher=b, decode_chunk=served.chunk,
                            greedy=True, stop_token=None)
    except WindowClosed:
        pass
    served.fresh_engine()
    adm = [(s.due - t0, s.admitted - s.due) for s in b.stamps.values()
           if s.admitted is not None and s.due >= t0]
    half = [[w for d, w in adm if (d < seconds / 2) == first]
            for first in (True, False)]
    q = [[n for t, n in backlog if 0 <= t and lo <= t / seconds < hi]
         for lo, hi in ((0, .25), (.75, 1.01))]
    toks = sum(len([t for t in s.tokens if t0 <= t <= t0 + seconds])
               for s in b.stamps.values())
    due = sum(1 for s in sched if s.due_s >= 0)
    return {"rate": rate, "due": due, "admitted": len(adm),
            "backlog_q1": float(np.mean(q[0])) if q[0] else None,
            "backlog_q4": float(np.mean(q[1])) if q[1] else None,
            "wait_ms_h1": float(np.mean(half[0]) * 1e3) if half[0] else None,
            "wait_ms_h2": float(np.mean(half[1]) * 1e3) if half[1] else None,
            "out_tokens_per_s": toks / seconds}


def main() -> int:
    import argparse
    from bench import harness
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    cell = harness.load_cell(args.workload)
    harness.check_devices(cell.chips)
    rates = [float(r) for r in args.rates.split(",")]
    served = harness.set_up(cell, args.seed, args.seconds, rate=max(rates))
    out = []
    for r in rates:
        # the highest rate draws the most lengths, so every bucket is warm
        out.append(offer(served, cell, r, args.seconds, args.seed))
        print(json.dumps(out[-1]), flush=True)
    print(json.dumps({"workload": args.workload, "sweep": out}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
