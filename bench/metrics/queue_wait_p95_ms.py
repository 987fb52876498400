"""Scheduler: scheduled send time to admission into a slot, 95th
percentile over the requests due before the profiler started (host clock
of the harness's batcher); starting and stopping the profiler stalls the
serving loop, so later requests would measure the profiler."""
from bench.readings import p95, queue_wait_ms


def read(run):
    return p95(queue_wait_ms(run))
