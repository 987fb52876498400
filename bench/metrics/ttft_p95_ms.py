"""Time to first token, 95th percentile over all requests due in the
window: scheduled send time to the host receiving the first token."""
from bench.readings import p95, ttft_ms


def read(run):
    return p95(ttft_ms(run))
