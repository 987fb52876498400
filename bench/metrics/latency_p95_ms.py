"""Request latency, 95th percentile over all requests: scheduled send time
to the last token, what a caller that waits for the whole answer feels."""
from bench.readings import latency_ms, p95


def read(run):
    return p95(latency_ms(run))
