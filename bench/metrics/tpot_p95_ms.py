"""Time per output token, 95th percentile over all requests: (last token
time - first token time) / (tokens - 1) of each answer."""
from bench.readings import p95, tpot_ms


def read(run):
    return p95(tpot_ms(run))
