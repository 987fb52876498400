"""Device: the share of the traced window in which no operation ran on the
device (1 - union of operation intervals / window), in percent."""


def read(run):
    if run.trace is None or not run.trace_window_s:
        return None
    return 100.0 * (1.0 - run.trace.busy_ns() / 1e9 / run.trace_window_s)
