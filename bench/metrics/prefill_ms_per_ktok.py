"""Engine, jitted admission prefill: device time of the prefill programs
in the traced turns, per 1000 unpadded prompt tokens they admitted."""
from bench.readings import PREFILL_MODULE, by_turn, seconds


def read(run):
    if run.trace is None:
        return None
    mods = by_turn(run.trace.modules(PREFILL_MODULE))
    if not mods:
        return None
    toks = sum(p for c in run.chunks if c.index in mods
               for _, p in c.admitted)
    return seconds(sum(mods.values(), [])) * 1e3 / (toks / 1e3)
