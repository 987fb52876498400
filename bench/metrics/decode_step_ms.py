"""Engine, jitted decode: device time of the decode programs in the traced
turns over the decode steps they ran (``decode_chunk`` per program)."""
from bench.readings import DECODE_MODULE, seconds


def read(run):
    if run.trace is None:
        return None
    mods = [m for _, m in run.trace.modules(DECODE_MODULE)]
    if not mods:
        return None
    steps = len(mods) * int(run.cell.settings["engine"]["decode_chunk"])
    return seconds(mods) * 1e3 / steps
