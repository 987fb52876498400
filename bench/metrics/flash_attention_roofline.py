"""Kernel ``kernels/flash_attention``: the least time the chip needs for
causal attention over each admitted prompt's real tokens (FLOPs at peak
bf16 or bytes at peak HBM bandwidth, whichever bounds), over the kernel's
device time in the traced turns, in percent."""
from bench import counts
from bench.readings import FLASH_KERNEL, by_turn, seconds


def read(run):
    shape = run.fam.kv_shape(run.cell.config)
    if run.trace is None or run.peak is None or shape is None:
        return None
    ops = by_turn(run.trace.ops(FLASH_KERNEL))
    if not ops:
        return None
    need = sum(counts.roofline_seconds(
        *counts.flash_attention_cost(p, **shape), run.peak)
        for c in run.chunks if c.index in ops for _, p in c.admitted)
    return 100.0 * need / seconds(sum(ops.values(), []))
