"""Kernel ``kernels/decode_attention``: the least time the chip needs for
the bytes the paged decode attention must move (keys and values of each
active slot's context, its query and output) at peak HBM bandwidth, over
the kernel's device time in the traced turns, in percent."""
from bench import counts
from bench.readings import DECODE_KERNEL, by_turn, seconds


def read(run):
    shape = run.fam.kv_shape(run.cell.config)
    if run.trace is None or run.peak is None or shape is None:
        return None
    ops = by_turn(run.trace.ops(DECODE_KERNEL))
    if not ops:
        return None
    need = 0.0
    for c in run.chunks:
        if c.index in ops:
            for ctx in c.steps:
                for n in ctx:
                    flops = shape["layers"] * counts.decode_attention_flops(
                        n, heads=shape["heads"], head_dim=shape["head_dim"])
                    need += counts.roofline_seconds(
                        flops, counts.decode_attention_bytes(n, **shape),
                        run.peak)
    return 100.0 * need / seconds(sum(ops.values(), []))
