"""Whole decode step: model FLOPs of the tokens decoded for active slots
(2 x matmul parameters per token plus attention over each active slot's
context) over the decode programs' device time times the chip's bf16
peak, in percent."""
from bench.readings import DECODE_MODULE, by_turn, seconds


def read(run):
    if run.trace is None or run.peak is None:
        return None
    mods = by_turn(run.trace.modules(DECODE_MODULE))
    if not mods:
        return None
    conf = run.cell.config
    flops = sum(run.fam.decode_step_flops(conf, ctx)
                for c in run.chunks if c.index in mods for ctx in c.steps)
    t = seconds(sum(mods.values(), []))
    return 100.0 * flops / (t * run.peak["bf16_flops"])
