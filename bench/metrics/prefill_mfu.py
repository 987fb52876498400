"""Whole admission prefill: model FLOPs of the prompts admitted in the
traced turns (every real position through every layer, causal attention
over the real tokens, the head once for the first token) over the prefill
programs' device time times the chip's bf16 peak, in percent."""
from bench.readings import PREFILL_MODULE, by_turn, seconds


def read(run):
    if run.trace is None or run.peak is None:
        return None
    mods = by_turn(run.trace.modules(PREFILL_MODULE))
    if not mods:
        return None
    conf = run.cell.config
    flops = sum(run.fam.prefill_flops(conf, p)
                for c in run.chunks if c.index in mods for _, p in c.admitted)
    t = seconds(sum(mods.values(), []))
    return 100.0 * flops / (t * run.peak["bf16_flops"])
