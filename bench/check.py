"""Is what the timed ``serve()`` produced right?  The comparison with the
plain float32 reference.

Once the window has closed, a sample of the finished requests, drawn from
the seed and always holding the longest one, is run through the reference
(``bench/reference/<family>.py``) over each prompt followed by its served
tokens.  At every served position the reference's logits say how far the
token the program served lies below the reference's best token:

    gap_j = max_v ref[j, v] - ref[j, served_j]

Greedy serving with exact arithmetic gives 0 everywhere; rounding gives
small gaps where the reference's top two logits nearly tie.  One number
compared is the widest gap over the sample (``widest_logit_gap``).

A gap shows only where rounding flips a near-tie.  The other number reads
the program's logits at every served position: the difficulty score
``u`` that ``serve()`` returns for each request, computed in its decode
scan from the logits behind every served token (paper Eq. 2-4: the served
token's -p log p, rescaled by e, and the variance of the top-10 logits
squashed as V / (V + 25), weighted 1/2 each, averaged over the answer).
The reference computes the same score from its own logits and the same
tokens; ``u_rel_error`` is the largest |u - u_ref| / u_ref over the sample.
A cell compares the numbers its file gives a limit for.

The control takes the program's place at the same positions: the
reference computed one precision below the configuration's bf16, in int8
(every matmul with a weight, the head included, on int8 activations and
int8 weights, :func:`int8_matmul`), and its widest gap is read the same
way, of the token it puts first.  The program's own ``weight_quant="int8"``
path is not that step: it stores weights in int8 but computes in bf16, and
quantizes no SSD weight.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import traffic

def sample(run, seed: int) -> list[int]:
    """rids to compare, ``check.sample`` of the cell file in all: the
    longest finished request due in the window, then others of those drawn
    from the seed."""
    done = sorted(s.rid for s in run.due_in_window()
                  if len(run.served.get(s.rid, ())) == s.max_new)
    if not done:
        return []
    longest = max(done, key=lambda r: (run.stamps[r].prompt_len
                                       + run.stamps[r].max_new, -r))
    rest = [r for r in done if r != longest]
    n = int(run.cell.settings["check"]["sample"])
    pick = traffic.rng_for(seed, 2).permutation(len(rest))[:n - 1]
    return [longest] + [rest[i] for i in sorted(pick)]


def rows(prompts: list, served: list, width: int):
    """Token rows (prompt + served tokens but the last) right-padded to
    ``width``, their lengths, and each row's first served position."""
    seqs = [list(p) + list(s[:-1]) for p, s in zip(prompts, served)]
    arr = np.zeros((len(seqs), width), np.int32)
    for i, q in enumerate(seqs):
        arr[i, :len(q)] = q
    starts = [len(p) - 1 for p in prompts]
    return arr, [len(q) for q in seqs], starts


def int8_matmul(x, w):
    """``x @ w`` computed in int8: symmetric int8 activations with one
    scale per row (token) and int8 weights with one scale per output
    column, products summed exactly (float32 on the dequantized values)."""
    x, w = x.astype(jnp.float32), w.astype(jnp.float32)
    sx = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
    sw = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 127.0
    xq = jnp.round(x / jnp.where(sx > 0, sx, 1.0)) * sx
    wq = jnp.round(w / jnp.where(sw > 0, sw, 1.0)) * sw
    return jnp.matmul(xq, wq, precision="highest")


def _uncertainty(logits, tokens, valid):
    """The difficulty score of one answer from its logits (A, V), its
    tokens (A,) and which of the A positions are real, paper Eq. 2-4 with
    ``serve()``'s settings."""
    n = jnp.sum(valid)
    logp = jax.nn.log_softmax(logits, axis=-1)
    lp = logp[jnp.arange(tokens.shape[0]), tokens]
    h = jnp.sum(jnp.where(valid, -jnp.exp(lp) * lp, 0.0)) / n * math.e
    v = jnp.sum(jnp.where(valid, jnp.var(jax.lax.top_k(logits, 10)[0],
                                         axis=-1), 0.0)) / n
    return 0.5 * h + 0.5 * v / (v + 25.0)


@partial(jax.jit, static_argnames=("control",))
def _row(h, hc, head, start, tokens, n, control: bool):
    """One sampled request at fixed shapes, so that one program serves
    every request of every run: ``h``/``hc`` (width, D) reference and
    control hidden states of its row, ``tokens`` (A,) its served tokens
    padded to the longest answer, ``n`` how many are real, ``start`` the
    position whose logits give the first.  Returns the widest gap and the
    difficulty score of the reference's logits, and with ``control`` the
    widest gap of the control's first choices and its score."""
    A = tokens.shape[0]
    valid = jnp.arange(A) < n
    idx = jnp.arange(A)

    def logits(rows, mm):
        rows = jax.lax.dynamic_slice_in_dim(
            jnp.pad(rows, ((0, A), (0, 0))), start, A)
        return mm(rows, head)

    lg = logits(h, lambda x, w: jnp.matmul(x, w, precision="highest"))
    best = jnp.max(lg, axis=-1)

    def widest(toks):
        return jnp.max(jnp.where(valid, best - lg[idx, toks], 0.0))

    out = (widest(tokens), _uncertainty(lg, tokens, valid))
    if control:
        lc = logits(hc, int8_matmul)
        out += (widest(jnp.argmax(lc, axis=-1)),
                _uncertainty(lc, tokens, valid))
    return out


def readings(fam, conf: dict, params, prompts: list, served: list,
             width: int, answer: int, us: list | None = None, *,
             control: bool = False) -> dict:
    """The compared numbers of a sample: the widest reference gap of the
    served tokens (``program``) and the largest relative error of their
    difficulty scores ``us`` (``program_u``); with ``control``, the same of
    the control at the same positions (``control``, ``control_u``).  Rows
    are padded to ``width`` tokens and answers to ``answer``, one shape
    for every run of a cell."""
    ref = fam.reference
    arr, _, starts = rows(prompts, served, width)
    h = ref.hidden_batch(params, conf, arr)
    head = ref.head(params, conf).astype(jnp.float32)
    hc = (ref.hidden_batch(params, conf, arr, mm=int8_matmul)
          if control else h)
    out = {"program": 0.0, "control": 0.0 if control else None,
           "program_u": 0.0, "control_u": 0.0 if control else None}
    for i, (s0, toks) in enumerate(zip(starts, served)):
        padded = np.zeros((answer,), np.int32)
        padded[:len(toks)] = toks
        got = [float(x) for x in _row(h[i], hc[i], head, s0, padded,
                                      len(toks), control)]
        out["program"] = max(out["program"], got[0])
        if us is not None:
            out["program_u"] = max(out["program_u"],
                                   abs(us[i] - got[1]) / got[1])
        if control:
            out["control"] = max(out["control"], got[2])
            out["control_u"] = max(out["control_u"],
                                   abs(got[3] - got[1]) / got[1])
    return out


def width(mix: dict) -> int:
    """Row width that holds the longest request the mix can draw."""
    p, a = traffic.longest_need(mix)
    return p + a - 1


NUMBERS = {"widest_logit_gap": "program", "u_rel_error": "program_u"}
CONTROL = {"widest_logit_gap": "control", "u_rel_error": "control_u"}


def run_check(run, params, mcfg, seed: int, *, control: bool = False,
              log=None) -> dict:
    """The checks of one run: {name: {"value", "limit"}} for each number
    the cell file gives a limit; a value of None (nothing finished to
    compare) fails.  With ``control`` the control takes the program's
    place: the numbers are the control's at the same positions (and
    ``log``, if given, receives the program's)."""
    limits = run.cell.settings["check"]
    rids = sample(run, seed)
    got = {}
    if rids:
        got = readings(run.fam, run.cell.config, params,
                       [run.schedule[r].prompt for r in rids],
                       [run.served[r] for r in rids],
                       width(run.cell.traffic),
                       traffic.longest_need(run.cell.traffic)[1],
                       [run.u[r] for r in rids], control=control)
    if control and log is not None:
        log("program " + " ".join(f"{name} {got.get(key)}"
                                  for name, key in NUMBERS.items()))
    keys = CONTROL if control else NUMBERS
    return {name: {"value": got.get(key), "limit": float(limits[name])}
            for name, key in keys.items() if name in limits}
