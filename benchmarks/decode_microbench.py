"""Per-phase decode microbench: kernel-first vs gathered-view paged serving.

Breaks one serving session into its three phases and times each under both
paged decode-attention impls (``attn_decode_impl`` in
``repro.serving.engine``):

* ``prefill``             — cold absorb of the context into pool blocks;
* ``continuation_insert`` — warm continuation prefill of a short span over
                            the live cache (multi-turn / swarm handoff);
* ``decode_step``         — per-token cost of a scanned decode dispatch
                            (the serving inner loop, reported per step).

Each row also carries an estimated bytes-moved figure.  On a TPU the row
joins it against the chip's HBM peak (``roofline.peaks(device_kind)``: the
time the bytes would take, and that time as a fraction of measured
wall-clock); elsewhere there is no device roofline and those fields are
None.  Byte estimates count the dominant streams — parameter
bytes + the slot-linear attention KV view per decode step, measured from
the engine's actual cache shapes via ``jax.eval_shape`` — not every
activation.

The harness is also the enforcement point for the kernel-first claims:

* ``--check-hlo``       — assert (via ``repro.serving.hlo_probe``) that the
                          kernel-first decode executable does NOT
                          materialise the O(B * S) slot-linear KV view the
                          gathered-view executable provably carries;
* ``--assert-ratio X``  — fail unless kernel-first decode-step wall-clock
                          is <= X * gathered-view (CI floor: 1.0);
* ``--compilation-cache-dir`` — engine-level persistent XLA cache, so a
                          re-run skips every already-seen jit.

Usage (CI smoke): PYTHONPATH=src python benchmarks/decode_microbench.py \
    --ctx 200 --steps 16 --check-hlo --assert-ratio 1.0
"""

from __future__ import annotations

import argparse
import dataclasses
import sys
import time

import jax
import numpy as np

sys.path.insert(0, "src")
sys.path.insert(0, "benchmarks")

import roofline  # noqa: E402


def best_of(fn, iters: int, warmup: int = 3) -> float:
    """Min-of-N seconds per call (min, not mean: immune to load spikes,
    which is what a CI floor needs)."""
    for _ in range(warmup):
        fn()
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def tree_bytes(tree) -> int:
    return sum(leaf.size * leaf.dtype.itemsize
               for leaf in jax.tree_util.tree_leaves(tree)
               if hasattr(leaf, "dtype"))


def view_bytes(cfg, cache: dict, block_len: int) -> int:
    """Bytes of the slot-linear attention KV view for this cache — the
    per-decode-step attention read stream (both impls stream exactly these
    elements; the gather impl additionally materialises them per dispatch)."""
    from repro.models import transformer as T
    view_lens = {cache["table"].shape[1] * block_len}
    if cfg.window is not None:
        view_lens.add(cfg.window)
    gathered = jax.eval_shape(lambda c: T.paged_gather(cfg, c), cache)
    return sum(leaf.size * leaf.dtype.itemsize
               for leaf in jax.tree_util.tree_leaves(gathered)
               if leaf.ndim >= 4 and leaf.shape[-3] in view_lens)


def stored_block_bytes(cfg, block_len: int, cache_quant) -> int:
    """Device bytes per pool block in the STORED representation (quantized
    payload + f32 scale sidecar), via eval_shape — no allocation."""
    from repro.models import transformer as T
    arrays = jax.eval_shape(lambda: T.init_block_pool(
        cfg, 8, block_len, 0, cache_quant=cache_quant))
    kv = sum(leaf.size * leaf.dtype.itemsize
             for sc in arrays for c in sc.values() if c.kv is not None
             for leaf in jax.tree_util.tree_leaves(c.kv))
    return kv // 8


def build_engine(args, impl: str, cache_quant=None, pool_blocks=None):
    from repro import configs as C
    from repro.core.uncertainty import UncertaintyConfig
    from repro.models import transformer as T
    from repro.serving.engine import InferenceEngine
    cfg = dataclasses.replace(C.get_smoke(args.arch), vocab_size=512)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    eng = InferenceEngine(
        f"microbench-{impl}-{cache_quant or 'bf16'}", cfg, params,
        UncertaintyConfig(mode="distribution"), paged=True,
        block_len=args.block_len,
        pool_blocks=pool_blocks or args.pool_blocks,
        max_len=args.ctx + args.steps + 32, attn_decode_impl=impl,
        cache_quant=cache_quant,
        compilation_cache_dir=args.compilation_cache_dir)
    return eng


def bench_impl(args, impl: str, prompts, span,
               cache_quant=None) -> dict[str, dict]:
    eng = build_engine(args, impl, cache_quant=cache_quant)
    hbm_bw = device_hbm_bw()
    B = args.batch
    p_bytes = tree_bytes(eng.params)

    # warm state shared by the insert/decode phases
    st = eng.absorb(prompts)
    cache, _ = eng._paged_grown(st, st.offset + args.steps)
    v_bytes = view_bytes(eng.cfg, cache, eng.block_len)
    if cache_quant is not None:
        # the decode read stream is the STORED pool bytes (quantized
        # payload + scales), not the bf16 view eval_shape reports
        v_bytes = v_bytes * eng.pool.block_bytes \
            // stored_block_bytes(eng.cfg, eng.block_len, None)
    kv_write = v_bytes * args.ctx // cache["table"].shape[1] // eng.block_len

    def run_prefill():
        s = eng.absorb(prompts)
        eng.release(s)

    def run_insert():
        eng.generate(span, 1, state=st)

    def run_decode():
        eng.generate(None, args.steps, state=st)

    phases = {
        # cold prefill streams the params once and writes the context KV
        "prefill": (run_prefill, p_bytes + kv_write, 1),
        # continuation prefill: params once + one pass over the live view
        "continuation_insert": (run_insert, p_bytes + v_bytes, 1),
        # each decode step streams params + the live attention KV; the
        # gather impl ALSO materialises + scatters the view per dispatch
        "decode_step": (run_decode,
                        args.steps * (p_bytes + v_bytes)
                        + (3 * v_bytes if impl == "gather" else 0),
                        args.steps),
    }
    out = {}
    for name, (fn, nbytes, per) in phases.items():
        sec = best_of(fn, args.iters)
        model_sec = nbytes / hbm_bw if hbm_bw else None
        out[name] = {
            "ms": sec / per * 1e3,
            "est_mb": nbytes / per / 1e6,
            "hbm_model_ms": (model_sec / per * 1e3
                             if model_sec is not None else None),
            "hbm_frac": (model_sec / sec if model_sec is not None and sec
                         else None),
        }
    return out


def device_hbm_bw() -> float | None:
    """HBM peak of the chip this process runs on; None off-TPU (a CPU run
    has no device roofline).  An unknown TPU raises (roofline.peaks)."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return None
    return roofline.peaks(dev.device_kind)["hbm_bw"]


def session_density(args) -> dict[str, dict]:
    """Concurrent sessions per fixed pool byte budget, per cache format.

    The budget is what ``--pool-blocks`` bf16 blocks cost; each format
    gets ``budget // block_bytes`` blocks and admits ``ctx + steps``-long
    sessions through the REAL allocator until famine.  Runs on the FULL
    arch geometry (no weights needed — this is allocator arithmetic):
    the smoke configs' tiny head_dim inflates the f32 scale sidecar's
    share ~4x and would understate density.  int8 lands ~1.87x bf16 —
    the 2x payload saving minus the scale sidecar (4/head_dim of the
    payload) and the shared int32 pos rows; fp8 is byte-identical to
    int8 — its win is range, not density."""
    from repro import configs as C
    from repro.serving.cache_manager import CachePool, PoolExhaustedError
    cfg = C.get_config(args.arch)
    nb_sess = -(-(args.ctx + args.steps) // args.block_len)
    budget = args.pool_blocks * stored_block_bytes(cfg, args.block_len, None)
    out = {}
    for quant in (None, "int8", "fp8"):
        bb = stored_block_bytes(cfg, args.block_len, quant)
        n_blocks = max(budget // bb, nb_sess)
        # rows (recurrent state) sit outside the KV block budget; size
        # them off the session bound so block famine is the binding limit
        pool = CachePool(cfg, args.block_len, n_blocks,
                         n_rows=n_blocks // nb_sess + 1, cache_quant=quant)
        n = 0
        try:
            while True:
                pool.alloc(1, nb_sess)
                n += 1
        except PoolExhaustedError:
            pass
        out[quant or "bf16"] = {
            "sessions": n, "blocks": pool.n_blocks,
            "block_kib": pool.block_bytes / 1024,
            "pool_mib": pool.pool_bytes / 2**20,
        }
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--ctx", type=int, default=200,
                    help="live context length before the timed phases")
    ap.add_argument("--span", type=int, default=8,
                    help="continuation-insert span length")
    ap.add_argument("--steps", type=int, default=16,
                    help="decode steps per dispatch")
    ap.add_argument("--block-len", type=int, default=32)
    ap.add_argument("--pool-blocks", type=int, default=512)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--check-hlo", action="store_true",
                    help="assert the kernel-first decode executable drops "
                         "the slot-linear KV view")
    ap.add_argument("--assert-ratio", type=float, default=None, metavar="X",
                    help="fail unless kernel decode_step <= X * gather")
    ap.add_argument("--quant", choices=("int8", "fp8"), default=None,
                    help="also bench a cache_quant engine (kernel impl) "
                         "and the per-format session_density table")
    ap.add_argument("--assert-density", type=float, default=None,
                    metavar="X", help="fail unless int8 fits >= X times "
                                      "the bf16 sessions at fixed pool "
                                      "bytes (CI floor: 1.8)")
    ap.add_argument("--assert-quant-decode", type=float, default=None,
                    metavar="X", help="fail unless the quantized decode "
                                      "step is <= X * the bf16 one "
                                      "(CI ceiling: 1.15 on CPU)")
    ap.add_argument("--compilation-cache-dir", default=None)
    args = ap.parse_args(argv)

    rng = np.random.default_rng(0)
    prompts = rng.integers(7, 500, size=(args.batch, args.ctx)).astype(
        np.int32)
    span = rng.integers(7, 500, size=(args.batch, args.span)).astype(np.int32)

    results = {impl: bench_impl(args, impl, prompts, span)
               for impl in ("kernel", "gather")}
    impls = [("kernel", "kernel"), ("gather", "gather")]
    if args.quant:
        results[f"kernel@{args.quant}"] = bench_impl(
            args, "kernel", prompts, span, cache_quant=args.quant)
        impls.append((f"kernel@{args.quant}", f"kernel@{args.quant}"))

    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}")
    hdr = (f"{'phase':<22}{'impl':<14}{'ms/call':>10}{'est MB':>10}"
           f"{'HBM-model ms':>14}{'frac':>8}")
    print(hdr)
    print("-" * len(hdr))

    def num(x, w, p):
        return f"{'n/a':>{w}}" if x is None else f"{x:>{w}.{p}f}"

    for name in ("prefill", "continuation_insert", "decode_step"):
        for key, label in impls:
            r = results[key][name]
            print(f"{name:<22}{label:<14}{r['ms']:>10.3f}"
                  f"{r['est_mb']:>10.2f}"
                  f"{num(r['hbm_model_ms'], 14, 4)}{num(r['hbm_frac'], 8, 3)}")
    ratio = (results["kernel"]["decode_step"]["ms"]
             / results["gather"]["decode_step"]["ms"])
    print(f"\nkernel_vs_gather_paged_decode: {ratio:.3f} "
          f"(kernel decode-step / gather decode-step; <1 = kernel faster)")

    failed = False
    if args.quant:
        dens = session_density(args)
        print(f"\n{'session_density':<22}{'format':<14}{'sessions':>10}"
              f"{'blocks':>10}{'block KiB':>12}{'pool MiB':>10}")
        for fmt, d in dens.items():
            print(f"{'':<22}{fmt:<14}{d['sessions']:>10}{d['blocks']:>10}"
                  f"{d['block_kib']:>12.1f}{d['pool_mib']:>10.1f}")
        drat = dens["int8"]["sessions"] / dens["bf16"]["sessions"]
        qrat = (results[f"kernel@{args.quant}"]["decode_step"]["ms"]
                / results["kernel"]["decode_step"]["ms"])
        print(f"session_density_int8_vs_bf16: {drat:.3f}x at fixed pool "
              "bytes (2x payload minus the f32 scale sidecar + pos rows)")
        print(f"quant_decode_step_vs_bf16: {qrat:.3f}x wall-clock")
        if args.assert_density is not None:
            ok = drat >= args.assert_density
            print(f"density_floor: {'OK' if ok else 'FAIL'} "
                  f"({drat:.3f} vs >= {args.assert_density})")
            failed |= not ok
        if args.assert_quant_decode is not None:
            ok = qrat <= args.assert_quant_decode
            print(f"quant_decode_ceiling: {'OK' if ok else 'FAIL'} "
                  f"({qrat:.3f} vs <= {args.assert_quant_decode})")
            failed |= not ok
    if args.check_hlo:
        from repro.serving.hlo_probe import assert_no_slot_linear_kv
        try:
            acct = assert_no_slot_linear_kv(
                build_engine(args, "gather"), build_engine(args, "kernel"),
                prompts[:, -16:], steps=4)
            print(f"hlo_check: OK — gather carries {acct['in_gather_hlo']}, "
                  f"kernel-first drops all of it")
        except AssertionError as e:
            print(f"hlo_check: FAIL — {e}")
            failed = True
    if args.assert_ratio is not None:
        ok = ratio <= args.assert_ratio
        print(f"ratio_floor: {'OK' if ok else 'FAIL'} "
              f"({ratio:.3f} vs <= {args.assert_ratio})")
        failed |= not ok
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
