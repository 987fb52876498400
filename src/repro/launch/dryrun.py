import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove every (arch x shape x mesh) cell lowers,
compiles, fits, and report its roofline inputs.

For each cell we compile:
  1. the FULL production step (scan-over-layers) -> memory_analysis (peak
     per-device bytes) + the lower/compile proof itself;
  2. two UNROLLED slice models (prefix + 1x / 2x pattern periods) ->
     linearly extrapolated per-device FLOPs / bytes / collective-bytes.
     (XLA's cost analysis counts a `while` body ONCE regardless of trip
     count, so scanned programs must be slice-corrected — measured, see
     EXPERIMENTS.md §Dry-run methodology.)

Collective bytes are parsed from the post-SPMD optimized HLO; per-device
link traffic uses ring-algorithm factors (AR 2(G-1)/G, AG/RS/A2A (G-1)/G of
the full payload, CP 1x).

Usage:
  python -m repro.launch.dryrun --arch llama3-8b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all [--mesh both] [--out-dir experiments/dryrun]
"""

import argparse
import dataclasses
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp

from repro import configs as C
from repro.distributed import sharding as sh
from repro.launch.mesh import data_shards, make_production_mesh
from repro.models import transformer as T
from repro.training import optimizer as opt
from repro.training import train as TR

DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
               "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
               "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_COLL_RE = re.compile(
    r"=\s+(\w+)\[([\d,]*)\][^ ]*\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_GROUP_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUP_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


def parse_collectives(hlo_text: str) -> dict:
    """Per-device collective traffic (bytes) by op type, ring-model factors."""
    out = {"all-reduce": 0.0, "all-gather": 0.0, "reduce-scatter": 0.0,
           "all-to-all": 0.0, "collective-permute": 0.0}
    counts = dict.fromkeys(out, 0)
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        dt, dims, op = m.group(1), m.group(2), m.group(3)
        if dt not in DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        nbytes = n * DTYPE_BYTES[dt]
        g = None
        gm = _GROUP_RE.search(line)
        if gm:
            g = int(gm.group(2))            # [n_groups, group_size]
        else:
            gl = _GROUP_LIST_RE.search(line)
            if gl:
                g = len(gl.group(1).split(","))
        g = g or 2
        if op == "all-reduce":
            traffic = 2 * (g - 1) / g * nbytes
        elif op == "all-gather":
            traffic = (g - 1) / g * nbytes          # result is full payload
        elif op == "reduce-scatter":
            traffic = (g - 1) * nbytes              # operand = result * g
        elif op == "all-to-all":
            traffic = (g - 1) / g * nbytes
        else:                                       # collective-permute
            traffic = nbytes
        out[op] += traffic
        counts[op] += 1
    out["counts"] = counts
    return out


# ---------------------------------------------------------------------------
# Step builders
# ---------------------------------------------------------------------------

def _abstract_batch(cfg, shape):
    return C.input_specs(cfg, shape)


ACT_BUDGET_BYTES = 8e9     # per-device live-activation target (v5e: 16 GB HBM)


def train_microbatches(cfg, shape, mesh) -> int:
    """Grad-accumulation factor so scanned-layer residuals fit HBM.

    The layer scan saves its carry (B_loc, S, D) per step for backward:
    L * B_loc * S * D * 2 bytes.  Choose the smallest power-of-two split
    keeping that (plus the logits block) under ACT_BUDGET_BYTES.
    """
    dp = data_shards(mesh)
    b_loc = max(shape.global_batch // dp, 1)
    resid = cfg.num_layers * b_loc * shape.seq_len * cfg.d_model * 2
    tp = mesh.shape.get("model", 1)
    logits = b_loc * shape.seq_len * (cfg.vocab_size // tp) * 6
    mb = 1
    while (resid + logits) / mb > ACT_BUDGET_BYTES and mb < b_loc:
        mb *= 2
    return mb


def build_cell(cfg, shape, mesh, rules=None, force_mb: int | None = None):
    """Returns (jitted_fn, example_args) for one cell."""
    rules = rules or sh.DEFAULT_RULES
    B = shape.global_batch
    tokens_total = B * (1 if shape.kind == "decode" else shape.seq_len)
    groups = data_shards(mesh)
    while tokens_total % groups:
        groups //= 2        # MoE dispatch groups must divide the token count

    ps = TR.param_shardings(cfg, mesh, rules)
    abs_p = T.abstract_params(cfg)

    if shape.kind == "train":
        mb = force_mb or train_microbatches(cfg, shape, mesh)
        step = TR.build_train_step(cfg, opt.AdamWConfig(), mesh, rules=rules,
                                   moe_groups=groups, microbatches=mb)
        abs_o = opt.abstract_state(abs_p)
        batch = _abstract_batch(cfg, shape)
        return step, (abs_p, abs_o, batch)

    if shape.kind == "prefill":
        batch = _abstract_batch(cfg, shape)

        def fwd(params, b):
            logits, _ = T.forward(params, cfg, b, moe_groups=groups,
                                  mesh=mesh, rules=rules)
            return logits
        bs = TR.batch_shardings(batch, mesh)
        return jax.jit(fwd, in_shardings=(ps, bs)), (abs_p, batch)

    # decode: one new token against a seq_len-deep cache
    spec = _abstract_batch(cfg, shape)
    cache_sds = spec["cache"]
    cax = T.cache_axes(cfg)
    cache_specs = sh.tree_specs(cache_sds, cax, mesh, rules.act_rules)
    cache_sh = jax.tree.map(
        lambda s: jax.sharding.NamedSharding(mesh, s), cache_specs)
    tok_sh = TR.batch_shardings({"t": spec["tokens"]}, mesh)["t"]
    idx_sh = TR.batch_shardings({"t": spec["index"]}, mesh)["t"]

    def serve_step(params, tokens, cache, index):
        # decode MoE dispatch is per-token exact top-k (no dispatch groups)
        logits, cache = T.decode_step(params, cfg, tokens, cache, index,
                                      mesh=mesh, rules=rules)
        return logits, cache

    fn = jax.jit(serve_step,
                 in_shardings=(ps, tok_sh, cache_sh, idx_sh),
                 out_shardings=(None, cache_sh),
                 donate_argnums=(2,))
    return fn, (abs_p, spec["tokens"], cache_sds, spec["index"])


def compile_cell(cfg, shape, mesh, rules=None, force_mb: int | None = None):
    fn, args = build_cell(cfg, shape, mesh, rules, force_mb=force_mb)
    t0 = time.time()
    with mesh:
        lowered = fn.lower(*args)
        compiled = lowered.compile()
    dt = time.time() - t0
    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    coll = parse_collectives(compiled.as_text())
    return {
        "compile_s": round(dt, 2),
        "flops_per_device": ca.get("flops", 0.0),
        "bytes_per_device": ca.get("bytes accessed", 0.0),
        "memory": {
            "argument": ma.argument_size_in_bytes,
            "output": ma.output_size_in_bytes,
            "temp": ma.temp_size_in_bytes,
            "alias": ma.alias_size_in_bytes,
            "peak_est": ma.argument_size_in_bytes + ma.temp_size_in_bytes
            + ma.output_size_in_bytes - ma.alias_size_in_bytes,
        },
        "collectives": coll,
    }


def _slice_configs(cfg):
    """(slice_a_cfg, slice_b_cfg, repeats_R): A has prefix+period+tail
    layers, B has one extra period; full = A + (R-1) * (B - A)."""
    stages = cfg.stage_plan()
    body = max(stages, key=lambda s: s.repeat)
    period = len(body.blocks)
    other = sum(len(s.blocks) * s.repeat for s in stages) \
        - period * body.repeat
    la = other + period
    lb = other + 2 * period
    a = dataclasses.replace(cfg, num_layers=la, scan_layers=False)
    b = dataclasses.replace(cfg, num_layers=lb, scan_layers=False)
    return a, b, body.repeat


def corrected_costs(cfg, shape, mesh, rules=None) -> dict:
    """Slice-extrapolated per-device flops/bytes/collectives for the cell.

    Slices compile with microbatches=1: the grad-accumulation scan is a
    `while` loop whose body XLA's cost analysis counts once, so slices with
    different mb would break the linear extrapolation (measured: command-r
    train_4k showed 6ND/HLO = 20x before this fix).  mb does not change the
    per-token flops/bytes, only live memory — which comes from the full
    compile.
    """
    ca_cfg, cb_cfg, R = _slice_configs(cfg)
    ra = compile_cell(ca_cfg, shape, mesh, rules, force_mb=1)
    rb = compile_cell(cb_cfg, shape, mesh, rules, force_mb=1)

    def lin(pa, pb):
        return pa + (R - 1) * max(pb - pa, 0.0)

    coll = {}
    for k in ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
              "collective-permute"):
        coll[k] = lin(ra["collectives"][k], rb["collectives"][k])
    return {
        "flops_per_device": lin(ra["flops_per_device"], rb["flops_per_device"]),
        "bytes_per_device": lin(ra["bytes_per_device"], rb["bytes_per_device"]),
        "collective_bytes_per_device": sum(coll.values()),
        "collectives": coll,
        "slice_layers": (ca_cfg.num_layers, cb_cfg.num_layers, R),
    }


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: str,
             skip_existing: bool = True, variant: str = "",
             rules_name: str = "default", moe_impl: str | None = None,
             act_budget: float | None = None,
             serve_dtype: str | None = None) -> dict:
    os.makedirs(out_dir, exist_ok=True)
    suffix = f"__{variant}" if variant else ""
    path = os.path.join(out_dir,
                        f"{arch}__{shape_name}__{mesh_kind}{suffix}.json")
    if skip_existing and os.path.exists(path):
        with open(path) as f:
            return json.load(f)
    cfg = C.get_config(arch)
    if moe_impl:
        cfg = dataclasses.replace(cfg, moe_impl=moe_impl)
    if serve_dtype == "f8":
        cfg = dataclasses.replace(cfg, dtype=jnp.float8_e4m3fn,
                                  compute_dtype=jnp.bfloat16)
    shape = C.SHAPES[shape_name]
    skip = C.applicability(cfg, shape)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "variant": variant, "rules": rules_name,
           "model_params": cfg.num_params(),
           "active_params": cfg.active_params()}
    if skip:
        rec["skipped"] = skip
    else:
        global ACT_BUDGET_BYTES
        old_budget = ACT_BUDGET_BYTES
        if act_budget:
            ACT_BUDGET_BYTES = act_budget
        rules = sh.RULE_SETS[rules_name]
        mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
        try:
            rec["full"] = compile_cell(cfg, shape, mesh, rules)
            rec["corrected"] = corrected_costs(cfg, shape, mesh, rules)
            rec["ok"] = True
        except Exception as e:  # noqa: BLE001 - record failure for the report
            rec["ok"] = False
            rec["error"] = f"{type(e).__name__}: {e}"
            rec["traceback"] = traceback.format_exc()[-3000:]
        finally:
            ACT_BUDGET_BYTES = old_budget
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    status = rec.get("skipped") and "SKIP" or (rec.get("ok") and "OK" or "FAIL")
    print(f"[dryrun] {arch} x {shape_name} x {mesh_kind}{suffix}: {status}",
          flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out-dir", default="experiments/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--variant", default="",
                    help="suffix for hillclimb artifacts")
    ap.add_argument("--rules", default="default",
                    choices=["default", "sp", "serve"])
    ap.add_argument("--moe-impl", default=None, choices=["sort", "cumsum"])
    ap.add_argument("--act-budget", type=float, default=None)
    ap.add_argument("--serve-dtype", default=None, choices=["f8"])
    args = ap.parse_args()

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    cells = (C.cells(include_skipped=True) if args.all
             else [(args.arch, args.shape, None)])
    n_ok = n_fail = 0
    for arch, shape_name, _ in cells:
        for mk in meshes:
            rec = run_cell(arch, shape_name, mk, args.out_dir,
                           skip_existing=not args.force,
                           variant=args.variant, rules_name=args.rules,
                           moe_impl=args.moe_impl,
                           act_budget=args.act_budget,
                           serve_dtype=args.serve_dtype)
            if rec.get("ok") or rec.get("skipped"):
                n_ok += 1
            else:
                n_fail += 1
    print(f"[dryrun] done: {n_ok} ok/skip, {n_fail} failed", flush=True)
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
