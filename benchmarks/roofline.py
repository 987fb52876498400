"""Roofline analysis from the dry-run artifacts (EXPERIMENTS.md §Roofline).

Hardware model: per-chip peaks keyed by ``jax.Device.device_kind``
(:data:`PEAKS`).  The dry-run sweep models a TPU v5e pod
(:data:`DRYRUN_DEVICE_KIND`).  Per (arch x shape x mesh):

  compute_term    = corrected FLOPs/device   / peak_flops
  memory_term     = corrected bytes/device   / hbm_bw
  collective_term = corrected coll-bytes/dev / link_bw

and MODEL_FLOPS = 6·N_active·D (train) / 2·N_active·D (inference) per device
for the usefulness ratio.
"""

from __future__ import annotations

import glob
import json
import os

# Published per-chip peaks, keyed by the device_kind JAX reports.  Source:
# Google Cloud documentation, "TPU v5e" (system architecture table): 197
# TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of
# inter-chip interconnect per chip over 4 ICI links (50 GB/s per link).
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes": 16e9, "hbm_bw": 819e9, "link_bw": 50e9},
}

DRYRUN_DEVICE_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> dict:
    """Peaks of one chip of ``device_kind``; a device not in :data:`PEAKS`
    is an error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}: add them to roofline.PEAKS with "
                       "their source") from None


_DRYRUN = peaks(DRYRUN_DEVICE_KIND)
PEAK_FLOPS = _DRYRUN["bf16_flops"]
HBM_BW = _DRYRUN["hbm_bw"]
LINK_BW = _DRYRUN["link_bw"]

CHIPS = {"single": 256, "multi": 512}


def model_flops_per_device(rec: dict, shape_kind: str, seq_len: int,
                           batch: int, chips: int) -> float:
    n = rec["active_params"]
    if shape_kind == "train":
        tokens = seq_len * batch
        return 6 * n * tokens / chips
    if shape_kind == "prefill":
        tokens = seq_len * batch
        return 2 * n * tokens / chips
    return 2 * n * batch / chips          # decode: one token per request


def analyse(out_dir: str = "experiments/dryrun") -> list[dict]:
    from repro import configs as C
    rows = []
    for path in sorted(glob.glob(os.path.join(out_dir, "*.json"))):
        rec = json.load(open(path))
        if rec.get("skipped") or not rec.get("ok"):
            rows.append({"arch": rec["arch"], "shape": rec["shape"],
                         "mesh": rec["mesh"],
                         "skip": rec.get("skipped") or rec.get("error")})
            continue
        shape = C.SHAPES[rec["shape"]]
        chips = CHIPS[rec["mesh"]]
        c = rec["corrected"]
        t_comp = c["flops_per_device"] / PEAK_FLOPS
        t_mem = c["bytes_per_device"] / HBM_BW
        t_coll = c["collective_bytes_per_device"] / LINK_BW
        dom = max((t_comp, "compute"), (t_mem, "memory"),
                  (t_coll, "collective"))[1]
        mf = model_flops_per_device(rec, shape.kind, shape.seq_len,
                                    shape.global_batch, chips)
        bound = max(t_comp, t_mem, t_coll)
        rows.append({
            "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
            "compute_s": t_comp, "memory_s": t_mem, "collective_s": t_coll,
            "dominant": dom,
            "model_flops_ratio": mf / max(c["flops_per_device"], 1.0),
            "roofline_fraction": (mf / PEAK_FLOPS) / bound if bound else 0.0,
            "peak_hbm_gb": rec["full"]["memory"]["peak_est"] / 1e9,
            "hbm_ok": rec["full"]["memory"]["peak_est"] < 16e9,
        })
    return rows


def emit_rows(rows):
    out = []
    for r in rows:
        if "skip" in r:
            continue
        cell = f"{r['arch']}__{r['shape']}__{r['mesh']}"
        out.append((f"roofline_{cell}_dominant_{r['dominant']}", "",
                    round(max(r["compute_s"], r["memory_s"],
                              r["collective_s"]), 6)))
        out.append((f"roofline_{cell}_fraction", "",
                    round(r["roofline_fraction"], 4)))
    return out


def next_lever(r: dict) -> str:
    """One sentence: what would move the dominant term down (per cell)."""
    dom, shape = r["dominant"], r["shape"]
    kind = ("train" if shape.startswith("train")
            else "prefill" if shape.startswith("prefill") else "decode")
    if dom == "collective":
        if kind == "decode":
            return ("stop re-gathering FSDP weight shards per token: "
                    "SERVE_RULES TP-resident weights (+f8) — see §Perf")
        if kind == "prefill":
            return ("overlap TP all-reduces with the next layer's GEMMs "
                    "(latency-hiding scheduler) or widen to 2D TP")
        return ("reduce-scatter grads instead of all-reduce + int8 "
                "error-feedback compression on the pod axis")
    if dom == "memory":
        if kind == "train":
            return ("fewer remat recomputes via dots-saveable policy, or "
                    "shard_map-local MoE dispatch (done for MoE cells)")
        if kind == "decode":
            return ("f8/int8 KV + weights (halves resident bytes); fuse "
                    "decode attention so cache is read once (Pallas kernel)")
        return ("fuse attention/FFN epilogues (Pallas) to cut HBM "
                "round-trips between blocks")
    return ("raise arithmetic intensity: larger per-device microbatch or "
            "MXU-aligned block shapes in the Pallas kernels")


def markdown_table(rows) -> str:
    lines = ["| arch | shape | mesh | compute s | memory s | collective s | "
             "dominant | 6ND/HLO | roofline frac | peak HBM GB | "
             "what would move the dominant term |",
             "|---|---|---|---|---|---|---|---|---|---|---|"]
    for r in rows:
        if "skip" in r:
            lines.append(f"| {r['arch']} | {r['shape']} | {r['mesh']} | "
                         f"— skipped: {r['skip']} ||||||||")
            continue
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} "
            f"| {r['compute_s']:.2e} | {r['memory_s']:.2e} "
            f"| {r['collective_s']:.2e} | {r['dominant']} "
            f"| {r['model_flops_ratio']:.2f} | {r['roofline_fraction']:.2f} "
            f"| {r['peak_hbm_gb']:.1f}{'' if r['hbm_ok'] else ' ⚠'} "
            f"| {next_lever(r)} |")
    return "\n".join(lines)


def hillclimb_table(hc_dir: str = "experiments/hillclimb",
                    base_dir: str = "experiments/dryrun_v0") -> str:
    """Baseline-vs-variant comparison for the §Perf cells."""
    lines = ["| cell | variant | compute s | memory s | collective s | "
             "peak GB |", "|---|---|---|---|---|---|"]
    for path in sorted(glob.glob(os.path.join(hc_dir, "*.json"))):
        rec = json.load(open(path))
        if not rec.get("ok"):
            continue
        cell = f"{rec['arch']} × {rec['shape']} × {rec['mesh']}"
        base_path = os.path.join(
            base_dir, f"{rec['arch']}__{rec['shape']}__{rec['mesh']}.json")
        if os.path.exists(base_path):
            b = json.load(open(base_path))
            if b.get("ok"):
                c = b["corrected"]
                lines.append(
                    f"| {cell} | baseline (v0) | "
                    f"{c['flops_per_device']/PEAK_FLOPS:.3f} | "
                    f"{c['bytes_per_device']/HBM_BW:.3f} | "
                    f"{c['collective_bytes_per_device']/LINK_BW:.3f} | "
                    f"{b['full']['memory']['peak_est']/1e9:.1f} |")
        c = rec["corrected"]
        lines.append(
            f"| {cell} | **{rec.get('variant')}** | "
            f"{c['flops_per_device']/PEAK_FLOPS:.3f} | "
            f"{c['bytes_per_device']/HBM_BW:.3f} | "
            f"{c['collective_bytes_per_device']/LINK_BW:.3f} | "
            f"{rec['full']['memory']['peak_est']/1e9:.1f} |")
    return "\n".join(lines)


if __name__ == "__main__":
    rows = analyse()
    print(markdown_table(rows))
    print()
    print(hillclimb_table())
