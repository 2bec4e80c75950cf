"""Roofline analysis over the dry-run artifacts (deliverable g).

Reads results/dryrun/*.json (written by repro.launch.dryrun) and derives the
three roofline terms per (arch × shape × mesh):

    compute    = HLO_FLOPs_per_device / 197e12            [bf16 peak / chip]
    memory     = HLO_bytes_per_device / 819e9              [HBM BW / chip]
    collective = collective_bytes_per_device / 50e9        [ICI / link]

Conventions: XLA compiles one SPMD program per device, so cost_analysis()
numbers are already per-chip; collective bytes are the summed *output-shape*
bytes of every all-gather/all-reduce/reduce-scatter/all-to-all/
collective-permute in the optimized HLO (ring transfer volume ≈ output size
× (n-1)/n ≈ output size). CPU-backend caveat recorded per row: XLA:CPU
canonicalizes bf16 dots to f32, so HLO_bytes (and some temps) are up to 2×
the TPU value — flagged, not corrected.

MODEL_FLOPS: train 6·N·D, prefill 2·N·D, decode 2·N_active·B (one token),
divided by chips (global→per-chip, to match the HLO numbers).

Usage:
    python -m benchmarks.roofline [--emit-md results/roofline.md]
"""
from __future__ import annotations

import argparse
import json
from pathlib import Path

PEAK_FLOPS = 197e12      # bf16 / chip (TPU v5e)
HBM_BW = 819e9           # bytes/s / chip
ICI_BW = 50e9            # bytes/s / link

RESULTS = Path(__file__).resolve().parents[1] / "results"


def model_flops_global(rec) -> float:
    n_act = rec["active_params"]
    d_tokens = rec["global_batch"] * rec["seq_len"]
    if rec["kind"] == "train":
        return 6.0 * n_act * d_tokens
    if rec["kind"] == "prefill":
        return 2.0 * n_act * d_tokens
    # decode: one new token per sequence (attention over the cache adds
    # 2·B·S·L·kv·hd·2 ~ included approximately via active params only)
    return 2.0 * n_act * rec["global_batch"]


def analyze(rec) -> dict:
    chips = rec["n_devices"]
    # prefer the trip-count-aware numbers (hlo_analysis.py); raw
    # HloCostAnalysis counts while bodies once (wrong by ~n_layers)
    flops = rec.get("hlo_flops_tc") or rec["hlo_flops"] or 0.0
    bytes_ = rec.get("hlo_bytes_tc") or rec["hlo_bytes"] or 0.0
    coll_d = rec.get("collective_bytes_tc") or rec["collective_bytes"]
    t_comp = flops / PEAK_FLOPS
    t_mem = bytes_ / HBM_BW
    coll = sum(coll_d.values())
    t_coll = coll / ICI_BW
    terms = {"compute": t_comp, "memory": t_mem, "collective": t_coll}
    dom = max(terms, key=terms.get)
    mf = model_flops_global(rec) / chips
    useful = mf / flops if flops else 0.0
    bound = max(terms.values())
    frac = t_comp / bound if bound else 0.0   # fraction of time that is MXU math
    return {
        "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
        "kind": rec["kind"],
        "t_compute_s": t_comp, "t_memory_s": t_mem, "t_collective_s": t_coll,
        "dominant": dom,
        "model_flops_per_chip": mf,
        "useful_flop_ratio": useful,
        "roofline_fraction": frac,
        "temp_bytes": rec["mem_per_device"]["temp_bytes"],
        "arg_bytes": rec["mem_per_device"]["argument_bytes"],
        "dispatch": rec.get("dispatch"),
    }


ADVICE = {
    ("compute", "train"): "cut recompute (remat policy) / raise MXU occupancy",
    ("compute", "prefill"): "halve causal-masked attention FLOPs via block skipping",
    ("compute", "decode"): "batch more sequences per step (MXU is idle at B·1)",
    ("memory", "train"): "fuse optimizer update into grad reduce; bf16 moments",
    ("memory", "prefill"): "keep KV in bf16 and widen VMEM tiles",
    ("memory", "decode"): "shrink KV reads: quantize cache / group-query sharing",
    ("collective", "train"): "overlap reduce-scatter with backward; int8 grads",
    ("collective", "prefill"): "shard seq (ring attention) to kill kv all-gathers",
    ("collective", "decode"): "replicate small weights over data to drop gathers",
}


def rows(pattern: str = "*.json"):
    recs = []
    for p in sorted((RESULTS / "dryrun").glob(pattern)):
        recs.append(analyze(json.loads(p.read_text())))
    return recs


def to_markdown(recs) -> str:
    hdr = ("| arch | shape | mesh | compute s | memory s | collective s | "
           "dominant | useful | advice |\n|---|---|---|---|---|---|---|---|---|\n")
    out = [hdr]
    for r in recs:
        adv = ADVICE.get((r["dominant"], r["kind"]), "-")
        out.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} "
            f"| {r['t_compute_s']:.3e} | {r['t_memory_s']:.3e} "
            f"| {r['t_collective_s']:.3e} | **{r['dominant']}** "
            f"| {r['useful_flop_ratio']:.2f} | {adv} |\n")
    return "".join(out)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--emit-md", default="")
    ap.add_argument("--mesh", default="", help="filter: pod16x16 / pod2x16x16")
    args = ap.parse_args()
    recs = rows()
    if args.mesh:
        recs = [r for r in recs if r["mesh"] == args.mesh]
    (RESULTS / "roofline.json").write_text(json.dumps(recs, indent=1))
    print(f"{'arch':24s} {'shape':12s} {'mesh':10s} "
          f"{'comp(s)':>9s} {'mem(s)':>9s} {'coll(s)':>9s} {'dom':>10s} "
          f"{'useful':>7s}")
    for r in recs:
        print(f"{r['arch']:24s} {r['shape']:12s} {r['mesh']:10s} "
              f"{r['t_compute_s']:9.2e} {r['t_memory_s']:9.2e} "
              f"{r['t_collective_s']:9.2e} {r['dominant']:>10s} "
              f"{r['useful_flop_ratio']:7.2f}")
    if args.emit_md:
        Path(args.emit_md).write_text(to_markdown(recs))
        print(f"wrote {args.emit_md}")


if __name__ == "__main__":
    main()
