import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
# ^ MUST precede any other import: jax locks the device count on first init,
# and this sweep compiles for 512 fake host devices, never for a chip.

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell we build the production mesh ((16,16) single-pod and
(2,16,16) multi-pod), assemble the *real* step function (the same one
train.py / serve.py execute), lower it with ShapeDtypeStruct stand-ins
(zero allocation), compile, and record:

  * memory_analysis()  — per-device bytes (proves the cell fits)
  * cost_analysis()    — HLO FLOPs / bytes for §Roofline
  * collective bytes   — parsed from the optimized HLO (all-gather,
    all-reduce, reduce-scatter, all-to-all, collective-permute)

Results land in results/dryrun/<arch>__<shape>__<mesh>.json;
EXPERIMENTS.md §Dry-run and §Roofline are generated from these files.

Usage:
  python -m repro.launch.dryrun --arch qwen2-0.5b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod-only|--single-pod-only]
"""
import argparse
import json
import re
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, applicable_shapes, get_config, get_shape
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import (abstract_decode_args, abstract_prefill_args,
                                abstract_train_args, make_prefill_step,
                                make_serve_step, make_train_step)
from repro.models import build_model
from repro.optim import AdamWConfig
from repro.parallel.sharding import sharding_rules

RESULTS = Path(__file__).resolve().parents[3] / "results" / "dryrun"

# HLO collective ops whose operand bytes we account as ICI traffic.
_COLL_RE = re.compile(
    r"(\w[\w.-]*)\s*=\s*((?:\([^)]*\))|(?:[a-z0-9_\[\]{},/ ]+))\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)\(",
)
_SHAPE_RE = re.compile(r"(f32|bf16|f16|s32|u32|s8|u8|pred|s64|f64)\[([\d,]*)\]")
_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
          "u8": 1, "pred": 1, "s64": 8, "f64": 8}


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _BYTES[dt]
    return total


def collective_bytes(hlo_text: str):
    """Sum output-shape bytes of every collective op, by kind."""
    out = {"all-gather": 0, "all-reduce": 0, "reduce-scatter": 0,
           "all-to-all": 0, "collective-permute": 0}
    count = dict.fromkeys(out, 0)
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        kind = m.group(3)
        out[kind] += _shape_bytes(m.group(2))
        count[kind] += 1
    return out, count


def _fmt_bytes(b: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if b < 1024:
            return f"{b:.2f}{unit}"
        b /= 1024
    return f"{b:.2f}PiB"


def run_cell(arch: str, shape: str, multi_pod: bool, out_dir: Path,
             dispatch: str = None, verbose: bool = True,
             xe_shard: str = None):
    cfg = get_config(arch)
    if dispatch and cfg.moe:
        import dataclasses
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, dispatch=dispatch,
                                         xe_shard=xe_shard or "both"))
    case = get_shape(shape)
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    cell = f"{cfg.name}__{case.name}__{mesh_name}" + (
        f"__{dispatch}" if dispatch else "") + (
        f"__{xe_shard}" if xe_shard else "")
    t0 = time.time()
    with sharding_rules(mesh), mesh:
        model = build_model(cfg)
        if case.kind == "train":
            step = make_train_step(model, AdamWConfig())
            args = abstract_train_args(model, case)
            fn = jax.jit(step, donate_argnums=(0, 1))
        elif case.kind == "prefill":
            step = make_prefill_step(model, s_max=case.seq_len)
            args = abstract_prefill_args(model, case)
            from repro.launch.steps import prefill_out_shardings
            fn = jax.jit(step, out_shardings=prefill_out_shardings(
                model, case, step))
        else:  # decode
            step = make_serve_step(model)
            args = abstract_decode_args(model, case)
            fn = jax.jit(step, donate_argnums=(1,))
        lowered = fn.lower(*args)
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
    coll, coll_count = collective_bytes(hlo)
    # trip-count-aware analysis (HloCostAnalysis counts while bodies once —
    # wrong by ~n_layers with scan-over-layers; see hlo_analysis.py)
    from repro.launch.hlo_analysis import analyze_hlo
    tc = analyze_hlo(hlo)
    n_dev = mesh.size
    rec = {
        "arch": cfg.name, "shape": case.name, "kind": case.kind,
        "mesh": mesh_name, "n_devices": n_dev,
        "dispatch": dispatch or (cfg.moe.dispatch if cfg.moe else None),
        "seq_len": case.seq_len, "global_batch": case.global_batch,
        "n_params": model.n_params(),
        "active_params": cfg.active_params(),
        "compile_s": round(time.time() - t0, 1),
        "hlo_flops": cost.get("flops", 0.0) if cost else None,
        "hlo_bytes": cost.get("bytes accessed", 0.0) if cost else None,
        "mem_per_device": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
        },
        "collective_bytes": coll,
        "collective_count": coll_count,
        "hlo_flops_tc": tc["flops"],
        "hlo_bytes_tc": tc["hbm_bytes"],
        "collective_bytes_tc": tc["collective_bytes"],
        "collective_count_tc": tc["collective_count"],
    }
    out_dir.mkdir(parents=True, exist_ok=True)
    (out_dir / f"{cell}.json").write_text(json.dumps(rec, indent=1))
    if verbose:
        mb = rec["mem_per_device"]
        tot_coll = sum(coll.values())
        print(f"[OK] {cell}: compile={rec['compile_s']}s "
              f"flops={rec['hlo_flops']:.3e} "
              f"args/dev={_fmt_bytes(mb['argument_bytes'] or 0)} "
              f"temp/dev={_fmt_bytes(mb['temp_bytes'] or 0)} "
              f"coll={_fmt_bytes(tot_coll)}", flush=True)
    return rec


def iter_cells():
    for name, cfg in ARCHS.items():
        for case in applicable_shapes(cfg):
            yield name, case.name


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--single-pod-only", action="store_true")
    ap.add_argument("--multi-pod-only", action="store_true")
    ap.add_argument("--dispatch", choices=["ellpack", "sort"])
    ap.add_argument("--moe-xe-shard", choices=["both", "batch", "expert"])
    ap.add_argument("--skip-done", action="store_true")
    ap.add_argument("--out", default=str(RESULTS))
    args = ap.parse_args()
    out_dir = Path(args.out)

    if args.all:
        cells = list(iter_cells())
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    pods = []
    if not args.multi_pod_only:
        pods.append(False)
    if not args.single_pod_only:
        pods.append(True)
    if args.multi_pod:
        pods = [True]

    failures = []
    for arch, shape in cells:
        for mp in pods:
            mesh_name = "pod2x16x16" if mp else "pod16x16"
            suffix = f"__{args.dispatch}" if args.dispatch else ""
            done = out_dir / f"{arch}__{shape}__{mesh_name}{suffix}.json"
            if args.skip_done and done.exists():
                print(f"[skip] {done.name}", flush=True)
                continue
            try:
                run_cell(arch, shape, mp, out_dir, dispatch=args.dispatch,
                         xe_shard=args.moe_xe_shard)
            except Exception as e:  # record and continue the sweep
                failures.append((arch, shape, mesh_name, repr(e)))
                print(f"[FAIL] {arch}__{shape}__{mesh_name}: {e}", flush=True)
                traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print("  ", f)
        raise SystemExit(1)
    print("\nAll dry-run cells compiled successfully.")


if __name__ == "__main__":
    main()
