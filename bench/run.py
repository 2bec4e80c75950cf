"""Run one cell of the benchmark once, on the accelerator it starts on.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix come from BENCHMARK.json
at the root of the checkout. With ``--trace 0`` the last line of standard
output is the result with the cell's end-to-end metrics; with
``--trace 1`` the window runs under the JAX profiler and ``repro.obs``
and the line carries the per-layer metrics and the breakdown instead.
Each number compared with the reference is printed with its limit as the
last lines of standard error and under ``checks`` in the result.

The run refuses to start, and prints no result, unless JAX's default
device is a TPU and there are as many as the cell asks for, and unless
the traffic's driver runs on that many chips.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def enable_compile_cache() -> str:
    """JAX's persistent compile cache at a fixed path in the checkout, or
    where ``JAX_COMPILATION_CACHE_DIR`` says. Every program is kept, so a
    second run of a cell compiles nothing."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def require_chips(n: int) -> list:
    """The local devices, or ``SystemExit`` where JAX finds no TPU or
    fewer than ``n`` of them."""
    import jax
    devs = jax.local_devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: JAX's default device is "
                         f"{devs[0].platform}, not a TPU; nothing run")
    if len(devs) < n:
        raise SystemExit(f"bench: the cell needs {n} chips, JAX finds "
                         f"{len(devs)}; nothing run")
    return devs


def import_program():
    """``repro`` from this checkout's ``src``, and nowhere else."""
    src = ROOT / "src"
    sys.path.insert(0, str(src))
    import repro
    if not Path(repro.__file__).resolve().is_relative_to(src):
        raise SystemExit(f"bench: repro imported from {repro.__file__}, "
                         f"not from {src}")
    return repro


def _finite(x):
    return x if not isinstance(x, float) or math.isfinite(x) else repr(x)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(BENCH))
    import harness
    cell = harness.load_cell(args.workload, ROOT)
    harness.driver_class(cell)          # refuses a cell its driver cannot run
    require_chips(cell.chips)
    import_program()
    harness.log(f"[cache] {enable_compile_cache()}")
    trace_dir = ROOT / "bench_out" / f"trace.{args.workload}"
    shutil.rmtree(trace_dir, ignore_errors=True)
    try:
        result = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                             T_START, trace_dir=trace_dir)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    checks = result["checks"]
    for name, c in checks.items():
        harness.log(f"check {name} = {c['value']!r} (limit {c['limit']!r})")
        c["value"] = _finite(c["value"])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
