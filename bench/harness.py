"""The benchmark's general driver: one cell, one run.

Everything that belongs to one configuration, traffic mix or per-layer
metric is found by name:

* ``BENCHMARK.json`` names the cell's configuration and traffic mix;
* the configuration's file (``configs/<name>.json``) names the pattern
  generator (``patterns/<generator>.py``) and holds the sizes;
* ``traffic/<mix>.json`` says how the window drives ``repro.spgemm``,
  through the driver module it names (``drivers/<driver>.py``, by default
  the one-chip closed loop), which places the operands and runs the
  window;
* ``metrics/<metric>.py`` reads one per-layer metric.

A run builds the cell's operands from the seed, warms up every shape the
window uses, lets the driver run the window, reads the device memory
peak, then compares the last output of every (pattern, value set) pair
with the plain reference (``reference.py``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import sys
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CHECKS = ("failed_calls", "nnz_gap", "coord_mismatch", "value_gap")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    cfg_entry = next(c for c in spec["configs"] if c["name"] == w["config"])
    config = json.loads((root / cfg_entry["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())

    def mine(m):
        return "workloads" not in m or name in m["workloads"]

    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=[m for m in spec["end_to_end"] if mine(m)],
                per_layer=[m for m in spec["per_layer"] if mine(m)])


# ---------------------------------------------------------------- operands

@dataclasses.dataclass
class Case:
    """One operand pair: pattern ``p`` with value set ``v``, on the host
    (coordinates and values of A) and on the device (the ELL operands of
    C = A·Aᵀ)."""

    p: int
    v: int
    n: int
    rows: np.ndarray
    cols: np.ndarray
    vals: np.ndarray
    a: object = None
    b: object = None


def ell_planes(rows, cols, vals, n: int, k: int):
    """A's column-wise slots: ``(val, idx)`` of shape (k, n), slot ``s`` of
    column ``c`` holding the ``s``-th entry of that column by row, -1 where
    empty."""
    order = np.lexsort((rows, cols))
    r, c, v = rows[order], cols[order], vals[order]
    first = np.searchsorted(c, np.arange(n))
    slot = np.arange(r.size) - first[c]
    if slot.size and slot.max() >= k:
        raise ValueError(f"a column holds {slot.max() + 1} entries, more "
                         f"than the ELL width {k}")
    val = np.zeros((k, n), np.float32)
    idx = np.full((k, n), -1, np.int32)
    val[slot, c] = v
    idx[slot, c] = r
    return val, idx


def make_cases(config: dict, traffic: dict, seed: int) -> List[Case]:
    """The traffic's patterns × value sets, generated from ``seed``."""
    gen = load_module(BENCH / "patterns" / f"{config['generator']}.py")
    out = []
    for p in range(int(traffic["patterns"])):
        rows, cols, n = gen.pattern(config, p, seed)
        for v in range(int(traffic["value_sets"])):
            rng = np.random.default_rng(np.random.SeedSequence([seed, 2, p, v]))
            vals = rng.standard_normal(rows.size, dtype=np.float32)
            out.append(Case(p=p, v=v, n=n, rows=rows, cols=cols, vals=vals))
    return out


def driver_class(cell: Cell):
    """The ``Driver`` of the module the cell's traffic names
    (``drivers/<driver>.py``, ``closed_loop`` where it names none).
    ``SystemExit`` where the driver does not run on the cell's chips."""
    name = cell.traffic.get("driver", "closed_loop")
    mod = load_module(BENCH / "drivers" / f"{name}.py")
    if cell.chips not in mod.CHIPS:
        raise SystemExit(f"bench: driver {name} runs on {mod.CHIPS} chip(s), "
                         f"cell {cell.name} asks for {cell.chips}; nothing run")
    return mod.Driver


def ready(out):
    import jax
    return jax.block_until_ready(out)


def to_host(out) -> dict:
    import jax
    row, col, val, ngroups = jax.device_get(
        (out.row, out.col, out.val, out.ngroups))
    return {"row": np.asarray(row), "col": np.asarray(col),
            "val": np.asarray(val), "ngroups": int(ngroups)}


# -------------------------------------------------------------------- run

@dataclasses.dataclass
class Window:
    """What a driver's window did: calls made and failed, its length, the
    cases of the products it completed, and the last output of each."""

    attempted: int = 0
    failed: int = 0
    seconds: float = 0.0
    calls: List[Case] = dataclasses.field(default_factory=list)
    outputs: Dict[tuple, object] = dataclasses.field(default_factory=dict)


def peak_bytes() -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


def check(cases: List[Case], outputs: Dict[tuple, dict], limits: dict,
          failed: int, produced: int):
    """Compare every kept output with the reference. Returns the worst
    reading of each number with its limit, and nnz(C) of each case by the
    reference."""
    import reference
    worst = {"failed_calls": failed + (0 if produced else 1),
             "nnz_gap": 0, "coord_mismatch": 0, "value_gap": 0.0}
    nnz_c = {}
    for case in cases:
        ref = reference.product(case.rows, case.cols, case.vals, case.n)
        nnz_c[(case.p, case.v)] = ref.nnz
        out = outputs.get((case.p, case.v))
        if out is None:
            worst["coord_mismatch"] = max(worst["coord_mismatch"], ref.nnz)
            worst["value_gap"] = float("inf")
            continue
        got = reference.compare(out, ref)
        log(f"[check] p{case.p}v{case.v}: nnz(C)={ref.nnz} "
            + " ".join(f"{k}={v!r}" for k, v in got.items()))
        for k, v in got.items():
            worst[k] = max(worst[k], v)
    return {k: {"value": worst[k], "limit": limits[k]} for k in CHECKS}, nnz_c


def counts(case: Case, nnz_c: int) -> dict:
    """Compulsory work of one product C = A·Aᵀ: entries read and written,
    and the valid products (Σ over columns of A of its count squared)."""
    col_n = np.bincount(case.cols, minlength=case.n).astype(np.int64)
    return {"nnz_a": int(case.rows.size), "nnz_b": int(case.rows.size),
            "nnz_c": int(nnz_c), "valid_products": int((col_n ** 2).sum())}


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader may read."""

    products: int
    spans: Dict[str, List[float]]       # repro.obs span durations, seconds
    trace: Optional[object]             # tracing.Summary of the window
    work: List[dict]                    # counts() of each window product
    peaks: Optional[dict]

    def span_mean(self, name: str) -> Optional[float]:
        """Seconds of span ``name`` per product in the window."""
        d = self.spans.get(name)
        if not d or not self.products:
            return None
        return sum(d) / self.products


def read_per_layer(cell: Cell, ctx: Context) -> Dict[str, dict]:
    out = {}
    for m in cell.per_layer:
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
        got = reader.read(ctx)
        if got is None:
            continue
        if not isinstance(got, dict):
            got = {"value": got}
        out[m["name"]] = {"value": got.pop("value"), "unit": m["unit"], **got}
    return out


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_start: float, trace_dir: Optional[Path] = None,
        driver_factory: Optional[Callable] = None) -> dict:
    """One run of ``cell``; returns the result object (without printing).
    ``driver_factory`` puts another timed path in place of the traffic's
    driver: a broken one in a test, the control in ``limits.py``."""
    import jax
    devs = jax.local_devices()
    k = int(cell.config["ell_k"])
    driver = (driver_factory or driver_class(cell))(cell.traffic)
    t = time.perf_counter()
    cases = make_cases(cell.config, cell.traffic, seed)
    for c in cases:
        driver.place(c, k)
    log(f"[setup] {len(cases)} case(s) of {cell.config['name']} built and "
        f"placed in {time.perf_counter() - t:.2f} s")
    t = time.perf_counter()
    driver.setup(cases)
    for c in cases:                     # warm up every shape the window uses
        t1 = time.perf_counter()
        ready(driver.call(c))
        log(f"[warmup] p{c.p}v{c.v}: {time.perf_counter() - t1:.2f} s")
    log(f"[setup] structures and warm-up in {time.perf_counter() - t:.2f} s")

    annotate = lambda name: contextlib.nullcontext()  # noqa: E731
    if trace:
        import repro.obs
        trace_dir.mkdir(parents=True, exist_ok=True)
        jax.profiler.start_trace(str(trace_dir))
        annotate = jax.profiler.TraceAnnotation
        repro.obs.enable(reset=True)
    setup_s = time.perf_counter() - t_start
    w = driver.window(cases, seconds, annotate)
    spans = {}
    if trace:
        import repro.obs
        repro.obs.disable()
        for e in repro.obs.get_tracer().spans():
            spans.setdefault(e["name"], []).append(e["dur_us"] / 1e6)
        jax.profiler.stop_trace()
    products = len(w.calls)
    log(f"[window] {products} products, {w.failed} failed, "
        f"{w.seconds:.3f} s")
    memory = peak_bytes()

    # free the program's state before the reference runs
    outputs = {key: to_host(o) for key, o in w.outputs.items()}
    w.outputs.clear()
    driver.close()
    for c in cases:
        c.a = c.b = None
    t = time.perf_counter()
    checks, nnz_c = check(cases, outputs, cell.config["limits"], w.failed,
                          products)
    log(f"[check] reference and comparison in {time.perf_counter() - t:.2f} s")
    correct = all(v["value"] <= v["limit"] for v in checks.values())

    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs), "memory_peak_bytes": memory}
    result = {"correct": correct, "attempted": w.attempted,
              "failed": w.failed}
    if not trace:
        values = {"product_s": w.seconds / products if products else None,
                  "peak_hbm_gb": memory / 1e9, "setup_s": setup_s}
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end
                             if values.get(m["name"]) is not None}
    else:
        import tracing
        from peaks import peak
        summary = tracing.summarize(tracing.read_events(
            tracing.find_xplane(str(trace_dir))))
        work = [counts(c, nnz_c[(c.p, c.v)]) for c in w.calls]
        ctx = Context(products=products, spans=spans, trace=summary,
                      work=work,
                      peaks=summary and peak(devs[0].device_kind))
        result["metrics"] = read_per_layer(cell, ctx)
        if summary is not None:
            dev["busy_s"] = summary.busy_s
            dev["window_s"] = summary.window_s
            result["breakdown"] = {"device_ops": summary.top_ops,
                                   "idle_gaps": summary.idle_gaps}
    result["device"] = dev
    result["checks"] = checks
    return result
