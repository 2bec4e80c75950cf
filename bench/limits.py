"""Readings from which a cell's limits are set, at the cell's own size.

    python bench/limits.py --workload <cell> --seeds 1,2,3 --control-seeds 1,2,3

Each seed of ``--seeds`` is one run of the cell with a window of no
length (``harness.run``: set-up, every case once through the timed path,
the comparison). Each seed of ``--control-seeds`` is the same run with
the control in the program's place: the reference one precision below
(``reference.control``), which has to come out as not correct. One JSON
line per seed and side goes to standard output, with ``correct`` and
every number compared. The benchmark's own runs do not run this; it needs
the chip the cell asks for.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import types
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402


class ControlDriver:
    """The control in the timed path's place: the output of each case is
    ``reference.control`` of its pattern and values, in the program's
    layout, computed once per case on the host."""

    def __init__(self, traffic: dict):
        self.done = {}

    def place(self, case, k: int) -> None:
        pass

    def setup(self, cases) -> None:
        pass

    def call(self, case):
        import reference
        key = (case.p, case.v)
        if key not in self.done:
            self.done[key] = types.SimpleNamespace(
                **reference.control(case.rows, case.cols, case.vals, case.n))
        return self.done[key]

    def window(self, cases, seconds: float, annotate) -> harness.Window:
        w = harness.Window()
        t0 = time.perf_counter()
        for case in cases:
            w.attempted += 1
            w.outputs[(case.p, case.v)] = self.call(case)
            w.calls.append(case)
        w.seconds = time.perf_counter() - t0
        return w

    def close(self) -> None:
        self.done.clear()


def readings(cell, seed: int, control: bool = False) -> dict:
    """``correct`` and the reading of every number compared, from one run
    of ``cell`` with a window of no length."""
    res = harness.run(cell, seed, 0.0, False, time.perf_counter(),
                      driver_factory=ControlDriver if control else None)
    return {"correct": res["correct"],
            **{k: v["value"] for k, v in res["checks"].items()}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    import run
    cell = harness.load_cell(args.workload, run.ROOT)
    harness.driver_class(cell)
    run.require_chips(cell.chips)
    run.import_program()
    run.enable_compile_cache()
    sides = [("program", s, False) for s in _ints(args.seeds)]
    sides += [("control", s, True) for s in _ints(args.control_seeds)]
    for side, seed, control in sides:
        t = time.perf_counter()
        got = readings(cell, seed, control)
        print(json.dumps({"workload": args.workload, "side": side,
                          "seed": seed, **got,
                          "seconds": time.perf_counter() - t}), flush=True)
    return 0


def _ints(s: str):
    return [int(x) for x in s.split(",") if x]


if __name__ == "__main__":
    sys.exit(main())
