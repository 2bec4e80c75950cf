"""Closed loop on one chip: one product in flight, calls back to back.

The traffic file says how each call is made: with ``structure`` true a
``SpgemmStructure`` is built per pattern in set-up and every call takes
the warm numeric path; otherwise every call runs cold. ``call`` holds the
keyword arguments of ``repro.spgemm``.

A driver module gives ``CHIPS``, the chip counts it runs on, and a
``Driver`` with ``place``, ``setup``, ``call``, ``window`` and ``close``.
A traffic file names its driver under ``"driver"``; this one is the
default.
"""
from __future__ import annotations

import time
import traceback
from typing import Callable, Dict, List

import harness

CHIPS = (1,)


class Driver:

    def __init__(self, traffic: dict):
        import repro
        self.repro = repro
        self.kw = dict(traffic.get("call", {}))
        self.warm = bool(traffic.get("structure", False))
        self.structures: Dict[int, object] = {}

    def place(self, case: harness.Case, k: int) -> None:
        """Put the case's operands on the default device: A as row-wise
        ELL (``EllRows``) and B = Aᵀ as column-wise ELL (``EllCols``)."""
        import jax.numpy as jnp
        import numpy as np
        val, idx = harness.ell_planes(case.rows, case.cols, case.vals,
                                      case.n, k)
        case.a = self.repro.EllRows(val=jnp.asarray(val),
                                    idx=jnp.asarray(idx), n_rows=case.n)
        case.b = self.repro.EllCols(
            val=jnp.asarray(np.ascontiguousarray(val.T)),
            idx=jnp.asarray(np.ascontiguousarray(idx.T)), n_cols=case.n)

    def setup(self, cases: List[harness.Case]) -> None:
        if self.warm:
            for c in cases:
                if c.p not in self.structures:
                    self.structures[c.p] = self.repro.make_structure(c.a, c.b)

    def call(self, case: harness.Case):
        kw = dict(self.kw)
        if self.warm:
            kw["structure"] = self.structures[case.p]
        return self.repro.spgemm(case.a, case.b, **kw)

    def window(self, cases: List[harness.Case], seconds: float,
               annotate: Callable) -> harness.Window:
        """Whole products over the cases in turn until ``seconds`` have
        passed and every case has run once. The last output of each case
        is kept (the previous one of a case is dropped before its next
        call)."""
        w = harness.Window()
        with annotate("bench.window"):
            t0 = time.perf_counter()
            while True:
                case = cases[w.attempted % len(cases)]
                w.outputs.pop((case.p, case.v), None)
                w.attempted += 1
                try:
                    t = time.perf_counter()
                    with annotate(f"bench.product.p{case.p}v{case.v}"):
                        out = harness.ready(self.call(case))
                    w.outputs[(case.p, case.v)] = out
                    w.calls.append(case)
                    harness.log(f"[product] p{case.p}v{case.v}: "
                                f"{time.perf_counter() - t:.3f} s")
                except Exception:               # a product that fails
                    w.failed += 1
                    harness.log(traceback.format_exc())
                if (time.perf_counter() - t0 >= seconds
                        and w.attempted >= len(cases)):
                    break
            w.seconds = time.perf_counter() - t0
        return w

    def close(self) -> None:
        self.structures.clear()
