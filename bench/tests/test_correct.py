"""``correct`` holds for the program and fails for the control and for a
timed path broken underneath, at sizes a test run can hold.

The faults planted under the harness are those a SpGEMM cell can have:
an answer altered where it is produced (one value, one coordinate), half
of the products left out, a numeric phase that hands back its previous
result for new values, and a call that raises. The control is the
reference computed in bfloat16 (``reference.control``).
"""
import dataclasses
import json
import time

import numpy as np
import pytest

import harness
import limits

SMALL = {"bcsstk32": {"n": 1500, "nnz": 20_000, "sigma": 4.0,
                      "ell_k": 32},
         "hpcg27": {"nx": 8, "ny": 8, "nz": 16}}


Driver = harness.load_module(
    harness.BENCH / "drivers" / "closed_loop.py").Driver


def small_cell(name):
    """The cell ``name`` from its files, at a small size. ``hpcg27.cold``,
    which ``BENCHMARK.json`` leaves out (the program's ``'search'``
    backend exhausts the chip host's memory at its size), is the cold
    traffic on the ``hpcg27`` configuration, for the harness's
    duplicate-heavy case."""
    if name == "hpcg27.cold":
        cell = harness.load_cell("bcsstk32.cold")
        cell.name = name
        cell.config = json.loads(
            (harness.BENCH / "configs" / "hpcg27.json").read_text())
    else:
        cell = harness.load_cell(name)
    cell.config.update(SMALL[cell.config["name"]])
    return cell


def run_cell(cell, driver=None, seed=3):
    return harness.run(cell, seed, 0.1, False, time.perf_counter(),
                       driver_factory=driver)


CELLS = ["bcsstk32.cold", "hpcg27.cold", "bcsstk32.warm"]


@pytest.mark.parametrize("name", CELLS)
def test_program_is_correct(name):
    res = run_cell(small_cell(name))
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 2


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    cell = small_cell(name)
    prog = limits.readings(cell, 5)
    ctrl = limits.readings(cell, 5, control=True)
    assert prog["correct"], prog
    assert not ctrl["correct"], ctrl
    assert ctrl["value_gap"] > cell.config["limits"]["value_gap"], ctrl


class AlterValue(Driver):
    def call(self, case):
        out = super().call(case)
        return dataclasses.replace(out, val=out.val.at[7].add(0.25))


class AlterCoordinate(Driver):
    def call(self, case):
        out = super().call(case)
        return dataclasses.replace(out, col=out.col.at[3].add(1))


class HalfProducts(Driver):
    def call(self, case):
        a = case.a
        half = a.val.shape[0] // 2
        case.a = dataclasses.replace(a, val=a.val.at[half:].set(0))
        try:
            return super().call(case)
        finally:
            case.a = a


class StaleResult(Driver):
    """Hands back the first result of each pattern for every value set."""

    def __init__(self, traffic):
        super().__init__(traffic)
        self.first = {}

    def call(self, case):
        if case.p not in self.first:
            self.first[case.p] = super().call(case)
        return self.first[case.p]


class Raises(Driver):
    """Every other call after the warm-up raises."""

    calls = 0

    def call(self, case):
        self.calls += 1
        if self.calls > 2 and self.calls % 2:
            raise RuntimeError("planted failure")
        return super().call(case)


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", [AlterValue, AlterCoordinate,
                                   HalfProducts, Raises])
def test_fault_is_not_correct(name, fault):
    res = run_cell(small_cell(name), driver=fault)
    assert not res["correct"], res["checks"]


def test_stale_numeric_result_is_not_correct():
    res = run_cell(small_cell("bcsstk32.warm"), driver=StaleResult)
    assert not res["correct"], res["checks"]
    assert res["checks"]["value_gap"]["value"] > 1e-3


def test_result_line_layout():
    res = run_cell(small_cell("hpcg27.cold"))
    assert list(res)[:3] == ["correct", "attempted", "failed"]
    assert list(res)[-1] == "checks"
    assert {"product_s", "peak_hbm_gb", "setup_s"} <= set(res["metrics"])
    for v in res["checks"].values():
        assert set(v) == {"value", "limit"}
        assert np.isfinite(v["value"])


def test_traced_run_reads_spans(tmp_path):
    cell = small_cell("hpcg27.cold")
    res = harness.run(cell, 4, 0.1, True, time.perf_counter(),
                      trace_dir=tmp_path / "trace")
    assert res["correct"], res["checks"]
    # the CPU has no device plane, so only the program's spans are read
    assert {"symbolic_s", "multiply_s", "accumulate_s"} <= set(res["metrics"])
    assert all(v["value"] > 0 for v in res["metrics"].values())


def _zero_values(make_cases):
    """``make_cases`` with a few values of each case set to exactly 0."""
    def made(config, traffic, seed):
        cases = make_cases(config, traffic, seed)
        for c in cases:
            c.vals = c.vals.copy()
            c.vals[:: max(1, c.vals.size // 5)] = 0.0
        return cases
    return made


@pytest.mark.parametrize("name", CELLS)
def test_program_with_zero_values_is_correct(name, monkeypatch):
    monkeypatch.setattr(harness, "make_cases",
                        _zero_values(harness.make_cases))
    res = run_cell(small_cell(name))
    assert res["correct"], res["checks"]


def test_reference_keeps_coordinates_of_zero_products():
    import reference
    import scipy.sparse as sp
    rows = np.array([0, 0, 1, 2, 2])
    cols = np.array([0, 1, 1, 0, 2])
    vals = np.array([0.0, 2.0, 3.0, 0.0, 1.0], np.float32)
    ref = reference.product(rows, cols, vals, 3)
    s = sp.csr_matrix((np.ones(5), (rows, cols)), shape=(3, 3))
    want = (s @ s.T).tocoo()
    assert ref.nnz == want.nnz == 7
    got = set(zip(ref.row.tolist(), ref.col.tolist()))
    assert got == set(zip(want.row.tolist(), want.col.tolist()))
    # (0, 2) and (2, 0) meet only through column 0, whose entries are 0
    at = {(r, c): (v, s) for r, c, v, s in
          zip(ref.row.tolist(), ref.col.tolist(), ref.val, ref.scale)}
    assert at[(0, 2)] == at[(2, 0)] == (0.0, 0.0)
    out = {"row": ref.row, "col": ref.col,
           "val": ref.val.astype(np.float32), "ngroups": ref.nnz}
    assert reference.compare(out, ref) == {
        "nnz_gap": 0, "coord_mismatch": 0, "value_gap": 0.0}
