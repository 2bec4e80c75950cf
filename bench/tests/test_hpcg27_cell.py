"""The cell ``hpcg27.cold`` from its files at a small grid: ``correct``
holds, a traced run reads the search accumulate's metrics, and the host
memory ceiling of its driver trips without ending the test's process."""
import threading
import time
from types import SimpleNamespace

import pytest

import harness
import scopes
import tracing
from metrics import search_roofline
from peaks import PEAKS

CELL = "hpcg27.cold"


@pytest.fixture
def chip_planner(monkeypatch):
    """The planner decides as on a TPU v5e; everything else runs on the
    CPU's realizations."""
    from repro.plan import planner
    monkeypatch.setattr(planner, "platform",
                        SimpleNamespace(on_tpu=lambda: True))


def small_cell():
    cell = harness.load_cell(CELL)
    cell.config.update(nx=8, ny=8, nz=16)
    return cell


def test_cell_runs_under_the_memory_ceiling():
    cell = harness.load_cell(CELL)
    assert cell.traffic["driver"] == "rss_capped"
    assert 0 < cell.traffic["host_rss_ceiling_gb"] < 40
    assert {m["name"] for m in cell.per_layer} == {
        "emit_s", "align_s", "land_s", "search_share", "search_roofline"}


def test_program_is_correct(chip_planner):
    res = harness.run(small_cell(), 2**31 + 7, 0.1, False,
                      time.perf_counter())
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 2


def test_traced_run_reads_search_metrics(chip_planner, tmp_path):
    res = harness.run(small_cell(), 5, 0.1, True, time.perf_counter(),
                      trace_dir=tmp_path / "trace")
    assert res["correct"], res["checks"]
    got = res["metrics"]
    for name in ("emit_s", "align_s", "land_s"):
        assert got[name]["value"] > 0, got
    assert got["search_share"]["value"] == 1.0
    # the CPU has no device plane, hence no peaks: no roofline share
    assert "search_roofline" not in got


def window_events(window_s=2.0):
    """A trace of one device whose window holds ops of the three search
    programs and of other programs."""
    lo, hi = 1e9, 1e9 + window_s * 1e9
    ops = [
        (lo + 0.0e9, lo + 0.2e9, "jit__search_emit(11)/sort.3"),
        (lo + 0.1e9, lo + 0.2e9, "jit__search_emit(11)/fusion.1"),
        (lo + 0.2e9, lo + 0.3e9, "jit_align_ranked(12)/sort.1"),
        (lo + 0.3e9, lo + 0.5e9, "jit__search_land(13)/scatter"),
        (lo + 0.5e9, lo + 1.5e9, "jit_scatter-add(14)/fusion"),
        (hi - 0.1e9, hi + 0.9e9, "jit__search_land(13)/scatter"),
        (lo - 0.5e9, lo - 0.1e9, "jit__search_emit(11)/sort.3"),
    ]
    return tracing.Events(window=(lo, hi), device_ops={"/device:TPU:0": ops},
                          host=[])


def test_search_roofline_program_seconds():
    # emit 0.2 (its two ops overlap), align 0.1, land 0.2 + 0.1 clipped
    # to the window; the other program and the op before the window left out
    assert search_roofline.program_seconds(window_events()) == \
        pytest.approx(0.6)
    ev = window_events()
    ev.device_ops["/device:TPU:0"] = [
        op for op in ev.device_ops["/device:TPU:0"]
        if not search_roofline.in_programs(op[2])]
    assert search_roofline.program_seconds(ev) is None


def test_search_roofline_reader(monkeypatch):
    work = {"valid_products": 27_049_400, "nnz_c": 4_600_904,
            "nnz_a": 0, "nnz_b": 0}
    peaks = PEAKS["TPU v5 lite"]
    monkeypatch.setattr(scopes, "find_trace", lambda root=None: "trace")
    monkeypatch.setattr(tracing, "read_events",
                        lambda path: window_events(2.0))
    summary = SimpleNamespace(window_s=2.0)

    def ctx(**kw):
        return harness.Context(**dict(dict(
            products=2, spans={}, trace=summary, work=[work, work],
            peaks=peaks), **kw))

    least = (27_049_400 + 4_600_904) * 12 / peaks["hbm_bytes_per_s"]
    got = search_roofline.read(ctx())
    assert got["value"] == pytest.approx(100 * 2 * least / 0.6)
    assert got["bound"] == "bytes"
    assert search_roofline.read(ctx(peaks=None)) is None
    assert search_roofline.read(ctx(trace=None)) is None
    # a trace of another window is not the run's
    assert search_roofline.read(ctx(trace=SimpleNamespace(window_s=3.0))) \
        is None


rss_capped = harness.load_module(harness.BENCH / "drivers" / "rss_capped.py")


class Recorder(rss_capped.Driver):
    """Records the trip instead of ending the process."""

    def __init__(self, traffic):
        self.tripped = threading.Event()
        self.rss = None
        super().__init__(traffic)

    def trip(self, rss):
        self.rss = rss
        self.tripped.set()


def test_ceiling_trips_once_rss_passes_it():
    traffic = dict(harness.load_cell(CELL).traffic,
                   host_rss_ceiling_gb=1e-6)
    drv = Recorder(traffic)
    try:
        assert drv.tripped.wait(timeout=20 * rss_capped.POLL_S)
        assert drv.rss > drv.ceiling
    finally:
        drv.close()
    assert not drv._watch.is_alive()


def test_ceiling_holds_below_it(capsys):
    drv = Recorder(harness.load_cell(CELL).traffic)
    time.sleep(3 * rss_capped.POLL_S)
    drv.close()
    assert not drv.tripped.is_set()
    assert not drv._watch.is_alive()
    assert "[rss] peak host RSS" in capsys.readouterr().err
