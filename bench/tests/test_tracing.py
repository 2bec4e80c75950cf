"""The reduction from trace and spans to the per-layer metrics."""
import pytest

import harness
import tracing
from metrics import device_idle_pct, spgemm_roofline, symbolic_s

MS = 1_000_000      # nanoseconds


def _events():
    # a 100 ms window; device 0 busy 10–30 and 25–40 (overlapping) and
    # 70–80 ms; the host sits in "plan" during 40–70 ms
    ops = [(10 * MS, 30 * MS, "jit_sort/sort.1"),
           (25 * MS, 40 * MS, "jit_sort/fusion.2"),
           (70 * MS, 80 * MS, "jit_scatter/scatter.3"),
           (120 * MS, 130 * MS, "after the window")]
    host = [(0, 100 * MS, tracing.WINDOW),
            (40 * MS, 70 * MS, "$planner.py:241 make_plan"),
            (45 * MS, 50 * MS, "short, away from the gap's midpoint")]
    return tracing.Events(window=(0, 100 * MS),
                          device_ops={"/device:TPU:0": ops}, host=host)


def test_union():
    assert tracing.union([(3, 4), (0, 2), (1, 3), (6, 7)]) == [(0, 4), (6, 7)]


def test_summary_busy_and_idle():
    s = tracing.summarize(_events())
    assert s.window_s == pytest.approx(0.1)
    assert s.busy_s == pytest.approx(0.04)        # 10–40 and 70–80 ms
    assert s.idle_pct == pytest.approx(60.0)
    assert sum(v for _, v in s.idle_gaps) == pytest.approx(0.06)
    labels = dict(s.idle_gaps)
    assert labels["planner.py:241 make_plan"] == pytest.approx(0.03)
    assert dict(s.top_ops)["jit_sort/sort.1"] == pytest.approx(0.02)
    assert "after the window" not in dict(s.top_ops)


def test_summary_needs_window_and_ops():
    ev = _events()
    assert tracing.summarize(tracing.Events(None, ev.device_ops,
                                            ev.host)) is None
    assert tracing.summarize(tracing.Events(ev.window, {}, ev.host)) is None


def _ctx(trace=None, spans=None, work=()):
    return harness.Context(products=2, spans=spans or {}, trace=trace,
                           work=list(work),
                           peaks={"flops_per_s": 1e12,
                                  "hbm_bytes_per_s": 1e9})


def test_span_mean_and_readers():
    ctx = _ctx(spans={"spgemm.symbolic": [0.5, 1.5]})
    assert symbolic_s.read(ctx) == pytest.approx(1.0)
    assert symbolic_s.read(_ctx()) is None
    assert device_idle_pct.read(_ctx()) is None
    s = tracing.summarize(_events())
    assert device_idle_pct.read(_ctx(trace=s)) == pytest.approx(60.0)


def test_roofline():
    work = {"nnz_a": 1000, "nnz_b": 1000, "nnz_c": 2000,
            "valid_products": 3000}
    # bytes: 2000·8 + 2000·12 = 40,000 B at 1e9 B/s = 40 µs;
    # ops: 6000 at 1e12/s = 6 ns, so the bytes bound sets it
    t, bound = spgemm_roofline.least_time(work, _ctx().peaks)
    assert (t, bound) == (pytest.approx(4e-5), "bytes")
    s = tracing.summarize(_events())               # 40 ms busy
    got = spgemm_roofline.read(_ctx(trace=s, work=[work, work]))
    assert got["value"] == pytest.approx(100 * 8e-5 / 0.04)
    assert got["bound"] == "bytes"
    assert spgemm_roofline.read(_ctx(work=[work])) is None
