"""The per-layer readers of the program's spans, named scopes and compile
counter, on synthetic contexts and traces, and the profiler mirror of the
program's spans read back from a CPU trace."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
import scopes
import tracing
from metrics import (merge_s, plan_s, search_s, validate_s,
                     window_compiles)

MS = 1_000_000      # nanoseconds


@pytest.fixture
def obs():
    import repro.obs
    repro.obs.disable()
    repro.obs.reset()
    yield repro.obs
    repro.obs.disable()
    repro.obs.reset()


def _ctx(spans=None, trace=None, products=2):
    return harness.Context(products=products, spans=spans or {},
                           trace=trace, work=[], peaks=None)


def _operands(n=48, seed=0):
    from repro.core import ell_cols_from_dense, ell_rows_from_dense
    rng = np.random.default_rng(seed)
    a = ((rng.random((n, n)) < 0.1)
         * rng.standard_normal((n, n))).astype(np.float32)
    return (ell_rows_from_dense(jnp.asarray(a), int((a != 0).sum(0).max())),
            ell_cols_from_dense(jnp.asarray(a.T),
                                int((a.T != 0).sum(1).max())))


def test_span_readers():
    ctx = _ctx({"spgemm.accumulate.merge": [2.0, 4.0],
                "spgemm.plan": [1.0, 1.5],
                "spgemm.validate": [0.1, 0.3]})
    assert merge_s.read(ctx) == pytest.approx(3.0)
    assert plan_s.read(ctx) == pytest.approx(1.25)
    assert validate_s.read(ctx) == pytest.approx(0.2)
    for reader in (merge_s, plan_s, validate_s):
        assert reader.read(_ctx()) is None


def test_scope_attribution():
    ops = {"/device:TPU:0": [
        (0, 10 * MS, "jit(f)/numeric.key/add:"),
        (10 * MS, 30 * MS, "jit(f)/numeric.search/jit(searchsorted)/sort:"),
        (25 * MS, 40 * MS, "jit(f)/numeric.search/jit(_take)/gather:"),
        (40 * MS, 50 * MS, "jit(f)/numeric.searchsorted_elsewhere/sort:"),
        (50 * MS, 60 * MS, ""),                       # a copy: no op_name
        (90 * MS, 120 * MS, "jit(f)/numeric.search/sort:")]}
    window = (5 * MS, 100 * MS)
    # 10–40 ms (overlap merged) plus 90–100 ms of the clipped last op
    assert scopes.scope_seconds(ops, window, "numeric.search") == \
        pytest.approx(0.04)
    assert scopes.scope_seconds(ops, window, "numeric.key") == \
        pytest.approx(0.005)
    assert scopes.scope_seconds(ops, window, "numeric.scatter") is None
    assert scopes.scope_seconds(ops, (200 * MS, 300 * MS),
                                "numeric.search") is None


# -- a minimal XSpace encoder, for the wire-format reader ----------------

def _varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _int(field, n):
    return _varint(field << 3) + _varint(n)


def _msg(field, payload):
    if isinstance(payload, str):
        payload = payload.encode()
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _xspace():
    stat_md = (_msg(5, _int(1, 7) + _msg(2, _int(1, 7) + _msg(2, "tf_op")))
               + _msg(5, _int(1, 8) + _msg(2, _int(1, 8) + _msg(
                   2, "jit(f)/numeric.search/sort:"))))
    event_md = (
        _msg(4, _int(1, 1) + _msg(2, _int(1, 1) + _msg(2, "%sort.1 = ...")
                                  + _msg(5, _int(1, 7) + _msg(
                                      5, "jit(f)/numeric.key/add:")))) +
        _msg(4, _int(1, 2) + _msg(2, _int(1, 2) + _msg(2, "%fusion.2")
                                  + _msg(5, _int(1, 7) + _int(7, 8)))) +
        _msg(4, _int(1, 3) + _msg(2, _int(1, 3) + _msg(2, "%copy.3"))))
    events = (_msg(4, _int(1, 1) + _int(2, 0) + _int(3, 10 * MS * 1000))
              + _msg(4, _int(1, 2) + _int(2, 10 * MS * 1000)
                     + _int(3, 20 * MS * 1000))
              + _msg(4, _int(1, 3) + _int(2, 30 * MS * 1000)
                     + _int(3, 5 * MS * 1000)))
    ops_line = _msg(3, _int(1, 1) + _msg(2, tracing.OPS_LINE)
                    + _int(3, 1000) + events)
    other_line = _msg(3, _int(1, 2) + _msg(2, "XLA Modules") + _int(3, 0)
                      + _msg(4, _int(1, 1) + _int(3, 99)))
    device = _msg(1, _int(1, 1) + _msg(2, "/device:TPU:0") + ops_line
                  + other_line + event_md + stat_md)
    host = _msg(1, _int(1, 2) + _msg(2, "/host:CPU")
                + _msg(3, _int(1, 1) + _msg(2, "python") + _int(3, 0)
                       + _msg(4, _int(1, 1) + _int(2, 0))))
    return device + host


def test_device_ops_reads_scope_stat(tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_xspace())
    ops = scopes.device_ops(str(path))
    assert list(ops) == ["/device:TPU:0"]
    assert ops["/device:TPU:0"] == [
        (1000, 1000 + 10 * MS, "jit(f)/numeric.key/add:"),
        (1000 + 10 * MS, 1000 + 30 * MS, "jit(f)/numeric.search/sort:"),
        (1000 + 30 * MS, 1000 + 35 * MS, "")]


def test_search_s_reader(tmp_path, monkeypatch):
    path = tmp_path / "run" / "t.xplane.pb"
    path.parent.mkdir()
    path.write_bytes(_xspace())
    window = (0, 100 * MS)
    monkeypatch.setattr(scopes, "TRACES", str(tmp_path))
    monkeypatch.setattr(scopes.tracing, "read_events",
                        lambda p: tracing.Events(window, {}, []))
    summary = tracing.Summary(window_s=0.1, busy_s=0.05, top_ops=[],
                              idle_gaps=[])
    assert search_s.read(_ctx(trace=summary)) == pytest.approx(0.01)
    assert search_s.read(_ctx(trace=None)) is None
    other = tracing.Summary(window_s=0.2, busy_s=0.05, top_ops=[],
                            idle_gaps=[])
    assert search_s.read(_ctx(trace=other)) is None     # another run's trace
    assert scopes.per_product(_ctx(trace=summary), "numeric.scatter",
                              str(tmp_path)) is None
    assert scopes.per_product(_ctx(trace=summary), "numeric.search",
                              str(tmp_path / "empty")) is None


def test_window_compiles_reader(obs):
    assert window_compiles.read(_ctx()) is None         # no counter kept
    x = jnp.ones(17)
    obs.enable(reset=True)
    assert window_compiles.read(_ctx()) == {"value": 0}
    with obs.span("spgemm.accumulate.sort"):
        jax.block_until_ready(jax.jit(lambda v: v * 7.0 - 3.0)(x))
    obs.disable()
    got = window_compiles.read(_ctx())
    assert got["value"] == 1
    assert got["where"] == "jit(<lambda>) in spgemm.accumulate.sort x1"


def test_profiler_trace_holds_program_spans(tmp_path, obs):
    """The program's spans are host events of the profiler's own trace, read
    back through ``tracing.read_events``, nested on the device-op clock."""
    import repro
    a, b = _operands()
    st = repro.make_structure(a, b)
    repro.spgemm(a, b, out_cap="auto", accumulator="sort")   # compiled
    obs.enable(reset=True)
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tracing.WINDOW):
        repro.spgemm(a, b, out_cap="auto", accumulator="sort", check=True)
        repro.spgemm(a, b, structure=st)
    jax.profiler.stop_trace()
    obs.disable()
    ev = tracing.read_events(tracing.find_xplane(str(tmp_path)))
    host = {}
    for s, e, name in ev.host:
        host.setdefault(name, []).append((s, e))
    for name in ("spgemm.call", "spgemm.plan", "spgemm.accumulate.sort",
                 "spgemm.accumulate.merge", "spgemm.validate",
                 "spgemm.numeric"):
        assert name in host, name
    assert len(host["spgemm.call"]) == 2
    (merge,) = host["spgemm.accumulate.merge"]
    assert any(s <= merge[0] and merge[1] <= e
               for s, e in host["spgemm.call"])
    lo, hi = ev.window
    assert all(lo <= s and e <= hi for s, e in host["spgemm.call"])


@pytest.mark.parametrize("name", ["bcsstk32.cold", "bcsstk32.warm"])
def test_traced_run_reports_new_metrics(name, tmp_path, obs):
    """A traced run of a small cell carries the cell's new metrics, each
    with its unit, and they sit inside the spans they refine."""
    cell = harness.load_cell(name)
    cell.config.update({"n": 1500, "nnz": 20_000, "sigma": 4.0,
                        "ell_k": 32})
    if name == "bcsstk32.cold":
        # the backend the planner picks for the full-size cell on the chip
        cell.traffic["call"]["accumulator"] = "sort"
    res = harness.run(cell, 3, 0.1, True, time.perf_counter(),
                      trace_dir=tmp_path)
    assert res["correct"], res["checks"]
    m = res["metrics"]
    assert m["window_compiles"]["unit"] == "count"
    assert m["window_compiles"]["value"] == 0
    if name == "bcsstk32.cold":
        assert 0 < m["merge_s"]["value"] < m["accumulate_s"]["value"]
        assert m["plan_s"]["value"] >= m["symbolic_s"]["value"]
        assert m["merge_s"]["unit"] == m["plan_s"]["unit"] == "s"
    else:
        assert 0 < m["validate_s"]["value"] < m["numeric_s"]["value"]
        assert "search_s" not in m          # a CPU trace has no device ops
