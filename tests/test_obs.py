"""Observability tests: the disabled-overhead contract, span nesting (jit,
threads), call ids, the profiler mirror, the compile counter, Chrome-trace
export, metrics stability, exactly-once poison / overflow events and
cache-stats snapshots."""
import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.obs as obs
from repro.obs import metrics as mt
from repro.obs import trace as tr


@pytest.fixture(autouse=True)
def _obs_clean():
    """Every test starts and ends with a disabled, empty tracer/registry."""
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


def _operands(n=64, dens=0.08, seed=0):
    from repro.core import ell_cols_from_dense, ell_rows_from_dense
    rng = np.random.default_rng(seed)
    A = ((rng.random((n, n)) < dens)
         * rng.standard_normal((n, n))).astype(np.float32)
    B = ((rng.random((n, n)) < dens)
         * rng.standard_normal((n, n))).astype(np.float32)
    a = ell_rows_from_dense(jnp.asarray(A), max(1, int((A != 0).sum(0).max())))
    b = ell_cols_from_dense(jnp.asarray(B), max(1, int((B != 0).sum(1).max())))
    return a, b


# ---------------------------------------------------------------- overhead


def test_disabled_span_is_shared_singleton():
    """Disabled tracing allocates no trace state: span() hands back one
    module-level null object, sync is identity, nothing is recorded."""
    from repro.core import spgemm_coo
    assert tr.span("anything") is tr.NULL_SPAN
    assert tr.span("other") is tr.NULL_SPAN
    x = jnp.ones(3)
    assert tr.sync(x) is x
    tr.instant("nope", k=1)
    mt.inc("nope")
    mt.observe("nope", 1.0)
    mt.record_plan("fp", "sort", {"cost_sort": 1.0})
    a, b = _operands()
    spgemm_coo(a, b, out_cap=2048, accumulator="sort")
    snap = obs.snapshot()
    assert snap["trace"]["events"] == []
    assert snap["metrics"]["counters"] == {}
    assert snap["metrics"]["planner"] == {}


def test_disabled_overhead_under_two_percent():
    """The disabled hot path adds is_enabled() checks + null-span returns.
    Bound that cost structurally: (measured per-touch-point cost) × (a
    generous touch-point count) must stay under 2% of one instrumented
    eager spgemm_coo call on a smoke shape."""
    from repro.core import spgemm_coo
    a, b = _operands()
    f = lambda: jax.block_until_ready(
        spgemm_coo(a, b, out_cap=2048, accumulator="sort").val)
    f()                                           # compile/warm
    times = []
    for _ in range(5):
        t0 = time.perf_counter()
        f()
        times.append(time.perf_counter() - t0)
    t_call = sorted(times)[len(times) // 2]

    n_iter = 20_000
    t0 = time.perf_counter()
    for _ in range(n_iter):
        tr.is_enabled()
        tr.span("spgemm.accumulate")
        tr.sync(None)
    per_point = (time.perf_counter() - t0) / n_iter
    # 64 touch points per call is far above the real count (~10)
    assert 64 * per_point < 0.02 * t_call, (
        f"disabled obs overhead {64 * per_point * 1e6:.1f}us vs "
        f"2% of call = {0.02 * t_call * 1e6:.1f}us")


# ----------------------------------------------------------------- nesting


def test_enabled_spans_nest():
    obs.enable(reset=True)
    with tr.span("outer", k=1):
        with tr.span("inner"):
            pass
    evs = tr.get_tracer().spans()
    by_name = {e["name"]: e for e in evs}
    assert by_name["inner"]["parent_id"] == by_name["outer"]["id"]
    assert by_name["inner"]["depth"] == 1
    assert by_name["outer"]["parent_id"] is None
    assert by_name["outer"]["depth"] == 0
    # child interval inside parent interval
    o, i = by_name["outer"], by_name["inner"]
    assert o["ts_us"] <= i["ts_us"]
    assert i["ts_us"] + i["dur_us"] <= o["ts_us"] + o["dur_us"] + 1e-6


def test_spans_nest_across_threads():
    obs.enable(reset=True)
    # both threads sit inside their spans at once: the spans interleave,
    # and neither thread can exit and hand its ident to the other
    both_inside = threading.Barrier(2, timeout=10)

    def work(tag):
        with tr.span(f"outer-{tag}"):
            with tr.span(f"inner-{tag}"):
                both_inside.wait()

    ts = [threading.Thread(target=work, args=(i,)) for i in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=10)
        assert not t.is_alive()
    evs = tr.get_tracer().spans()
    for i in range(2):
        inner = next(e for e in evs if e["name"] == f"inner-{i}")
        outer = next(e for e in evs if e["name"] == f"outer-{i}")
        assert inner["parent_id"] == outer["id"]    # never the other thread's
        assert inner["depth"] == 1 and outer["depth"] == 0
        assert inner["tid"] == outer["tid"]
    tids = {e["tid"] for e in evs}
    assert len(tids) == 2


def test_spans_under_jit_are_flagged_and_fire_once():
    from functools import partial
    from repro.core import spgemm_coo
    obs.enable(reset=True)
    a, b = _operands()
    f = jax.jit(partial(spgemm_coo, out_cap=2048, accumulator="sort"))
    jax.block_until_ready(f(a, b).val)
    evs1 = tr.get_tracer().spans()
    traced = [e for e in evs1 if e["args"].get("traced")]
    assert traced, "trace-time spans must carry traced=True"
    # compiled repeat: instrumentation inside the jaxpr does not re-fire
    jax.block_until_ready(f(a, b).val)
    assert len(tr.get_tracer().spans()) == len(evs1)
    # span stack balanced after tracing
    assert tr._stack.get() == ()


def test_enabled_span_outside_jit_runs_no_device_op(tmp_path):
    """Deciding whether a span runs under jit reads JAX's trace state: an
    enabled span outside jit executes no XLA program (the CPU profiler
    records one ``PjRtCpuExecutable::Execute`` per program run)."""
    from jax.profiler import ProfileData

    def executed(fn):
        path = tmp_path / fn.__name__
        jax.profiler.start_trace(str(path))
        fn()
        jax.profiler.stop_trace()
        (pb,) = path.glob("**/*.xplane.pb")
        names = [e.name for pl in ProfileData.from_file(str(pb)).planes
                 for ln in pl.lines for e in ln.events]
        assert "probe" in names                   # the span's own annotation
        return sum(n.startswith("PjRtCpuExecutable::Execute") for n in names)

    x = jnp.ones(3)
    jax.block_until_ready(x + 1)                  # compiled before tracing
    obs.enable(reset=True)

    def span_only():
        with tr.span("probe", n=1):
            pass

    def span_and_op():
        with tr.span("probe"):
            jax.block_until_ready(x + 1)

    assert executed(span_only) == 0
    assert executed(span_and_op) >= 1             # the detector sees ops


def test_span_under_jit_flagged_without_device_work():
    obs.enable(reset=True)

    def f(x):
        with tr.span("inside"):
            return x * 2

    jax.block_until_ready(jax.jit(f)(jnp.ones(5)))
    with tr.span("outside"):
        pass
    by_name = {e["name"]: e for e in tr.get_tracer().spans()}
    assert by_name["inside"]["args"].get("traced") is True
    assert "traced" not in by_name["outside"]["args"]


def test_spgemm_spans_share_call_id_and_link_parents():
    """Every span of one repro.spgemm call carries the id of its
    ``spgemm.call`` root as ``call_id``; ``parent_id`` gives the nesting:
    plan ⊃ symbolic, accumulate ⊃ sort, merge (cold); validate beside
    numeric ⊃ multiply (warm)."""
    import repro
    a, b = _operands()
    st = repro.make_structure(a, b)
    obs.enable(reset=True)
    repro.spgemm(a, b, out_cap="auto", accumulator="sort", check=True)
    repro.spgemm(a, b, structure=st)
    evs = tr.get_tracer().spans()
    roots = [e for e in evs if e["name"] == "spgemm.call"]
    assert len(roots) == 2
    cold, warm = sorted(roots, key=lambda e: e["ts_us"])
    for root in roots:
        assert root["parent_id"] is None and root["call_id"] == root["id"]
        assert root["args"]["lanes"] == a.k * a.n_cols * b.k
        assert root["args"]["nnz"] > 0
    by_id = {e["id"]: e for e in evs}

    def parent(name, call):
        (e,) = [e for e in evs if e["name"] == name
                and e["call_id"] == call["id"]]
        return by_id[e["parent_id"]]["name"]

    assert parent("spgemm.plan", cold) == "spgemm.call"
    assert parent("spgemm.symbolic", cold) == "spgemm.plan"
    assert parent("spgemm.multiply", cold) == "spgemm.call"
    assert parent("spgemm.accumulate", cold) == "spgemm.call"
    assert parent("spgemm.accumulate.sort", cold) == "spgemm.accumulate"
    assert parent("spgemm.accumulate.merge", cold) == "spgemm.accumulate"
    assert parent("spgemm.validate", warm) == "spgemm.call"
    assert parent("spgemm.numeric", warm) == "spgemm.call"
    assert parent("spgemm.multiply", warm) == "spgemm.numeric"
    assert all(e["call_id"] in (cold["id"], warm["id"]) for e in evs)


def test_disabled_front_door_records_and_annotates_nothing(monkeypatch):
    """Tracing off, ``repro.spgemm`` takes the bare route: the root span is
    the shared null span and no profiler annotation is opened."""
    import jax.profiler
    import repro

    def refuse(*_a, **_k):
        raise AssertionError("annotation opened while tracing is off")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    assert tr.call("spgemm.call", lanes=1) is tr.NULL_SPAN
    a, b = _operands()
    repro.spgemm(a, b, out_cap="auto", accumulator="sort")
    assert obs.snapshot()["trace"]["events"] == []


def test_compile_listener_counts_fresh_compile_and_names_parent():
    x = jnp.ones(11)
    obs.enable(reset=True)
    obs.disable()
    f = jax.jit(lambda v: v * 3.0 - 1.0)
    jax.block_until_ready(f(x))                   # disabled: not counted
    assert mt.snapshot()["counters"]["jax.compiles"] == 0
    obs.enable()
    g = jax.jit(lambda v: v * 5.0 + 2.0)
    with tr.span("step") as sp:
        jax.block_until_ready(g(x))
        jax.block_until_ready(g(x))               # cached: no second compile
    counters = mt.snapshot()["counters"]
    assert counters["jax.compiles"] == 1
    assert counters["jax.compile_s"] > 0
    (ev,) = [e for e in tr.get_tracer().snapshot()["events"]
             if e["name"] == "jax.compile"]
    assert ev["parent_id"] == sp.id and ev["ph"] == "i"
    assert ev["args"]["seconds"] > 0


def test_enable_seeds_compile_counter_at_zero():
    obs.enable(reset=True)
    assert mt.snapshot()["counters"]["jax.compiles"] == 0
    obs.reset()
    assert mt.snapshot()["counters"] == {}


# ------------------------------------------------------------------ export


def test_chrome_export_roundtrip(tmp_path):
    from repro.core import spgemm_coo
    from repro.plan import make_plan
    a, b = _operands()
    plan = make_plan(a, b)                # planner spans stay out of the trace
    obs.enable(reset=True)
    with tr.span("test.root"):
        jax.block_until_ready(spgemm_coo(a, b, out_cap=plan.out_cap,
                                         accumulator="sort", plan=plan).val)
    path = tmp_path / "trace.json"
    obs.export_chrome(str(path), extra={"metrics": mt.snapshot()})
    doc = json.loads(path.read_text())
    evs = doc["traceEvents"]
    assert evs and isinstance(evs, list)
    for e in evs:
        assert set(e) >= {"name", "cat", "ph", "ts", "dur", "pid", "tid",
                          "args"}
        assert e["ts"] >= 0 and e["dur"] >= 0
    # the root span must enclose every other event recorded inside it
    root = next(e for e in evs if e["name"] == "test.root")
    for e in evs:
        if e is root:
            continue
        assert root["ts"] <= e["ts"] + 1e-6
        assert e["ts"] + e["dur"] <= root["ts"] + root["dur"] + 1e-6
    # span args carry backend + measured nnz, and the metrics merge survived
    acc = next(e for e in evs if e["name"] == "spgemm.accumulate")
    assert acc["args"]["backend"] == "sort"
    assert acc["args"]["nnz"] > 0
    assert "planner" in doc["metrics"]


def test_trace_args_never_carry_matrix_values():
    obs.enable(reset=True)
    v = jnp.asarray(np.array([3.14159, 2.71828], np.float32))
    with tr.span("s", data=v, n=4, tag="x"):
        pass
    (e,) = tr.get_tracer().spans()
    assert e["args"]["n"] == 4 and e["args"]["tag"] == "x"
    assert e["args"]["data"] == "<float32(2,)>"     # shape/dtype only


def test_metrics_snapshot_stable_across_identical_runs():
    from repro.core import spgemm_coo
    from repro.plan import make_plan

    def run():
        obs.enable(reset=True)
        a, b = _operands()
        plan = make_plan(a, b)
        jax.block_until_ready(spgemm_coo(a, b, out_cap=plan.out_cap,
                                         accumulator=plan.backend,
                                         plan=plan).val)
        snap = mt.snapshot()
        obs.disable()
        obs.reset()
        return snap

    def program_counters(snap):
        # compile counts follow the jit cache, which the first run fills
        return {k: v for k, v in snap["counters"].items()
                if not k.startswith("jax.compile")}

    s1, s2 = run(), run()
    assert program_counters(s1) == program_counters(s2)
    assert set(s1["planner"]) == set(s2["planner"])
    for k in s1["planner"]:
        assert s1["planner"][k]["backend"] == s2["planner"][k]["backend"]
        assert s1["planner"][k]["est"] == s2["planner"][k]["est"]


# ---------------------------------------------------------- poison/overflow


def test_overflow_event_increments_exactly_once_per_call():
    from repro.core import spgemm_coo
    from repro.core.accumulate import AccumulatorOverflow
    obs.enable(reset=True)
    a, b = _operands()
    for expected in (1, 2):
        with pytest.raises(AccumulatorOverflow):
            spgemm_coo(a, b, out_cap=4, accumulator="sort", check=True)
        assert mt.snapshot()["counters"]["spgemm.overflow_events"] == expected
    instants = [e for e in tr.get_tracer().snapshot()["events"]
                if e["name"] == "spgemm.overflow"]
    assert len(instants) == 2


def test_poison_event_increments_exactly_once_per_call():
    from repro.core.spgemm import accumulate_stream
    from repro.plan import Plan
    obs.enable(reset=True)
    rng = np.random.default_rng(3)
    n_rows = n_cols = 32
    m = 256
    row = jnp.asarray(rng.integers(0, n_rows, m), jnp.int32)
    col = jnp.asarray(rng.integers(0, n_cols, m), jnp.int32)
    val = jnp.asarray(rng.standard_normal(m), jnp.float32)
    # one 8-slot table for ~hundreds of distinct keys: guaranteed drops
    plan = Plan(backend="hash", out_cap=1024, n_blocks=1, block_cap=8,
                max_probes=2)
    for expected in (1, 2):
        coo = accumulate_stream(row, col, val, 1024, n_rows, n_cols,
                                backend="hash", plan=plan)
        assert int(coo.ngroups) > 1024              # poisoned past cap
        assert mt.snapshot()["counters"]["spgemm.poison_events"] == expected


def test_numeric_miss_poison_event_exactly_once_per_call():
    """A stale structure (validate=False) makes the numeric phase drop the
    unknown products into the overflow slot: one poison counter increment
    and one instant per call, never per miss."""
    from repro.core.spgemm import spgemm_coo_numeric
    from repro.plan import make_structure
    a1, b1 = _operands(dens=0.05, seed=1)
    st = make_structure(a1, b1)
    a2, b2 = _operands(dens=0.3, seed=2)
    obs.enable(reset=True)
    for expected in (1, 2):
        coo = spgemm_coo_numeric(a2, b2, st, validate=False)
        assert int(coo.ngroups) > st.out_cap        # poisoned past cap
        assert mt.snapshot()["counters"]["spgemm.poison_events"] == expected
    instants = [e for e in tr.get_tracer().snapshot()["events"]
                if e["name"] == "spgemm.poison"]
    assert len(instants) == 2


# ------------------------------------------------------------- cache/serve


def test_structure_cache_stats_snapshot():
    from repro.plan import StructureCache
    a, b = _operands()
    cache = StructureCache(capacity=4)
    cache.get(a, b)
    cache.get(a, b)
    s = cache.stats()
    assert s["misses"] == 1 and s["hits"] == 1 and s["size"] == 1
    s["hits"] = 999                                  # a copy, not a view
    assert cache.stats()["hits"] == 1


def test_engine_stats_dict_and_callable():
    from repro.serve import ServeConfig, ServingEngine
    cfg = ServeConfig(max_batch=2, max_new_tokens=4, s_max=16, eos_id=2)
    vocab = 8

    class _Stub:
        def prefill(self, params, batch, s_max):
            bsz = batch["tokens"].shape[0]
            return jnp.zeros((bsz, vocab)).at[:, 3].set(5.0), {}

        def decode_step(self, params, cache, tokens):
            bsz = tokens.shape[0]
            return jnp.zeros((bsz, vocab)).at[:, cfg.eos_id].set(5.0), cache

    eng = ServingEngine(_Stub(), {}, cfg)
    outs = eng.generate_batch([np.array([3, 4], np.int32)])
    assert eng.stats["tokens"] == sum(len(o) for o in outs)   # dict access
    snap = eng.stats()                                        # callable
    assert snap["requests"] == 1
    assert 0.0 <= snap["batch_occupancy"] <= 1.0
    assert snap["queue_s_per_request"] >= 0.0
    assert snap["compute_s_per_request"] > 0.0
    assert "hits" in snap["structure_cache"]
