"""Backend compiles in the window: repro.obs's ``jax.compiles`` counter,
which counts only while tracing is on, i.e. over the traced run's window.
``where`` names each compiled function with the span it compiled in."""

from collections import Counter


def read(ctx):
    import repro.obs
    snap = repro.obs.snapshot()
    got = snap["metrics"]["counters"].get("jax.compiles")
    if got is None:
        return None
    events = snap["trace"]["events"]
    spans = {e["id"]: e["name"] for e in events if e["ph"] == "X"}
    where = Counter(f"{e['args'].get('fun')} in "
                    f"{spans.get(e['parent_id'], '(no span)')}"
                    for e in events if e["name"] == "jax.compile")
    out = {"value": int(got)}
    if where:
        out["where"] = "; ".join(f"{k} x{n}" for k, n in where.most_common(8))
    return out
