"""Share of the window's accumulate calls that ran the 'search' backend:
repro.obs's ``spgemm.accumulate.search_calls`` over
``spgemm.accumulate.calls``, counted one per call while tracing is on,
i.e. over the traced run's window. 1.0 where the planner chose 'search'
for every product; None where the program counts no accumulate calls."""


def read(ctx):
    import repro.obs
    counters = repro.obs.snapshot()["metrics"]["counters"]
    calls = counters.get("spgemm.accumulate.calls", 0)
    if not calls:
        return None
    search = counters.get("spgemm.accumulate.search_calls", 0)
    return {"value": search / calls, "search_calls": int(search),
            "calls": int(calls)}
