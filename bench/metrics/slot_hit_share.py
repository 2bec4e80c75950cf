"""Share of the window's warm numeric calls that summed into the
structure's cached per-lane slots instead of searching the structure's
keys: repro.obs's ``spgemm.numeric.slot_hits`` over it plus
``spgemm.numeric.slot_searches``, counted one per call while tracing is
on, i.e. over the traced run's window. None where the program counts
neither."""


def read(ctx):
    import repro.obs
    counters = repro.obs.snapshot()["metrics"]["counters"]
    hits = counters.get("spgemm.numeric.slot_hits", 0)
    searches = counters.get("spgemm.numeric.slot_searches", 0)
    if not hits + searches:
        return None
    return {"value": hits / (hits + searches), "hits": int(hits),
            "searches": int(searches)}
