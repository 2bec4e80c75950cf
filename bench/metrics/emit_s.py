"""Seconds of the program's ``spgemm.accumulate.emit`` span per product in
the window: the 'search' accumulate's emission, one sort of the packed
keys with their lanes and the run-head compaction into the sorted unique
keys (repro.obs; the span closes after the device finished)."""


def read(ctx):
    return ctx.span_mean("spgemm.accumulate.emit")
