"""Seconds of the program's ``spgemm.accumulate.merge`` span per product in
the window: the 'sort' accumulate's coalescing of the sorted stream
(repro.obs; the span closes after the device finished)."""


def read(ctx):
    return ctx.span_mean("spgemm.accumulate.merge")
