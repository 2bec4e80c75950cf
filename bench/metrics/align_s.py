"""Seconds of the program's ``spgemm.accumulate.align`` span per product in
the window: the 'search' accumulate's alignment, each product's output
slot (repro.obs; the span closes after the device finished)."""


def read(ctx):
    return ctx.span_mean("spgemm.accumulate.align")
