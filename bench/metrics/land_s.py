"""Seconds of the program's ``spgemm.accumulate.land`` span per product in
the window: the 'search' accumulate's segment-sum of the values into their
slots (repro.obs; the span closes after the device finished)."""


def read(ctx):
    return ctx.span_mean("spgemm.accumulate.land")
