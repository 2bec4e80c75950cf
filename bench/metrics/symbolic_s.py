"""Seconds of the program's ``spgemm.symbolic`` span per product in the
window (repro.obs; the span closes after the device finished)."""


def read(ctx):
    return ctx.span_mean("spgemm.symbolic")
