"""Seconds of the program's ``spgemm.validate`` span per product in the
window: the warm call's fingerprint check of the operands against the
structure (repro.obs)."""


def read(ctx):
    return ctx.span_mean("spgemm.validate")
