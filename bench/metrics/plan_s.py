"""Seconds of the program's ``spgemm.plan`` span per product in the window:
all of ``make_plan``, the symbolic pass included (repro.obs)."""


def read(ctx):
    return ctx.span_mean("spgemm.plan")
