"""Seconds from a provisioning request to a committed pool: the window's
length over the decisions it committed."""


def read(ctx):
    units = ctx.work.get("units", 0)
    return ctx.elapsed_s / units if units else None
