"""Evaluations the search made per committed pool: a count, so that a
change of the search's path shows apart from a change of speed."""


def read(ctx):
    samples, units = ctx.work.get("samples", 0), ctx.work.get("units", 0)
    return samples / units if samples and units else None
