"""Test fixture: units of work in the window."""


def read(ctx):
    return ctx.work["units"]
