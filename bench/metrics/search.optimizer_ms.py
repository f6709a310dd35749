"""Host milliseconds per search sample spent in the optimizer (ask and
tell spans), outside the QoS oracle."""


def read(ctx):
    samples = ctx.work.get("samples", 0)
    if not samples:
        return None
    seconds, _ = ctx.spans.total("ask", "tell")
    return 1e3 * seconds / samples
