"""Host milliseconds per QoS oracle call (PoolEvaluator.__call__ to the
single-config scan, ending in a device sync)."""


def read(ctx):
    seconds, n = ctx.spans.total("oracle")
    return 1e3 * seconds / n if n else None
