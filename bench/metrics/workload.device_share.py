"""Share of the device's busy time spent drawing the query stream (layer
"workload", layers/workload.json: the on-device generator's chunks)."""


def read(ctx):
    if ctx.trace is None or ctx.trace["busy_s"] <= 0:
        return None
    gen_s = ctx.trace["layer_s"].get("workload", 0.0)
    return gen_s / ctx.trace["busy_s"] if gen_s > 0 else None
