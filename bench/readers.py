"""Readers that several metrics share: one quantity reported under a
name of its own in each group of cells whose end-to-end metric it moves.
Each ``metrics/<name>.py`` imports the one it reads; each returns None
where it finds nothing to read."""


def scored_queries_per_s(ctx):
    """Candidate-queries scored per second: pools x load levels x queries
    of every unit in the window, over the window's length."""
    q = ctx.work.get("candidate_queries", 0)
    return q / ctx.elapsed_s if q else None


def device_us_per_step(ctx):
    """Device microseconds per scan step: device time of the simulator's
    scan executables (layer "scan", layers/scan.json) over the scan steps
    they ran in the traced stretch (executions times steps per dispatch),
    per device."""
    if ctx.trace is None:
        return None
    scan_s = ctx.trace["layer_s"].get("scan", 0.0)
    runs = ctx.trace["layer_n"].get("scan", 0.0)
    steps = runs * ctx.work.get("steps_per_dispatch", 0)
    return 1e6 * scan_s / steps if scan_s > 0 and steps > 0 else None


def idle_share(ctx):
    """Share of the traced stretch in which no operation ran on the
    device, mean over the cell's devices."""
    t = ctx.trace
    if t is None or t["window_s"] <= 0:
        return None
    return 1.0 - t["busy_s"] / t["window_s"]
