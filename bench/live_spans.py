"""Readers of the live plane's program spans (``serving/engine.py``,
``serving/llm.py``) and of its device time, for the ``live`` cells.

Span readers take every record the program collected while the profiler
recorded, as ``program_spans.py``'s do.  The two device shares divide the
work of the spans that ran wholly inside the traced stretch (each such
span waits for its program to finish, so that program's device time lies
inside the stretch too) by the device time of the stretch's modules of
that layer (``layers/live.prefill.json``, ``layers/live.decode.json``):
work the stretch cut in part only lowers the share.  Each returns None
where it finds nothing to read.
"""

from __future__ import annotations

from bench import model_cost
from bench.program_spans import collected, count, total_ms


def _ms(rec) -> float:
    return (rec.end_ns - rec.start_ns) / 1e6


def execute_ms_p95(ctx):
    """95th percentile of a paper model's measured execution
    (``live.execute``), ms."""
    import numpy as np

    recs = collected()
    durs = [_ms(r) for r in recs or () if r.name == "live.execute"]
    return float(np.percentile(durs, 95)) if durs else None


def host_ms_per_query(ctx):
    """ms of a live query (``live.serve``) outside its execution
    (``live.execute``, or ``live.prefill`` + ``live.decode``)."""
    recs = collected()
    if recs is None:
        return None
    n = count(recs, "live.serve")
    inner = (total_ms(recs, "live.execute") + total_ms(recs, "live.prefill")
             + total_ms(recs, "live.decode"))
    return (total_ms(recs, "live.serve") - inner) / n if n else None


def prefill_ms_per_query(ctx):
    """ms of a query's prefill (``live.prefill``: dispatch to the end of
    its program)."""
    recs = collected()
    if recs is None:
        return None
    n = count(recs, "live.prefill")
    return total_ms(recs, "live.prefill") / n if n else None


def decode_ms_per_step(ctx):
    """ms of a decode step: ``live.decode`` over the steps it ran."""
    recs = collected()
    steps = sum(r.args.get("steps", 0) for r in recs or ()
                if r.name == "live.decode")
    return total_ms(recs, "live.decode") / steps if steps else None


def _stretch(ctx):
    """(start, end) in ns of the traced stretch: it opens as the window's
    first benchmark span closes and ends at the first that closes
    ``TRACE_SECONDS`` later (``run.Tracer``)."""
    from bench.run import TRACE_SECONDS

    ends = [e for _, _, e in ctx.spans.events]
    if not ends:
        return None
    t0 = ends[0]
    t1 = next((e for e in ends if e - t0 >= TRACE_SECONDS), ends[-1])
    return t0 * 1e9, t1 * 1e9


def _inside(ctx, name: str, arg: str):
    if ctx.trace is None:
        return None
    recs, stretch = collected(), _stretch(ctx)
    if recs is None or stretch is None:
        return None
    lo, hi = stretch
    return [r for r in recs if r.name == name and arg in r.args
            and r.start_ns >= lo and r.end_ns <= hi]


def mfu_prefill(ctx):
    """Model FLOPs of the prefills over their modules' device time x the
    bf16 peak, in %."""
    recs = _inside(ctx, "live.prefill", "held_tokens")
    busy = (ctx.trace or {}).get("layer_s", {}).get("live.prefill", 0.0)
    if not recs or busy <= 0:
        return None
    cfg = ctx.cell.config
    flops = sum(model_cost.prefill_flops(cfg, r.args["rows"],
                                         r.args["prompt"],
                                         r.args["held_tokens"])
                for r in recs)
    return 100.0 * flops / (busy * model_cost.peaks()["bf16_flops_per_s"])


def decode_roofline(ctx):
    """Bytes the decode steps must move over their module's device time x
    the HBM peak, in %."""
    recs = _inside(ctx, "live.decode", "active")
    busy = (ctx.trace or {}).get("layer_s", {}).get("live.decode", 0.0)
    if not recs or busy <= 0:
        return None
    cfg = ctx.cell.config
    moved = sum(model_cost.decode_bytes(cfg, r.args["rows"],
                                        r.args["prompt"], r.args["steps"],
                                        r.args["active"])
                for r in recs)
    return 100.0 * moved / (busy * model_cost.peaks()["hbm_bytes_per_s"])
