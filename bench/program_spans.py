"""Readers of the program's own spans (``repro.tracing``).

The program collects its spans while a JAX profiler session records: in a
``--trace 1`` run, over the window's traced stretch.  Each metric that
reads them imports its reader from here, one quantity under a name of its
own in each group of cells, as ``readers.py`` does.  A reader returns None
where there is nothing to read: a program without the registry, no record
collected, or records dropped from the registry's full ring.
"""

from __future__ import annotations


def collected():
    """The program's span records, or None where there are none to read
    (or the ring dropped some)."""
    try:
        from repro import tracing
    except ImportError:
        return None
    recs = tracing.records()
    if not recs or tracing.dropped():
        return None
    return recs


def _ms(rec) -> float:
    return (rec.end_ns - rec.start_ns) / 1e6


def _named(recs, name: str, args: dict):
    return [r for r in recs if r.name == name
            and all(r.args.get(k) == v for k, v in args.items())]


def total_ms(recs, name: str, **args) -> float:
    """Milliseconds in the spans named ``name`` whose args match ``args``."""
    return sum(_ms(r) for r in _named(recs, name, args))


def count(recs, name: str, **args) -> int:
    return len(_named(recs, name, args))


def self_ms(recs, name: str, minus: tuple[str, ...]) -> float:
    """Milliseconds in the spans named ``name`` outside their children
    named in ``minus``."""
    ids = {r.sid for r in recs if r.name == name}
    inner = sum(_ms(r) for r in recs if r.parent in ids and r.name in minus)
    return total_ms(recs, name) - inner


def _per(recs, num, den_name: str, **den_args):
    if recs is None:
        return None
    n = count(recs, den_name, **den_args)
    return num(recs) / n if n else None


def select_ms(ctx):
    """ms in the acquisition's round trip (``ribbon.select``: GP buffers
    up, ``select_batch``, picks back on the host) per ``ribbon.tell``."""
    return _per(collected(), lambda r: total_ms(r, "ribbon.select"),
                "ribbon.tell")


def search_host_ms(ctx):
    """ms of the optimizer outside the acquisition's round trip
    (``ribbon.ask`` + ``ribbon.tell`` - ``ribbon.select``) per
    ``ribbon.tell``."""
    def host(recs):
        return (total_ms(recs, "ribbon.ask") + total_ms(recs, "ribbon.tell")
                - total_ms(recs, "ribbon.select"))

    return _per(collected(), host, "ribbon.tell")


def oracle_host_ms(ctx):
    """ms of a QoS oracle call (``pool.eval``, a memo miss) outside its
    waits on the device (``sim.wait``), per call."""
    return _per(collected(), lambda r: self_ms(r, "pool.eval", ("sim.wait",)),
                "pool.eval")


def memo_ms_per_dispatch(ctx):
    """ms in the evaluator's memo (``pool.memo``) per grid dispatch (a
    grid lane's ``sim.wait``)."""
    return _per(collected(), lambda r: total_ms(r, "pool.memo"),
                "sim.wait", lane="grid")


def stage_ms_per_dispatch(ctx):
    """ms of the grid lane's host staging (``sim.stage``) per grid
    dispatch."""
    return _per(collected(), lambda r: total_ms(r, "sim.stage", lane="grid"),
                "sim.wait", lane="grid")


def stream_draw_ms_per_chunk(ctx):
    """ms of a streamed chunk's host part (``sim.stream_draw``: draw and
    dispatch) per chunk."""
    return _per(collected(), lambda r: total_ms(r, "sim.stream_draw"),
                "sim.stream_draw")


def gc_ms_per_s(ctx):
    """ms of garbage collection (``host.gc``) per second collected, from
    the first record's start to the last one's end."""
    recs = collected()
    if recs is None:
        return None
    seconds = (max(r.end_ns for r in recs)
               - min(r.start_ns for r in recs)) / 1e9
    return total_ms(recs, "host.gc") / seconds if seconds > 0 else None
