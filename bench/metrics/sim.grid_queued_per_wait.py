"""Grid dispatches issued ahead of each fetch: the mean ``queued`` arg of
the program's ``sim.wait`` spans of lane ``grid``, in cells that report
scored_queries_per_s.  A sweep that fetches each dispatch before staging
the next reads 0; one whose n dispatches are all issued before the first
fetch reads (n - 1) / 2.  It shows that the sweep's pipeline engaged, and
is set by the number of dispatches a sweep has: it is no depth of the
device's own queue, which the runtime bounds.  None where the waits carry
no such arg."""

from bench.program_spans import collected


def read(ctx):
    recs = collected()
    if recs is None:
        return None
    queued = [r.args["queued"] for r in recs
              if r.name == "sim.wait" and r.args.get("lane") == "grid"
              and "queued" in r.args]
    return sum(queued) / len(queued) if queued else None
