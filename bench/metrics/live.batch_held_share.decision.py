"""Share of a paper model's live queries served a held input batch: the
program spans ``live.serve`` whose ``drawn`` arg reads 0, over those that
carry the arg, in cells that report decision_s.  An LLM's queries carry no
such arg and are not counted.  None where no span carries it (a program
that draws every query's batch anew)."""

from bench.program_spans import collected


def read(ctx):
    recs = collected()
    if recs is None:
        return None
    drawn = [r.args["drawn"] for r in recs
             if r.name == "live.serve" and "drawn" in r.args]
    return drawn.count(0) / len(drawn) if drawn else None
