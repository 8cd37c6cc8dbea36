"""DSE: seconds of set-up in the DSE's plan search (program spans
``dse.plan_search``, one per plan the cell searches)."""

from benchlib import program


def read(run):
    spans = program.setup_spans(run)
    if not spans or not any(s.name == "dse.plan_search" for s in spans):
        return None
    return program.seconds(spans, "dse.plan_search")
