"""Scheduler: the scheduler's own count of occupied decode lanes over
``n_slots``: mean ``lanes`` / ``slots`` of the window's ``serve.step``
spans that hold a ``serve.decode``."""

from benchlib import program


def read(run):
    if run.kind != "serve":
        return None
    spans = program.window_spans(run)
    if spans is None:
        return None
    by_id = {s.id: s for s in spans}
    steps = [by_id[s.parent] for s in spans
             if s.name == "serve.decode" and s.parent in by_id]
    if not steps:
        return None
    return 100.0 * sum(s.attrs["lanes"] / s.attrs["slots"]
                       for s in steps) / len(steps)
