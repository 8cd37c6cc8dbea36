"""Engine: copies of logits to the host (program spans ``*.fetch``) per
decode step (``serve.decode`` spans) in the window: one per step plus
one per prefill."""

from benchlib import program


def read(run):
    if run.kind != "serve":
        return None
    spans = program.window_spans(run)
    if spans is None:
        return None
    steps = sum(s.name == "serve.decode" for s in spans)
    if not steps:
        return None
    return sum(s.name.startswith("serve.") and s.name.endswith(".fetch")
               for s in spans) / steps
