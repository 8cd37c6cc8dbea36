"""Scheduler: share of the window in the scheduler's host-side token
picks (program spans ``serve.sample``: the NumPy argmax over each lane's
logits after a decode step, and each first-token pick)."""

from benchlib import program


def read(run):
    if run.kind != "serve":
        return None
    return program.window_share(run, "serve.sample")
