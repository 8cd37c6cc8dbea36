"""Engine: share of the window inside the engine's prefill and admit
(program spans ``serve.prefill`` and ``serve.admit``), in which no lane
decodes; what ``prefill_share`` times from outside the engine."""

from benchlib import program


def read(run):
    if run.kind != "serve":
        return None
    return program.window_share(run, "serve.prefill", "serve.admit")
