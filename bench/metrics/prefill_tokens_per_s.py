"""Engine: real prompt tokens prefilled per second of prefill, the sum of
``real`` (the unpadded prompt length) over the window's ``serve.prefill``
spans divided by those spans' seconds.  A program whose prefill spans
carry no ``real`` gives None."""

from benchlib import program


def read(run):
    if run.kind != "serve":
        return None
    spans = program.window_spans(run)
    if spans is None:
        return None
    pre = [s for s in spans if s.name == "serve.prefill"]
    if not pre or any("real" not in s.attrs for s in pre):
        return None
    secs = sum(s.t1 - s.t0 for s in pre)
    if secs <= 0:
        return None
    return sum(s.attrs["real"] for s in pre) / secs
