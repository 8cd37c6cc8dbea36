"""The program's own record of a traced run: its spans and its named
kernels.

Program spans (``repro.runtime.spans``, on ``time.perf_counter`` like the
window) are read clipped to the window, or before it for set-up.  Named
kernels are the device ops whose name (the HLO instruction, as the trace
reduction keys ``ops``) starts ``%<kernel>.``.  A program without the
span recorder, or whose kernels carry no name, gives None, never an
error: a metric reader then reports nothing.
"""

from __future__ import annotations

from typing import Optional


def _recorder():
    try:
        from repro.runtime import spans
    except ImportError:
        return None
    return spans


def window_spans(run) -> Optional[list]:
    """The program's spans clipped to the window; None without a
    recorder or with no span in the window."""
    rec = _recorder()
    if rec is None:
        return None
    t0 = run.window["t_start"]
    got = rec.spans(t0, t0 + run.window["window_s"])
    return got or None


def setup_spans(run) -> Optional[list]:
    """The program's spans before the window; None without a recorder."""
    rec = _recorder()
    return None if rec is None else rec.spans(until=run.window["t_start"])


def seconds(spans, *names: str) -> float:
    return sum(s.t1 - s.t0 for s in spans if s.name in names)


def window_share(run, *names: str) -> Optional[float]:
    """Percent of the window inside the program spans ``names``; None if
    none of them is in the window."""
    spans = window_spans(run)
    if spans is None or not any(s.name in names for s in spans):
        return None
    return 100.0 * seconds(spans, *names) / run.window["window_s"]


def kernel_share(run, kernel: str) -> Optional[float]:
    """Percent of device busy time in ops named ``%<kernel>.``; None where
    no op carries the name."""
    t = run.trace
    if t is None or t["busy_s"] <= 0:
        return None
    prefix = (f"%{kernel}.", f"%{kernel} ")
    secs = [v for name, v in t["ops"].items() if name.startswith(prefix)]
    if not secs:
        return None
    return 100.0 * sum(secs) / t["busy_s"]
