"""From a profiler trace to busy time, kernel time and idle gaps.

The run records a window with ``jax.profiler`` and marks it with a host
span named ``WINDOW``.  The reduction reads only events, as
``(plane, line, name, start_ns, duration_ns[, detail])`` tuples, so it
can be checked on a hand-made trace and on a slice recorded on the chip
(``tests/test_trace.py``); ``detail`` joins an event's string statistics (on the TPU its HLO text and op
name), where a Pallas kernel shows as ``tpu_custom_call`` and
``pallas_call`` while its own name is that of the enclosing call:

  busy      the union of the intervals in which an operation runs on a
            device (the ``XLA Ops`` line of each ``/device:TPU:n`` plane),
            clipped to the window and averaged over the devices;
  ops       device time by operation name;
  families  device time by kernel family: ``kernel_families.json`` maps
            substrings of an operation's name or detail to families, first
            match wins;
  gaps      the idle time of device 0, split by the benchmark's host
            spans (``bench.*``, around each call into the program) open
            during it, and ``host`` where none is.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
from typing import Iterable, Optional

WINDOW = "bench.window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
FAMILIES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "kernel_families.json")


def load_events(trace_dir: str) -> list[tuple]:
    """Every event of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    out = []
    for plane in data.planes:
        keep_all = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            if keep_all and line.name != OPS_LINE:
                continue
            for ev in line.events:
                if not keep_all and not ev.name.startswith("bench."):
                    continue
                detail = " ".join(str(v) for _, v in ev.stats
                                  if isinstance(v, str)) if keep_all else ""
                out.append((plane.name, line.name, ev.name,
                            int(ev.start_ns), int(ev.duration_ns), detail))
    return out


def _union(intervals: Iterable[tuple[int, int]]) -> list[tuple[int, int]]:
    merged: list[list[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def family_of(name: str, families: list) -> Optional[str]:
    for sub, fam in families:
        if sub in name:
            return fam
    return None


def reduce(events: list[tuple], families: Optional[list] = None) -> dict:
    """``busy_s``, ``window_s``, ``ops`` {name: s}, ``families``
    {family: s}, ``gaps`` {host span: s} and ``n_devices`` of a trace."""
    if families is None:
        with open(FAMILIES) as f:
            families = [tuple(x) for x in json.load(f)["families"]]
    events = [tuple(e) + ("",) * (6 - len(e)) for e in events]
    win = [(s, s + d) for p, _, n, s, d, _ in events
           if n == WINDOW and not p.startswith(DEVICE_PREFIX)]
    if not win:
        raise ValueError(f"no {WINDOW} span in the trace")
    w0, w1 = min(s for s, _ in win), max(e for _, e in win)
    devices = sorted({p for p, *_ in events if p.startswith(DEVICE_PREFIX)})
    if not devices:
        raise ValueError("no device operations in the trace")
    ops: dict[str, float] = {}
    fams: dict[str, float] = {}
    busy = 0.0
    dev0 = []
    for dev in devices:
        iv = []
        for p, _, name, s, d, detail in events:
            if p != dev:
                continue
            s, e = max(s, w0), min(s + d, w1)
            if e <= s:
                continue
            iv.append((s, e))
            secs = (e - s) * 1e-9
            ops[name] = ops.get(name, 0.0) + secs
            fam = family_of(f"{name} {detail}", families)
            if fam is not None:
                fams[fam] = fams.get(fam, 0.0) + secs
        u = _union(iv)
        busy += sum(e - s for s, e in u) * 1e-9
        if dev == devices[0]:
            dev0 = u
    n = len(devices)
    # the benchmark's spans inside the window do not nest: sorted by start
    # they are sorted by end too
    spans = sorted((s, s + d, name) for p, _, name, s, d, _ in events
                   if p == HOST_PLANE and name != WINDOW)
    ends = [e for _, e, _ in spans]
    gaps: dict[str, float] = {}
    edges = [w0] + [x for iv in dev0 for x in iv] + [w1]
    for s, e in zip(edges[0::2], edges[1::2]):
        if e <= s:
            continue
        covered = 0
        j = bisect.bisect_right(ends, s)
        while j < len(spans) and spans[j][0] < e:
            a, b, name = spans[j]
            part = min(b, e) - max(a, s)
            gaps[name] = gaps.get(name, 0.0) + part * 1e-9
            covered += part
            j += 1
        if e - s > covered:
            gaps["host"] = gaps.get("host", 0.0) + (e - s - covered) * 1e-9
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy / n,
        "n_devices": n,
        "ops": {k: v / n for k, v in ops.items()},
        "families": {k: v / n for k, v in fams.items()},
        "gaps": gaps,
    }


def top(d: dict, k: int = 10) -> list:
    return [[name, secs] for name, secs in
            sorted(d.items(), key=lambda kv: -kv[1])[:k]]
