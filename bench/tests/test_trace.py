"""The reduction from trace events to busy time, kernel time and gaps."""

import pytest

from benchlib import trace

DEV, HOST = "/device:TPU:0", "/host:CPU"
FAMILIES = [("tpu_custom_call", "tt")]
KERNEL = 'custom_call_target="tpu_custom_call"'


def ev(plane, name, start_us, dur_us, line="XLA Ops", detail=""):
    return (plane, line, name, start_us * 1000, dur_us * 1000, detail)


def test_hand_made_trace():
    events = [
        ev(HOST, "bench.window", 100, 1000, "python"),
        ev(HOST, "bench.decode", 100, 300, "python"),
        ev(HOST, "bench.prefill", 600, 200, "python"),
        ev(DEV, "fusion.1", 50, 100),           # clipped to [100, 150)
        ev(DEV, "closed_call.3", 150, 100, detail=KERNEL),   # [150, 250)
        ev(DEV, "fusion.2", 200, 100),          # overlaps: union to 300
        ev(DEV, "gemm.1", 700, 50, detail=KERNEL),  # [700, 750)
        ev(DEV, "copy", 1050, 200),             # clipped to [1050, 1100)
    ]
    r = trace.reduce(events, FAMILIES)
    assert r["window_s"] == pytest.approx(1000e-6)
    assert r["busy_s"] == pytest.approx(300e-6)
    assert r["families"]["tt"] == pytest.approx(150e-6)
    assert r["ops"]["fusion.2"] == pytest.approx(100e-6)
    # idle [300, 400) in decode, [400, 600) and [800, 1050) outside spans,
    # [600, 700) and [750, 800) in prefill
    assert r["gaps"]["bench.decode"] == pytest.approx(100e-6)
    assert r["gaps"]["bench.prefill"] == pytest.approx(150e-6)
    assert r["gaps"]["host"] == pytest.approx(450e-6)
    assert sum(r["gaps"].values()) + r["busy_s"] == pytest.approx(
        r["window_s"])


def test_needs_a_window_and_a_device():
    with pytest.raises(ValueError):
        trace.reduce([ev(DEV, "x", 0, 1)], FAMILIES)
    # five-field events (no detail) reduce too
    r = trace.reduce([e[:5] for e in (ev(HOST, "bench.window", 0, 10, "p"),
                                       ev(DEV, "x", 0, 5))], FAMILIES)
    assert r["busy_s"] == pytest.approx(5e-6)
    with pytest.raises(ValueError):
        trace.reduce([ev(HOST, "bench.window", 0, 10, "python")], FAMILIES)


@pytest.mark.parametrize("cell", ["serve", "train"])
def test_recorded_trace_slice(cell):
    """A slice of a real chip trace: the kernel families of
    ``kernel_families.json`` find the Mosaic kernels by their op names,
    and busy and idle time add up to the window."""
    import json
    import os

    path = os.path.join(os.path.dirname(__file__), "data", "trace-slice.json")
    with open(path) as f:
        rec = json.load(f)["slices"][cell]
    r = trace.reduce([tuple(e) for e in rec["events"]])
    want = rec["reduced"]
    assert r["window_s"] == pytest.approx(want["window_s"])
    assert r["busy_s"] == pytest.approx(want["busy_s"])
    assert r["families"] == pytest.approx(want["families"])
    assert r["gaps"] == pytest.approx(want["gaps"])
    assert r["families"]["tt"] > 0
    assert 0 < r["busy_s"] <= r["window_s"]
    assert sum(r["gaps"].values()) + r["busy_s"] == pytest.approx(
        r["window_s"])
