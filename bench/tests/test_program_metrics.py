"""The per-layer metrics that read the program's own spans and kernel
names, on spans put into the recorder by hand and a hand-made trace
reduction."""

import types

import pytest

import run as bench_run
from repro.runtime import spans as spans_mod
from repro.runtime.spans import Recorder

HOST = ("host_sample_share", "logits_fetch_share", "host_syncs_per_step",
        "sched_lane_occupancy", "engine_prefill_share", "plan_search_s")
KERNELS = ("tt_gemm_time_share.train", "streaming_tt_time_share.train")


def read(name, run):
    return bench_run.load_module("metrics", name).read(run)


def _serve_spans():
    """Set-up, then a window [100, 200): a tick with an admission and a
    decode, a tick with a decode alone, and a last tick the window closed
    before its decode."""
    rows = [
        # id, name, t0, t1, parent, attrs
        (0, "dse.plan_search", 10, 30, None, {"phase": "prefill"}),
        (1, "dse.plan_search", 30, 35, None, {"phase": "decode"}),
        (2, "serve.decode", 50, 60, None, {}),          # warm-up
        (4, "serve.prefill.dispatch", 100, 101, 3, {}),
        (5, "serve.prefill.fetch", 101, 106, 3, {}),
        (3, "serve.prefill", 100, 106, 6, {"tokens": 128}),
        (7, "serve.sample", 106, 107, 6, {}),
        (8, "serve.admit", 107, 108, 6, {}),
        (6, "serve.admission", 100, 108, 10, {"rid": 0}),
        (11, "serve.decode.dispatch", 108, 109, 12, {}),
        (13, "serve.decode.wait", 109, 115, 12, {}),
        (14, "serve.decode.fetch", 115, 117, 12, {}),
        (12, "serve.decode", 108, 118, 10, {}),
        (15, "serve.sample", 118, 120, 10, {}),
        (10, "serve.step", 100, 120, None, {"slots": 4, "lanes": 3}),
        (17, "serve.decode.fetch", 124, 126, 18, {}),
        (18, "serve.decode", 120, 126, 16, {}),
        (19, "serve.sample", 126, 129, 16, {}),
        (16, "serve.step", 120, 130, None, {"slots": 4, "lanes": 2}),
        (20, "serve.step", 195, 205, None, {"slots": 4, "lanes": 4}),
    ]
    rec = Recorder()
    rec._ring.extend(rows)     # as the spans would have closed
    return rec


def _run(kind, trace=None):
    return types.SimpleNamespace(
        kind=kind, window={"t_start": 100.0, "window_s": 100.0},
        trace=trace)


@pytest.fixture
def recorded(monkeypatch):
    monkeypatch.setattr(spans_mod, "spans", _serve_spans().spans)


def test_serving_host_metrics(recorded):
    run = _run("serve")
    assert read("host_sample_share", run) == pytest.approx(6.0)
    assert read("logits_fetch_share", run) == pytest.approx(9.0)
    assert read("host_syncs_per_step", run) == pytest.approx(1.5)
    assert read("sched_lane_occupancy", run) == pytest.approx(62.5)
    assert read("engine_prefill_share", run) == pytest.approx(7.0)
    assert read("plan_search_s", run) == pytest.approx(25.0)
    train = _run("train")
    assert read("plan_search_s", train) == pytest.approx(25.0)
    assert all(read(m, train) is None for m in HOST if m != "plan_search_s")


TRAIN_OPS = {
    '%tt_gemm.7 = f32[4096,768]{1,0} custom-call(%a, %b), '
    'custom_call_target="tpu_custom_call"': 0.2,
    '%tt_gemm = f32[768,768]{1,0} custom-call(%c, %d), '
    'custom_call_target="tpu_custom_call"': 0.1,
    '%streaming_tt.3 = f32[4096,768]{1,0} custom-call(%x, %e), '
    'custom_call_target="tpu_custom_call"': 0.3,
    "%fusion.1 = f32[4096,768]{1,0} fusion(%y)": 1.0,
}


def test_kernel_shares_by_op_name():
    run = _run("train", {"busy_s": 2.0, "ops": TRAIN_OPS})
    assert read("tt_gemm_time_share.train", run) == pytest.approx(15.0)
    assert read("streaming_tt_time_share.train", run) == pytest.approx(15.0)
    serve = _run("serve", {"busy_s": 2.0, "ops": TRAIN_OPS})
    assert all(read(m, serve) is None for m in KERNELS)


def test_a_program_without_spans_or_kernel_names_reads_nothing(monkeypatch):
    import sys

    import repro.runtime

    monkeypatch.delattr(repro.runtime, "spans")
    monkeypatch.setitem(sys.modules, "repro.runtime.spans", None)
    for kind in ("serve", "train"):
        assert all(read(m, _run(kind)) is None for m in HOST)
    unnamed = {'%gemm.1212 = f32[8,8]{1,0} custom-call(%a), '
               'custom_call_target="tpu_custom_call"': 1.0}
    run = _run("train", {"busy_s": 2.0, "ops": unnamed})
    assert all(read(m, run) is None for m in KERNELS)
    assert all(read(m, _run("train")) is None for m in KERNELS)
