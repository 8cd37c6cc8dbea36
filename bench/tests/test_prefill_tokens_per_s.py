"""``prefill_tokens_per_s`` on spans put into the recorder by hand."""

import types

import pytest

import run as bench_run
from repro.runtime import spans as spans_mod
from repro.runtime.spans import Recorder


def read(run):
    return bench_run.load_module("metrics", "prefill_tokens_per_s").read(run)


def _run(kind="serve"):
    return types.SimpleNamespace(
        kind=kind, window={"t_start": 100.0, "window_s": 100.0}, trace=None)


def _recorded(monkeypatch, rows):
    rec = Recorder()
    rec._ring.extend(rows)     # as the spans would have closed
    monkeypatch.setattr(spans_mod, "spans", rec.spans)


def test_real_tokens_over_prefill_seconds(monkeypatch):
    _recorded(monkeypatch, [
        # id, name, t0, t1, parent, attrs
        (0, "serve.prefill", 50, 60, None, {"tokens": 512, "real": 500}),
        (2, "serve.prefill.dispatch", 110, 111, 1, {}),
        (1, "serve.prefill", 110, 114, None, {"tokens": 1024, "real": 1000}),
        (3, "serve.decode", 114, 120, None, {}),
        (4, "serve.prefill", 130, 136, None, {"tokens": 2048, "real": 2000}),
    ])
    # the warm-up's prefill lies before the window: (1000 + 2000) / 10 s
    assert read(_run()) == pytest.approx(300.0)
    assert read(_run("train")) is None


def test_window_without_prefill_gives_none(monkeypatch):
    _recorded(monkeypatch, [
        (0, "serve.prefill", 50, 60, None, {"tokens": 512, "real": 500}),
        (1, "serve.decode", 110, 120, None, {}),
    ])
    assert read(_run()) is None


def test_prefill_spans_without_real_give_none(monkeypatch):
    _recorded(monkeypatch, [
        (0, "serve.prefill", 110, 114, None, {"tokens": 1024}),
    ])
    assert read(_run()) is None


def test_no_recorder_gives_none(monkeypatch):
    from benchlib import program

    monkeypatch.setattr(program, "_recorder", lambda: None)
    assert read(_run()) is None
