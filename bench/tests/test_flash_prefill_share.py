"""``flash_prefill_time_share.serve`` on a hand-made trace reduction."""

import types

import pytest

import run as bench_run

NAME = "flash_prefill_time_share.serve"
OPS = {
    '%flash_prefill.3 = bf16[1,6144,16,256]{3,2,1,0} custom-call(%q, %k, %v), '
    'custom_call_target="tpu_custom_call"': 0.3,
    '%flash_prefill.7 = bf16[1,1024,16,256]{3,2,1,0} custom-call(%q, %k, %v), '
    'custom_call_target="tpu_custom_call"': 0.1,
    '%tt_gemm.2 = bf16[6144,4096]{1,0} custom-call(%a, %b), '
    'custom_call_target="tpu_custom_call"': 0.6,
    "%fusion.1 = f32[1,16,2,3072,6144]{4,3,2,1,0} fusion(%y)": 1.0,
}


def _read(kind, ops):
    run = types.SimpleNamespace(kind=kind, trace={"busy_s": 4.0, "ops": ops})
    return bench_run.load_module("metrics", NAME).read(run)


def test_share_of_busy_time_in_the_kernel():
    assert _read("serve", OPS) == pytest.approx(10.0)


def test_nothing_to_read_without_the_kernel_or_in_training():
    assert _read("train", OPS) is None
    parent = {k: v for k, v in OPS.items() if "flash_prefill" not in k}
    assert _read("serve", parent) is None
