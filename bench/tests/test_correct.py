"""The comparison that decides ``correct``, at smoke size on the CPU.

The control (the plain reference in the next precision down, put in the
program's place) must read above a limit the program reads under, and a
run whose timed path is broken underneath must come out not correct, once
for each fault the cell can have.  The chip's limits are set from the
chip's readings (``bench/readings.py``); these tests pin the mechanism.
"""

import pytest

import run as bench_run
from benchlib import faults
from helpers import PEAK, smoke_cell

SEED = 2**33 + 5          # wider than 32 bits, as the driver's seeds are
#: smoke-size limits: the CPU runs the program in exact float32, so they
#: sit between its readings (about 0, and 1e-6 for training) and the
#: bfloat16 control's (about 0.01 for the widest gap, 3e-5 for the loss,
#: 3e-3 for the gradient and change)
SERVE_LIMIT = {"max_logit_gap": 0.004}
TRAIN_LIMIT = {"loss_gap": 1e-5, "grad_gap": 1e-3, "change_gap": 1e-3}


def serve(controls=()):
    cell = smoke_cell("chatglm-smoke", "serve-smoke", SERVE_LIMIT)
    return bench_run.measure(cell, SEED, 2.0, False, peak=PEAK,
                             controls=controls)


def train(controls=()):
    cell = smoke_cell("tt-lm-smoke", "train-smoke", TRAIN_LIMIT)
    return bench_run.measure(cell, SEED, 0.5, False, peak=PEAK,
                             controls=controls)


def test_serving_control_fails_where_the_program_passes():
    r = serve(controls=("bf16",))
    assert r["result"]["correct"], r["readings"]
    assert r["readings"]["control.bf16.max_logit_gap"] > SERVE_LIMIT[
        "max_logit_gap"] > r["readings"]["max_logit_gap"]


def test_training_control_and_half_batch_fail_where_the_program_passes():
    r = train(controls=("bf16", "half_batch"))
    assert r["result"]["correct"], r["readings"]
    for c in ("bf16", "half_batch"):
        assert any(r["readings"][f"control.{c}.{k}"] > v
                   for k, v in TRAIN_LIMIT.items()), (c, r["readings"])


# -- faults planted under the timed path ---------------------------------

@pytest.fixture
def planted():
    undo = []
    yield lambda name: undo.append(faults.plant(name))
    for f in reversed(undo):
        f()


@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged",
                                   "half_batch"])
def test_serving_fault_is_not_correct(planted, fault):
    planted(f"serve.{fault}")
    r = serve()
    assert not r["result"]["correct"], r["readings"]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_training_fault_is_not_correct(planted, fault):
    planted(f"train.{fault}")
    r = train()
    assert not r["result"]["correct"], r["readings"]
