"""Optimizer / data / checkpoint / runtime substrate tests."""

import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.checkpoint import CheckpointManager
from repro.data import make_pipeline
from repro.optim import (
    adamw_init,
    adamw_update,
    clip_by_global_norm,
    compress_decompress,
    compress_init,
    cosine_schedule,
    linear_warmup_cosine,
)
from repro.runtime import FaultTolerantLoop, StragglerMonitor


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------

def test_adamw_converges_on_quadratic():
    p = {"w": jnp.asarray([3.0, -2.0])}
    st_ = adamw_init(p)
    for _ in range(300):
        g = jax.grad(lambda pp: jnp.sum(pp["w"] ** 2))(p)
        p, st_ = adamw_update(g, st_, p, 0.05, weight_decay=0.0)
    assert float(jnp.max(jnp.abs(p["w"]))) < 1e-2


def test_adamw_weight_decay_shrinks():
    p = {"w": jnp.asarray([5.0])}
    st_ = adamw_init(p)
    zero_g = {"w": jnp.asarray([0.0])}
    for _ in range(50):
        p, st_ = adamw_update(zero_g, st_, p, 0.1, weight_decay=0.5)
    assert float(p["w"][0]) < 5.0


def test_clip_by_global_norm():
    g = {"a": jnp.asarray([3.0, 4.0])}
    clipped, norm = clip_by_global_norm(g, 1.0)
    assert abs(float(norm) - 5.0) < 1e-6
    cn = float(jnp.linalg.norm(clipped["a"]))
    assert abs(cn - 1.0) < 1e-5


def test_schedules():
    lr = linear_warmup_cosine(1.0, 10, 100)
    assert float(lr(jnp.asarray(5))) < 1.0
    assert abs(float(lr(jnp.asarray(10))) - 1.0) < 1e-6
    end = float(lr(jnp.asarray(100)))
    assert end < 0.2
    c = cosine_schedule(2.0, 10)
    assert abs(float(c(jnp.asarray(0))) - 2.0) < 1e-6


@given(st.integers(0, 100))
@settings(max_examples=20, deadline=None)
def test_compression_error_feedback_unbiased(seed):
    """Over many steps the int8+EF pipeline transmits the true mean."""
    rng = np.random.default_rng(seed)
    g = {"w": jnp.asarray(rng.normal(size=(16,)) * rng.uniform(0.1, 10),
                          jnp.float32)}
    state = compress_init(g)
    acc = jnp.zeros(16)
    n = 64
    for _ in range(n):
        dq, state = compress_decompress(g, state)
        acc = acc + dq["w"]
    np.testing.assert_allclose(np.asarray(acc / n), np.asarray(g["w"]),
                               rtol=0.05, atol=0.05)


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------

def test_pipeline_deterministic_and_resumable():
    pipe = make_pipeline(vocab=97, seq_len=12, global_batch=4, seed=5)
    a = pipe.batch(7)
    b = pipe.batch(7)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    # labels are next-token shifted
    np.testing.assert_array_equal(a["tokens"][:, 1:], a["labels"][:, :-1])


def test_pipeline_shards_partition_global_batch():
    pipe = make_pipeline(vocab=50, seq_len=8, global_batch=8, seed=1)
    full = pipe.batch(3, 0, 1)
    parts = [pipe.batch(3, r, 4)["tokens"] for r in range(4)]
    np.testing.assert_array_equal(np.concatenate(parts), full["tokens"])


def test_pipeline_learnable_structure():
    """The bigram skeleton makes successor prediction beat chance."""
    pipe = make_pipeline(vocab=100, seq_len=64, global_batch=8, seed=2)
    b = pipe.batch(0)
    hits = 0
    total = 0
    succ = pipe._succ
    for row in b["tokens"]:
        for t in range(1, len(row)):
            total += 1
            hits += int(row[t] == succ[row[t - 1]])
    assert hits / total > 0.3  # ~50% by construction, >>1% chance


def test_pipeline_divisibility_error():
    pipe = make_pipeline(vocab=10, seq_len=4, global_batch=6)
    with pytest.raises(ValueError):
        pipe.batch(0, 0, 4)


# ---------------------------------------------------------------------------
# checkpoint
# ---------------------------------------------------------------------------

def _tree():
    return {
        "a": jnp.arange(12, dtype=jnp.int32).reshape(3, 4),
        "b": {"c": jnp.ones((4,), jnp.bfloat16) * 1.5,
              "d": jnp.zeros((2, 2), jnp.float32)},
    }


def test_checkpoint_roundtrip_bf16():
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d)
        tree = _tree()
        mgr.save(3, tree)
        step, restored = mgr.restore(tree)
        assert step == 3
        assert restored["b"]["c"].dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(restored["a"], np.int32),
                                      np.asarray(tree["a"], np.int32))


def test_checkpoint_retention_and_latest():
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, keep=2)
        for s in (1, 5, 9, 12):
            mgr.save(s, _tree())
        assert mgr.all_steps() == [9, 12]
        assert mgr.latest_step() == 12


def test_checkpoint_async_save():
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d)
        mgr.save_async(1, _tree())
        mgr.wait()
        assert mgr.latest_step() == 1


def test_checkpoint_structure_mismatch_raises():
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d)
        mgr.save(1, _tree())
        with pytest.raises(ValueError):
            mgr.restore({"only": jnp.zeros(1)})


# ---------------------------------------------------------------------------
# runtime fault tolerance
# ---------------------------------------------------------------------------

def test_fault_tolerant_loop_recovers_from_failure():
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d)
        fail_at = {"step": 7, "armed": True}

        def step_fn(state, step):
            if step == fail_at["step"] and fail_at["armed"]:
                fail_at["armed"] = False
                raise RuntimeError("injected node failure")
            return {"x": state["x"] + 1}

        loop = FaultTolerantLoop(step_fn, mgr, checkpoint_every=5)
        state, done = loop.run({"x": jnp.asarray(0)}, 0, 10)
        assert done == 10
        assert loop.recoveries == 1
        assert int(state["x"]) == 10  # deterministic recovery, no lost steps


@pytest.mark.parametrize("landed", [False, True])
def test_fault_tolerant_loop_poison_step_aborts(landed):
    """A step that always fails is tried 1 + max_retries times, also when
    the step-2 checkpoint has landed and each restore replays step 2."""
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d)
        calls = [0]

        def step_fn(state, step):
            if step == 3:
                calls[0] += 1
                if landed:
                    mgr.wait()
                if calls[0] > 3:
                    pytest.fail("poison step retried past max_retries_per_step")
                raise RuntimeError("always fails")
            return state

        loop = FaultTolerantLoop(step_fn, mgr, checkpoint_every=2,
                                 max_retries_per_step=2)
        with pytest.raises(RuntimeError):
            loop.run({"x": jnp.asarray(0)}, 0, 10)
        assert calls[0] == 3


def test_straggler_monitor():
    mon = StragglerMonitor(threshold=2.0, window=8)
    for i in range(8):
        assert not mon.record(i, 1.0)
    assert mon.record(8, 5.0)
    assert mon.flagged == [8]
