"""Execution-plan subsystem: schema round-trip, compiler, planned execution.

Covers the acceptance criteria of the plan PR: (1) serialize ->
deserialize -> re-serialize is byte-identical; (2) an installed plan
changes which path/dataflow/kernel executes (asserted via the trace-time
execution log); (3) planned outputs match the pure-jnp reference within
fp tolerance, per backend and at model level; (4) the emitted-plan ->
serve --plan loop works end to end (subprocess, slow).
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FPGA_VU9P, find_topk_paths
from repro.core.dse import global_search
from repro.nn import (
    LinearSpec,
    TTConfig,
    install_plan,
    linear_apply,
    linear_init,
    planned_layer,
    planned_path_index,
)
from repro.plan import (
    BACKENDS,
    ExecutionPlan,
    LayerPlan,
    Tiling,
    compile_plan,
    execution_log,
    load_plan,
    reset_execution_log,
)

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


@pytest.fixture(autouse=True)
def _clean_plan_state():
    install_plan(None)
    reset_execution_log()
    yield
    install_plan(None)
    reset_execution_log()


def _unit_problem(tokens=32):
    tt = TTConfig(enabled=True, d=2, rank=8, min_dim=64)
    spec = LinearSpec("demo", 128, 256, tag="mlp", tt=tt)
    tn = spec.network(tokens)
    res = global_search([find_topk_paths(tn, k=4)], FPGA_VU9P)
    plan = compile_plan([("demo", tn)], res, FPGA_VU9P,
                        arch="unit", tokens=tokens)
    return spec, tn, res, plan


# ---------------------------------------------------------------------------
# schema round-trip
# ---------------------------------------------------------------------------

def test_plan_roundtrip_bit_equal(tmp_path):
    _, _, _, plan = _unit_problem()
    text = plan.dumps()
    again = ExecutionPlan.loads(text)
    assert again == plan
    assert again.dumps() == text  # canonical: re-serialization is byte-equal

    path = str(tmp_path / "plan.json")
    plan.save(path)
    loaded = load_plan(path)
    assert loaded == plan
    assert loaded.dumps() == text


def test_v1_plan_migrates_to_current_bit_equal(tmp_path):
    """A v1 plan (no ``backward`` entries, no ``hardware``) loads,
    upgrades through every migration, and the migrated serialization
    round-trips byte-identically."""
    _, _, _, plan = _unit_problem()
    d = plan.to_json()
    d["version"] = 1
    d.pop("hardware")
    for layer in d["layers"]:
        layer.pop("backward")
        layer.pop("bwd_latency_s")
    v1_text = json.dumps(d, indent=2, sort_keys=True) + "\n"

    migrated = ExecutionPlan.loads(v1_text)
    from repro.plan import PLAN_FORMAT_VERSION

    assert migrated.version == PLAN_FORMAT_VERSION == 4
    assert all(lp.backward == () for lp in migrated.layers)
    # everything but the version/backward/hardware fields survives untouched
    assert migrated.names == plan.names
    assert [lp.path_steps for lp in migrated.layers] == [
        lp.path_steps for lp in plan.layers]

    text = migrated.dumps()
    assert ExecutionPlan.loads(text).dumps() == text  # bit-equal
    # migration is idempotent at the JSON level too
    from repro.plan import migrate_plan_json

    assert migrate_plan_json(json.loads(text)) == json.loads(text)


def test_v2_plan_migrates_to_v3_with_registry_hardware():
    """v2 -> v3 resolves ``hardware`` from the ``hw`` name through the
    repro.hw registry; unregistered names migrate with hardware=None.
    Either way the migrated serialization is bit-stable."""
    from repro.hw import get_target
    from repro.plan import migrate_plan_json

    _, _, _, plan = _unit_problem()
    d = plan.to_json()
    d["version"] = 2
    d.pop("hardware")
    v2_text = json.dumps(d, indent=2, sort_keys=True) + "\n"

    migrated = ExecutionPlan.loads(v2_text)
    assert migrated.version == 4
    assert migrated.hardware == get_target("fpga_vu9p")
    text = migrated.dumps()
    assert ExecutionPlan.loads(text).dumps() == text
    assert migrate_plan_json(json.loads(text)) == json.loads(text)

    # unregistered hw name: plan still loads, provenance is just absent
    d["hw"] = "asic_rev_b"
    orphan = ExecutionPlan.from_json(json.loads(json.dumps(d)))
    assert orphan.hardware is None
    assert ExecutionPlan.loads(orphan.dumps()).dumps() == orphan.dumps()


def test_v3_plan_embeds_searched_hardware():
    """A freshly compiled plan embeds the architecture it was compiled
    for, and the embedded config survives the canonical round-trip."""
    from repro.hw import FPGA_VU9P as BASE

    _, _, _, plan = _unit_problem()
    assert plan.version == 4
    assert plan.hardware == BASE
    again = ExecutionPlan.loads(plan.dumps())
    assert again.hardware == plan.hardware
    # non-HardwareConfig payloads are rejected at construction
    import dataclasses

    with pytest.raises(ValueError, match="hardware"):
        dataclasses.replace(plan, hardware={"pe_rows": 32})


def test_train_plan_backward_ops_roundtrip():
    from repro.core import memoised_layer_backwards
    from repro.plan import BackwardOp

    tokens = 32
    tt = TTConfig(enabled=True, d=2, rank=8, min_dim=64)
    spec = LinearSpec("demo", 128, 256, tag="mlp", tt=tt)
    tn = spec.network(tokens)
    res = global_search([find_topk_paths(tn, k=4)], FPGA_VU9P,
                        objective="train-latency",
                        layer_backwards=memoised_layer_backwards([tn], k=4))
    plan = compile_plan([("demo", tn)], res, FPGA_VU9P,
                        arch="unit", objective="train-latency", tokens=tokens)
    lp = plan.layers[0]
    assert [op.wrt for op in lp.backward] == ["dx", "G1", "G2", "G3", "G4"]
    assert all(op.backend in BACKENDS and op.path_steps
               for op in lp.backward)
    text = plan.dumps()
    again = ExecutionPlan.loads(text)
    assert again == plan and again.dumps() == text
    # BackwardOp validation: streaming is dx-only
    with pytest.raises(ValueError, match="streaming"):
        BackwardOp("G1", 0, ((0, 1),), "streaming_tt")


def test_plan_version_and_format_guard():
    _, _, _, plan = _unit_problem()
    d = plan.to_json()
    d["version"] = 999
    with pytest.raises(ValueError, match="version"):
        ExecutionPlan.from_json(d)
    d = plan.to_json()
    d["format"] = "something-else"
    with pytest.raises(ValueError, match="format"):
        ExecutionPlan.from_json(d)


def test_layer_plan_validation():
    with pytest.raises(ValueError, match="dataflow"):
        LayerPlan("x", 0, (), "XX", (1, 1), "jnp")
    with pytest.raises(ValueError, match="backend"):
        LayerPlan("x", 0, (), "OS", (1, 1), "cuda")
    with pytest.raises(ValueError, match="tiling"):
        Tiling(block_m=0)


def test_compiler_collapses_instances():
    from repro.dse_cli import run_dse_plan

    report, plan = run_dse_plan("tt-lm-100m", smoke=True, top_k=2, tokens=32)
    # instances attn.wq[0..1] etc. collapse to one plan per projection family
    assert all("[" not in n for n in plan.names)
    wq = plan.layer("attn.wq")
    assert wq is not None and wq.instances == 2
    assert plan.layer("head").instances == 1
    assert report["n_layers"] == sum(lp.instances for lp in plan.layers)
    # every plan carries executable steps and a known backend
    for lp in plan.layers:
        assert lp.path_steps and lp.backend in BACKENDS


# ---------------------------------------------------------------------------
# planned execution: routing + numerics
# ---------------------------------------------------------------------------

def test_install_plan_changes_execution_and_matches_reference():
    spec, _, res, plan = _unit_problem()
    params = linear_init(jax.random.PRNGKey(0), spec)
    x = jax.random.normal(jax.random.PRNGKey(1), (32, spec.d_in))

    y_ref = linear_apply(spec, params, x)     # no plan: default executor
    assert execution_log() == ()              # nothing planned executed

    for backend in BACKENDS:
        reset_execution_log()
        install_plan(plan.with_backend(backend))
        y = linear_apply(spec, params, x)
        log = execution_log()
        assert len(log) == 1, f"{backend}: planned execution not recorded"
        rec = log[0]
        assert rec["name"] == "demo"
        assert rec["backend"] == backend      # the plan changed the kernel
        assert rec["dataflow"] == res.choices[0].dataflow.value
        assert rec["path_steps"] == res.choices[0].path.steps
        tol = 0 if backend == "jnp" else 1e-5
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=tol, atol=tol,
                                   err_msg=f"backend {backend}")


def test_planned_execution_under_jit_and_3d_batch():
    spec, _, _, plan = _unit_problem()
    params = linear_init(jax.random.PRNGKey(0), spec)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 16, spec.d_in))
    y_ref = linear_apply(spec, params, x)
    install_plan(plan.with_backend("tt_gemm"))
    y = jax.jit(lambda p, x: linear_apply(spec, p, x))(params, x)
    assert y.shape == (2, 16, spec.d_out)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)


def test_explicit_path_index_overrides_plan():
    spec, tn, _, plan = _unit_problem()
    params = linear_init(jax.random.PRNGKey(0), spec)
    x = jax.random.normal(jax.random.PRNGKey(3), (32, spec.d_in))
    install_plan(plan.with_backend("streaming_tt"))
    reset_execution_log()
    y = linear_apply(spec, params, x, path_index=0)
    assert execution_log() == ()  # explicit index bypasses the plan
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(linear_apply(spec, params, x,
                                                       path_index=0)))


def test_legacy_dict_install_still_works():
    spec, _, _, _ = _unit_problem()
    install_plan({"demo": 1})
    assert planned_path_index("demo") == 1
    lp = planned_layer("demo")
    assert lp is not None and lp.backend == "jnp" and lp.path_steps == ()
    params = linear_init(jax.random.PRNGKey(0), spec)
    x = jax.random.normal(jax.random.PRNGKey(4), (16, spec.d_in))
    y = linear_apply(spec, params, x)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(linear_apply(spec, params, x, path_index=1)),
        rtol=0, atol=0)


def test_model_prefill_planned_matches_unplanned():
    """Whole-model numerics: a planned smoke LM prefill == unplanned."""
    from repro.configs import get_config
    from repro.dse_cli import run_dse_plan
    from repro.models import api

    cfg = get_config("tt-lm-100m", smoke=True)
    m = api(cfg)
    params = m.init_params(jax.random.PRNGKey(0))
    batch = {"tokens": jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab, size=(2, 8)), jnp.int32)}
    logits_ref, _ = m.prefill(params, batch, 8)

    _, plan = run_dse_plan("tt-lm-100m", smoke=True, top_k=2, tokens=16)
    reset_execution_log()
    m_planned = api(cfg, plan=plan)
    logits, _ = m_planned.prefill(params, batch, 8)
    assert len(execution_log()) > 0  # planned kernels actually ran
    np.testing.assert_allclose(np.asarray(logits), np.asarray(logits_ref),
                               rtol=2e-4, atol=2e-4)


def test_batch_dim_and_rebatch_handle_conv_networks():
    """The batch edge is the input node's free edge — trailing 'l' for
    conv networks, not dims[0] (which is an input-channel mode)."""
    from repro.core.tensor_network import tt_conv_network
    from repro.plan import batch_dim
    from repro.plan.compiler import rebatch

    tn = tt_conv_network(patches=64, in_modes=(4, 8), out_modes=(8, 4),
                         kernel=9, ranks=(4, 4, 4, 4))
    assert batch_dim(tn) == 64
    rb = rebatch(tn, 16)
    x = next(n for n in rb.nodes if n.kind == "input")
    assert x.dims[x.edges.index("l")] == 16     # batch rebinds
    assert x.dims[x.edges.index("i1")] == 4     # modes untouched


def test_validate_plan_catches_mismatches():
    from repro.configs import get_config
    from repro.plan import check_plan_for_config, validate_plan

    _, _, _, plan = _unit_problem()
    # wrong geometry: same name, but a d=3 network needs 6 steps, not 4
    tt3 = TTConfig(enabled=True, d=3, rank=8, min_dim=64)
    tn3 = LinearSpec("demo", 512, 512, tag="mlp", tt=tt3).network(32)
    problems = validate_plan(plan, [("demo", tn3)])
    assert problems and "contraction steps" in problems[0]
    # no name overlap at all
    problems = validate_plan(plan, [("other", tn3)])
    assert problems and "matches no tensorized projection" in problems[0]
    # driver guard: arch provenance + structure against a real config
    cfg = get_config("tt-lm-100m", smoke=True)
    problems = check_plan_for_config(plan, "tt-lm-100m", cfg)
    assert any("matches no tensorized projection" in p for p in problems)
    import dataclasses
    foreign = dataclasses.replace(plan, arch="glm4-9b")
    problems = check_plan_for_config(foreign, "tt-lm-100m", cfg)
    assert any("emitted for arch" in p for p in problems)
    # out-of-range step indices (right count, bogus values) are caught too
    _, tn, _, _ = _unit_problem()
    bad_lp = dataclasses.replace(
        plan.layers[0],
        path_steps=((9, 10),) + plan.layers[0].path_steps[1:])
    bad = dataclasses.replace(plan, layers=(bad_lp,))
    problems = validate_plan(bad, [("demo", tn)])
    assert problems and "step indices" in problems[0]
    # empty steps are only legitimate on jnp entries
    stepless = dataclasses.replace(
        plan, layers=(dataclasses.replace(
            plan.layers[0], path_steps=(), backend="streaming_tt"),))
    problems = validate_plan(stepless, [("demo", tn)])
    assert problems and "index-only" in problems[0]


def test_force_backend_rejected_on_stepless_entries():
    install_plan({"demo": 0}, force_backend="jnp")  # jnp is fine
    with pytest.raises(ValueError, match="path steps"):
        install_plan({"demo": 0}, force_backend="tt_gemm")


def test_api_plan_state_semantics():
    """api(cfg) leaves plan state untouched (internal dispatch safety);
    api(cfg, plan=None) explicitly clears; plan_backend needs a plan."""
    from repro.configs import get_config
    from repro.models import api

    cfg = get_config("tt-lm-100m", smoke=True)
    api(cfg, plan={"attn.wq": 1})
    assert planned_path_index("attn.wq") == 1
    api(cfg)  # plan omitted: the installed plan survives
    assert planned_path_index("attn.wq") == 1
    api(cfg, plan=None)  # explicit clear
    assert planned_layer("attn.wq") is None
    with pytest.raises(ValueError, match="plan_backend"):
        api(cfg, plan_backend="jnp")


def test_kernel_routing_restricted_to_single_device():
    """Planned kernels run locally only on a single-device mesh; with
    multi-device rules the dispatcher asks ``shard_decision`` for a
    shard_map route and takes the sharding-preserving jnp executor only
    when the mesh cannot take the problem (rules without a real mesh
    object here, so the decision declines — tests/test_shard_exec.py
    covers the accepting side)."""
    from repro.nn.linear import _single_device
    from repro.plan.sharded import shard_decision
    from repro.sharding import ShardingRules, use_rules

    assert _single_device()
    with use_rules(ShardingRules(axis_sizes={"data": 1, "model": 1})):
        assert _single_device()
    with use_rules(ShardingRules(axis_sizes={"data": 2, "model": 1})):
        assert not _single_device()
        from repro.sharding import get_rules

        # no mesh object installed -> no shard route -> jnp fallback
        assert shard_decision(get_rules(), 64, (8, 8)) is None


def test_tiling_clamped_to_runtime_shapes():
    from repro.kernels.ops import clamp_block

    assert clamp_block(256, 4) == 8      # decode-step batch: one tiny block
    assert clamp_block(256, 100) == 128  # next pow2 >= dim
    assert clamp_block(64, 1000) == 64   # plan block already smaller

    # behavioural: a plan compiled at 32 tokens executes correctly (and
    # without inflating to the plan block) on an 8-token batch
    spec, _, _, plan = _unit_problem(tokens=32)
    params = linear_init(jax.random.PRNGKey(0), spec)
    x = jax.random.normal(jax.random.PRNGKey(5), (8, spec.d_in))
    y_ref = linear_apply(spec, params, x)
    for backend in ("streaming_tt", "tt_gemm"):
        install_plan(plan.with_backend(backend))
        y = linear_apply(spec, params, x)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-5, atol=1e-5,
                                   err_msg=f"backend {backend}")
    install_plan(None)


# ---------------------------------------------------------------------------
# end-to-end: emit-plan CLI -> serve --plan (the acceptance loop)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_emit_plan_then_serve_subprocess(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    # a forced host-device count would make serve build a multi-device
    # mesh — this test wants the single-device kernel route
    env.pop("XLA_FLAGS", None)
    plan_path = str(tmp_path / "plan.json")
    res = subprocess.run(
        [sys.executable, "-m", "repro.dse", "--arch", "tt-lm-100m", "--smoke",
         "--top-k", "2", "--tokens", "32", "--emit-plan", plan_path,
         "--out", str(tmp_path / "report.json")],
        env=env, capture_output=True, text=True, timeout=560,
    )
    assert res.returncode == 0, res.stderr[-2000:]
    plan = load_plan(plan_path)
    assert len(plan.layers) > 0

    res = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch", "tt-lm-100m",
         "--smoke", "--plan", plan_path, "--batch", "2", "--prompt-len", "8",
         "--gen", "2"],
        env=env, capture_output=True, text=True, timeout=560,
    )
    assert res.returncode == 0, res.stderr[-2000:]
    assert "installed plan" in res.stdout
    assert "planned executions" in res.stdout
    # the log line proves non-jnp kernels were selected by the plan
    assert "streaming_tt" in res.stdout or "tt_gemm" in res.stdout
