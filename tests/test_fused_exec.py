"""Fusion-aware path execution: kernel, segmentation, costing, tuning.

Covers the fused-segment PR's acceptance criteria: (1) fused-segment
execution is *bit-identical* to the per-step ``tt_gemm`` route
(property-tested over random modes/ranks/paths/segmentations), and a
sharded variant through ``plan/sharded.py`` agrees with the solo call to
fp32 rounding; (2) a DSE run exists where
fused-aware costing flips the chosen path vs spill-always costing;
(3) the ``segments`` schema field round-trips and is absent-on-wire
backward compatible; (4) the execution-log ring stays bounded; (5) the
WS/IS fp32-accumulation fix pins their bf16 results to OS; (6) the
backward-path cache is keyed on a stable pow2 token bucket.
"""

import dataclasses
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import FPGA_VU9P, fusion
from repro.core.contraction import core_tensors, execute_path
from repro.core.cost_table import build_cost_tables, fused_cost_tables
from repro.core.dse import global_search
from repro.core.paths import find_topk_paths
from repro.core.simulator import (
    Dataflow,
    fused_layer_latency,
    gemm_latency,
)
from repro.core.tensor_network import tt_linear_network
from repro.kernels import ops
from repro.plan import (
    LayerPlan,
    Tiling,
    choose_segments,
    execution_log,
    execution_log_dropped,
    load_plan,
    reset_execution_log,
)
from repro.plan import executor as plan_executor
from repro.plan.executor import planned_tt_linear

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

#: (in_modes, out_modes, ranks) draws for the property tests — kept
#: small so interpret-mode Pallas stays fast, but spanning d=2/d=3,
#: rank-1 boundary edges, and non-square mode products
PROBLEMS = (
    ((4, 8), (8, 4), (1, 4, 1)),
    ((8, 8), (8, 8), (4, 8, 4)),
    ((3, 5), (5, 3), (1, 3, 1)),
    ((3, 5, 2), (2, 5, 3), (2, 3, 4, 3, 2)),
    ((10, 6), (6, 10), (1, 6, 1)),
)


@pytest.fixture(autouse=True)
def _clean_log():
    reset_execution_log()
    yield
    reset_execution_log()


def _layer_inputs(tn, in_modes, tokens, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(
        rng.standard_normal((tokens, int(np.prod(in_modes)))), dtype)
    cores = [jnp.asarray(rng.standard_normal(n.dims), dtype)
             for n in tn.nodes if n.kind != "input"]
    return x, cores


def _layer_plan(steps, tiling, dataflow="OS", segments=None):
    return LayerPlan(
        name="l", path_index=0,
        path_steps=tuple(tuple(s) for s in steps),
        dataflow=dataflow, partitioning=(1, 1), backend="tt_gemm",
        tiling=tiling, segments=segments)


# ---------------------------------------------------------------------------
# property: fused-segment execution == per-step route, bit for bit
# ---------------------------------------------------------------------------

@given(
    prob=st.sampled_from(PROBLEMS),
    tokens=st.sampled_from((12, 64, 100)),
    path_idx=st.integers(0, 3),
    block_tokens=st.sampled_from((8, 32, 64)),
    budget_kib=st.sampled_from((2, 64, 8192)),
    dataflow=st.sampled_from(("OS", "WS", "IS")),
)
@settings(max_examples=12, deadline=None)
def test_fused_execution_bit_identical_property(
        prob, tokens, path_idx, block_tokens, budget_kib, dataflow):
    in_modes, out_modes, ranks = prob
    tn = tt_linear_network(tokens, in_modes, out_modes, ranks)
    paths = find_topk_paths(tn, k=4)
    steps = tuple(tuple(s) for s in paths[min(path_idx, len(paths) - 1)].steps)
    # random segmentation: the VMEM budget draw varies how much fuses
    segs = fusion.segment_path(tn, steps, block_tokens=block_tokens,
                               budget_bytes=budget_kib * 1024)
    tiling = Tiling(block_tokens=block_tokens)
    x, cores = _layer_inputs(tn, in_modes, tokens)
    y_plain = planned_tt_linear(_layer_plan(steps, tiling, dataflow),
                                x, cores, in_modes, out_modes, ranks,
                                interpret=True)
    reset_execution_log()
    y_seg = planned_tt_linear(_layer_plan(steps, tiling, dataflow, segs),
                              x, cores, in_modes, out_modes, ranks,
                              interpret=True)
    a, b = np.asarray(y_plain), np.asarray(y_seg)
    assert np.array_equal(a.view(np.uint32), b.view(np.uint32)), (
        prob, tokens, path_idx, block_tokens, budget_kib, dataflow, segs)
    seg_recs = [r for r in execution_log() if "segment" in r]
    if fusion.has_fused(segs):
        assert len(seg_recs) == len(segs)
        assert [tuple(r["segment"]) for r in seg_recs] == list(segs)
    else:
        # all-singleton segmentations take the plain per-step route
        assert seg_recs == []


def test_fused_kernel_matches_per_step_contract_directly():
    """Kernel-level check, no plan machinery: ops.fused_segment returns
    exactly what the sequential gemm_contract steps would have."""
    tokens, in_modes, out_modes, ranks = 64, (8, 8), (8, 8), (4, 8, 4)
    tn = tt_linear_network(tokens, in_modes, out_modes, ranks)
    steps = tuple(tuple(s) for s in find_topk_paths(tn, k=1)[0].steps)
    segs = fusion.segment_path(tn, steps, block_tokens=64,
                               budget_bytes=8 * 2**20)
    assert fusion.has_fused(segs)
    x, cores = _layer_inputs(tn, in_modes, tokens)
    tensors = {"X": x.reshape((tokens,) + tuple(in_modes))}
    tensors.update(core_tensors(tn, cores))
    out_edges = ("b",) + tuple(f"i{t + 1}" for t in range(len(out_modes)))
    (s, e) = next(seg for seg in segs if seg[1] - seg[0] >= 2)
    work = [(n.edges, tensors[n.name]) for n in tn.nodes]
    # only check a leading fused run (s == 0 keeps indices literal)
    assert s == 0
    ec, val = ops.fused_segment(work, steps[s:e], block_tokens=64,
                                interpret=True)
    assert val.dtype == jnp.float32
    assert set(ec) <= {"b"} | {edge for n in tn.nodes for edge in n.edges}
    # sequential per-step reference over the same work list
    contract = ops.gemm_contract(dataflow="OS", interpret=True)
    w = list(work)
    for i, j in steps[s:e]:
        (ea, ta), (eb, tb) = w[i], w[j]
        shared = [x for x in ea if x in eb]
        seq = contract(ta, tb, (tuple(ea.index(x) for x in shared),
                                tuple(eb.index(x) for x in shared)))
        ecs = tuple(x for x in ea if x not in shared) + tuple(
            x for x in eb if x not in shared)
        w = [q for t, q in enumerate(w) if t not in (i, j)]
        w.append((ecs, seq))
    ec_ref, val_ref = w[-1]
    a, b = np.asarray(val), np.asarray(val_ref)
    if ec != ec_ref:
        b = np.transpose(b, [ec_ref.index(x) for x in ec])
    assert np.array_equal(a.view(np.uint32), b.view(np.uint32))


@pytest.mark.parametrize("prob", PROBLEMS)
def test_token_minor_segment_matches_per_step(prob):
    """The TPU lowering of a fused segment (tokens on the lane axis, one
    2-d dot per step) agrees with the per-step route to fp32 rounding."""
    from repro.kernels.fused_path import token_minor_segment

    in_modes, out_modes, ranks = prob
    tokens = 100
    tn = tt_linear_network(tokens, in_modes, out_modes, ranks)
    steps = tuple(tuple(s) for s in find_topk_paths(tn, k=1)[0].steps)
    segs = fusion.segment_path(tn, steps, block_tokens=128,
                               budget_bytes=8 * 2**20)
    x, cores = _layer_inputs(tn, in_modes, tokens)
    tensors = {"X": x.reshape((tokens,) + tuple(in_modes))}
    tensors.update(core_tensors(tn, cores))
    w = [(n.edges, tensors[n.name]) for n in tn.nodes]
    contract = ops.gemm_contract(dataflow="OS", interpret=True)
    for s, e in segs:
        if e - s >= 2:
            ec, val = token_minor_segment(w, steps[s:e], block_tokens=128,
                                          interpret=True)
        for i, j in steps[s:e]:
            (ea, ta), (eb, tb) = w[i], w[j]
            shared = [x for x in ea if x in eb]
            seq = contract(ta, tb, (tuple(ea.index(x) for x in shared),
                                    tuple(eb.index(x) for x in shared)))
            ecs = tuple(x for x in ea if x not in shared) + tuple(
                x for x in eb if x not in shared)
            w = [q for t, q in enumerate(w) if t not in (i, j)]
            w.append((ecs, seq))
        if e - s >= 2:
            assert ec == w[-1][0]
            a, b = np.asarray(val), np.asarray(w[-1][1])
            assert np.max(np.abs(a - b)) <= 1e-5 * np.max(np.abs(b))


# ---------------------------------------------------------------------------
# sharded variant: fused routing inside the shard_map body
# ---------------------------------------------------------------------------

_SHARD_HARNESS = r"""
import jax
assert jax.device_count() == 4, jax.device_count()
import jax.numpy as jnp
import numpy as np
from repro.core import fusion
from repro.core.paths import find_topk_paths
from repro.core.tensor_network import tt_linear_network
from repro.plan import LayerPlan, Tiling, execution_log, reset_execution_log
from repro.plan.executor import planned_tt_linear
from repro.plan.sharded import shard_decision, sharded_tt_linear
from repro.sharding import ShardingRules

FP32_RTOL = 1e-5
tokens, in_modes, out_modes, ranks = 64, (8, 8), (8, 8), (4, 8, 4)
tn = tt_linear_network(tokens, in_modes, out_modes, ranks)
steps = tuple(tuple(s) for s in find_topk_paths(tn, k=1)[0].steps)
segs = fusion.segment_path(tn, steps, block_tokens=16,
                           budget_bytes=8 * 2**20)
assert fusion.has_fused(segs), segs
lp = LayerPlan(name="l", path_index=0, path_steps=steps, dataflow="OS",
               partitioning=(1, 1), backend="tt_gemm",
               tiling=Tiling(block_tokens=16), segments=segs)
rng = np.random.default_rng(0)
x = jnp.asarray(rng.standard_normal((tokens, 64)), jnp.float32)
cores = [jnp.asarray(rng.standard_normal(n.dims), jnp.float32)
         for n in tn.nodes if n.kind != "input"]

mesh = jax.sharding.Mesh(np.array(jax.devices()), ("data",))
rules = ShardingRules(axis_sizes={"data": 4}, mesh=mesh)
dec = shard_decision(rules, tokens, (8, 8))
assert dec is not None and dec.n_shards == 4, dec

y_solo = planned_tt_linear(lp, x, cores, in_modes, out_modes, ranks,
                           interpret=True)
reset_execution_log()
y_shard = sharded_tt_linear(lp, x, cores, in_modes, out_modes, ranks,
                            rules=rules, decision=dec, interpret=True)
a, b = np.asarray(y_solo), np.asarray(y_shard)
# per-shard calls contract fewer rows; XLA tiles a dot by its shape, so
# the sum order (not the math) may differ from the solo call
err = float(np.max(np.abs(a - b)) / np.max(np.abs(a)))
assert err <= FP32_RTOL, err
recs = [r for r in execution_log() if "segment" in r]
assert recs and all(r["mesh"] == "data=4" for r in recs), recs
assert all(tuple(r["shard_shape"]) == (16, 64) for r in recs), recs
print("PASS")
"""


@pytest.mark.slow
def test_sharded_fused_execution_bit_identical():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", _SHARD_HARNESS],
                          capture_output=True, text=True, timeout=600,
                          env=env)
    assert proc.returncode == 0 and "PASS" in proc.stdout, (
        f"harness failed (rc={proc.returncode})\n{proc.stdout}\n"
        f"{proc.stderr[-4000:]}")


# ---------------------------------------------------------------------------
# WS/IS fp32 accumulation (satellite): bf16 results pinned to OS
# ---------------------------------------------------------------------------

@given(
    shape=st.sampled_from(((64, 256, 48), (100, 512, 33), (16, 640, 8))),
    dataflow=st.sampled_from(("WS", "IS")),
)
@settings(max_examples=6, deadline=None)
def test_ws_is_bf16_accumulation_matches_os(shape, dataflow):
    M, K, N = shape
    rng = np.random.default_rng(M * 31 + N)
    a = jnp.asarray(rng.standard_normal((M, K)), jnp.bfloat16)
    b = jnp.asarray(rng.standard_normal((K, N)), jnp.bfloat16)
    ref = ops.gemm(a, b, dataflow="OS", interpret=True)
    out = ops.gemm(a, b, dataflow=dataflow, interpret=True)
    assert out.dtype == ref.dtype == jnp.bfloat16
    # cross-k partials accumulate in fp32 in every dataflow, so the
    # rounded bf16 outputs agree exactly — K large enough that output-
    # dtype accumulation would visibly drift
    assert np.array_equal(np.asarray(out, np.float32),
                          np.asarray(ref, np.float32)), (shape, dataflow)


# ---------------------------------------------------------------------------
# DSE: fused-aware costing flips the chosen path
# ---------------------------------------------------------------------------

def test_fused_costing_flips_chosen_path():
    hw = dataclasses.replace(FPGA_VU9P, name="hi_overhead",
                             gemm_overhead_cycles=200000)
    tn = tt_linear_network(256, (16, 16), (16, 16), (16, 8, 16))
    layer_paths = [find_topk_paths(tn, k=6)]
    base = build_cost_tables(layer_paths, hw,
                             ((1, 1), (2, 1), (1, 2), (2, 2)))
    fused = fused_cost_tables(layer_paths, [tn], hw, block_tokens=256,
                              budget_bytes=8 * 2**20, base=base)
    spill = global_search(layer_paths, hw, table=base.seconds).choices[0]
    aware = global_search(layer_paths, hw, table=fused.seconds).choices[0]
    # per-launch overhead dominates: fused chain runs pay ONE overhead,
    # so a monolithic fuseable path beats the split spill-always winner
    assert aware.path_index != spill.path_index, (spill, aware)
    assert aware.partitioning == (1, 1)
    # the fused table only discounts, never inflates
    assert all(fused.seconds[k] <= base.seconds[k] + 1e-12
               for k in base.seconds)


def test_fused_cost_tables_zero_interior_traffic():
    tn = tt_linear_network(64, (8, 8), (8, 8), (4, 8, 4))
    paths = find_topk_paths(tn, k=1)
    steps = tuple(tuple(s) for s in paths[0].steps)
    segs = fusion.segment_path(tn, steps, block_tokens=64,
                               budget_bytes=8 * 2**20)
    assert fusion.has_fused(segs)
    roles = fusion.step_roles(len(tn.nodes), steps, segs)
    rep = fused_layer_latency(paths[0], Dataflow.OS, FPGA_VU9P, segs, roles)
    spill = sum(
        gemm_latency(g, Dataflow.OS, FPGA_VU9P).traffic_words
        for g in paths[0].gemms)
    assert rep.traffic_words < spill
    # every interior output and chain operand of a fused run is VMEM-
    # resident: at least one step must have been zero-charged
    zeroed = [r for r in roles
              if r.interior_output or r.chain_operand is not None]
    assert zeroed


# ---------------------------------------------------------------------------
# schema: segments round-trip, absent-on-wire, validation
# ---------------------------------------------------------------------------

def _segmented_layer_plan():
    tn = tt_linear_network(64, (8, 8), (8, 8), (4, 8, 4))
    steps = tuple(tuple(s) for s in find_topk_paths(tn, k=1)[0].steps)
    tiling = Tiling(block_tokens=64)
    segs = choose_segments(tn, steps, tiling)
    assert segs is not None
    return _layer_plan(steps, tiling, segments=segs), tn


def test_segments_json_roundtrip(tmp_path):
    from repro.plan import ExecutionPlan

    lp, _ = _segmented_layer_plan()
    plan = ExecutionPlan(arch="unit", hw="fpga_vu9p", objective="latency",
                         strategy="split", tokens=64, layers=(lp,))
    p = tmp_path / "plan.json"
    plan.save(str(p))
    loaded = load_plan(str(p))
    assert loaded.layers[0].segments == lp.segments
    # absent-on-wire: stripping the key loads as unsegmented (old plans)
    d = json.loads(p.read_text())
    for layer in d["layers"]:
        layer.pop("segments", None)
    p2 = tmp_path / "old.json"
    p2.write_text(json.dumps(d))
    old = load_plan(str(p2))
    assert old.layers[0].segments is None
    assert old.layers[0].path_steps == lp.path_steps


def test_segments_dropped_on_backend_change():
    lp, _ = _segmented_layer_plan()
    assert lp.with_backend("jnp").segments is None
    assert lp.with_backend("tt_gemm").segments == lp.segments


def test_segments_validation_rejects_bad_cover():
    lp, _ = _segmented_layer_plan()
    n = len(lp.path_steps)
    with pytest.raises(ValueError):
        dataclasses.replace(lp, segments=((0, n - 1),))  # gap at the end
    with pytest.raises(ValueError):
        dataclasses.replace(lp, segments=((1, n), (0, 1)))  # not ascending
    with pytest.raises(ValueError):
        dataclasses.replace(lp, backend="jnp")  # segments need tt_gemm


def test_chain_problems_catches_invalid_fusion():
    _, tn = _segmented_layer_plan()
    steps = tuple(tuple(s) for s in find_topk_paths(tn, k=1)[0].steps)
    # a core-core step can never open a fused chain run
    core_steps = [
        t for t, (i, j) in enumerate(steps)
        if t == 0 and "b" not in tn.nodes[i].edges
        and "b" not in tn.nodes[j].edges
    ]
    if core_steps:
        bad = ((0, len(steps)),)
        assert fusion.chain_problems(tn, steps, bad)


# ---------------------------------------------------------------------------
# execution-log ring (satellite): bounded with a dropped counter
# ---------------------------------------------------------------------------

def test_execution_log_ring_bounded(monkeypatch):
    monkeypatch.setattr(plan_executor, "_EXEC_LOG_MAX", 16)
    lp, _ = _segmented_layer_plan()
    for _ in range(20):
        plan_executor.record_execution(lp, 64)
    log = execution_log()
    assert len(log) == 16
    assert execution_log_dropped() == 4
    reset_execution_log()
    assert list(execution_log()) == [] and execution_log_dropped() == 0


def test_segment_records_carry_range():
    lp, _ = _segmented_layer_plan()
    plan_executor.record_execution(lp, 64, segment=(0, 2))
    (rec,) = execution_log()
    assert rec["segment"] == [0, 2]
    assert rec["tiling"]["block_m"] == lp.tiling.block_m  # serve.py reads it


# ---------------------------------------------------------------------------
# backward-path cache (satellite): pow2 token bucket, stable + capped
# ---------------------------------------------------------------------------

def test_bwd_token_bucket_stability():
    bucket = plan_executor._bwd_token_bucket
    assert bucket(1) == 1 and bucket(2) == 2 and bucket(3) == 4
    assert bucket(65) == bucket(100) == bucket(128) == 128
    im, om, rk = (8, 8), (8, 8), (4, 8, 4)
    steps = {
        t: plan_executor._default_bwd_steps(bucket(t), im, om, rk)
        for t in (65, 100, 127, 128)
    }
    # one bucket -> one cache entry -> identical backward paths
    assert len({id(v) for v in steps.values()}) == 1
    assert plan_executor._default_bwd_steps.cache_info().maxsize == 256


# ---------------------------------------------------------------------------
# autotuner: fused vs per-step sweep (injected measurements)
# ---------------------------------------------------------------------------

def test_tune_fused_sweep_and_cache_replay():
    from repro.tune import Autotuner, TuningCache

    tn = tt_linear_network(64, (8, 8), (8, 8), (4, 8, 4))
    steps = tuple(tuple(s) for s in find_topk_paths(tn, k=1)[0].steps)
    segs = fusion.segment_path(tn, steps, block_tokens=64,
                               budget_bytes=8 * 2**20)
    assert fusion.has_fused(segs)
    calls = []

    def fake_fused(tn_, steps_, segs_, bt, **kw):
        calls.append(("fused", bt))
        return 1.0 / bt  # larger blocks measure faster

    def fake_per_step(tn_, steps_, **kw):
        calls.append(("per_step", None))
        return 1.0

    def make(cache):
        return Autotuner(cache, "cache", device_kind="test", interpret=True,
                         kernel_fp="deadbeef", measure_fused_fn=fake_fused,
                         measure_per_step_fn=fake_per_step)

    cache = TuningCache()
    tuner = make(cache)
    res = tuner.tune_fused(tn, steps, segs, 64, include=(64,))
    assert res is not None
    # the only lane-legal block for 64 tokens is one 128-token block
    assert res["block_tokens"] == 128
    assert res["per_step_s"] == 1.0 and res["fused_s"] == 1.0 / 128
    assert tuner.n_measured == len(calls) > 0
    # warm replay: a fresh tuner over the same cache measures nothing
    n_calls = len(calls)
    tuner2 = make(cache)
    res2 = tuner2.tune_fused(tn, steps, segs, 64, include=(64,))
    assert res2 == res
    assert tuner2.n_measured == 0 and len(calls) == n_calls


def test_fused_token_variants_preserve_segmentation():
    from repro.tune import fused_token_variants

    tn = tt_linear_network(64, (8, 8), (8, 8), (4, 8, 4))
    steps = tuple(tuple(s) for s in find_topk_paths(tn, k=1)[0].steps)
    segs = fusion.segment_path(tn, steps, block_tokens=64,
                               budget_bytes=8 * 2**20)
    variants = fused_token_variants(tn, steps, segs, 64, include=(64,))
    assert variants, "heuristic block must be feasible"
    for bt in variants:
        assert fusion.segment_path(tn, steps, block_tokens=bt,
                                   budget_bytes=8 * 2**20) == segs


# ---------------------------------------------------------------------------
# dse_cli: --fused-cost report section + compatibility gauntlet
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_run_dse_fused_cost_report():
    from repro.dse_cli import run_dse

    report = run_dse("tt-lm-100m", smoke=True, tokens=64, fused_cost=True)
    fc = report["fused_cost"]
    assert fc["enabled"] and fc["n_fused_cells"] > 0
    assert fc["n_fused_layers"] > 0
    assert fc["block_tokens"] == 128  # one lane-legal block covers 64
    # spill-always runs keep the section absent-but-present (None)
    base = run_dse("tt-lm-100m", smoke=True, tokens=64)
    assert base["fused_cost"] is None


def test_run_dse_fused_cost_rejects_incompatible_modes():
    from repro.dse_cli import run_dse

    for kw in ({"mode": "train"}, {"objective": "throughput"},
               {"engine": "scalar"}, {"hw_search": "budget"},
               {"search": "guided"}, {"rank_search": "budget"},
               {"mode": "both"}):
        with pytest.raises(ValueError):
            run_dse("tt-lm-100m", smoke=True, tokens=64, fused_cost=True,
                    **kw)


# ---------------------------------------------------------------------------
# compiler: emitted tt_gemm plans carry segments that validate
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_emitted_plan_carries_valid_segments():
    from repro.configs import get_config
    from repro.plan.compiler import check_plan_for_config

    from repro.dse_cli import run_dse_plan

    _, plan = run_dse_plan("tt-lm-100m", smoke=True, tokens=64,
                           plan_backend="tt_gemm", top_k=2)
    segged = [lp for lp in plan.layers if lp.segments is not None]
    assert segged, "expected at least one segmented tt_gemm layer"
    for lp in segged:
        assert any(e - s >= 2 for s, e in lp.segments)
    cfg = get_config("tt-lm-100m", tt=True, smoke=True)
    assert check_plan_for_config(plan, "tt-lm-100m", cfg) == []
