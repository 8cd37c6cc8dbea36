"""Sharded planned execution: shard_map routing, provenance, equivalence.

Covers the sharded-execution PR:

1. ``shard_decision`` routes a planned projection onto the installed
   mesh exactly when the mesh can take the problem (real mesh object,
   token count divisible over the DP axes) — and declines otherwise, so
   the constrained jnp fallback is preserved;
2. ``PlanSharding`` provenance round-trips through plan JSON and is
   absent-on-wire for unsharded plans (no schema version bump);
3. ``repro.dse --shards N`` searches per-shard problems and stamps the
   emitted plan with the shard context;
4. the load-bearing equivalence (hypothesis, subprocess on a forced
   8-device host mesh): continuous serving with shard_map-routed Pallas
   kernels produces per-request token ids bit-identical to the
   single-device oneshot reference, with the execution log proving both
   streams ran Pallas backends at per-shard shapes (no silent jnp
   demotion).

The equivalence test forks a subprocess because device count is fixed at
jax init: the main pytest process runs single-device, the child forces
``--xla_force_host_platform_device_count=8``.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest
from hypothesis import given, settings, strategies as st

from repro.core.cost_table import shard_streamed_tokens
from repro.plan import ExecutionPlan, PlanSharding
from repro.plan.sharded import ShardDecision, shard_decision
from repro.sharding import ShardingRules

ARCH = "tt-lm-100m"


def _rules(axis_sizes, *, mesh="not-none", seq_axis=None, reduce=False):
    return ShardingRules(axis_sizes=dict(axis_sizes), mesh=mesh,
                         seq_axis=seq_axis, tt_model_reduce=reduce)


# ---------------------------------------------------------------------------
# shard_decision: routing policy
# ---------------------------------------------------------------------------

def test_no_rules_or_no_mesh_declines():
    assert shard_decision(None, 64, (8, 8)) is None
    rules = _rules({"data": 4, "model": 2}, mesh=None)
    assert shard_decision(rules, 64, (8, 8)) is None


def test_token_dp_decision():
    rules = _rules({"data": 4, "model": 2})
    d = shard_decision(rules, 64, (8, 8))
    assert d == ShardDecision(("data",), 4)
    assert d.describe(rules.axis_sizes, "model") == "data=4"
    # indivisible token count -> decline (shard_map needs exact blocks)
    assert shard_decision(rules, 3, (8, 8)) is None


def test_sp_axis_joins_token_shards():
    rules = _rules({"data": 4, "model": 2}, seq_axis="model")
    d = shard_decision(rules, 64, (8, 8))
    assert d is not None
    assert d.axes == ("data", "model") and d.n_shards == 8


def test_model_reduce_is_opt_in():
    # default: model axis unused, pure DP
    d = shard_decision(_rules({"data": 4, "model": 2}), 64, (8, 8))
    assert d is not None and not d.model_reduce
    # opted in: leading input mode splits over the model axis
    d = shard_decision(_rules({"data": 4, "model": 2}, reduce=True),
                       64, (8, 8))
    assert d is not None and d.model_reduce and d.tp == 2
    assert d.describe({"data": 4, "model": 2}, "model") == \
        "data=4+reduce(model=2)"
    # leading mode not divisible by tp -> reduction declined, DP kept
    d = shard_decision(_rules({"data": 4, "model": 2}, reduce=True),
                       64, (7, 8))
    assert d is not None and not d.model_reduce


def test_single_axis_mesh_replicated_model():
    rules = _rules({"data": 8, "model": 1})
    d = shard_decision(rules, 64, (8, 8))
    assert d is not None and d.axes == ("data",) and d.n_shards == 8


def test_shard_streamed_tokens():
    assert shard_streamed_tokens(1024, 1) == 1024
    assert shard_streamed_tokens(1024, 4) == 256
    assert shard_streamed_tokens(2, 4) == 1  # floor at one token


# ---------------------------------------------------------------------------
# PlanSharding provenance: round-trip, absent-on-wire
# ---------------------------------------------------------------------------

def test_plan_sharding_roundtrip():
    s = PlanSharding(n_shards=4, axes=(("data", 4),), tokens_per_shard=256)
    assert PlanSharding.from_json(s.to_json()) == s
    with pytest.raises(ValueError):
        PlanSharding(n_shards=0, axes=(), tokens_per_shard=1)


def test_plan_json_sharding_field(tmp_path):
    from repro.dse_cli import run_dse_plan

    _, plan = run_dse_plan(ARCH, smoke=True, top_k=2, tokens=64,
                           plan_backend="jnp")
    assert plan.sharding is None
    d = plan.to_json()
    assert d["sharding"] is None
    # absent-on-wire: a v4 plan without the key still loads (no bump)
    d2 = {k: v for k, v in d.items() if k != "sharding"}
    assert ExecutionPlan.from_json(d2).sharding is None

    _, sharded = run_dse_plan(ARCH, smoke=True, top_k=2, tokens=64,
                              plan_backend="jnp", shards=4)
    assert sharded.sharding == PlanSharding(
        n_shards=4, axes=(("data", 4),), tokens_per_shard=16)
    path = str(tmp_path / "p.json")
    sharded.save(path)
    from repro.plan import load_plan

    assert load_plan(path).sharding == sharded.sharding


def test_dse_report_carries_shard_context():
    from repro.dse_cli import run_dse

    report = run_dse(ARCH, smoke=True, top_k=2, tokens=64, shards=4)
    sh = report["sharding"]
    assert sh["n_shards"] == 4 and sh["axes"] == [["data", 4]]
    assert sh["tokens_per_shard"] == 16 and sh["global_tokens"] == 64
    # the searched problems are the per-shard ones
    assert report["tokens"] == 16
    # unsharded report keeps the null field
    assert run_dse(ARCH, smoke=True, top_k=2, tokens=64)["sharding"] is None


def test_rank_search_rejects_shards():
    from repro.dse_cli import run_dse

    with pytest.raises(ValueError, match="rank"):
        run_dse(ARCH, smoke=True, tokens=64, shards=4, rank_search="budget")


# ---------------------------------------------------------------------------
# the equivalence property (subprocess: forced 8-device host mesh)
# ---------------------------------------------------------------------------

_HARNESS = r"""
import json, sys
import jax

assert jax.device_count() == 8, jax.device_count()

import numpy as np
from repro.configs import get_config
from repro.dse_cli import run_dse_plan
from repro.launch.mesh import make_rules, make_test_mesh
from repro.models import api
from repro.models.config import ShapeConfig
from repro.plan import execution_log, reset_execution_log
from repro.serve import Request, Scheduler, ServeEngine, ServePolicy
from repro.sharding import use_rules

spec = json.loads(sys.argv[1])
ARCH, N_SLOTS, BUCKET, MAX_SEQ = "tt-lm-100m", 8, 8, 16
cfg = get_config(ARCH, smoke=True)

_, plan_p = run_dse_plan(ARCH, smoke=True, top_k=2, tokens=64,
                         plan_backend="streaming_tt", phase="prefill",
                         shards=4)
_, plan_d = run_dse_plan(ARCH, smoke=True, top_k=2, tokens=N_SLOTS,
                         plan_backend="streaming_tt", phase="decode",
                         shards=4)
assert plan_p.sharding is not None and plan_p.sharding.n_shards == 4

reqs = []
for i, (p, g) in enumerate(spec):
    rng = np.random.default_rng((0xBEEF, i))
    prompt = tuple(int(t) for t in rng.integers(0, cfg.vocab, size=p))
    reqs.append(Request(rid=i, prompt=prompt, max_new_tokens=g))

params = api(cfg).init_params(jax.random.PRNGKey(0))


def run(schedule, rules):
    reset_execution_log()
    eng = ServeEngine(cfg, params, n_slots=N_SLOTS, max_seq=MAX_SEQ,
                      prompt_bucket=BUCKET, prefill_plan=plan_p,
                      decode_plan=plan_d, arch=ARCH)
    sched = Scheduler(eng, ServePolicy(schedule=schedule))
    with use_rules(rules):
        res = sched.run(reqs)
    return res.tokens_by_rid(), execution_log()


mesh = make_test_mesh()
assert mesh is not None and mesh.devices.size == 8
shape = ShapeConfig("test", MAX_SEQ, N_SLOTS, "decode")
rules = make_rules(cfg, shape, mesh)
sharded_tokens, sharded_log = run("continuous", rules)
solo_tokens, solo_log = run("oneshot", None)

# the property: per-request tokens bit-identical across mesh widths
assert sharded_tokens == solo_tokens, (sharded_tokens, solo_tokens)

# both runs executed planned Pallas on every stream that ran — no
# silent jnp (a decode step runs only if some request wants > 1 token)
ran = {"prefill"} | ({"decode"} if any(g > 1 for _, g in spec) else set())
for tag, log in (("sharded", sharded_log), ("solo", solo_log)):
    assert log, tag
    streams = {r["stream"] for r in log}
    assert streams == ran, (tag, streams, ran)
    backends = {r["backend"] for r in log}
    assert backends == {"streaming_tt"}, (tag, backends)

# sharded records carry mesh provenance at per-shard shapes; solo none
for r in sharded_log:
    assert r["mesh"] == "data=4", r
    assert r["shard_shape"] is not None and r["shard_shape"][0] >= 1, r
    # the record was traced inside the shard_map body, so its token
    # count IS the per-shard problem size
    assert r["shard_shape"][0] == r["tokens"], r
for r in solo_log:
    assert r["mesh"] == "" and r["shard_shape"] is None, r

print("PASS")
"""


def _run_harness(spec: list) -> None:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = (
        os.path.join(os.path.dirname(__file__), os.pardir, "src")
        + os.pathsep + env.get("PYTHONPATH", ""))
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-c", _HARNESS, json.dumps(spec)],
        capture_output=True, text=True, timeout=1200, env=env)
    assert proc.returncode == 0 and "PASS" in proc.stdout, (
        f"harness failed (rc={proc.returncode})\n"
        f"--- stdout ---\n{proc.stdout}\n--- stderr ---\n{proc.stderr[-4000:]}")


@pytest.mark.slow
@given(raw=st.lists(st.integers(0, 10**9), min_size=2, max_size=8))
@settings(max_examples=2, deadline=None)
def test_sharded_continuous_matches_single_device_oneshot(raw):
    # (prompt_len 1..8, gen 1..4) per request — prompts bucket to 8, so
    # the prefill token count stays divisible over the data axis
    spec = [[1 + raw[2 * i] % 8, 1 + raw[2 * i + 1] % 4]
            for i in range(len(raw) // 2)]
    _run_harness(spec)
