"""Accuracy-aware rank search (repro.rank): candidate space, accuracy
proxy, joint frontier search, v4 plan embedding."""

import json

import numpy as np
import pytest

from repro.configs import get_config
from repro.hw import get_target
from repro.rank import (
    FamilyFactorization,
    RankCandidate,
    RankSpace,
    candidate_proxy,
    clip_ranks,
    rank_search,
    reconstruction_proxy,
    reference_weight,
    vision_rank_space,
)


# -- candidate space ---------------------------------------------------


def test_clip_ranks_full_rank_bound():
    # each cut clipped to min(rank, prod(left), prod(right))
    assert clip_ranks((4, 6, 6, 3), 1000) == (4, 18, 3)
    assert clip_ranks((4, 6, 6, 3), 8) == (4, 8, 3)
    assert clip_ranks((24,), 5) == ()          # d=1 per side, one mode total
    assert clip_ranks((24, 18), 5) == (5,)     # degenerate TT: one cut


def test_family_factorization_validates():
    with pytest.raises(ValueError, match="do not factor"):
        FamilyFactorization("f", 24, 18, (4, 7), (6, 3), (4, 8, 3))
    with pytest.raises(ValueError, match="interior ranks"):
        FamilyFactorization("f", 24, 18, (4, 6), (6, 3), (4,))
    f = FamilyFactorization("f", 24, 18, (4, 6), (6, 3), (4, 8, 3))
    assert f.triple == ((4, 6), (6, 3), (4, 8, 3))
    assert f.dense_params == 24 * 18
    # cores: 1*4*4 + 4*6*8 + 8*6*3 + 3*3*1
    assert f.n_params == 16 + 192 + 144 + 9


def test_rank_space_frozen_first_dedup_budget():
    fams = [("proj", 64, 64, 2, 1.0)]
    space = RankSpace(fams, base_d=2, base_rank=8)
    cands = space.candidates()
    assert cands[0].name == "frozen"
    assert cands[0].d == 2 and cands[0].rank == 8
    names = [c.name for c in cands]
    assert len(names) == len(set(names))
    # frozen's grid twin (d2_r8) must have been dedup'd away
    assert "d2_r8" not in names
    budget = space.param_budget_ratio * cands[0].n_params
    assert all(c.n_params <= budget for c in cands)
    # distinct factorization keys across the grid
    keys = [c._key() for c in cands]
    assert len(keys) == len(set(keys))


def test_rank_space_tight_budget_keeps_frozen():
    fams = [("proj", 64, 64, 1, 1.0)]
    space = RankSpace(fams, base_d=2, base_rank=4, param_budget_ratio=1.0)
    cands = space.candidates()
    assert cands[0].name == "frozen"
    assert all(c.n_params <= cands[0].n_params for c in cands)


def test_rank_space_from_config_matches_tt():
    cfg = get_config("tt-lm-100m", tt=True, smoke=True)
    space = RankSpace.from_config(cfg)
    assert space.base_d == cfg.tt.d
    assert space.base_rank == cfg.tt.rank
    frozen = space.frozen
    assert frozen.compression > 1.0
    assert {f.name for f in frozen.families} >= {"attn.wq", "attn.wk"}


def test_rank_space_rejects_dense_config():
    cfg = get_config("tt-lm-100m", tt=False, smoke=True)
    with pytest.raises(ValueError, match="no tensorized"):
        RankSpace.from_config(cfg)


def test_d1_candidate_is_plain_low_rank():
    fams = [("proj", 24, 18, 1, 1.0)]
    space = RankSpace(fams, base_d=2, base_rank=4, mode_counts=(1,),
                      ladder=(1.0,))
    cands = space.candidates()
    d1 = next(c for c in cands if c.d == 1)
    f = d1.families[0]
    assert f.out_modes == (24,) and f.in_modes == (18,)
    assert f.ranks == (4,)
    # A (24x4) + B (4x18) plus the boundary-rank layout
    assert f.n_params == 1 * 24 * 4 + 4 * 18 * 1


# -- accuracy proxy ----------------------------------------------------


def test_reference_weight_deterministic_and_frozen():
    w1 = reference_weight("attn.wq", 64, 48)
    w2 = reference_weight("attn.wq", 64, 48)
    assert w1 is w2                     # lru cached
    assert w1.shape == (64, 48) and w1.dtype == np.float32
    assert not w1.flags.writeable
    # distinct family names draw distinct spectra
    w3 = reference_weight("mlp.w1", 64, 48)
    assert not np.allclose(w1, w3)


def test_reconstruction_proxy_monotone_in_rank():
    errs = [reconstruction_proxy("attn.wq", 64, 64, (8, 8), (8, 8), r)
            for r in (1, 2, 4, 8, 16)]
    assert all(e >= 0 for e in errs)
    assert all(errs[i] >= errs[i + 1] - 1e-12 for i in range(len(errs) - 1))
    # determinism across calls
    assert errs[0] == reconstruction_proxy(
        "attn.wq", 64, 64, (8, 8), (8, 8), 1)


def test_candidate_proxy_weighting():
    good = FamilyFactorization("a", 64, 64, (64,), (64,), (64,))  # lossless
    bad = FamilyFactorization("b", 64, 64, (64,), (64,), (1,))
    cand = RankCandidate("x", 1, 1, (good, bad))
    base = candidate_proxy(cand)
    upweight_bad = candidate_proxy(cand, weights={"b": 100.0})
    downweight_bad = candidate_proxy(cand, weights={"b": 0.01})
    assert downweight_bad < base < upweight_bad


# -- joint search ------------------------------------------------------


def _small_space(cfg):
    return RankSpace.from_config(cfg, ladder=(0.5, 1.0), mode_counts=(1, 2))


def test_rank_search_smoke_frontier_and_chosen():
    cfg = get_config("tt-lm-100m", tt=True, smoke=True)
    res = rank_search("tt-lm-100m", get_target("fpga_vu9p"), top_k=2,
                      tokens=32, smoke=True, space=_small_space(cfg))
    assert res.evals[res.frozen].candidate.name == "frozen"
    assert res.evals[0].candidate.name == "frozen"
    assert res.frontier, "pareto frontier must be non-empty"
    chosen = res.chosen_eval
    # the chosen candidate respects the default cap (frozen's proxy)
    assert chosen.accuracy_proxy <= res.frozen_eval.accuracy_proxy + 1e-9
    # and is the fastest eligible one
    eligible = [e for e in res.evals
                if e.accuracy_proxy <= res.frozen_eval.accuracy_proxy + 1e-9]
    assert chosen.total_latency_s == min(e.total_latency_s for e in eligible)


def test_rank_search_accuracy_budget_infeasible():
    cfg = get_config("tt-lm-100m", tt=True, smoke=True)
    with pytest.raises(ValueError, match="infeasible"):
        rank_search("tt-lm-100m", get_target("fpga_vu9p"), top_k=2,
                    tokens=32, smoke=True, space=_small_space(cfg),
                    accuracy_budget=1e-9)
    with pytest.raises(ValueError, match="positive"):
        rank_search("tt-lm-100m", get_target("fpga_vu9p"),
                    accuracy_budget=-1.0)


def test_rank_search_frozen_matches_plain_dse():
    """The frozen candidate's joint-search leg must be bit-identical to
    an unsearched run — same tables, same argmin, same total latency."""
    from repro.dse_cli import run_dse

    cfg = get_config("tt-lm-100m", tt=True, smoke=True)
    space = RankSpace.from_config(cfg, ladder=(1.0,),
                                  mode_counts=(cfg.tt.d,))
    res = rank_search("tt-lm-100m", get_target("fpga_vu9p"), top_k=2,
                      tokens=32, smoke=True, space=space)
    report = run_dse("tt-lm-100m", "fpga_vu9p", top_k=2, tokens=32,
                     smoke=True)
    assert res.frozen_eval.total_latency_s == report["total_latency_s"]


# -- CLI plumbing ------------------------------------------------------


def test_run_dse_rank_search_report():
    from repro.dse_cli import run_dse

    report = run_dse("tt-lm-100m", "fpga_vu9p", top_k=2, tokens=32,
                     smoke=True, rank_search="budget")
    rs = report["rank_search"]
    assert rs["mode"] == "budget"
    assert rs["n_candidates"] >= 2
    assert rs["chosen"]["name"] in {c["name"] for c in rs["candidates"]}
    assert rs["plan_embeddable"] is True
    assert rs["chosen"]["families"], "chosen candidate must carry families"
    for fam in rs["chosen"]["families"]:
        assert set(fam) >= {"name", "out_modes", "in_modes", "ranks",
                            "accuracy_proxy"}
    assert report["total_latency_s"] == rs["chosen"]["total_latency_s"]


def test_cli_rejects_rank_pair_and_budget_without_rank():
    from repro.dse_cli import main

    with pytest.raises(SystemExit):
        main(["--arch", "tt-lm-100m", "--smoke", "--accuracy-budget", "0.5"])
    with pytest.raises(SystemExit):
        main(["--arch", "tt-lm-100m", "--smoke", "--rank-search", "budget",
              "--emit-plan-pair", "/tmp/x"])


# -- v4 plan embedding -------------------------------------------------


def test_emit_plan_v4_roundtrip(tmp_path):
    from repro.dse_cli import run_dse_plan
    from repro.plan import load_plan

    path = tmp_path / "p.json"
    _, emitted = run_dse_plan("tt-lm-100m", "fpga_vu9p", top_k=2, tokens=32,
                              smoke=True, rank_search="budget")
    path.write_text(emitted.dumps())
    raw = path.read_text()
    plan = load_plan(str(path))
    assert plan.version == 4
    facts = {lp.name: lp.factorization for lp in plan.layers}
    assert any(f is not None for f in facts.values())
    for f in facts.values():
        if f is not None:
            assert len(f.ranks) == len(f.out_modes) + len(f.in_modes) - 1
    # bit-stable round-trip
    assert json.dumps(plan.to_json(), indent=2, sort_keys=True) + "\n" == raw


def test_v3_plan_migrates_to_v4(tmp_path):
    from repro.dse_cli import run_dse_plan
    from repro.plan import load_plan

    _, emitted = run_dse_plan("tt-lm-100m", "fpga_vu9p", top_k=2, tokens=32,
                              smoke=True)
    j = json.loads(emitted.dumps())
    j["version"] = 3
    for layer in j["layers"]:
        layer.pop("factorization", None)
    p3 = tmp_path / "p3.json"
    p3.write_text(json.dumps(j, indent=2, sort_keys=True) + "\n")
    plan = load_plan(str(p3))
    assert plan.version == 4
    assert all(lp.factorization is None for lp in plan.layers)


def test_factorization_schema_validates():
    from repro.plan.schema import Factorization

    with pytest.raises(ValueError, match="interior ranks"):
        Factorization(out_modes=(4, 6), in_modes=(6, 3), ranks=(4,))
    with pytest.raises(ValueError, match="positive ints"):
        Factorization(out_modes=(4, 0), in_modes=(6,), ranks=(4, 4))
    f = Factorization(out_modes=(24,), in_modes=(18,), ranks=(4,),
                      accuracy_proxy=0.25)
    assert f.triple == ((24,), (18,), (4,))


# -- parameter shapes under a factorization ----------------------------


def test_linear_init_under_factorization():
    import jax

    from repro.nn.linear import LinearSpec, TTConfig, linear_apply, linear_init

    tt = TTConfig(enabled=True, d=2, rank=4)
    spec = LinearSpec("proj", d_in=512, d_out=1024, tag="mlp", tt=tt)
    pinned = spec.with_factorization((1024,), (512,), (6,))
    assert pinned.tensorized
    params = linear_init(jax.random.PRNGKey(0), pinned)
    shapes = sorted(v.shape for v in params.values())
    # degenerate TT: two cores (out then in mode), boundary ranks squeezed
    assert shapes == [(6, 512), (1024, 6)]
    x = jax.numpy.ones((2, 512))
    y = linear_apply(pinned, params, x)
    assert y.shape == (2, 1024)
    assert bool(jax.numpy.isfinite(y).all())


def test_plan_context_restores_factorizations(tmp_path):
    from repro.dse_cli import run_dse_plan
    from repro.nn import installed_factorizations, plan_context
    from repro.plan import load_plan

    path = tmp_path / "p.json"
    _, emitted = run_dse_plan("tt-lm-100m", "fpga_vu9p", top_k=2, tokens=32,
                              smoke=True, rank_search="budget")
    path.write_text(emitted.dumps())
    plan = load_plan(str(path))
    assert installed_factorizations() == {}
    with plan_context(plan):
        inner = installed_factorizations()
        assert inner  # the searched decomposition is live
    assert installed_factorizations() == {}


# -- serving pair consistency ------------------------------------------


def _fact_plan(ranks, phase):
    from repro.plan.schema import ExecutionPlan, Factorization, LayerPlan

    lp = LayerPlan(
        name="attn.wq", path_index=0, path_steps=((0, 1), (0, 1)),
        dataflow="OS",
        partitioning=(1, 1), backend="jnp",
        factorization=Factorization(out_modes=(128,), in_modes=(128,),
                                    ranks=(ranks,)))
    return ExecutionPlan(layers=(lp,), arch="tt-lm-100m", hw="fpga_vu9p",
                         strategy="split", phase=phase)


def test_serve_engine_rejects_inconsistent_factorization_pair():
    from repro.serve import ServeEngine

    cfg = get_config("tt-lm-100m", tt=True, smoke=True)
    with pytest.raises(ValueError, match="BOTH phases"):
        ServeEngine(cfg, None, n_slots=1, max_seq=16,
                    prefill_plan=_fact_plan(2, "prefill"))
    with pytest.raises(ValueError, match="different factorizations"):
        ServeEngine(cfg, None, n_slots=1, max_seq=16,
                    prefill_plan=_fact_plan(2, "prefill"),
                    decode_plan=_fact_plan(4, "decode"))


# -- vision ------------------------------------------------------------


def test_vision_rank_space():
    space = vision_rank_space("vit_ti4/cifar10", base_rank=8)
    cands = space.candidates()
    assert cands[0].name == "frozen"
    assert all(c.d == 2 or c.name == "frozen" for c in cands)
    ranks = {c.rank for c in cands}
    assert len(ranks) > 1
