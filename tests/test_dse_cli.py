"""`python -m repro.dse` CLI: report schema, objectives, module hook."""

import json
import os
import subprocess
import sys

import pytest

from repro.dse_cli import SearchSpec, main, model_dse_layers, run_dse
from repro.configs import get_config

REQUIRED_KEYS = {
    "arch", "hw", "objective", "top_k", "tokens", "strategy",
    "total_latency_s", "total_objective", "n_layers", "timings", "table",
    "layers",
}


def test_cli_smoke_json(capsys):
    assert main(["--arch", "tt-lm-100m", "--top-k", "2"]) == 0
    report = json.loads(capsys.readouterr().out)  # must be valid JSON
    assert REQUIRED_KEYS <= set(report)
    assert report["strategy"] in ("monolithic", "split")
    assert report["n_layers"] == len(report["layers"]) > 0
    assert report["total_latency_s"] > 0
    for layer in report["layers"]:
        assert layer["dataflow"] in ("IS", "OS", "WS")
        assert tuple(layer["partitioning"]) in ((1, 1), (1, 2), (2, 1))
        assert 0 <= layer["path_index"] < 2
        assert layer["latency_s"] > 0
    assert pytest.approx(report["total_latency_s"]) == sum(
        l["latency_s"] for l in report["layers"])


def test_cli_out_file(tmp_path, capsys):
    out = tmp_path / "report.json"
    assert main(["--arch", "tt-lm-100m", "--top-k", "2", "--tokens", "64",
                 "--out", str(out)]) == 0
    capsys.readouterr()
    report = json.loads(out.read_text())
    assert report["tokens"] == 64


def test_edp_objective_consistent():
    lat = run_dse("tt-lm-100m", top_k=2, tokens=128)
    edp = run_dse("tt-lm-100m", top_k=2, tokens=128, objective="edp")
    assert edp["total_objective"] <= edp["total_latency_s"] * 1  # joule-seconds, tiny
    # the EDP argmin can only match or exceed the latency argmin's latency
    assert edp["total_latency_s"] >= lat["total_latency_s"] - 1e-15


def test_tpu_target_and_vision_arch():
    r = run_dse("tt-lm-100m", hw="tpu_v5e", top_k=2, tokens=64)
    assert r["hw"] == "tpu_v5e" and r["total_latency_s"] > 0
    v = run_dse("vit_ti4/cifar10", top_k=2)
    assert v["n_layers"] > 0 and v["tokens"] == 1


def test_unknown_arch_and_hw_raise():
    with pytest.raises(KeyError):
        run_dse("no-such-model")
    # unknown --hw lists the registered choices in the error
    with pytest.raises(KeyError, match="fpga_vu9p"):
        run_dse("tt-lm-100m", hw="no-such-hw")


def test_list_hw_flag(capsys):
    assert main(["--list-hw"]) == 0
    out = capsys.readouterr().out.split()
    assert "fpga_vu9p" in out and "tpu_v5e" in out


def test_hw_search_report_and_guarantee():
    """--hw-search budget: >= 64 feasible candidates, co-searched optimum
    <= the fixed-target optimum, per-candidate rows sorted best-first."""
    r = run_dse("vit_ti4/cifar10", top_k=2, hw_search="budget")
    hs = r["hw_search"]
    assert hs["mode"] == "budget" and hs["n_candidates"] >= 64
    assert len(hs["candidates"]) == hs["n_candidates"]
    lats = [c["total_latency_s"] for c in hs["candidates"]]
    assert lats == sorted(lats)
    assert hs["chosen"]["total_latency_s"] == lats[0]
    assert hs["chosen"]["total_latency_s"] <= hs["fixed"]["total_latency_s"]
    assert r["total_latency_s"] == pytest.approx(
        hs["chosen"]["total_latency_s"], rel=1e-12)
    # the top-level label names the architecture the numbers describe
    assert r["hw_chosen"] == hs["chosen"]["name"]
    assert r["hw"] == "fpga_vu9p"  # the requested base target, unchanged
    # fixed-target run agrees with the space's row for the base target
    fixed = run_dse("vit_ti4/cifar10", top_k=2)
    assert fixed["hw_search"] is None
    assert fixed["total_latency_s"] == hs["fixed"]["total_latency_s"]


def test_hw_search_emit_plan_v3_tilings():
    """--hw-search --emit-plan embeds the winning architecture and caps
    the kernel tilings by its array shape."""
    from repro.dse_cli import run_dse_plan

    report, plan = run_dse_plan("tt-lm-100m", smoke=True, top_k=2,
                                tokens=32, hw_search="budget")
    assert plan.version == 4
    assert plan.hardware is not None
    assert plan.hardware.name == report["hw_search"]["chosen"]["name"]
    assert plan.hw == plan.hardware.name
    for lp in plan.layers:
        assert lp.tiling.block_m <= max(8, plan.hardware.pe_rows)
        assert lp.tiling.block_n <= max(8, plan.hardware.pe_cols)
        assert lp.tiling.block_k <= max(
            8, plan.hardware.pe_rows, plan.hardware.pe_cols)


def test_fixed_target_plan_keeps_default_tiling_caps():
    """Without --hw-search the cost-model target must NOT shrink the
    Pallas tiling caps: the FPGA model is not the execution substrate,
    and pre-existing fixed-target plans tiled for the 128-wide MXU."""
    from repro.dse_cli import run_dse_plan

    _, plan = run_dse_plan("tt-lm-100m", smoke=True, top_k=2, tokens=32)
    assert plan.hardware is not None and plan.hardware.pe_rows == 32
    caps = {max(lp.tiling.block_m, lp.tiling.block_k, lp.tiling.block_n)
            for lp in plan.layers}
    assert max(caps) > 32  # FPGA's 32x32 array did not cap the blocks


def test_hw_search_mode_both_flags_arch_divergence():
    """Each leg of --mode both co-searches its own architecture; the
    combined report names both winners and flags when they differ."""
    r = run_dse("vit_ti4/cifar10", top_k=2, mode="both", hw_search="budget")
    hs = r["hw_search"]
    assert hs["infer_chosen"] == r["infer"]["hw_search"]["chosen"]["name"]
    assert hs["train_chosen"] == r["train"]["hw_search"]["chosen"]["name"]
    assert hs["hw_divergent"] == (hs["infer_chosen"] != hs["train_chosen"])


# one case per SearchSpec rule, in the order the rules are checked
_REJECTED = [
    pytest.param(dict(objective="power"), KeyError, "objective",
                 id="objective"),
    pytest.param(dict(mode="no-such-mode"), KeyError, "mode", id="mode"),
    pytest.param(dict(arch="vit_ti4/cifar10", objective="throughput"),
                 ValueError, "vision", id="throughput-vision"),
    pytest.param(dict(objective="throughput", serve_slots=0), ValueError,
                 "serve_gen and serve_slots", id="throughput-slots"),
    pytest.param(dict(mode="train", objective="edp"), ValueError,
                 "train-latency", id="train-objective"),
    pytest.param(dict(hw_search="exhaustive"), KeyError, "hw_search",
                 id="hw-search"),
    pytest.param(dict(hw_search="budget", objective="edp"), ValueError,
                 "edp", id="hw-search-objective"),
    pytest.param(dict(search="random"), KeyError, "search", id="search"),
    pytest.param(dict(search="guided", objective="edp"), ValueError,
                 "guided", id="guided-objective"),
    pytest.param(dict(search_budget=10), ValueError, "search_budget",
                 id="search-budget"),
    pytest.param(dict(tune="always"), KeyError, "tune", id="tune"),
    # --mode train composes since the tiling lift (ROADMAP gap b); the
    # ambiguous --mode both combination is what's rejected
    pytest.param(dict(mode="both", tune="cache"), ValueError, "ambiguous",
                 id="tune-both"),
    pytest.param(dict(objective="edp", tune="cache"), ValueError,
                 "analytic-only", id="tune-objective"),
    pytest.param(dict(fused_cost=True, mode="train"), ValueError,
                 "fused-cost", id="fused-train"),
    pytest.param(dict(fused_cost=True, mode="both"), ValueError,
                 "fused-cost", id="fused-both"),
    pytest.param(dict(fused_cost=True, objective="throughput"), ValueError,
                 "fused-cost", id="fused-objective"),
    pytest.param(dict(fused_cost=True, hw_search="budget"), ValueError,
                 "fused-cost", id="fused-hw-search"),
    pytest.param(dict(fused_cost=True, search="guided"), ValueError,
                 "fused-cost", id="fused-guided"),
    pytest.param(dict(fused_cost=True, rank_search="budget"), ValueError,
                 "fused-cost", id="fused-rank"),
    pytest.param(dict(shards=0), ValueError, "shards", id="shards"),
    pytest.param(dict(rank_search="exhaustive"), KeyError, "rank_search",
                 id="rank-search"),
    pytest.param(dict(rank_search="budget", mode="train"), ValueError,
                 "rank", id="rank-train"),
    pytest.param(dict(rank_search="budget", objective="edp"), ValueError,
                 "rank", id="rank-objective"),
    pytest.param(dict(rank_search="budget", tune="measure"), ValueError,
                 "rank", id="rank-tune"),
    pytest.param(dict(rank_search="budget", shards=4), ValueError, "rank",
                 id="rank-shards"),
    pytest.param(dict(accuracy_budget=0.5), ValueError, "accuracy_budget",
                 id="accuracy-budget"),
    pytest.param(dict(hw_budget=4096), ValueError, "hw_budget",
                 id="hw-budget"),
    pytest.param(dict(tune_cache="cache.json"), ValueError, "tune_cache",
                 id="tune-cache"),
]


@pytest.mark.parametrize("kw, exc, match", _REJECTED)
def test_search_spec_rejects(kw, exc, match):
    """Each rule refuses its combination when the spec is built — before
    any search work — and so through ``run_dse`` too."""
    kw = {"arch": "tt-lm-100m", "top_k": 2, "tokens": 32, "smoke": True,
          **kw}
    with pytest.raises(exc, match=match):
        SearchSpec(**kw)
    with pytest.raises(exc, match=match):
        run_dse(**kw)


def test_model_dse_layers_covers_families():
    """Every config family enumerates at least its head projection when
    tensorized; tt-lm-100m covers attn+mlp+head."""
    cfg = get_config("tt-lm-100m")
    names = [n for n, _ in model_dse_layers(cfg, tokens=64)]
    assert any(n.startswith("attn.") for n in names)
    assert any(n.startswith("mlp.") for n in names)
    assert "head" in names


def test_mode_train_report_and_plan():
    """--mode train: decomposed per-layer latencies + a train-aware plan
    with backward entries."""
    from repro.dse_cli import run_dse_plan

    report, plan = run_dse_plan("tt-lm-100m", smoke=True, top_k=2, tokens=32,
                                mode="train")
    assert report["mode"] == "train"
    assert report["objective"] == "train-latency"
    for layer in report["layers"]:
        assert layer["latency_s"] == pytest.approx(
            layer["fwd_latency_s"] + layer["bwd_latency_s"]
            + layer["update_latency_s"], rel=1e-12)
        assert layer["bwd_latency_s"] > 0
        assert {b["wrt"] for b in layer["backward"]} >= {"dx"}
    assert report["total_latency_s"] == pytest.approx(
        report["total_fwd_latency_s"] + report["total_bwd_latency_s"]
        + report["total_update_latency_s"], rel=1e-12)
    assert plan.version == 4
    assert all(lp.backward for lp in plan.layers)
    assert plan.objective == "train-latency"


def test_mode_both_reports_divergence():
    r = run_dse("vit_ti4/cifar10", top_k=4, mode="both")
    assert r["mode"] == "both"
    assert r["infer"]["mode"] == "infer" and r["train"]["mode"] == "train"
    assert r["n_divergent_layers"] == len(r["divergent_layers"]) > 0
    named = {l["name"] for l in r["infer"]["layers"]}
    assert all(d["name"] in named for d in r["divergent_layers"])


def test_mode_and_backend_validation():
    from repro.dse_cli import run_dse_plan

    with pytest.raises(KeyError, match="mode"):
        run_dse_plan("tt-lm-100m", smoke=True, mode="no-such-mode")
    # early validation — before any search work happens
    with pytest.raises(ValueError, match="backend"):
        run_dse_plan("tt-lm-100m", smoke=True, plan_backend="cuda")


def test_api_rejects_unknown_plan_backend():
    """models.api(cfg, plan_backend=...) validates the backend up front."""
    from repro.models import api
    from repro.nn import install_plan

    cfg = get_config("tt-lm-100m", smoke=True)
    with pytest.raises(ValueError, match="plan_backend"):
        api(cfg, plan={"attn.wq": 0}, plan_backend="no-such-backend")
    with pytest.raises(ValueError, match="force_backend"):
        install_plan({"attn.wq": 0}, force_backend="no-such-backend")
    install_plan(None)


@pytest.mark.slow
def test_module_invocation_subprocess():
    """The documented entry point: PYTHONPATH=src python -m repro.dse ..."""
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.dse", "--arch", "tt-lm-100m",
         "--top-k", "2", "--tokens", "64"],
        capture_output=True, text=True, timeout=600, env=env,
    )
    assert proc.returncode == 0, proc.stderr
    report = json.loads(proc.stdout)
    assert report["arch"] == "tt-lm-100m"


# --- the plans the benchmark's chip cells run ------------------------------

_BENCH = os.path.join(os.path.dirname(__file__), "..", "bench")


def _cell_layers(mlp: str, head_segments, block_tokens: int) -> dict:
    """{layer: (backend, segments, tiling)}: the attention projections
    stream, the MLP runs on ``mlp``, the head on tt_gemm."""
    qo = (128, 128, 16, block_tokens)  # block_m, block_k, block_n, tokens
    gu = (128, 16, 128, block_tokens)
    return {
        "attn.wq": ("streaming_tt", None, qo),
        "attn.wo": ("streaming_tt", None, qo),
        "mlp.wg": (mlp, None, gu),
        "mlp.wu": (mlp, None, gu),
        "mlp.wd": (mlp, None, qo),
        "head": ("tt_gemm", head_segments, gu),
    }


_CHAT_HEAD = ((0, 1), (1, 2), (2, 5), (5, 6))


@pytest.mark.parametrize("config, traffic, phase, layers", [
    pytest.param("tt-lm-100m-bf16", "serve-chat", "prefill",
                 _cell_layers("streaming_tt", _CHAT_HEAD, 256),
                 id="serve-chat-prefill"),
    pytest.param("tt-lm-100m-bf16", "serve-chat", "decode",
                 _cell_layers("streaming_tt", _CHAT_HEAD, 256),
                 id="serve-chat-decode"),
    pytest.param("tt-lm-100m", "train-1k", "train",
                 _cell_layers("streaming_tt", None, 256),
                 id="train-1k"),
    pytest.param("chatglm3-6b-tt", "serve-docs", "prefill",
                 _cell_layers("tt_gemm",
                              ((0, 1), (1, 2), (2, 3), (3, 5), (5, 6)),
                              256), id="serve-docs-prefill"),
    pytest.param("chatglm3-6b-tt", "serve-docs", "decode",
                 _cell_layers("streaming_tt", _CHAT_HEAD, 128),
                 id="serve-docs-decode"),
])
def test_bench_cell_plans(config, traffic, phase, layers):
    """Each chip cell's plan, searched with the cell's own arguments as
    the benchmark's set-up searches it: per-layer kernel backend, fused
    segments and tiling.  A change here changes what the chip runs."""
    from repro.dse_cli import run_dse_plan

    with open(os.path.join(_BENCH, "configs", f"{config}.json")) as f:
        arch = json.load(f)["arch"]
    with open(os.path.join(_BENCH, "traffic", f"{traffic}.json")) as f:
        tr = json.load(f)
    if phase == "train":
        _, plan = run_dse_plan(arch, hw=tr["plan"]["hw"], mode="train",
                               smoke=False, tokens=tr["batch"] * tr["seq"])
    else:
        tokens = (tr["plan"]["prefill_tokens"] if phase == "prefill"
                  else tr["n_slots"])
        _, plan = run_dse_plan(arch, hw=tr["plan"]["hw"], phase=phase,
                               smoke=False, tokens=tokens,
                               serve_slots=tr["n_slots"],
                               serve_gen=tr["plan"]["serve_gen"])
    got = {lp.name: (lp.backend, lp.segments,
                     tuple(lp.tiling.to_json().values()))
           for lp in plan.layers}
    assert got == layers
    if phase == "train":
        # the input gradient streams like the forward pass; every weight
        # gradient (and the head's input gradient) runs on tt_gemm
        for lp in plan.layers:
            want = {b.wrt: "tt_gemm" for b in lp.backward}
            if lp.backend == "streaming_tt":
                want["dx"] = "streaming_tt"
            assert {b.wrt: b.backend for b in lp.backward} == want
