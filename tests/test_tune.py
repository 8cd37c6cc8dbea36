"""Measured-latency autotuner: cache, sweeps, measured plans, calibration.

Covers the acceptance criteria of the autotuner PR:

1. the tuning cache round-trips bit-stably and is keyed by device kind
   (and interpret flag) — one machine's numbers never leak onto another;
2. ``compile_plan(tilings="measured")`` emits plans that validate
   against schema v3 and, over a warm cache, replay with **zero**
   measurements to a bit-identical artifact;
3. ``global_search(calibration=...)`` genuinely changes the argmin when
   measurements invert the analytic per-dataflow ranking;
4. ``kernels.tt_gemm`` auto-pads non-block-multiple dims (autotuned
   tilings never need caller-side padding logic).

Most tests inject stub measurement functions into the Autotuner (fast,
deterministic); one small real-measurement test exercises the actual
harness end to end.
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FPGA_VU9P, Dataflow, find_topk_paths, global_search
from repro.core.dse import apply_calibration
from repro.nn import LinearSpec, TTConfig
from repro.plan import ExecutionPlan, compile_plan
from repro.tune import (
    KERNEL_MODULES,
    Autotuner,
    TuningCache,
    gemm_variants,
    gemm_work_items,
    heuristic_blocks,
    kernel_fingerprint,
    measured_calibration,
    streaming_variants,
    variant_key,
)


# fake measurements: strictly decreasing in total block volume, so the
# argmin is always the largest feasible variant — deterministic, fast,
# and distinguishable from the (128-capped) heuristic on large shapes
def _fake_gemm(M, K, N, dataflow, blocks, **kw):
    bm, bk, bn = blocks
    return 1.0 / (bm * bk * bn)


def _fake_streaming(tn_block, steps, tokens, block_tokens, **kw):
    return 1.0 / block_tokens


def _fail_gemm(*a, **kw):
    raise AssertionError("measurement requested on a warm cache")


def _fail_streaming(*a, **kw):
    raise AssertionError("measurement requested on a warm cache")


def _stub_tuner(cache=None, mode="cache", device_kind="cpu", **kw):
    return Autotuner(cache, mode, device_kind=device_kind, interpret=True,
                     measure_gemm_fn=_fake_gemm,
                     measure_streaming_fn=_fake_streaming, **kw)


def _unit_problem(tokens=32, d_out=256):
    tt = TTConfig(enabled=True, d=2, rank=8, min_dim=64)
    spec = LinearSpec("demo", 128, d_out, tag="mlp", tt=tt)
    tn = spec.network(tokens)
    paths = find_topk_paths(tn, k=4)
    res = global_search([paths], FPGA_VU9P)
    return spec, tn, paths, res


# ---------------------------------------------------------------------------
# cache round-trip + device keying
# ---------------------------------------------------------------------------

def test_cache_roundtrip_bit_stable(tmp_path):
    tuner = _stub_tuner()
    tuner.tune_gemm(96, 160, 512, "OS", include=[heuristic_blocks(96, 160, 512)])
    _, tn, paths, _ = _unit_problem()
    tuner.tune_streaming(tn, paths[0].steps, 32, include=[32])
    assert len(tuner.cache) == 2

    text = tuner.cache.dumps()
    loaded = TuningCache.loads(text)
    assert loaded.dumps() == text  # canonical: load -> dump is byte-stable

    path = tmp_path / "cache.json"
    tuner.cache.save(str(path))
    assert TuningCache.load(str(path)).dumps() == text
    # load -> save -> load is also stable on disk
    TuningCache.load(str(path)).save(str(path))
    assert path.read_text() == text


def test_cache_is_device_keyed():
    cache = TuningCache()
    cpu = _stub_tuner(cache, device_kind="cpu")
    best = cpu.tune_gemm(64, 64, 64, "OS")
    assert cpu.n_measured > 0

    # same shapes, different device kind: every lookup must miss
    tpu = _stub_tuner(cache, device_kind="TPU_v5e")
    assert tpu.tune_gemm(64, 64, 64, "OS") == best  # same fake model
    assert tpu.n_cache_hits == 0
    assert tpu.n_measured == cpu.n_measured
    keys = set(cache.entries)
    assert any(":cpu:" in k for k in keys)
    assert any(":TPU_v5e:" in k for k in keys)


def test_kernel_fingerprint_tracks_kernel_sources(tmp_path):
    """The fingerprint hashes the Pallas kernel sources: stable across
    calls, sensitive to any byte of any kernel file."""
    assert kernel_fingerprint() == kernel_fingerprint()
    assert len(kernel_fingerprint()) == 12
    assert len(KERNEL_MODULES) == 4

    a = tmp_path / "a.py"
    b = tmp_path / "b.py"
    a.write_text("def kernel(): return 1\n")
    b.write_text("def other(): return 2\n")
    fp = kernel_fingerprint([str(a), str(b)])
    assert kernel_fingerprint([str(a), str(b)]) == fp
    # path order must not matter (sorted before hashing)
    assert kernel_fingerprint([str(b), str(a)]) == fp
    # a one-byte kernel edit yields a different fingerprint
    a.write_text("def kernel(): return 9\n")
    assert kernel_fingerprint([str(a), str(b)]) != fp


def test_cache_is_kernel_fingerprint_keyed():
    """ROADMAP gap (d): mutating a kernel invalidates cached timings —
    entries keyed under the old fingerprint simply stop matching, so the
    tuner re-measures instead of replaying stale numbers."""
    cache = TuningCache()
    v1 = _stub_tuner(cache, kernel_fp="aaaa00000000")
    best = v1.tune_gemm(64, 64, 64, "OS")
    assert v1.n_measured > 0

    # same kernels -> warm replay, zero measurements (even with a
    # measurement fn that would fail the test if called)
    warm = Autotuner(cache, "cache", device_kind="cpu", interpret=True,
                     measure_gemm_fn=_fail_gemm,
                     measure_streaming_fn=_fail_streaming,
                     kernel_fp="aaaa00000000")
    assert warm.tune_gemm(64, 64, 64, "OS") == best
    assert warm.n_measured == 0 and warm.n_cache_hits > 0

    # mutated kernels -> every lookup misses, fresh measurements
    v2 = _stub_tuner(cache, kernel_fp="bbbb11111111")
    assert v2.tune_gemm(64, 64, 64, "OS") == best  # same fake model
    assert v2.n_cache_hits == 0 and v2.n_measured > 0
    keys = set(cache.entries)
    assert any(":kaaaa00000000" in k for k in keys)
    assert any(":kbbbb11111111" in k for k in keys)


def test_cache_rejects_foreign_json():
    with pytest.raises(ValueError, match="not a tuning cache"):
        TuningCache.loads('{"format": "something.else", "version": 1}')
    with pytest.raises(ValueError, match="version"):
        TuningCache.loads('{"format": "repro.tuning_cache", "version": 99}')


def test_entry_fingerprint_parsing():
    from repro.tune import entry_fingerprint

    assert entry_fingerprint("gemm:32:32:32:OS:cpu:i1:kdeadbeef") == "deadbeef"
    assert entry_fingerprint("gemm:32:32:32:OS:cpu:i1") is None  # pre-PR7 key
    assert entry_fingerprint("gemm:32:kXYZ") is None             # not hex


def test_merge_caches_union_and_last_writer_wins():
    from repro.tune import entry_fingerprint, merge_caches

    a = _stub_tuner(TuningCache())
    a.tune_gemm(96, 160, 512, "OS", include=[heuristic_blocks(96, 160, 512)])
    b = _stub_tuner(TuningCache())
    b.tune_gemm(96, 160, 512, "OS", include=[heuristic_blocks(96, 160, 512)])
    b.tune_gemm(64, 64, 64, "IS", include=[heuristic_blocks(64, 64, 64)])

    fp = entry_fingerprint(next(iter(a.cache.entries)))
    merged, dropped, dropped_shards = merge_caches(
        [a.cache, b.cache], fingerprint=fp)
    assert dropped == 0 and dropped_shards == 0
    assert len(merged) == 2  # union: shared key merges, new key added
    # last writer wins: the colliding entry's measurements come from b
    key = next(k for k in merged.entries if k in a.cache.entries)
    assert merged.entries[key].measured_s == b.cache.entries[key].measured_s
    # merging is idempotent
    again, _, _ = merge_caches([merged], fingerprint=fp)
    assert again.dumps() == merged.dumps()


def test_merge_caches_drops_foreign_fingerprints():
    from repro.tune import merge_caches

    a = _stub_tuner(TuningCache())
    a.tune_gemm(96, 160, 512, "OS", include=[heuristic_blocks(96, 160, 512)])
    merged, dropped, _ = merge_caches([a.cache], fingerprint="0" * 12)
    assert len(merged) == 0 and dropped == 1


def test_entry_shards_parsing():
    from repro.tune import entry_shards

    assert entry_shards("gemm:32x32x32:OS:cpu:interp:s4:kdeadbeef") == 4
    assert entry_shards("gemm:32x32x32:OS:cpu:interp:s1:kdeadbeef") == 1
    # pre-shard key: no segment
    assert entry_shards("gemm:32x32x32:OS:cpu:interp:kdeadbeef") is None


def test_tuner_keys_carry_shard_count():
    t1 = _stub_tuner(TuningCache())
    t4 = Autotuner(t1.cache, "cache", device_kind="cpu", interpret=True,
                   measure_gemm_fn=_fake_gemm,
                   measure_streaming_fn=_fake_streaming,
                   kernel_fp=t1.kernel_fp, shards=4)
    k1 = t1.gemm_key(64, 64, 64, "OS")
    k4 = t4.gemm_key(64, 64, 64, "OS")
    assert k1 != k4 and ":s1:" in k1 and ":s4:" in k4
    # a 4-shard measurement never answers a single-device lookup
    t4.tune_gemm(64, 64, 64, "OS")
    assert t1.cached_gemm_blocks(64, 64, 64, "OS") is None


def test_merge_caches_drops_shard_mismatches():
    from repro.tune import entry_fingerprint, merge_caches

    a = _stub_tuner(TuningCache())  # shards=1 keys
    a.tune_gemm(96, 160, 512, "OS", include=[heuristic_blocks(96, 160, 512)])
    b_cache = TuningCache()
    b = Autotuner(b_cache, "cache", device_kind="cpu", interpret=True,
                  measure_gemm_fn=_fake_gemm,
                  measure_streaming_fn=_fake_streaming,
                  kernel_fp=a.kernel_fp, shards=4)
    b.tune_gemm(64, 64, 64, "OS", include=[heuristic_blocks(64, 64, 64)])

    fp = entry_fingerprint(next(iter(a.cache.entries)))
    # no filter: every mesh width survives (keys never collide)
    merged, dropped, dropped_shards = merge_caches(
        [a.cache, b_cache], fingerprint=fp)
    assert len(merged) == 2 and dropped == 0 and dropped_shards == 0
    # shard filter: the 1-shard entry is a shard-shape mismatch at s4
    merged4, dropped, dropped_shards = merge_caches(
        [a.cache, b_cache], fingerprint=fp, shards=4)
    assert len(merged4) == 1 and dropped == 0 and dropped_shards == 1
    assert all(":s4:" in k for k in merged4.entries)


def test_merge_cli_roundtrip(tmp_path, capsys):
    from repro.tune import entry_fingerprint
    from repro.tune.cli import run_merge

    a = _stub_tuner(TuningCache())
    a.tune_gemm(96, 160, 512, "OS", include=[heuristic_blocks(96, 160, 512)])
    b = _stub_tuner(TuningCache())
    b.tune_gemm(64, 64, 64, "IS", include=[heuristic_blocks(64, 64, 64)])
    pa, pb = str(tmp_path / "a.json"), str(tmp_path / "b.json")
    out = str(tmp_path / "merged.json")
    a.cache.save(pa)
    b.cache.save(pb)

    fp = entry_fingerprint(next(iter(a.cache.entries)))
    assert run_merge([pa, pb, "-o", out, "--fingerprint", fp]) == 0
    merged = TuningCache.load(out)
    assert len(merged) == 2
    assert "2 entries kept, 0 dropped" in capsys.readouterr().err
    # unreadable input is a clean exit-2, not a traceback
    assert run_merge([str(tmp_path / "missing.json"), "-o", out]) == 2


def test_cached_tiling_lookups_never_measure():
    from repro.plan.compiler import rebatch

    t = _stub_tuner(TuningCache())
    t.tune_gemm(96, 160, 512, "OS", include=[heuristic_blocks(96, 160, 512)])
    _, tn, paths, _ = _unit_problem()
    t.tune_streaming(tn, paths[0].steps, 32, include=[32])

    warm = Autotuner(t.cache, "cache", device_kind="cpu", interpret=True,
                     measure_gemm_fn=_fail_gemm,
                     measure_streaming_fn=_fail_streaming)
    assert warm.cached_gemm_blocks(96, 160, 512, "OS") is not None
    assert warm.cached_gemm_blocks(97, 160, 512, "OS") is None  # miss: None
    # 32 tokens run as one lane-legal 128-token block
    assert warm.cached_streaming_tokens(tn, paths[0].steps, 32) == 128
    assert warm.cached_streaming_tokens(rebatch(tn, 64), paths[0].steps,
                                        64) is None


def test_entry_argmin_is_deterministic_on_ties():
    tuner = _stub_tuner()
    key = tuner.gemm_key(64, 64, 64, "OS")
    entry = tuner.cache.ensure(key, kind="gemm", backend="tt_gemm",
                               device_kind="cpu", interpret=True, problem={})
    entry.measured_s[variant_key((64, 64, 64))] = 1.0
    entry.measured_s[variant_key((32, 64, 64))] = 1.0
    entry.measured_s[variant_key((64, 32, 64))] = 2.0
    assert entry.best_blocks == (32, 64, 64)  # tie -> smallest variant


# ---------------------------------------------------------------------------
# variant generators
# ---------------------------------------------------------------------------

def test_gemm_variants_feasible_and_include_heuristic():
    vs = gemm_variants(96, 160, 512, include=[heuristic_blocks(96, 160, 512)])
    assert heuristic_blocks(96, 160, 512) in vs
    for bm, bk, bn in vs:
        # pow2, >= 8, never beyond the next pow2 of the dim
        for b, dim in ((bm, 96), (bk, 160), (bn, 512)):
            assert b >= 8 and (b & (b - 1)) == 0
            assert b <= max(8, 1 << (dim - 1).bit_length())
    assert vs == sorted(set(vs))


def test_streaming_variants_respect_vmem_budget():
    _, tn, paths, _ = _unit_problem(tokens=512)
    steps = paths[0].steps
    all_bt = streaming_variants(tn, steps, 512, include=[256])
    assert 256 in all_bt
    tight = streaming_variants(tn, steps, 512, include=[256],
                               budget_bytes=1)  # nothing fits
    assert tight == []
    from repro.plan import streaming_fits
    for bt in all_bt:
        assert streaming_fits(tn, steps, bt)


def test_gemm_work_items_dedup_and_order():
    _, _, paths, _ = _unit_problem()
    items = gemm_work_items([paths, paths, paths])  # repeated layers dedup
    assert len(items) == len(set(items))
    assert items == gemm_work_items([paths])
    capped = gemm_work_items([paths], max_shapes=1)
    assert len(capped) == 1 and capped[0] == items[0]


# ---------------------------------------------------------------------------
# measured plans: schema v3, zero-measurement replay, bit-identity
# ---------------------------------------------------------------------------

def test_measured_plan_validates_and_replays_from_cache(tmp_path):
    spec, tn, paths, res = _unit_problem(tokens=32)
    cache = TuningCache()
    tuner = _stub_tuner(cache)
    plan = compile_plan([("demo", tn)], res, FPGA_VU9P, arch="unit",
                        tokens=32, tilings="measured", tuner=tuner)
    assert plan.tilings == "measured"
    assert tuner.n_measured > 0

    # schema round-trip: canonical, bit-stable, version preserved
    d = plan.to_json()
    assert d["version"] == 4 and d["tilings"] == "measured"
    text = plan.dumps()
    assert ExecutionPlan.loads(text).dumps() == text

    # replay over the warm cache: zero measurements, bit-identical plan
    replay = Autotuner(cache, "cache", device_kind="cpu", interpret=True,
                       measure_gemm_fn=_fail_gemm,
                       measure_streaming_fn=_fail_streaming)
    plan2 = compile_plan([("demo", tn)], res, FPGA_VU9P, arch="unit",
                         tokens=32, tilings="measured", tuner=replay)
    assert replay.n_measured == 0 and replay.n_cache_hits > 0
    assert plan2.dumps() == text

    # a kernel-source mutation (different fingerprint) makes the same
    # cache stale: nothing replays, everything re-measures (gap (d))
    stale = _stub_tuner(cache, kernel_fp="deadbeef0000")
    plan3 = compile_plan([("demo", tn)], res, FPGA_VU9P, arch="unit",
                         tokens=32, tilings="measured", tuner=stale)
    assert stale.n_cache_hits == 0 and stale.n_measured > 0
    assert plan3.dumps() == text  # same fake measurements -> same plan


def test_measured_tilings_differ_from_heuristic_on_large_shapes():
    # tokens 512 > the heuristic's 256 block_tokens cap; the fake
    # measurements prefer the largest feasible block, so the measured
    # tiling must move
    spec, tn, paths, res = _unit_problem(tokens=512)
    plan_h = compile_plan([("demo", tn)], res, FPGA_VU9P, arch="unit",
                          tokens=512)
    assert plan_h.tilings == "heuristic"
    tuner = _stub_tuner()
    plan_m = compile_plan([("demo", tn)], res, FPGA_VU9P, arch="unit",
                          tokens=512, tilings="measured", tuner=tuner)
    (lp_h,), (lp_m,) = plan_h.layers, plan_m.layers
    assert lp_h.backend == lp_m.backend  # backend choice stays heuristic
    if lp_m.backend == "streaming_tt":
        assert lp_m.tiling.block_tokens > lp_h.tiling.block_tokens
    else:
        assert lp_m.tiling != lp_h.tiling


def test_compile_plan_rejects_bad_tiling_modes():
    _, tn, _, res = _unit_problem()
    with pytest.raises(ValueError, match="tilings"):
        compile_plan([("demo", tn)], res, FPGA_VU9P, tilings="magic")
    with pytest.raises(ValueError, match="requires a tuner"):
        compile_plan([("demo", tn)], res, FPGA_VU9P, tilings="measured")


def test_schema_rejects_unknown_tilings_provenance():
    _, tn, _, res = _unit_problem()
    plan = compile_plan([("demo", tn)], res, FPGA_VU9P)
    with pytest.raises(ValueError, match="tilings"):
        dataclasses.replace(plan, tilings="vibes")
    # absent wire field defaults to heuristic (pre-autotuner v3 files)
    d = plan.to_json()
    del d["tilings"]
    assert ExecutionPlan.from_json(d).tilings == "heuristic"


# ---------------------------------------------------------------------------
# calibration: measured feedback can flip the DSE argmin
# ---------------------------------------------------------------------------

def test_calibration_changes_argmin_when_measurements_invert_ranking():
    _, tn, paths, base = _unit_problem()
    (choice,) = base.choices
    won = choice.dataflow
    others = [d for d in Dataflow if d is not won]

    # synthetic measurement: the analytically-chosen dataflow is 1000x
    # slower on this machine than the model believes
    calibration = {won.value: 1000.0}
    res = global_search([paths], FPGA_VU9P, calibration=calibration)
    (new,) = res.choices
    assert new.dataflow in others
    assert new.dataflow != won

    # a uniform calibration cannot move any argmin
    uniform = {d.value: 7.5 for d in Dataflow}
    res_u = global_search([paths], FPGA_VU9P, calibration=uniform)
    assert res_u.choices[0].dataflow == won
    assert res_u.total_latency_s == pytest.approx(7.5 * base.total_latency_s)


def test_apply_calibration_validation():
    table = {(0, 0, (1, 1), Dataflow.OS): 1.0}
    assert apply_calibration(table, {"OS": 2.0})[
        (0, 0, (1, 1), Dataflow.OS)] == 2.0
    with pytest.raises(ValueError, match="positive"):
        apply_calibration(table, {"OS": 0.0})
    with pytest.raises(ValueError):
        apply_calibration(table, {"XX": 1.0})
    _, _, paths, _ = _unit_problem()
    # calibration composes with the architecture co-search (ROADMAP gap
    # (c), closed): uniform scale -> same winner, scaled cost — the
    # argmin-flipping case lives in tests/test_search_oracle.py
    plain = global_search([paths], FPGA_VU9P, hw_space=(FPGA_VU9P,))
    res_hw = global_search([paths], FPGA_VU9P, calibration={d.value: 2.0
                                                            for d in Dataflow},
                           hw_space=(FPGA_VU9P,))
    assert res_hw.total_latency_s == pytest.approx(2 * plain.total_latency_s)
    from repro.core import memoised_layer_backwards
    _, tn, _, _ = _unit_problem()
    with pytest.raises(ValueError, match="train"):
        global_search([paths], FPGA_VU9P, calibration={"OS": 2.0},
                      objective="train-latency",
                      layer_backwards=memoised_layer_backwards([tn], k=2))


def test_measured_calibration_scales_follow_measurements():
    # fake measurement is dataflow-independent, analytic costs differ per
    # dataflow -> scales must differ and be positive
    tuner = _stub_tuner()
    scales = measured_calibration([(128, 128, 256)], tuner, FPGA_VU9P)
    assert set(scales) == {"IS", "OS", "WS"}
    assert all(s > 0 for s in scales.values())
    assert len(set(scales.values())) > 1


# ---------------------------------------------------------------------------
# dse_cli --tune plumbing (stubbed measurements)
# ---------------------------------------------------------------------------

def test_run_dse_tune_cache_reports_and_replays(tmp_path, monkeypatch):
    import repro.tune.measure as tmeasure
    from repro.dse_cli import run_dse_plan

    monkeypatch.setattr(tmeasure, "measure_gemm", _fake_gemm)
    monkeypatch.setattr(tmeasure, "measure_streaming", _fake_streaming)
    cache = str(tmp_path / "cache.json")

    report, plan = run_dse_plan("tt-lm-100m", smoke=True, top_k=2, tokens=32,
                                tune="cache", tune_cache=cache)
    t = report["tune"]
    assert t["mode"] == "cache" and t["cache"] == cache
    assert set(t["calibration"]) == {"IS", "OS", "WS"}
    assert t["n_measured"] > 0
    assert plan.tilings == "measured"
    # per-layer latency provenance stays in analytic seconds: the
    # calibration scale is divided back out, so instances sum to the
    # plan's (analytic) total exactly as in untuned plans
    assert sum(lp.latency_s * lp.instances
               for lp in plan.layers) == pytest.approx(plan.total_latency_s)

    # second run: fully cache-served, bit-identical plan
    monkeypatch.setattr(tmeasure, "measure_gemm", _fail_gemm)
    monkeypatch.setattr(tmeasure, "measure_streaming", _fail_streaming)
    report2, plan2 = run_dse_plan("tt-lm-100m", smoke=True, top_k=2,
                                  tokens=32, tune="cache", tune_cache=cache)
    assert report2["tune"]["n_measured"] == 0
    assert plan2.dumps() == plan.dumps()


def test_run_dse_tune_train_mode_measured_tilings(tmp_path, monkeypatch):
    """ROADMAP gap (b) closed: train plans may carry measured tilings.
    The train *search* stays analytic (no calibration), but the emitted
    plan replays measured forward tilings and cache-served backward
    tilings."""
    import repro.tune.measure as tmeasure
    from repro.dse_cli import run_dse_plan

    monkeypatch.setattr(tmeasure, "measure_gemm", _fake_gemm)
    monkeypatch.setattr(tmeasure, "measure_streaming", _fake_streaming)
    cache = str(tmp_path / "cache.json")
    report, plan = run_dse_plan("tt-lm-100m", smoke=True, top_k=2,
                                tokens=32, mode="train", tune="cache",
                                tune_cache=cache)
    t = report["tune"]
    assert t["mode"] == "cache"
    assert t["calibration"] is None          # train search is analytic
    assert t["n_calibration_shapes"] == 0
    assert "analytic" in t["note"]
    assert t["n_measured"] > 0
    assert plan.tilings == "measured"
    assert any(lp.backward for lp in plan.layers)  # it is a train plan


def test_run_dse_tune_composes_with_hw_search(tmp_path, monkeypatch):
    """ROADMAP gap (c) closed: --tune now composes with --hw-search —
    the calibrated tables rescale every candidate before its argmin."""
    import repro.tune.measure as tmeasure
    from repro.dse_cli import run_dse

    monkeypatch.setattr(tmeasure, "measure_gemm", _fake_gemm)
    monkeypatch.setattr(tmeasure, "measure_streaming", _fake_streaming)
    cache = str(tmp_path / "cache.json")
    report = run_dse("tt-lm-100m", smoke=True, top_k=2, tokens=32,
                     hw_search="budget", tune="cache", tune_cache=cache)
    assert report["hw_search"]["n_candidates"] >= 64
    assert set(report["tune"]["calibration"]) == {"IS", "OS", "WS"}
    assert report["tune"]["correction"]["model"] == "shape-bucket-geomean"


def test_combine_phase_tables_calibrates_each_phase_at_own_shapes():
    """ROADMAP serving follow-on (a): the throughput objective's combined
    table applies the measured correction per phase *at that phase's own
    GEMM shapes* — decode GEMMs are skinnier, so the shape-aware model
    must resolve each phase's cells against its own candidate paths."""
    from types import SimpleNamespace

    from repro.core.dse import combine_phase_tables

    df = Dataflow.OS
    key = (0, 0, (1, 1), df)
    pre = {key: 10.0}
    dec = {key: 1.0}

    def path_with_gemm(M, K, N):
        g = SimpleNamespace(M=M, K=K, N=N, macs=M * K * N)
        return SimpleNamespace(gemms=(g,))

    prefill_paths = [[path_with_gemm(1024, 64, 64)]]
    decode_paths = [[path_with_gemm(8, 64, 64)]]

    class ShapeScale:
        def scale(self, M, K, N, dataflow):
            return 2.0 if M >= 1024 else 5.0

    out = combine_phase_tables(
        pre, dec, w_prefill=1.0, w_decode=3.0,
        calibration=ShapeScale(),
        prefill_paths=prefill_paths, decode_paths=decode_paths)
    # prefill cell scaled by 2 (big GEMM), decode cell by 5 (skinny)
    assert out[key] == pytest.approx(1.0 * 2.0 * 10.0 + 3.0 * 5.0 * 1.0)

    # flat per-dataflow calibration scales both phases uniformly
    flat = combine_phase_tables(pre, dec, w_decode=3.0,
                                calibration={df.value: 2.0})
    assert flat[key] == pytest.approx(2.0 * (10.0 + 3.0 * 1.0))
    # and no calibration leaves the weighted sum untouched
    plain = combine_phase_tables(pre, dec, w_decode=3.0)
    assert plain[key] == pytest.approx(13.0)


def test_run_dse_tune_throughput_calibrated(tmp_path, monkeypatch):
    """--tune now composes with --objective throughput: the measured
    correction rescales both phase tables before the decode-weighted
    combine (previously rejected as latency-only)."""
    import repro.tune.measure as tmeasure
    from repro.dse_cli import run_dse

    monkeypatch.setattr(tmeasure, "measure_gemm", _fake_gemm)
    monkeypatch.setattr(tmeasure, "measure_streaming", _fake_streaming)
    cache = str(tmp_path / "cache.json")

    report = run_dse("tt-lm-100m", smoke=True, top_k=2, tokens=32,
                     objective="throughput", tune="cache", tune_cache=cache)
    assert report["objective"] == "throughput"
    assert set(report["tune"]["calibration"]) == {"IS", "OS", "WS"}
    assert report["tune"]["n_measured"] > 0
    assert report["serving"]["calibrated"] is True
    # the combined objective is in calibrated units; the analytic phase
    # split stays analytic seconds
    assert report["serving"]["total_prefill_s"] > 0
    assert report["serving"]["total_decode_step_s"] > 0

    untuned = run_dse("tt-lm-100m", smoke=True, top_k=2, tokens=32,
                      objective="throughput")
    assert untuned["serving"]["calibrated"] is False
    w = untuned["serving"]["decode_weight"]
    assert untuned["total_objective"] == pytest.approx(
        untuned["serving"]["total_prefill_s"]
        + w * untuned["serving"]["total_decode_step_s"])


def test_run_tune_cli_pipeline_with_stub_tuner(tmp_path):
    from repro.tune.cli import run_tune

    cache_path = str(tmp_path / "cache.json")
    tuner = _stub_tuner(TuningCache(), cache_path=cache_path)
    report = run_tune("tt-lm-100m", smoke=True, top_k=2, tokens=32,
                      max_shapes=2, tuner=tuner)
    assert report["n_shapes"] == 2
    assert report["n_families"] == 2
    assert report["n_measured"] == tuner.n_measured > 0
    assert set(report["calibration"]) == {"IS", "OS", "WS"}
    for fam in report["families"]:
        if "speedup_vs_heuristic" in fam and fam["speedup_vs_heuristic"]:
            assert fam["speedup_vs_heuristic"] >= 1.0
    # the cache was persisted and reloads bit-stably
    assert TuningCache.load(cache_path).dumps() == tuner.cache.dumps()


# ---------------------------------------------------------------------------
# kernels: auto-padding + one real measurement
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dataflow", ["IS", "OS", "WS"])
def test_tt_gemm_auto_pads_non_multiple_dims(dataflow, rng):
    from repro.kernels.tt_gemm import tt_gemm

    a = jnp.asarray(rng.standard_normal((48, 96)).astype(np.float32))
    b = jnp.asarray(rng.standard_normal((96, 160)).astype(np.float32))
    out = tt_gemm(a, b, dataflow=dataflow, block_m=32, block_k=64,
                  block_n=128, interpret=True)
    assert out.shape == (48, 160)
    np.testing.assert_allclose(np.asarray(out), np.asarray(a @ b),
                               rtol=1e-4, atol=1e-4)


def test_real_measurement_harness_smoke():
    # one genuine interpret-mode measurement through each harness path
    from repro.tune import measure_gemm, measure_streaming
    from repro.plan.compiler import rebatch

    s = measure_gemm(32, 32, 32, "OS", (32, 32, 32), interpret=True,
                     warmup=1, repeats=2)
    assert s > 0
    _, tn, paths, _ = _unit_problem(tokens=32)
    s2 = measure_streaming(rebatch(tn, 16), paths[0].steps, 32, 16,
                           interpret=True, warmup=1, repeats=2)
    assert s2 > 0
