"""Serving scheduler suite: continuous-batching equivalence, phase-plan
switching, and scheduler robustness.

The load-bearing property: per-request token ids under continuous
batching are bit-identical to one-shot serving of each request alone.
This is *structural* — both modes prefill at batch 1 and decode at the
same fixed slot width (one-shot = concurrency 1 on the same engine), so
no cross-batch-size GEMM comparison is involved (XLA GEMMs are not
batch-size invariant).
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.models import api
from repro.plan import execution_log, reset_execution_log
from repro.plan.compiler import check_plan_for_config
from repro.serve import (
    Request,
    Scheduler,
    ServeEngine,
    ServePolicy,
    load_trace,
    save_trace,
    synthetic_trace,
)

ARCH = "tt-lm-100m"
N_SLOTS = 2
MAX_SEQ = 16
BUCKET = 4

_CACHE: dict = {}


def _model():
    if "params" not in _CACHE:
        cfg = get_config(ARCH, smoke=True)
        _CACHE["cfg"] = cfg
        _CACHE["params"] = api(cfg).init_params(jax.random.PRNGKey(0))
    return _CACHE["cfg"], _CACHE["params"]


def _engine(**kw) -> ServeEngine:
    """The shared plain engine (jit caches reused across tests)."""
    if kw:
        cfg, params = _model()
        return ServeEngine(cfg, params, n_slots=N_SLOTS, max_seq=MAX_SEQ,
                           prompt_bucket=BUCKET, **kw)
    if "engine" not in _CACHE:
        cfg, params = _model()
        _CACHE["engine"] = ServeEngine(cfg, params, n_slots=N_SLOTS,
                                       max_seq=MAX_SEQ, prompt_bucket=BUCKET)
    return _CACHE["engine"]


def _requests(raw: list[int]) -> list[Request]:
    """Decode a flat integer draw into requests (p 1..6, gen 1..4,
    arrival 0..6; prompt ids deterministic per request index)."""
    reqs = []
    for i in range(len(raw) // 3):
        p = 1 + raw[3 * i] % 6
        g = 1 + raw[3 * i + 1] % 4
        arrival = float(raw[3 * i + 2] % 7)
        rng = np.random.default_rng((0xC0FFEE, i))
        cfg, _ = _model()
        prompt = tuple(int(t) for t in rng.integers(0, cfg.vocab, size=p))
        reqs.append(Request(rid=i, prompt=prompt, max_new_tokens=g,
                            arrival=arrival))
    return reqs


def _run(schedule: str, reqs, *, temperature=0.0, seed=0, engine=None,
         policy_kw=None):
    eng = engine if engine is not None else _engine()
    policy = ServePolicy(schedule=schedule, **(policy_kw or {}))
    return Scheduler(eng, policy, temperature=temperature, seed=seed).run(reqs)


# ---------------------------------------------------------------------------
# property: continuous batching == one-shot, bit-identical per request
# ---------------------------------------------------------------------------

@given(raw=st.lists(st.integers(0, 10**9), min_size=3, max_size=12))
@settings(max_examples=8, deadline=None)
def test_continuous_matches_oneshot_bitexact(raw):
    reqs = _requests(raw)
    if not reqs:
        return
    cont = _run("continuous", reqs)
    solo = _run("oneshot", reqs)
    assert cont.tokens_by_rid() == solo.tokens_by_rid()


def test_sampled_continuous_matches_oneshot():
    """Per-(seed, rid) Gumbel-max sampling is lane-independent too."""
    reqs = _requests([5, 2, 0, 1, 3, 1, 4, 1, 2, 2, 0, 4])
    cont = _run("continuous", reqs, temperature=0.7, seed=11)
    solo = _run("oneshot", reqs, temperature=0.7, seed=11)
    assert cont.tokens_by_rid() == solo.tokens_by_rid()
    # a different seed genuinely resamples
    other = _run("continuous", reqs, temperature=0.7, seed=12)
    assert other.tokens_by_rid() != cont.tokens_by_rid()


# ---------------------------------------------------------------------------
# phase-switch coverage: plan pair drives each stream
# ---------------------------------------------------------------------------

def _plan_pair():
    if "pair" not in _CACHE:
        from repro.dse_cli import run_dse_plan

        _, plan_p = run_dse_plan(ARCH, smoke=True, top_k=2, tokens=64,
                                 plan_backend="jnp", phase="prefill")
        _, plan_d = run_dse_plan(ARCH, smoke=True, top_k=2, tokens=8,
                                 plan_backend="jnp", phase="decode")
        _CACHE["pair"] = (plan_p, plan_d)
    return _CACHE["pair"]


def test_phase_switch_runs_each_stream_under_its_plan():
    plan_p, plan_d = _plan_pair()
    assert plan_p.phase == "prefill" and plan_d.phase == "decode"
    tilings_p = {lp.name: lp.tiling.to_json() for lp in plan_p.layers}
    tilings_d = {lp.name: lp.tiling.to_json() for lp in plan_d.layers}
    # the pair is genuinely specialized: decode tilings differ (fewer
    # streamed tokens per step than a 64-token prefill)
    assert tilings_p != tilings_d

    eng = _engine(prefill_plan=plan_p, decode_plan=plan_d, arch=ARCH)
    reqs = _requests([0, 2, 0, 2, 2, 0])  # 2 requests, gen 3 each
    reset_execution_log()
    res = _run("continuous", reqs, engine=eng)
    assert len(res.completions) == 2
    log = execution_log()
    by_stream = {"prefill": [], "decode": []}
    for rec in log:
        assert rec["stream"] in by_stream, rec
        by_stream[rec["stream"]].append(rec)
    assert by_stream["prefill"] and by_stream["decode"]
    for rec in by_stream["prefill"]:
        assert rec["backend"] == "jnp"
        assert rec["tiling"] == tilings_p[rec["name"]]
    for rec in by_stream["decode"]:
        assert rec["backend"] == "jnp"
        assert rec["tiling"] == tilings_d[rec["name"]]


def test_swapped_pair_rejected_before_any_step():
    plan_p, plan_d = _plan_pair()
    problems = check_plan_for_config(plan_d, ARCH, _model()[0],
                                     phase="prefill")
    assert any("swapped" in p for p in problems)
    with pytest.raises(ValueError, match="prefill half"):
        _engine(prefill_plan=plan_d, decode_plan=plan_p, arch=ARCH)


def test_foreign_arch_plan_rejected():
    plan_p, _ = _plan_pair()
    foreign = dataclasses.replace(plan_p, arch="glm4-9b")
    assert check_plan_for_config(foreign, ARCH, _model()[0],
                                 phase="prefill")
    with pytest.raises(ValueError):
        _engine(prefill_plan=foreign, arch=ARCH)


# ---------------------------------------------------------------------------
# robustness: starvation, full queue, edge cases, replay
# ---------------------------------------------------------------------------

def _prompt(i, p=4):
    cfg, _ = _model()
    rng = np.random.default_rng((7, i))
    return tuple(int(t) for t in rng.integers(0, cfg.vocab, size=p))


def test_long_request_does_not_starve_later_short_one():
    reqs = [
        Request(rid=0, prompt=_prompt(0), max_new_tokens=6, arrival=0.0),
        Request(rid=1, prompt=_prompt(1), max_new_tokens=6, arrival=0.0),
        Request(rid=2, prompt=_prompt(2), max_new_tokens=1, arrival=0.0),
    ]
    res = _run("continuous", reqs)
    by = {c.rid: c for c in res.completions}
    assert len(by) == 3
    # FIFO bound: the short request is admitted the moment a lane frees
    first_free = min(by[0].done_step, by[1].done_step)
    assert by[2].admitted_step == first_free
    assert by[2].done_step <= max(by[0].done_step, by[1].done_step)


def test_full_queue_burst_admission():
    reqs = [Request(rid=i, prompt=_prompt(i), max_new_tokens=2, arrival=0.0)
            for i in range(5)]
    res = _run("continuous", reqs)
    assert sorted(c.rid for c in res.completions) == list(range(5))
    assert all(len(c.tokens) == 2 for c in res.completions)
    # 5 requests over 2 lanes, 1 decode step each -> 3 admission waves
    assert res.steps == 3
    assert res.occupancy > 0.5


def test_admission_cap_bounds_prefills_per_step():
    reqs = [Request(rid=i, prompt=_prompt(i), max_new_tokens=2, arrival=0.0)
            for i in range(4)]
    res = _run("continuous", reqs,
               policy_kw={"max_admissions_per_step": 1})
    by = {c.rid: c for c in res.completions}
    assert len(by) == 4
    # one prefill per tick: admission steps are strictly increasing
    steps = [by[i].admitted_step for i in range(4)]
    assert steps == sorted(steps) and len(set(steps)) == 4


def test_zero_requests():
    res = _run("continuous", [])
    assert res.completions == () and res.steps == 0
    assert res.occupancy == 0.0


def test_single_token_gen_completes_at_admission():
    reqs = [Request(rid=0, prompt=_prompt(0), max_new_tokens=1, arrival=0.0)]
    res = _run("continuous", reqs)
    (c,) = res.completions
    assert len(c.tokens) == 1
    assert c.done_step == c.admitted_step
    assert res.occupancy == 0.0  # never occupied a decode lane


def test_deterministic_trace_replay():
    reqs = _requests([9, 9, 9, 3, 1, 4, 1, 5, 2])
    a = _run("continuous", reqs)
    b = _run("continuous", reqs)
    assert [c.replay_key for c in a.completions] == \
        [c.replay_key for c in b.completions]
    assert a.steps == b.steps


def test_trace_roundtrip(tmp_path):
    cfg, _ = _model()
    reqs = synthetic_trace(3, cfg.vocab, prompt_len=(1, 6), gen=(1, 3),
                           arrival_rate=1.0, seed=5)
    path = str(tmp_path / "trace.json")
    save_trace(path, reqs)
    loaded = load_trace(path, cfg.vocab)
    assert [(r.prompt, r.max_new_tokens, r.arrival) for r in loaded] == \
        [(r.prompt, r.max_new_tokens, r.arrival) for r in reqs]


def test_validation_errors():
    with pytest.raises(ValueError, match="duplicate"):
        _run("continuous", [
            Request(rid=0, prompt=_prompt(0), max_new_tokens=1),
            Request(rid=0, prompt=_prompt(1), max_new_tokens=1),
        ])
    with pytest.raises(ValueError, match="max_seq"):
        _run("continuous", [Request(rid=0, prompt=_prompt(0, p=10),
                                    max_new_tokens=MAX_SEQ)])
    with pytest.raises(ValueError, match="unknown schedule"):
        ServePolicy(schedule="batch")
    with pytest.raises(ValueError, match="empty prompt"):
        Request(rid=0, prompt=(), max_new_tokens=1)


def test_arrival_gating_idles_until_next_request():
    reqs = [Request(rid=0, prompt=_prompt(0), max_new_tokens=2,
                    arrival=5.0)]
    res = _run("continuous", reqs)
    (c,) = res.completions
    assert c.admitted_step >= 5


def test_spans_count_what_the_scheduler_did(monkeypatch):
    """The serving spans agree with the scheduler's own counts: one
    ``serve.decode`` per decode step, the steps' mean ``lanes`` equal to
    the result's occupancy, one ``*.fetch`` per decode step and prefill,
    every pick inside a step, and no name the benchmark reserves."""
    import time

    from repro.runtime import spans

    eng = _engine()
    calls = {"decode": 0, "prefill_request": 0}
    for name in calls:
        def count(*a, _name=name, _fn=getattr(eng, name)):
            calls[_name] += 1
            return _fn(*a)
        monkeypatch.setattr(eng, name, count)
    # an idle jump, a single-token request and a full queue
    reqs = [Request(rid=i, prompt=_prompt(i), max_new_tokens=g, arrival=a)
            for i, (g, a) in enumerate([(4, 0.0), (1, 0.0), (3, 0.0),
                                        (2, 0.0), (3, 9.0)])]
    t0 = time.perf_counter()
    res = _run("continuous", reqs)
    got = spans.spans(t0, time.perf_counter())
    by_id = {s.id: s for s in got}

    def named(name):
        return [s for s in got if s.name == name]

    assert len(named("serve.decode")) == calls["decode"] > 0
    decoded = [by_id[s.parent] for s in named("serve.decode")]
    assert all(s.name == "serve.step" for s in decoded)
    assert np.mean([s.attrs["lanes"] for s in decoded]) == pytest.approx(
        res.occupancy * res.n_slots)
    assert sum(s.name.endswith(".fetch") for s in got) == (
        calls["decode"] + calls["prefill_request"])
    assert len(named("serve.admission")) == calls["prefill_request"] == 5
    for s in named("serve.sample"):
        while s.name != "serve.step":
            s = by_id[s.parent]
    assert not [s for s in got if s.name.startswith("bench.")]
