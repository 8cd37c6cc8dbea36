#!/usr/bin/env python3
"""Bring-up check of the main path on a TPU: plan -> serve -> train.

    python chip_smoke.py               # one chip (the default)
    python chip_smoke.py --four-chips  # sharded serving on a 2x2 mesh only

One process drives everything, through the entry points' own functions
(no subprocesses: a process that has touched JAX holds the chip).  On one
chip, for full-width tt-lm-100m with random weights from a fixed seed:

  plans    emit a tpu_v5e prefill/decode plan pair and a train plan
           (``repro.dse_cli.run_dse_plan``);
  serve    8 requests of 128 prompt + 32 generated tokens through
           ``repro.launch.serve`` (continuous batching over 4 decode
           slots, ``--strict-plan``): no layer may fall back to jnp, and
           both jitted steps must call compiled Mosaic kernels
           (``tpu_custom_call``);
  logits   prefill and first-decode-step logits of the same requests
           against the model with no plan, in float32 under
           ``jax.default_matmul_precision("highest")``;
  train    3 steps through ``repro.launch.train`` under the train plan:
           finite losses, no fault-loop recoveries, Mosaic kernels in the
           step, and the step-0 loss against a ``--plan-backend jnp`` run;
  kernels  each ``tt_gemm`` dataflow (OS, WS, IS) against ``a @ b`` with
           8 k-folds, and a fused path segment against the jnp executor.

``--four-chips`` runs only the multi-device serving path: a plan pair
searched at the per-shard token count of the 4-chip mesh, served
continuously over that mesh, against one-chip oneshot serving of the
same requests under the same plans.

Any failed phase, or a JAX that finds no TPU, exits non-zero without a
result line.  On success the last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")
OUT = os.path.join(ROOT, "results", "chip_smoke")

ARCH = "tt-lm-100m"
HW = "tpu_v5e"
SLOTS, N_REQUESTS, PROMPT_LEN, GEN, BUCKET = 4, 8, 128, 32, 8
TRAIN_STEPS, TRAIN_BATCH, TRAIN_SEQ = 3, 4, 256
GEMM_SHAPE, GEMM_BLOCK = (512, 1024, 384), 128  # K / block = 8 k-folds
#: (in_modes, out_modes, ranks) of the plans' LM-head layer
FUSED_GEOMETRY = ((12, 8, 8), (40, 40, 20), (16, 16, 16, 16, 8))

#: relative L2 error of a logit row against the reference row.  The
#: served model runs at the default matmul precision (one bf16 pass per
#: f32 matmul, relative error ~2^-9 each) through 8 layers; the
#: reference runs every matmul in full f32.  The phase also prints the
#: unplanned model's error at default precision: what precision alone
#: costs, without any kernel.
LOGIT_RTOL = 5e-2
#: relative difference of the step-0 loss between the Pallas plan and the
#: same plan forced onto the jnp executor (both at default precision; only
#: the TT contractions differ)
LOSS_RTOL = 1e-3
#: max |out - ref| / max |ref| of one kernel call against a full-f32
#: reference.  A dataflow that lost its k-fold partials would be off by
#: O(1).
GEMM_RTOL = 2e-2


class PhaseFailed(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise PhaseFailed(msg)


def rel_l2(a, b) -> float:
    import numpy as np

    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def has_mosaic_kernel(hlo_text: str) -> bool:
    return "tpu_custom_call" in hlo_text


def backends_by_stream(log) -> dict:
    out: dict = {}
    for r in log:
        key = r.get("stream") or r.get("phase", "fwd")
        out.setdefault(key, set()).add(r["backend"])
    return {k: sorted(v) for k, v in sorted(out.items())}


def first_logits(engine, requests, first=None):
    """Prefill logits, first-decode-step logits and first tokens.

    Requests fill the decode slots in groups, exactly as the scheduler
    admits them: prefill alone, admit into a lane, then one decode step
    with every lane's first token — its greedy pick, or ``first[i]`` when
    given, so that two models decode from the same tokens.
    """
    import numpy as np

    pre, dec, toks = [], [], []
    n = engine.n_slots
    for g in range(0, len(requests), n):
        group = requests[g:g + n]
        caches = engine.fresh_caches()
        tok = np.zeros(n, np.int32)
        pos = np.zeros(n, np.int32)
        for slot, r in enumerate(group):
            row, small = engine.prefill_request(r.prompt)
            caches = engine.admit(caches, small, slot)
            pre.append(row)
            tok[slot] = (int(np.argmax(row)) if first is None
                         else first[g + slot])
            toks.append(int(tok[slot]))
            pos[slot] = len(r.prompt)
        rows, caches = engine.decode(tok, pos, caches)
        dec.extend(rows[:len(group)])
    return np.stack(pre), np.stack(dec), toks


def compare_logits(label, got, ref, *, check_tol: bool = True) -> None:
    (gp, gd, _), (rp, rd, _) = got, ref
    errs = [rel_l2(g, r) for g, r in zip(gp, rp)]
    errs_d = [rel_l2(g, r) for g, r in zip(gd, rd)]
    print(f"  {label}: prefill logits rel-L2 max {max(errs)!r}, "
          f"first decode step rel-L2 max {max(errs_d)!r} "
          + (f"(tolerance {LOGIT_RTOL})" if check_tol else ""))
    if not check_tol:
        return
    check(all(math.isfinite(e) for e in errs + errs_d),
          f"{label}: non-finite logits")
    check(max(errs + errs_d) <= LOGIT_RTOL,
          f"{label}: logits differ beyond {LOGIT_RTOL}")


def demoted(log, plans: dict) -> list:
    """Records of layers planned for Pallas that ran on the jnp executor."""
    want = {s: {lp.name: lp.backend for lp in p.layers}
            for s, p in plans.items()}
    return [r for r in log if r["backend"] == "jnp"
            and want.get(r["stream"], {}).get(r["name"], "jnp") != "jnp"]


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def phase_plans(ctx) -> None:
    from repro.dse_cli import run_dse_plan

    os.makedirs(OUT, exist_ok=True)
    plans = {
        "prefill": run_dse_plan(ARCH, hw=HW, phase="prefill",
                                serve_slots=SLOTS)[1],
        "decode": run_dse_plan(ARCH, hw=HW, tokens=SLOTS, phase="decode",
                               serve_slots=SLOTS)[1],
        "train": run_dse_plan(ARCH, hw=HW, mode="train")[1],
    }
    for name, plan in plans.items():
        path = os.path.join(OUT, f"{name}.json")
        plan.save(path)
        ctx[f"{name}_path"] = path
        print(f"  {name} plan: {len(plan.layers)} layers at {plan.tokens} "
              f"tokens, " + ", ".join(
                  f"{lp.name}={lp.backend}/{lp.dataflow}" for lp in plan.layers))
    ctx["plans"] = plans


def phase_serve(ctx) -> None:
    from repro.launch import serve

    run = serve.run([
        "--arch", ARCH, "--schedule", "continuous", "--batch", str(SLOTS),
        "--n-requests", str(N_REQUESTS), "--prompt-len", str(PROMPT_LEN),
        "--gen", str(GEN), "--prompt-bucket", str(BUCKET),
        "--plan-prefill", ctx["prefill_path"],
        "--plan-decode", ctx["decode_path"], "--strict-plan"])
    check(run.code == 0, f"serve exited {run.code}")
    check(not run.demoted, f"{len(run.demoted)} demoted executions")
    comps = run.result.completions
    check(len(comps) == N_REQUESTS, f"{len(comps)} of {N_REQUESTS} served")
    check(all(len(c.tokens) == GEN for c in comps), "short completion")
    print(f"  backends by stream: {backends_by_stream(run.log)}; "
          f"demoted executions: {len(run.demoted)}")
    for phase, text in run.engine.lowered_text(PROMPT_LEN).items():
        check(has_mosaic_kernel(text), f"{phase} step calls no Mosaic kernel")
    print("  prefill and decode steps call Mosaic kernels (tpu_custom_call)")
    ctx["serve"] = run


def phase_logits(ctx) -> None:
    import jax

    from repro.serve import ServeEngine

    run = ctx["serve"]
    eng = run.engine

    def unplanned():
        return ServeEngine(eng.cfg, eng.params, n_slots=SLOTS,
                           max_seq=eng.max_seq, prompt_bucket=BUCKET)

    got = first_logits(eng, run.requests)
    with jax.default_matmul_precision("highest"):
        ref = first_logits(unplanned(), run.requests, got[2])
    base = first_logits(unplanned(), run.requests, got[2])
    compare_logits("unplanned at default precision vs reference", base, ref,
                   check_tol=False)
    compare_logits("planned vs unplanned float32 reference", got, ref)


def phase_train(ctx) -> None:
    from repro.launch import train

    def argv(tag, steps, *extra):
        ck = os.path.join(OUT, f"ckpt_{tag}")
        shutil.rmtree(ck, ignore_errors=True)
        return ["--arch", ARCH, "--plan", ctx["train_path"],
                "--steps", str(steps), "--batch", str(TRAIN_BATCH),
                "--seq", str(TRAIN_SEQ), "--log-every", "1",
                "--ckpt-every", "1000", "--ckpt-dir", ck, *extra]

    run = train.run(argv("pallas", TRAIN_STEPS))
    losses = [run.losses.get(s) for s in range(TRAIN_STEPS)]
    check(all(v is not None and math.isfinite(v) for v in losses),
          f"losses {losses}")
    check(run.recoveries == 0, f"{run.recoveries} fault-loop recoveries")
    bwd = sorted({r["backend"] for r in run.log if r.get("phase") == "bwd"})
    check(bwd and "jnp" not in bwd, f"backward backends {bwd}")
    text = run.jit_step.lower(*run.example_args).as_text()
    check(has_mosaic_kernel(text), "train step calls no Mosaic kernel")
    print(f"  losses {losses}, recoveries {run.recoveries}, backward "
          f"backends {bwd}; the step calls Mosaic kernels")
    ref = train.run(argv("jnp", 1, "--plan-backend", "jnp"))
    d = abs(losses[0] - ref.losses[0]) / abs(ref.losses[0])
    print(f"  step-0 loss {losses[0]!r} vs jnp executor {ref.losses[0]!r}: "
          f"relative difference {d!r} (tolerance {LOSS_RTOL})")
    check(d <= LOSS_RTOL, "step-0 loss differs from the jnp executor")


def phase_kernels(ctx) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import fusion
    from repro.core.paths import find_topk_paths
    from repro.core.tensor_network import tt_linear_network
    from repro.kernels import ops, ref
    from repro.plan import LayerPlan, Tiling
    from repro.plan.executor import planned_tt_linear

    def max_err(out, want) -> float:
        out, want = np.asarray(out), np.asarray(want)
        return float(np.max(np.abs(out - want)) / np.max(np.abs(want)))

    M, K, N = GEMM_SHAPE
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((M, K), dtype=np.float32))
    b = jnp.asarray(rng.standard_normal((K, N), dtype=np.float32))
    want = jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST)
    for df in ("OS", "WS", "IS"):
        kw = dict(dataflow=df, block_m=GEMM_BLOCK, block_k=GEMM_BLOCK,
                  block_n=GEMM_BLOCK)
        check(has_mosaic_kernel(ops.gemm.lower(a, b, **kw).as_text()),
              f"{df} gemm is not a Mosaic kernel")
        err = max_err(ops.gemm(a, b, **kw), want)
        print(f"  tt_gemm {df}: max error / max |a@b| = {err!r} "
              f"({K // GEMM_BLOCK} k-folds, tolerance {GEMM_RTOL})")
        check(err <= GEMM_RTOL, f"{df} gemm disagrees with a @ b")

    # a fused path segment (the tied-embedding model never runs one) at
    # the plans' head geometry, against the jnp executor in full f32
    in_modes, out_modes, ranks = FUSED_GEOMETRY
    tn = tt_linear_network(512, in_modes, out_modes, ranks)
    steps = tuple(tuple(s) for s in find_topk_paths(tn, k=1)[0].steps)
    segs = fusion.segment_path(tn, steps, block_tokens=256,
                               budget_bytes=8 * 2**20)
    check(fusion.has_fused(segs), f"no fused run in {segs}")
    lp = LayerPlan(name="fused", path_index=0, path_steps=steps,
                   dataflow="OS", partitioning=(1, 1), backend="tt_gemm",
                   tiling=Tiling(block_tokens=256), segments=segs)
    x = jnp.asarray(rng.standard_normal((512, math.prod(in_modes)),
                                        dtype=np.float32))
    cores = [jnp.asarray(rng.standard_normal(n.dims, dtype=np.float32))
             for n in tn.nodes if n.kind != "input"]
    got = jax.jit(lambda x, cs: planned_tt_linear(
        lp, x, cs, in_modes, out_modes, ranks))(x, cores)
    with jax.default_matmul_precision("highest"):
        want = ref.tt_linear_ref(x, cores, tn, find_topk_paths(tn, k=1)[0])
    err = max_err(got, want)
    print(f"  fused segments {segs}: max error / max |ref| = {err!r} "
          f"(tolerance {GEMM_RTOL})")
    check(err <= GEMM_RTOL, "fused segment disagrees with the jnp executor")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def phase_sharded_serve(ctx) -> None:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_config
    from repro.dse_cli import run_dse_plan
    from repro.launch.mesh import make_rules, make_test_mesh
    from repro.models import api
    from repro.models.config import ShapeConfig
    from repro.plan import execution_log, reset_execution_log
    from repro.serve import Scheduler, ServeEngine, ServePolicy
    from repro.serve import synthetic_trace
    from repro.sharding import use_rules

    cfg = get_config(ARCH)
    requests = synthetic_trace(N_REQUESTS, cfg.vocab, prompt_len=PROMPT_LEN,
                               gen=GEN, seed=0)
    max_seq = PROMPT_LEN + GEN - 1
    mesh = make_test_mesh()
    rules = make_rules(cfg, ShapeConfig("chip_smoke", max_seq, SLOTS,
                                        "decode"), mesh)
    # the shard count comes from the installed mesh's token axes
    with use_rules(rules):
        plans = {
            "prefill": run_dse_plan(ARCH, hw=HW, phase="prefill",
                                    serve_slots=SLOTS)[1],
            "decode": run_dse_plan(ARCH, hw=HW, tokens=SLOTS,
                                   phase="decode", serve_slots=SLOTS)[1],
        }
    sh = plans["prefill"].sharding
    check(sh is not None, "plans carry no sharding")
    print(f"  mesh {dict(mesh.shape)}; plans searched at "
          f"{sh.n_shards} token shards {sh.axes}")
    params = api(cfg).init_params(jax.random.PRNGKey(0))

    def engine(p):
        return ServeEngine(cfg, p, n_slots=SLOTS, max_seq=max_seq,
                           prompt_bucket=BUCKET,
                           prefill_plan=plans["prefill"],
                           decode_plan=plans["decode"], arch=ARCH)

    reset_execution_log()
    eng_s = engine(jax.device_put(params, NamedSharding(mesh, P())))
    with use_rules(rules):
        res_s = Scheduler(eng_s, ServePolicy("continuous")).run(requests)
        logits_s = first_logits(eng_s, requests)
        texts = eng_s.lowered_text(PROMPT_LEN)
    log_s = execution_log()
    reset_execution_log()
    eng_1 = engine(params)
    res_1 = Scheduler(eng_1, ServePolicy("oneshot")).run(requests)
    logits_1 = first_logits(eng_1, requests, logits_s[2])
    log_1 = execution_log()

    bad = demoted(log_s, plans) + demoted(log_1, plans)
    check(not bad, f"{len(bad)} demoted executions")
    meshes = sorted({r["mesh"] for r in log_s if r["backend"] != "jnp"})
    check(meshes and "" not in meshes,
          f"Pallas records of the sharded run: meshes {meshes}")
    for phase, text in texts.items():
        check(has_mosaic_kernel(text), f"sharded {phase} calls no Mosaic kernel")
    same = sum(a == b for a, b in zip(res_s.tokens_by_rid().items(),
                                      res_1.tokens_by_rid().items()))
    print(f"  sharded backends {backends_by_stream(log_s)} on mesh {meshes}; "
          f"one-chip backends {backends_by_stream(log_1)}; demoted "
          f"executions: 0; {same}/{N_REQUESTS} requests generated "
          "identical tokens")
    compare_logits("4-chip continuous vs 1-chip oneshot", logits_s, logits_1)


# ---------------------------------------------------------------------------

ONE_CHIP = (("plans", phase_plans), ("serve", phase_serve),
            ("logits", phase_logits), ("train", phase_train),
            ("kernels", phase_kernels))
FOUR_CHIPS = (("sharded-serve", phase_sharded_serve),)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only sharded serving on a 4-chip mesh")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"error: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    import jax
    from jax import monitoring

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"error: JAX finds no TPU (default backend {backend!r})",
              file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if len(jax.devices()) < want:
        print(f"error: {want} chips needed, {len(jax.devices())} found",
              file=sys.stderr)
        return 1

    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    events: dict = {}
    monitoring.register_event_listener(
        lambda name, **kw: events.__setitem__(name, events.get(name, 0) + 1))
    monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: events.__setitem__(
            name + ":s", events.get(name + ":s", 0.0) + secs))

    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"compilation cache {cache_dir}")
    ctx: dict = {}
    for name, fn in (FOUR_CHIPS if args.four_chips else ONE_CHIP):
        t0 = time.perf_counter()
        c0 = events.get("/jax/core/compile/backend_compile_duration:s", 0.0)
        print(f"phase {name}:", flush=True)
        try:
            fn(ctx)
        except PhaseFailed as e:
            print(f"FAIL {name}: {e}", file=sys.stderr)
            return 1
        wall = time.perf_counter() - t0
        comp = events.get("/jax/core/compile/backend_compile_duration:s",
                          0.0) - c0
        print(f"phase {name}: ok in {wall:.1f} s ({comp:.1f} s compiling)",
              flush=True)
    print(f"compilation cache: "
          f"{events.get('/jax/compilation_cache/cache_hits', 0)} hits, "
          f"{events.get('/jax/compilation_cache/cache_misses', 0)} misses")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
