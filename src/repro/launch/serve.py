"""Serving driver: request scheduler + phase-specialized execution plans.

  PYTHONPATH=src python -m repro.launch.serve --arch tt-lm-100m --smoke \
      --schedule continuous --batch 4 --n-requests 8 --prompt-len 32 --gen 16

A thin CLI over :mod:`repro.serve`: requests (synthetic sustained load,
or a ``--trace`` JSON) flow through the continuous-batching scheduler —
batch-1 prefills admitted into free decode lanes of a fixed-width decode
batch.  ``--schedule oneshot`` runs the same engine at concurrency 1
(the bit-exact per-request reference).

``--plan plan.json`` installs one DSE-compiled execution plan (emitted
by ``python -m repro.dse --emit-plan``, see docs/plan_format.md) for
both phases; ``--plan-prefill``/``--plan-decode`` install a
phase-specialized pair (``--emit-plan-pair``) so each stream contracts
under its own searched paths/backends/tilings.  ``--strict-plan`` makes
an entirely unplanned run (a plan was given but no projection executed
under it) a non-zero exit.
"""

from __future__ import annotations

import argparse
import dataclasses
import sys
import time
from typing import Optional

import jax

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_rules, make_test_mesh
from repro.models import api
from repro.models.config import ShapeConfig
from repro.serve import (
    Scheduler,
    ServeEngine,
    ServePolicy,
    load_trace,
    summarize,
    synthetic_trace,
)
from repro.sharding import use_rules

EXIT_UNPLANNED = 3   # --strict-plan: plan given, zero planned executions
EXIT_DEMOTED = 4     # --strict-plan: planned Pallas layers fell back to jnp


def _load_and_describe(path: str, label: str):
    from repro.plan import load_plan

    plan = load_plan(path)
    print(f"installed {label}: arch={plan.arch} hw={plan.hw} "
          f"strategy={plan.strategy} ({len(plan.layers)} layer plans)"
          + (f" [phase {plan.phase}]" if plan.phase else ""))
    print(f"plan tilings: {plan.tilings}"
          + (" (autotuned — repro.tune)" if plan.tilings == "measured" else ""))
    if plan.hardware is not None:
        h = plan.hardware
        print(f"plan hardware: {h.name} ({h.pe_rows}x{h.pe_cols} PEs, "
              f"sram {h.sram_input_bytes // 1024}+"
              f"{h.sram_output_bytes // 1024} KiB, "
              f"bw {h.dram_words_per_cycle:g} words/cycle)")
    return plan


@dataclasses.dataclass
class ServeRun:
    """What one serving run did: exit code, engine, requests, results.

    ``demoted`` holds the execution records of layers planned for a
    Pallas backend that ran on the jnp executor instead.
    """

    code: int
    engine: Optional[ServeEngine] = None
    requests: tuple = ()
    result: object = None
    log: tuple = ()
    demoted: tuple = ()


def main(argv=None) -> int:
    return run(argv).code


def run(argv=None) -> ServeRun:
    """The serving driver: parse ``argv``, serve, print a summary."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tt-lm-100m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--dense", action="store_true")
    ap.add_argument("--schedule", default="oneshot",
                    choices=("oneshot", "continuous"),
                    help="oneshot: each request decodes alone (default); "
                         "continuous: admit into free decode lanes each step")
    ap.add_argument("--batch", type=int, default=4,
                    help="decode slot width (fixed decode batch; default 4)")
    ap.add_argument("--n-requests", type=int, default=None,
                    help="synthetic-trace request count (default: --batch)")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="mean inter-arrival gap in decode steps "
                         "(0 = all requests arrive at t=0)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="request-trace JSON (repro.serve.load_trace) "
                         "instead of the synthetic trace")
    ap.add_argument("--prompt-bucket", type=int, default=8,
                    help="round prompt lengths up to a multiple (bounds "
                         "prefill trace count; default 8)")
    ap.add_argument("--max-admissions", type=int, default=None,
                    help="admission-policy cap per step (default: fill "
                         "every free lane)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--plan", default=None, metavar="PATH",
                    help="install one DSE execution plan for both phases "
                         "(repro.dse --emit-plan)")
    ap.add_argument("--plan-prefill", default=None, metavar="PATH",
                    help="prefill-phase plan of a pair "
                         "(repro.dse --emit-plan-pair)")
    ap.add_argument("--plan-decode", default=None, metavar="PATH",
                    help="decode-phase plan of a pair")
    ap.add_argument("--strict-plan", action="store_true",
                    help="exit non-zero if a plan was given but the run "
                         "executed no planned projection (entirely "
                         "UNPLANNED run), or if any layer planned for a "
                         "Pallas backend silently fell back to the jnp "
                         "executor (DEMOTED run)")
    args = ap.parse_args(argv)

    if args.plan and (args.plan_prefill or args.plan_decode):
        ap.error("--plan is mutually exclusive with "
                 "--plan-prefill/--plan-decode")

    cfg = get_config(args.arch, tt=not args.dense, smoke=args.smoke)
    if cfg.family == "encdec":
        print("error: the serve scheduler is causal-LM only; encdec runs "
              "its own scalar-position decoder", file=sys.stderr)
        return ServeRun(2)

    any_plan = bool(args.plan or args.plan_prefill or args.plan_decode)
    if args.plan:
        prefill_plan = decode_plan = _load_and_describe(args.plan, "plan")
    else:
        prefill_plan = (_load_and_describe(args.plan_prefill, "prefill plan")
                        if args.plan_prefill else None)
        decode_plan = (_load_and_describe(args.plan_decode, "decode plan")
                       if args.plan_decode else None)

    if args.trace:
        requests = load_trace(args.trace, cfg.vocab, seed=args.seed)
    else:
        n = args.n_requests if args.n_requests is not None else args.batch
        requests = synthetic_trace(
            n, cfg.vocab, prompt_len=args.prompt_len, gen=args.gen,
            arrival_rate=args.arrival_rate, seed=args.seed)

    bucket = args.prompt_bucket
    max_seq = max(
        max(-(-len(r.prompt) // bucket) * bucket,
            len(r.prompt) + r.max_new_tokens - 1)
        for r in requests) if requests else bucket

    shape = ShapeConfig("cli", max_seq, args.batch, "decode")
    mesh = make_test_mesh()
    rules = make_rules(cfg, shape, mesh)

    from repro.plan import (
        execution_log,
        execution_log_dropped,
        reset_execution_log,
    )

    enable_compile_cache()
    reset_execution_log()
    t0 = time.perf_counter()
    with use_rules(rules):
        # install a plan before init: v4 plans embed searched
        # factorizations, which set the TT parameter shapes themselves
        init_plan = prefill_plan if prefill_plan is not None else decode_plan
        m = api(cfg, plan=init_plan) if init_plan is not None else api(cfg)
        params = m.init_params(jax.random.PRNGKey(0))
        try:
            engine = ServeEngine(
                cfg, params, n_slots=args.batch, max_seq=max_seq,
                prompt_bucket=bucket, prefill_plan=prefill_plan,
                decode_plan=decode_plan, arch=args.arch)
        except ValueError as e:
            print(f"error: plan/model mismatch: {e}", file=sys.stderr)
            return ServeRun(2)
        sched = Scheduler(
            engine,
            ServePolicy(schedule=args.schedule,
                        max_admissions_per_step=args.max_admissions),
            temperature=args.temperature, seed=args.seed)
        result = sched.run(requests)
    total_s = time.perf_counter() - t0

    s = summarize(result)
    print(f"schedule {args.schedule}: {s['n_requests']} requests over "
          f"{s['steps']} steps, {result.n_slots} decode slots, "
          f"occupancy {s['mean_occupancy']:.2f}")
    print(f"throughput: {s['gen_tok_s']:.1f} gen tok/s, "
          f"{s['total_tok_s']:.1f} total tok/s "
          f"({s['generated_tokens']} generated / {s['total_tokens']} total "
          f"tokens, serve {s['wall_s']*1e3:.1f} ms, "
          f"end-to-end {total_s*1e3:.1f} ms)")
    print(f"latency: ttft p50/p95 {s['ttft_p50_ms']:.1f}/"
          f"{s['ttft_p95_ms']:.1f} ms, request p50/p95 "
          f"{s['latency_p50_ms']:.1f}/{s['latency_p95_ms']:.1f} ms")
    if result.completions:
        c0 = result.completions[0]
        print(f"generated token ids (rid {c0.rid}): "
              f"{list(c0.tokens)[:16]}")

    log, demoted = (), ()
    code = 0
    if any_plan:
        log = execution_log()
        by_stream: dict[str, dict[str, int]] = {}
        for r in log:
            st = by_stream.setdefault(r["stream"] or "?", {})
            st[r["backend"]] = st.get(r["backend"], 0) + 1
        n_pre = sum(by_stream.get("prefill", {}).values())
        n_dec = sum(by_stream.get("decode", {}).values())
        print(f"planned executions (trace-time): {len(log)} — "
              f"prefill stream: {n_pre}, decode stream: {n_dec}")
        for stream in ("prefill", "decode"):
            if stream in by_stream:
                print(f"  {stream} backends "
                      f"{dict(sorted(by_stream[stream].items()))}")
        tilings = sorted({
            (r["tiling"]["block_m"], r["tiling"]["block_k"],
             r["tiling"]["block_n"], r["tiling"]["block_tokens"])
            for r in log})
        if tilings:
            print("kernel tilings (block_m,k,n,tokens): "
                  + " ".join(str(t) for t in tilings))
        seg_recs = [r for r in log if r.get("segment")]
        if seg_recs:
            fused = [r for r in seg_recs
                     if r["segment"][1] - r["segment"][0] >= 2]
            n_steps = sum(r["segment"][1] - r["segment"][0] for r in fused)
            print(f"fused segments (trace-time): {len(seg_recs)} segment "
                  f"records, {len(fused)} fused chain runs ({n_steps} path "
                  "steps with VMEM-resident intermediates)")
        dropped = execution_log_dropped()
        if dropped:
            print(f"NOTE: the execution log ring dropped {dropped} oldest "
                  f"records (cap {len(log)}); trace-time counts above are "
                  "lower bounds")
        meshes = sorted({r.get("mesh", "") for r in log} - {""})
        if meshes:
            shapes = sorted({tuple(r["shard_shape"]) for r in log
                             if r.get("shard_shape")})
            print(f"sharded execution: mesh {' '.join(meshes)}, "
                  f"per-shard (tokens, d_in) "
                  + " ".join(str(s) for s in shapes))
        # a layer planned for a Pallas backend that recorded backend
        # "jnp" was demoted by the dispatcher (e.g. a mesh the problem
        # could not shard over) — surface it; --strict-plan makes it fatal
        backends_by_stream = {
            s: ({lp.name: lp.backend for lp in p.layers}
                if p is not None else {})
            for s, p in (("prefill", prefill_plan), ("decode", decode_plan))
        }
        demoted = tuple(
            r for r in log
            if r["backend"] == "jnp"
            and backends_by_stream.get(
                r["stream"], {}).get(r["name"], "jnp") != "jnp"
        )
        if demoted:
            names = sorted({r["name"] for r in demoted})
            print(f"WARNING: {len(demoted)} planned-Pallas executions were "
                  f"DEMOTED to the jnp executor ({len(names)} layers: "
                  f"{names[:4]}{'...' if len(names) > 4 else ''})",
                  file=sys.stderr)
            if args.strict_plan:
                code = EXIT_DEMOTED
        if not log:
            print(
                "WARNING: a plan was given but the run executed no planned "
                "projection — the run was entirely UNPLANNED (layer names "
                "did not line up; was the plan emitted for a different arch "
                "or tt/--dense setting?)",
                file=sys.stderr,
            )
            if args.strict_plan:
                code = EXIT_UNPLANNED
    return ServeRun(code, engine, tuple(requests), result, log, demoted)


if __name__ == "__main__":
    raise SystemExit(main())
