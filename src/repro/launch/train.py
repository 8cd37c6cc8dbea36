"""Training driver: data pipeline -> jitted step -> fault-tolerant loop.

Runs any registry config end-to-end (CPU-feasible with ``--smoke`` or the
``tt-lm-100m`` example arch).  The loop composes every substrate layer:
deterministic resumable data, AdamW + cosine schedule, optional int8
error-feedback gradient compression, async atomic checkpoints, straggler
monitoring and preemption-safe shutdown.

  PYTHONPATH=src python -m repro.launch.train --arch tt-lm-100m \
      --steps 200 --batch 8 --seq 256 --ckpt-dir /tmp/ckpt
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.data import make_pipeline
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_rules, make_test_mesh, param_shardings
from repro.launch.steps import make_train_step
from repro.models import api
from repro.models.config import ShapeConfig
from repro.optim import adamw_init, compress_init, linear_warmup_cosine
from repro.runtime import FaultTolerantLoop, StragglerMonitor
from repro.sharding import use_rules


@dataclasses.dataclass
class TrainRun:
    """What one training run did.

    ``losses`` maps each logged step to its loss; ``recoveries`` counts
    steps the fault-tolerant loop retried after an exception;
    ``jit_step`` with ``example_args`` (abstract params, optimizer state
    and batch) re-lowers the step program for inspection.
    """

    losses: dict
    recoveries: int
    jit_step: object
    example_args: tuple
    log: tuple = ()


def main(argv=None) -> int:
    run(argv)
    return 0


def run(argv=None) -> TrainRun:
    """The training driver: parse ``argv``, train, print progress."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tt-lm-100m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--dense", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--grad-compress", action="store_true")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--plan", default=None, metavar="PATH",
                    help="install a DSE execution plan (repro.dse --emit-plan, "
                         "ideally --mode train): projections contract along "
                         "the planned paths through the planned Pallas "
                         "kernels, forward AND backward — the kernels' "
                         "custom VJPs contract the plan's gradient networks, "
                         "so jax.grad crosses pallas_call end-to-end")
    ap.add_argument("--plan-backend", default=None,
                    choices=("jnp", "tt_gemm", "streaming_tt"),
                    help="force one kernel backend for every plan layer "
                         "(jnp = the pre-v2 reference behaviour)")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch, tt=not args.dense, smoke=args.smoke)
    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    mesh = make_test_mesh()
    rules = make_rules(cfg, shape, mesh)
    if args.plan:
        from repro.plan import check_plan_for_config, load_plan, reset_execution_log

        plan = load_plan(args.plan)
        problems = check_plan_for_config(plan, args.arch, cfg)
        if problems:
            raise SystemExit(
                "error: plan/model mismatch: " + "; ".join(problems))
        reset_execution_log()
        m = api(cfg, plan=plan, plan_backend=args.plan_backend)
        backends = sorted({lp.backend for lp in plan.layers})
        forced = (f", backends forced to {args.plan_backend}"
                  if args.plan_backend else "")
        print(f"installed plan {args.plan} (backends {backends}{forced})")
    else:
        m = api(cfg)
    pipe = make_pipeline(cfg.vocab, args.seq, args.batch)

    lr = linear_warmup_cosine(args.lr, args.warmup, args.steps)
    step_fn = make_train_step(cfg, lr=lr, grad_compress=args.grad_compress)

    enable_compile_cache()
    losses: dict[int, float] = {}
    with use_rules(rules):
        p_shapes = jax.eval_shape(m.init_params, jax.random.PRNGKey(0))
        p_sh = param_shardings(p_shapes, mesh)
        params = jax.jit(m.init_params, out_shardings=p_sh)(jax.random.PRNGKey(0))
        opt = adamw_init(params)
        if args.grad_compress:
            opt = (opt, compress_init(params))
        jit_step = jax.jit(step_fn, donate_argnums=(0, 1))

        mgr = CheckpointManager(args.ckpt_dir, keep=3)
        start = 0
        state = {"params": params, "opt": opt}
        if args.resume and mgr.latest_step() is not None:
            start, state = mgr.restore(state)
            print(f"resumed from step {start}")

        example_args = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=x.sharding),
            (state["params"], state["opt"],
             {k: jnp.asarray(v) for k, v in pipe.batch(start).items()}))
        monitor = StragglerMonitor()
        t_start = time.time()

        def one_step(state, step):
            batch = pipe.batch(step)
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            params, opt, metrics = jit_step(state["params"], state["opt"], batch)
            if step % args.log_every == 0:
                loss = losses[step] = float(metrics["loss"])
                gn = float(metrics["grad_norm"])
                dt = time.time() - t_start
                print(f"step {step:5d}  loss {loss:.4f}  gnorm {gn:.3f}  "
                      f"({dt:.1f}s)", flush=True)
            return {"params": params, "opt": opt}

        loop = FaultTolerantLoop(one_step, mgr, checkpoint_every=args.ckpt_every,
                                 straggler=monitor)
        state, done = loop.run(state, start, args.steps - start)
        mgr.save(done, state)
        log = ()
        if args.plan:
            from repro.plan import execution_log

            log = execution_log()
            fwd = sorted({r["backend"] for r in log
                          if r.get("phase", "fwd") == "fwd"})
            bwd = sorted({r["backend"] for r in log
                          if r.get("phase") == "bwd"})
            print(f"planned execution under grad: fwd backends {fwd}, "
                  f"bwd backends {bwd}")
            fused = [r for r in log if r.get("segment")
                     and r["segment"][1] - r["segment"][0] >= 2]
            if fused:
                print(f"fused segments under grad: {len(fused)} chain runs "
                      "(VMEM-resident intermediates)")
            meshes = sorted({r.get("mesh", "") for r in log} - {""})
            if meshes:
                print(f"sharded planned execution: mesh {' '.join(meshes)} "
                      f"(per-shard kernels via shard_map)")
        print(f"finished at step {done}; stragglers flagged: {monitor.flagged}")
    return TrainRun(losses, loop.recoveries, jit_step, example_args, log)


if __name__ == "__main__":
    raise SystemExit(main())
