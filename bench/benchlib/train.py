"""A training cell: the program's jitted train step on its data pipeline.

Set-up searches the train plan with the program's DSE, installs it, makes
the weights on the device from the seed and builds the step the training
driver builds (``repro.launch.steps.make_train_step`` with AdamW and a
warm-up cosine schedule) over batches of ``repro.data``'s pipeline.  It
then drives that one step object through its first three steps, which
compile it and give the correctness check its readings: each step's
loss, the first gradient as the optimizer got it (AdamW's first moment
after one step is (1 - b1) g), and each parameter's change after three
steps.  The window continues the same step, state and feed from step 3
for ``--seconds``, and ends in ``block_until_ready``.  No checkpoint is
written.
"""

from __future__ import annotations

import contextlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from . import weights


def leaf_norms(tree) -> list[float]:
    """Float32 L2 norm of each leaf, in tree order."""
    fn = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                            for x in jax.tree.leaves(t)])
    return [float(v) for v in fn(tree)]


def diff_norms(a, b) -> list[float]:
    fn = jax.jit(lambda a, b: [
        jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)
                                    - y.astype(jnp.float32))))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))])
    return [float(v) for v in fn(a, b)]


class TrainCell:
    """Set-up, window and check of one training cell."""

    CHECK_STEPS = 3

    def __init__(self, cell, seed: int, ref_module) -> None:
        from repro.data import make_pipeline
        from repro.dse_cli import run_dse_plan
        from repro.launch.steps import make_train_step
        from repro.models import api
        from repro.optim import adamw_init, linear_warmup_cosine
        from repro.plan import execution_log, reset_execution_log

        from .spec import program_config

        self.cell, self.seed, self.ref = cell, seed, ref_module
        tr, conf = cell.traffic, cell.config
        cfg = self.cfg = program_config(conf)
        self.batch, self.seq = int(tr["batch"]), int(tr["seq"])
        plan = run_dse_plan(conf["arch"], hw=tr["plan"]["hw"], mode="train",
                            smoke=bool(conf.get("smoke")),
                            tokens=self.batch * self.seq)[1]
        reset_execution_log()
        m = api(cfg, plan=plan)
        opt = tr["optimizer"]
        self.opt_cfg = opt
        lr = linear_warmup_cosine(opt["lr"], opt["warmup"], opt["total_steps"])
        step_fn = make_train_step(cfg, lr=lr,
                                  weight_decay=opt["weight_decay"],
                                  clip_norm=opt["clip_norm"])
        self.pipe = make_pipeline(cfg.vocab, self.seq, self.batch, seed=seed)
        self.init_shapes = jax.eval_shape(m.init_params, jax.random.PRNGKey(0))
        params = weights.make(self.init_shapes, seed, cfg.dtype,
                              conf["tt_factorization"]["d"])
        p0 = jax.tree.map(jnp.copy, params)
        state = adamw_init(params)
        self.jit_step = jax.jit(step_fn, donate_argnums=(0, 1))
        self.losses, self.batches = [], []
        for s in range(self.CHECK_STEPS):
            b = self.pipe.batch(s)
            self.batches.append(b)
            params, state, metrics = self.jit_step(
                params, state, {k: jnp.asarray(v) for k, v in b.items()})
            self.losses.append(float(metrics["loss"]))
            if s == 0:   # AdamW's first moment after one step: (1 - b1) g
                self.grad_norms = [v / (1.0 - opt["b1"])
                                   for v in leaf_norms(state.m)]
        self.change_norms = diff_norms(params, p0)
        del p0
        self.params, self.state = params, state
        self.kernels = {r["name"] for r in execution_log()
                        if r["backend"] != "jnp"}

    def window(self, seconds: float, counter, trace: bool) -> dict:
        def span(name):
            return (jax.profiler.TraceAnnotation(name) if trace
                    else contextlib.nullcontext())

        params, state = self.params, self.state
        step = self.CHECK_STEPS
        t0 = time.perf_counter()
        with span("bench.window"):
            while time.perf_counter() < t0 + seconds:
                with span("bench.batch"):
                    b = self.pipe.batch(step)
                    b = {k: jnp.asarray(v) for k, v in b.items()}
                with span("bench.step"):
                    params, state, _ = self.jit_step(params, state, b)
                step += 1
            with span("bench.wait"):
                jax.block_until_ready((params, state))
        t1 = time.perf_counter()
        self.params, self.state = params, state
        n = step - self.CHECK_STEPS
        for _ in range(n):
            counter.train_step(self.batch, self.seq, self.kernels)
        return {"t_start": t0, "window_s": t1 - t0, "attempted": n,
                "train_tokens_per_s": n * self.batch * self.seq / (t1 - t0)}

    def release(self) -> None:
        del self.params, self.state, self.jit_step
        import gc

        gc.collect()

    def check(self, controls=()):
        """``(readings, limits, failed)``: the three gaps of the program's
        first steps against the float32 reference.  ``controls`` names
        lower-precision references (``bf16``, ``fp8``) and planted
        faults (``half_batch``) whose gaps to read beside them."""
        conf, opt = self.cell.config, self.opt_cfg
        arch = self.ref.arch_of(conf)
        params = weights.make(self.init_shapes, self.seed, self.cfg.dtype,
                              conf["tt_factorization"]["d"])
        ref32 = reference_steps(self.ref, arch, params, self.batches, opt,
                                "f32")
        got = {"losses": self.losses, "grad_norms": self.grad_norms,
               "change_norms": self.change_norms}
        readings = gaps(got, ref32)
        for c in controls:
            if c == "half_batch":
                other = reference_steps(self.ref, arch, params, self.batches,
                                        opt, "f32", keep_rows=self.batch // 2)
            else:
                other = reference_steps(self.ref, arch, params, self.batches,
                                        opt, c)
            for k, v in gaps(other, ref32).items():
                readings[f"control.{c}.{k}"] = v
        limits = dict(self.cell.limits["limits"])
        failed = sum(readings[k] > limits[k] for k in limits)
        return readings, limits, failed


def reference_steps(ref, arch, params, batches, opt: dict, mode: str,
                    rows_per_block: int = 2, keep_rows=None) -> dict:
    """The plain reference's first steps on the same batches: losses, the
    first clipped gradient's leaf norms, and each leaf's change.

    ``mode`` ``"f32"`` keeps parameters and optimizer state in float32, as
    the configuration states; any other mode (a control) computes the
    whole step in bfloat16, parameters, gradients and optimizer state
    included, with the matmuls of that mode.

    ``keep_rows`` keeps only that many rows of each batch (a fault: part
    of the batch left out, the mean taken over the rest)."""
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
    wd, clip = opt["weight_decay"], opt["clip_norm"]

    def lr_at(step):
        peak, warm, total = opt["lr"], opt["warmup"], opt["total_steps"]
        if step <= warm:
            return peak * step / max(warm, 1)
        t = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
        return peak * (0.1 + 0.9 * 0.5 * (1.0 + np.cos(np.pi * t)))

    @jax.jit
    def block_grad(p, toks, labels):
        return jax.value_and_grad(
            lambda q: ref.loss(q, toks, labels, arch, mode))(p)

    @jax.jit
    def update(p, m, v, g, step, lr):
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
        g = jax.tree.map(lambda x: x * jnp.minimum(1.0, clip / (gn + 1e-12)),
                         g)
        m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
        v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)
        c1, c2 = 1 - b1 ** step, 1 - b2 ** step
        p = jax.tree.map(
            lambda p_, m_, v_: (p_ - lr * ((m_ / c1) / (jnp.sqrt(v_ / c2) + eps)
                                           + wd * p_)).astype(p_.dtype),
            p, m, v)
        return p, m, v, g

    store = jnp.float32 if mode == "f32" else jnp.bfloat16
    p = jax.tree.map(lambda x: x.astype(store), params)
    p0 = p
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    losses, grad_norms = [], None
    for s, batch in enumerate(batches):
        toks, labels = batch["tokens"], batch["labels"]
        if keep_rows is not None:
            toks, labels = toks[:keep_rows], labels[:keep_rows]
        total, g = 0.0, None
        for r in range(0, toks.shape[0], rows_per_block):
            lv, gb = block_grad(p, jnp.asarray(toks[r:r + rows_per_block]),
                                jnp.asarray(labels[r:r + rows_per_block]))
            total += float(lv)
            g = gb if g is None else jax.tree.map(jnp.add, g, gb)
        n = toks.size
        g = jax.tree.map(lambda x: x / n, g)
        losses.append(total / n)
        p, m, v, g = update(p, m, v, g, float(s + 1), float(lr_at(s + 1)))
        if s == 0:
            grad_norms = leaf_norms(g)
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": diff_norms(p, p0)}


def gaps(got: dict, ref: dict, *, quiet: float = 1e-3) -> dict:
    """The three numbers compared, each the worst over steps or leaves.

    A leaf's gap is the distance between the two norms over the larger of
    the reference's norm of that leaf and of the median leaf.  Leaves
    whose reference gradient is below ``quiet`` of the median leaf's move
    under AdamW by round-off alone, and are left out of the change."""
    loss = max(abs(a - b) / abs(b) for a, b in zip(got["losses"],
                                                   ref["losses"]))

    def worst(a, b, keep):
        med = float(np.median(b))
        return max(abs(x - y) / max(y, med)
                   for x, y, k in zip(a, b, keep) if k)

    gmed = float(np.median(ref["grad_norms"]))
    moving = [g >= quiet * gmed for g in ref["grad_norms"]]
    return {
        "loss_gap": loss,
        "grad_gap": worst(got["grad_norms"], ref["grad_norms"],
                          [True] * len(moving)),
        "change_gap": worst(got["change_norms"], ref["change_norms"], moving),
    }
