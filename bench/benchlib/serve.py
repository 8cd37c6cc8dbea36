"""A serving cell: a seeded backlog through the program's scheduler.

Set-up searches the cell's prefill/decode plan pair with the program's
DSE, makes the weights on the device, builds ``repro.serve.ServeEngine``
and warms every prompt bucket the mix produces, one admission and the
decode step.  The window then runs ``repro.serve.Scheduler.run`` over the
whole backlog, every request due at its start.  ``TimedEngine`` stands
between the scheduler and the engine: it times each ``prefill_request``,
``admit`` and ``decode`` call, records when every token was produced and
which token the scheduler served, and closes the window at the first
call after ``--seconds`` by raising ``WindowClosed`` out of the
scheduler.

After the window, the correctness check takes a seeded sample of the
requests that finished, the longest among them and the others spread over
the decode lanes (one request per lane, lanes drawn from the lower and the
upper half of the batch in turn), and runs the plain reference once over
each prompt with its served tokens (all of the longest request's, the
first ``tokens_per_request`` of each other's): the number compared is the
widest gap by which a served token's logit lies below the reference's best
logit at that position.
"""

from __future__ import annotations

import contextlib
import itertools
import time

import jax
import numpy as np

from . import traffic as traffic_mod
from . import weights
from .stats import percentile


class WindowClosed(Exception):
    """Raised out of the scheduler at the first call after the window."""


class TimedEngine:
    """The engine as the scheduler sees it, timed call by call."""

    def __init__(self, engine, requests, deadline: float, counter, kernels,
                 trace: bool, logit_rows: int = 0) -> None:
        self._e = engine
        self.n_slots, self.max_seq = engine.n_slots, engine.max_seq
        self.padded_len = engine.padded_len
        self.fresh_caches = engine.fresh_caches
        self._by_prompt = {id(r.prompt): r for r in requests}
        self.deadline = deadline
        self.counter, self.kernels = counter, kernels
        self.trace = trace
        self.lanes: list = [None] * engine.n_slots
        self.pending = None
        self.served: dict[int, list] = {}     # rid -> served token ids
        self.times: dict[int, list] = {}      # rid -> when each was produced
        self.done: list[int] = []
        self.lane_of: dict[int, int] = {}     # rid -> its decode lane
        self.logit_rows = logit_rows
        self.logits: dict[int, list] = {}     # rid -> its first logit rows
        self.prefill_s = 0.0
        self.occupancy: list[int] = []
        self.t_stop = None

    def _span(self, name: str):
        if not self.trace:
            return contextlib.nullcontext()
        return jax.profiler.TraceAnnotation(name)

    def _check(self) -> None:
        now = time.perf_counter()
        if now >= self.deadline:
            self.t_stop = now
            raise WindowClosed

    def prefill_request(self, prompt):
        self._check()
        req = self._by_prompt[id(prompt)]
        t0 = time.perf_counter()
        with self._span("bench.prefill"):
            row, small = self._e.prefill_request(prompt)
        t1 = time.perf_counter()
        self.prefill_s += t1 - t0
        self.served[req.rid] = [None]
        self.times[req.rid] = [t1]
        self.logits[req.rid] = [row.copy()] if self.logit_rows else []
        self.pending = req
        self.counter.prefill(len(prompt), self.kernels["prefill"])
        return row, small

    def admit(self, caches, small, slot):
        t0 = time.perf_counter()
        with self._span("bench.admit"):
            out = self._e.admit(caches, small, slot)
        self.prefill_s += time.perf_counter() - t0
        self.lanes[slot] = self.pending
        self.lane_of[self.pending.rid] = slot
        return out

    def decode(self, tok, pos, caches):
        self._check()
        busy = [i for i, r in enumerate(self.lanes) if r is not None]
        for i in busy:      # the token the scheduler chose last call
            self.served[self.lanes[i].rid][-1] = int(tok[i])
        self.counter.decode(np.asarray(pos)[busy] + 1,
                            self.kernels["decode"])
        with self._span("bench.decode"):
            rows, caches = self._e.decode(tok, pos, caches)
        t1 = time.perf_counter()
        self.occupancy.append(len(busy))
        for i in busy:
            req = self.lanes[i]
            toks = self.served[req.rid]
            toks.append(None)
            self.times[req.rid].append(t1)
            if len(self.logits[req.rid]) < self.logit_rows:
                self.logits[req.rid].append(rows[i].copy())
            if len(toks) >= req.max_new_tokens:
                # the scheduler's greedy pick of the request's last token
                toks[-1] = int(np.argmax(rows[i]))
                self.done.append(req.rid)
                self.lanes[i] = None
        return rows, caches


def kernel_layers(log) -> dict[str, set]:
    """Per stream, the projections the execution log saw on a Pallas
    kernel (recorded when the programs were traced)."""
    out = {"prefill": set(), "decode": set()}
    for r in log:
        if r["backend"] != "jnp" and r.get("phase", "fwd") == "fwd":
            out.setdefault(r["stream"], set()).add(r["name"])
    return out


class ServeCell:
    """Set-up, window and check of one serving cell."""

    def __init__(self, cell, seed: int, ref_module) -> None:
        from repro.dse_cli import run_dse_plan
        from repro.models import api
        from repro.plan import execution_log, reset_execution_log
        from repro.serve import ServeEngine

        from .spec import program_config

        self.cell, self.seed, self.ref = cell, seed, ref_module
        tr, conf = cell.traffic, cell.config
        cfg = self.cfg = program_config(conf)
        plan = tr["plan"]
        pre = run_dse_plan(conf["arch"], hw=plan["hw"], phase="prefill",
                           smoke=bool(conf.get("smoke")),
                           tokens=plan["prefill_tokens"],
                           serve_slots=tr["n_slots"],
                           serve_gen=plan["serve_gen"])[1]
        dec = run_dse_plan(conf["arch"], hw=plan["hw"], phase="decode",
                           smoke=bool(conf.get("smoke")),
                           tokens=tr["n_slots"], serve_slots=tr["n_slots"],
                           serve_gen=plan["serve_gen"])[1]
        self.plans = {"prefill": pre, "decode": dec}
        self.init_shapes = jax.eval_shape(api(cfg).init_params,
                                          jax.random.PRNGKey(0))
        params = weights.make(self.init_shapes, seed, cfg.dtype,
                              conf["tt_factorization"]["d"])
        self.max_seq = traffic_mod.max_seq(tr)
        reset_execution_log()
        self.engine = ServeEngine(
            cfg, params, n_slots=tr["n_slots"], max_seq=self.max_seq,
            prompt_bucket=tr["prompt_bucket"], prefill_plan=pre,
            decode_plan=dec, arch=conf["arch"])
        self.requests = traffic_mod.backlog(tr, cfg.vocab, seed)
        self._warm()
        self.kernels = kernel_layers(execution_log())

    def _warm(self) -> None:
        """Compile every shape the window uses, and no other: one prefill
        per prompt bucket the mix produces, an admission, a decode step
        and the zeroed decode cache."""
        eng = self.engine
        pads = sorted({eng.padded_len(len(r.prompt)) for r in self.requests})
        caches = eng.fresh_caches()
        n = eng.n_slots
        for p in pads:
            _, small = eng.prefill_request([1] * p)
            caches = eng.admit(caches, small, 0)
        rows, caches = eng.decode(np.ones(n, np.int64),
                                  np.arange(n, dtype=np.int64), caches)
        jax.block_until_ready(caches)

    def window(self, seconds: float, counter, trace: bool) -> dict:
        from repro.serve import Scheduler, ServePolicy

        t0 = time.perf_counter()
        timed = TimedEngine(self.engine, self.requests, t0 + seconds,
                            counter, self.kernels, trace,
                            int(self.cell.traffic["check"].get("logit_rows",
                                                               0)))
        sched = Scheduler(timed, ServePolicy("continuous"))
        drained = False
        span = (jax.profiler.TraceAnnotation("bench.window") if trace
                else contextlib.nullcontext())
        with span:
            try:
                sched.run(self.requests)
                drained = True
            except WindowClosed:
                pass
        t1 = timed.t_stop if timed.t_stop is not None else time.perf_counter()
        self.timed = timed
        gaps = [(b - a) * 1e3 for ts in timed.times.values()
                for a, b in zip(ts, ts[1:])]
        n_tok = sum(len(ts) for ts in timed.times.values())
        return {
            "t_start": t0, "window_s": t1 - t0, "drained": drained,
            "tokens": n_tok, "attempted": len(timed.times),
            "finished": len(timed.done),
            "gen_tokens_per_s": n_tok / (t1 - t0),
            "tbt_p95_ms": percentile(gaps, 95) if gaps else None,
            "lane_occupancy": (float(np.mean(timed.occupancy)) / timed.n_slots
                               if timed.occupancy else None),
            "prefill_share": timed.prefill_s / (t1 - t0),
        }

    def release(self) -> None:
        """Drop the program's state before the reference runs."""
        del self.engine
        self.timed._e = None
        import gc

        gc.collect()

    def check(self, controls=()):
        """``(readings, limits, failed)``: the widest gap of the sample
        against the float32 reference and the program's logit error, and
        with ``controls`` the same of each lower-precision reference's
        own picks and logits."""
        conf = self.cell.config
        limits = dict(self.cell.limits["limits"])
        t = self.timed
        by_rid = {r.rid: r for r in self.requests}
        check = self.cell.traffic["check"]
        picked = self.sample(int(check["sample_requests"]))
        # every served token of the longest request, the first ones of
        # each of the others
        cap = int(check["tokens_per_request"])
        samples = [(by_rid[rid].prompt,
                    tuple(t.served[rid][:None if i == 0 else cap]),
                    t.logits.get(rid, [])) for i, rid in enumerate(picked)]
        if not samples:     # nothing finished: nothing shown correct
            return {"max_logit_gap": None}, limits, 0
        params = weights.make(self.init_shapes, self.seed, self.cfg.dtype,
                              conf["tt_factorization"]["d"])
        per = reference_gaps(self.ref, self.ref.arch_of(conf), params,
                             samples, ("f32",) + tuple(controls),
                             seq=self.max_seq,
                             rows=int(self.cell.traffic["output"]["max"]))
        readings = {"max_logit_gap": max(per["f32"])}
        errs = [e for es in per["f32.err"] for e in es]
        if errs:
            readings["logit_err"] = float(np.median(errs))
            readings["max_logit_err"] = float(np.max(errs))
        for m in controls:
            readings[f"control.{m}.max_logit_gap"] = max(per[m])
            errs = [e for es in per[f"{m}.err"] for e in es]
            readings[f"control.{m}.logit_err"] = float(np.median(errs))
            readings[f"control.{m}.max_logit_err"] = float(np.max(errs))
        readings["sample_tokens"] = sum(len(s[1]) for s in samples)
        readings["sample_requests"] = len(samples)
        failed = sum(g > limits["max_logit_gap"] for g in per["f32"])
        return readings, limits, failed

    def sample(self, n_requests: int) -> list[int]:
        """Ids of the longest finished request and of a seeded sample of
        the others, spread over the decode lanes: one request of each lane
        before a second of any, the lanes drawn from the lower and the
        upper half of the batch in turn, ``n_requests`` in all."""
        t = self.timed
        by_rid = {r.rid: r for r in self.requests}
        done = sorted(t.done)
        if not done:
            return []
        longest = max(done, key=lambda rid: len(by_rid[rid].prompt)
                      + len(t.served[rid]))
        rng = np.random.default_rng((self.seed, 1))
        per_lane: dict[int, list] = {}
        for rid in rng.permutation(done).tolist():
            if rid != longest:
                per_lane.setdefault(t.lane_of[rid], []).append(rid)
        half = t.n_slots // 2
        lanes = rng.permutation(sorted(per_lane)).tolist()
        low = [per_lane[x] for x in lanes if x < half]
        high = [per_lane[x] for x in lanes if x >= half]
        order = [q for pair in itertools.zip_longest(low, high)
                 for q in pair if q is not None]
        picked = [longest]
        for k in range(max(len(q) for q in order) if order else 0):
            picked += [q[k] for q in order if k < len(q)]
        return picked[:n_requests]


def reference_gaps(ref, arch, params, samples, modes, seq: int,
                   rows: int) -> dict:
    """Per mode, the widest gap of each sample, and under ``<mode>.err``
    the logit error at each position whose program logits were kept.

    Every sample is padded to ``seq`` tokens and ``rows`` positions, so
    that the reference compiles once per mode.

    ``"f32"``: how far each served token's logit lies below the float32
    reference's best, and the distance of the program's logit rows from
    the reference's.  A lower-precision mode (a control): how far the
    token that mode puts first lies below the float32 reference's best,
    and the distance of its logit rows from the reference's.  A logit
    error is ``|l - r| / |r - mean(r)|`` of one position's rows.
    """
    import jax.numpy as jnp

    def err(lg, r):
        c = r - r.mean(axis=1, keepdims=True)
        return (np.linalg.norm(lg - r, axis=1)
                / np.linalg.norm(c, axis=1)).tolist()

    fn = jax.jit(ref.logits_at, static_argnames=("arch", "mode"))
    out: dict[str, list] = {}
    for m in modes:
        out[m], out[f"{m}.err"] = [], []
    for prompt, served, kept in samples:
        toks = list(prompt) + list(served[:-1])
        tokens = np.zeros((1, seq), np.int32)
        tokens[0, :len(toks)] = toks
        cols = np.arange(len(prompt) - 1, len(toks))
        n = len(cols)
        cols_p = np.concatenate([cols, np.full(rows - n, cols[-1])])
        zeros = np.zeros(rows, np.int32)
        ref32 = np.asarray(fn(params, jnp.asarray(tokens), zeros, cols_p,
                              arch=arch, mode="f32"))[:n]
        best = ref32.max(axis=1)
        k = len(kept)
        for mode in modes:
            if mode == "f32":
                pick = np.asarray(served)
                if k:
                    out["f32.err"].append(err(np.stack(kept), ref32[:k]))
            else:
                low = np.asarray(fn(params, jnp.asarray(tokens), zeros,
                                    cols_p, arch=arch, mode=mode))[:n]
                pick = low.argmax(axis=1)
                out[f"{mode}.err"].append(err(low[:max(k, 1)],
                                              ref32[:max(k, 1)]))
            gap = best - ref32[np.arange(n), pick]
            out[mode].append(float(gap.max()))
    return out
