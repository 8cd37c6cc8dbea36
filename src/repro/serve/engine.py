"""Phase-split serving engine: batch-1 prefill + fixed-width decode.

Bit-exactness contract
----------------------
XLA GEMMs are *not* batch-size invariant (an M=1 and an M=3 matmul may
differ in the last ulp), so the scheduler never compares runs at
different widths.  Instead both serving modes share one structural
shape:

* every prompt prefills alone at batch 1 (bucket-padded to a small set
  of lengths so prefill traces are reused), and
* every decode step runs at the engine's fixed slot width ``n_slots``
  with a per-lane ``(B,)`` cache position vector (free lanes idle at
  position 0).

Lane *i*'s decode result depends only on lane *i*'s cache row and
position (verified bit-identical to a solo scalar-position decode), so
one-shot serving (concurrency 1 on the same engine) and continuous
batching produce identical per-request token ids.

Phase-specialized plans
-----------------------
The engine holds an optional prefill/decode :class:`ExecutionPlan` pair.
Each phase's calls run under :func:`repro.nn.plan_context` with its own
plan and inside :func:`repro.plan.execution_stream`, so the execution
log records which plan actually traced each contraction.  Plans are
validated against the model config (and their ``phase`` stamp) at
construction — a swapped or wrong-arch pair is rejected before any step
runs.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import ModelConfig, api
from repro.nn import plan_context
from repro.plan import ExecutionPlan, execution_stream
from repro.plan.compiler import check_plan_for_config
from repro.runtime.spans import span


class DeviceRows:
    """A decode step's ``(n_slots, V)`` logits, left on the device.

    It reads as a host array of logits and copies only what a caller
    asks for: ``rows[i]`` is lane *i*'s row (a device array), while
    ``rows.copy()`` and ``np.asarray(rows)`` copy the whole batch to the
    host (``copy`` a writable array).  ``array`` is the device array
    itself, which :func:`greedy_ids` reduces where it lies.
    """

    __slots__ = ("array",)

    def __init__(self, array: jax.Array) -> None:
        self.array = array

    @property
    def shape(self) -> tuple:
        return self.array.shape

    def __getitem__(self, key) -> jax.Array:
        return self.array[key]

    def __array__(self, dtype=None, copy=None) -> np.ndarray:
        if copy:
            return np.array(self.array, dtype=dtype)
        return np.asarray(self.array, dtype=dtype)

    def copy(self) -> np.ndarray:
        return np.array(self.array)


@jax.jit
def _argmax_rows(logits: jax.Array) -> jax.Array:
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def greedy_ids(logits) -> jax.Array:
    """Greedy pick of each row of ``(n, V)`` logits (a :class:`DeviceRows`
    or any array), on the device: ``(n,)`` int32 ids.  Ties go to the
    first maximum and a row holding a NaN to its first NaN, as
    ``np.argmax`` picks."""
    if isinstance(logits, DeviceRows):
        logits = logits.array
    return _argmax_rows(logits)


class ServeEngine:
    """Model + plan pair + jitted phase kernels behind the scheduler.

    ``n_slots`` is the fixed decode width; ``prompt_bucket`` rounds
    prompt lengths up to a multiple (token 0 padding — safe for
    attention families because padded K/V sits beyond the per-lane valid
    horizon and is progressively overwritten; recurrent-state families
    force a bucket of 1 since junk tokens would advance their state).
    """

    def __init__(
        self,
        cfg: ModelConfig,
        params: Any,
        *,
        n_slots: int,
        max_seq: int,
        prompt_bucket: int = 8,
        prefill_plan: Optional[ExecutionPlan] = None,
        decode_plan: Optional[ExecutionPlan] = None,
        arch: str = "",
        plan_backend: Optional[str] = None,
    ) -> None:
        if cfg.family == "encdec":
            raise ValueError(
                "serve scheduler is causal-LM only: encdec runs its own "
                "scalar-position decoder (use launch.serve --schedule oneshot "
                "semantics via the legacy prefill/decode steps)")
        if n_slots < 1:
            raise ValueError(f"n_slots must be >= 1 (got {n_slots})")
        if prompt_bucket < 1:
            raise ValueError(f"prompt_bucket must be >= 1 (got {prompt_bucket})")
        if arch:
            for plan, phase in ((prefill_plan, "prefill"),
                                (decode_plan, "decode")):
                if plan is None:
                    continue
                problems = check_plan_for_config(plan, arch, cfg, phase=phase)
                if problems:
                    raise ValueError(
                        f"{phase} plan rejected for arch {arch!r}:\n  "
                        + "\n  ".join(problems))
        # v4 factorizations set parameter *shapes*: both phases contract
        # the same params, so a searched decomposition must be identical
        # across the pair and present on both halves
        fact = {
            phase: {lp.name: lp.factorization.triple
                    for lp in plan.layers if lp.factorization is not None}
            for plan, phase in ((prefill_plan, "prefill"),
                                (decode_plan, "decode"))
            if plan is not None
        }
        if any(fact.values()):
            if prefill_plan is None or decode_plan is None:
                raise ValueError(
                    "a plan with searched factorizations (schema v4) must "
                    "install for BOTH phases: the unplanned phase would "
                    "contract default-decomposition networks over "
                    "factorized params")
            if fact["prefill"] != fact["decode"]:
                diff = sorted(
                    set(fact["prefill"].items())
                    ^ set(fact["decode"].items()))
                raise ValueError(
                    "prefill/decode plans carry different factorizations "
                    f"({[n for n, _ in diff]}); a serving pair shares one "
                    "decomposition (it defines the parameter shapes)")
        self.cfg = cfg
        self.params = params
        self.n_slots = int(n_slots)
        self.max_seq = int(max_seq)
        # junk prompt padding advances recurrent state — exact lengths only
        self.prompt_bucket = 1 if cfg.supports_long_context else int(prompt_bucket)
        self.prefill_plan = prefill_plan
        self.decode_plan = decode_plan
        self._plan_backend = plan_backend
        self._m = api(cfg)  # leaves any globally installed plan untouched

        self._prefill_fn = jax.jit(
            lambda p, toks, last: self._m.prefill(p, {"tokens": toks},
                                                  self.max_seq, last))
        self._decode_fn = jax.jit(
            lambda p, t, c, pos: self._m.decode_step(p, t, c, pos),
            donate_argnums=(2,))
        # write batch-1 caches into slot `slot` of the width-n_slots tree
        # (every stacked cache leaf carries batch on axis 1)
        self._admit_fn = jax.jit(
            lambda big, small, slot: jax.tree.map(
                lambda b, s: jax.lax.dynamic_update_index_in_dim(
                    b, s[:, 0], slot, axis=1),
                big, small),
            donate_argnums=(0,))
        self._pick_compiled = False

    # -- phase kernels -------------------------------------------------

    def padded_len(self, prompt_len: int) -> int:
        b = self.prompt_bucket
        return -(-prompt_len // b) * b

    def prefill_request(self, prompt: Sequence[int]):
        """Prefill one prompt at batch 1 under the prefill plan.

        Returns ``(last_logits (V,) np.ndarray, batch-1 caches)`` where
        the logits are taken at the last *real* token of the
        bucket-padded prompt.
        """
        p = len(prompt)
        pp = self.padded_len(p)
        if pp > self.max_seq:
            raise ValueError(
                f"padded prompt length {pp} exceeds max_seq {self.max_seq}")
        toks = np.zeros((1, pp), np.int32)
        toks[0, :p] = np.asarray(prompt, np.int32)
        with span("serve.prefill", tokens=pp, real=p):
            with span("serve.prefill.dispatch"), plan_context(
                    self.prefill_plan, force_backend=self._plan_backend):
                with execution_stream("prefill"):
                    last, caches = self._prefill_fn(
                        self.params, jnp.asarray(toks),
                        jnp.asarray(p - 1, jnp.int32))
            with span("serve.prefill.fetch"):
                row = np.asarray(last[0])
        return row, caches

    def fresh_caches(self):
        """A zeroed width-``n_slots`` decode cache tree."""
        return self._m.init_caches(self.n_slots, self.max_seq)

    def admit(self, caches, small, slot: int):
        """Copy a prefilled batch-1 cache tree into decode lane ``slot``.

        Donates ``caches`` — the caller must use the returned tree.
        """
        with span("serve.admit"):
            return self._admit_fn(caches, small, jnp.asarray(slot, jnp.int32))

    def decode(self, tok: np.ndarray, pos: np.ndarray, caches):
        """One fixed-width decode step under the decode plan.

        ``tok``/``pos`` are ``(n_slots,)`` host arrays (free lanes pass
        0).  Returns ``(logits (n_slots, V) DeviceRows, new caches)``;
        donates ``caches``.  The logits stay on the device, finished:
        the call returns once the step has run.  Its spans split the call
        into the jitted call's return (``dispatch``) and the device
        finishing the step (``wait``).  The first call also compiles
        :func:`greedy_ids` for the logits as the step lays them out
        (sharded under a mesh), so a later pick compiles nothing.
        """
        with span("serve.decode"):
            with span("serve.decode.dispatch"), plan_context(
                    self.decode_plan, force_backend=self._plan_backend):
                with execution_stream("decode"):
                    logits, caches = self._decode_fn(
                        self.params,
                        jnp.asarray(tok, jnp.int32)[:, None],
                        caches,
                        jnp.asarray(pos, jnp.int32))
            with span("serve.decode.wait"):
                jax.block_until_ready(logits)
            rows = DeviceRows(logits)
            if not self._pick_compiled:
                greedy_ids(rows)
                self._pick_compiled = True
        return rows, caches

    def lowered_text(self, prompt_len: int) -> dict[str, str]:
        """StableHLO of the prefill and decode programs, each traced under
        its phase's plan — what a caller greps to see which kernels the
        jitted steps call (e.g. ``tpu_custom_call`` for Mosaic kernels)."""
        i32 = jnp.int32
        with plan_context(self.prefill_plan,
                          force_backend=self._plan_backend):
            with execution_stream("prefill"):
                pre = self._prefill_fn.lower(
                    self.params,
                    jax.ShapeDtypeStruct((1, self.padded_len(prompt_len)), i32),
                    jax.ShapeDtypeStruct((), i32))
        with plan_context(self.decode_plan,
                          force_backend=self._plan_backend):
            with execution_stream("decode"):
                dec = self._decode_fn.lower(
                    self.params,
                    jax.ShapeDtypeStruct((self.n_slots, 1), i32),
                    jax.eval_shape(self.fresh_caches),
                    jax.ShapeDtypeStruct((self.n_slots,), i32))
        return {"prefill": pre.as_text(), "decode": dec.as_text()}
