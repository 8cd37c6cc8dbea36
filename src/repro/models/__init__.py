"""Model zoo + family-dispatched API.

``api(cfg)`` returns the family's (init_params, train_loss, prefill,
decode_step, init_caches) callables with a uniform signature, and
``input_specs(cfg, shape)`` builds the ShapeDtypeStruct stand-ins the
multi-pod dry-run lowers against (no device allocation).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from . import encdec as _encdec
from . import lm as _lm
from .config import SHAPES, ModelConfig, ShapeConfig, shape_applicable

ENC_LEN_CAP = 4096   # encoder frame length for enc-dec decode shapes


@dataclasses.dataclass(frozen=True)
class ModelAPI:
    init_params: Callable
    train_loss: Callable
    prefill: Callable
    decode_step: Callable
    init_caches: Callable


_PLAN_UNSET = object()  # sentinel: "plan argument not given"


def api(cfg: ModelConfig, plan=_PLAN_UNSET, *,
        plan_backend: Optional[str] = None) -> ModelAPI:
    """Family-dispatched model API.

    ``plan`` (an :class:`repro.plan.ExecutionPlan`, a plan-file path, or a
    legacy ``{name: path_index}`` dict) is installed into the TT linear
    layers before any callable is traced, so every projection contracts
    along its planned path / kernel backend.  ``plan_backend`` forces one
    executor for all layers (the train driver passes ``"jnp"`` — autodiff
    never crosses a ``pallas_call``).

    Plan state is global and *explicit*: omitting ``plan`` leaves
    whatever is installed untouched (so the step builders' internal
    ``api(cfg)`` dispatch never un-installs a driver's plan), while
    passing ``plan=None`` clears it — use that when building an unplanned
    baseline after a planned model in the same process.
    """
    if plan_backend is not None:
        from repro.plan.schema import BACKENDS

        if plan_backend not in BACKENDS:
            raise ValueError(
                f"unknown plan_backend {plan_backend!r}; have {BACKENDS}")
    if plan is not _PLAN_UNSET or plan_backend is not None:
        from repro.nn import install_plan

        if plan is _PLAN_UNSET or plan is None:
            if plan_backend is not None:
                raise ValueError(
                    "plan_backend given without a plan to apply it to")
            plan = None
        if isinstance(plan, str):
            from repro.plan import load_plan

            plan = load_plan(plan)
        install_plan(plan, force_backend=plan_backend)
    if cfg.family == "encdec":
        return ModelAPI(
            init_params=lambda rng: _encdec.init_params(rng, cfg),
            train_loss=lambda p, b: _encdec.train_loss(cfg, p, b),
            prefill=lambda p, b, max_seq: _encdec.prefill(cfg, p, b, max_seq),
            decode_step=lambda p, t, c, pos: _encdec.decode_step(cfg, p, t, c, pos),
            init_caches=lambda batch, max_seq: _encdec.init_caches(
                cfg, batch, max_seq, min(ENC_LEN_CAP, max_seq), jnp.dtype(cfg.dtype)),
        )
    return ModelAPI(
        init_params=lambda rng: _lm.init_params(rng, cfg),
        train_loss=lambda p, b: _lm.train_loss(cfg, p, b),
        prefill=lambda p, b, max_seq, last=None: _lm.prefill(
            cfg, p, b, max_seq, last),
        decode_step=lambda p, t, c, pos: _lm.decode_step(cfg, p, t, c, pos),
        init_caches=lambda batch, max_seq: _lm.init_caches(
            cfg, batch, max_seq, jnp.dtype(cfg.dtype)),
    )


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every input of the cell's step.

    train:   {tokens, labels[, frontend]}
    prefill: {tokens[, frontend]}
    decode:  {token, cache_pos, caches}
    """
    gb, s = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    dt = jnp.dtype(cfg.dtype)
    tok = jax.ShapeDtypeStruct((gb, s), i32)

    def frontend_spec(seq: int):
        if cfg.family == "vlm":
            n = cfg.n_frontend_tokens or 256
            return jax.ShapeDtypeStruct((gb, n, cfg.d_model), dt)
        if cfg.family == "encdec":
            n = min(ENC_LEN_CAP, seq)
            return jax.ShapeDtypeStruct((gb, n, cfg.d_model), dt)
        return None

    if shape.step == "train":
        batch = {"tokens": tok, "labels": jax.ShapeDtypeStruct((gb, s), i32)}
        fe = frontend_spec(s)
        if fe is not None:
            batch["frontend"] = fe
        return batch
    if shape.step == "prefill":
        batch = {"tokens": tok}
        fe = frontend_spec(s)
        if fe is not None:
            batch["frontend"] = fe
        return batch
    if shape.step == "decode":
        max_seq = s + (cfg.n_frontend_tokens or 256 if cfg.family == "vlm" else 0)
        caches = jax.eval_shape(lambda: api(cfg).init_caches(gb, max_seq))
        return {
            "token": jax.ShapeDtypeStruct((gb, 1), i32),
            "cache_pos": jax.ShapeDtypeStruct((), i32),
            "caches": caches,
        }
    raise ValueError(shape.step)


__all__ = [
    "ModelConfig", "ShapeConfig", "SHAPES", "shape_applicable",
    "ModelAPI", "api", "input_specs", "ENC_LEN_CAP",
]
