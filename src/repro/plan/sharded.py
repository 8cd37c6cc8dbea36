"""shard_map-routed planned execution on multi-device meshes.

PR 2's executor restricted Pallas plan backends to single-device meshes:
the kernels flatten ``(B, S)`` to ``tokens`` and carry no sharding
annotations, so under GSPMD a >1-device mesh would force a relayout and
the dispatcher demoted every planned contraction to the jnp executor.
This module lifts that gate by making the sharding *explicit* instead:

* the flattened token dim is split over the installed
  :class:`~repro.sharding.ShardingRules` token axes (DP, plus the SP
  axis when sequence parallelism is on) via ``jax.shard_map``;
* each shard runs the *same* ``streaming_tt`` / ``tt_gemm`` kernel at
  its per-shard ``(tokens/n_shards, d_in)`` shape — which is also the
  shape the DSE/tuner searched when a shard context was active
  (``repro.dse --shards``);
* TT cores are tiny by construction and replicate (``in_specs=P()``);
  their gradient cotangents are psummed across shards by the shard_map
  transpose, so training stays correct (``check_vma=False`` because the
  Pallas ``custom_vjp`` body defeats replication checking);
* an optional model-axis output reduction
  (``ShardingRules.tt_model_reduce``) splits the leading input mode and
  its TT core over the model axis and reduces partial outputs with an
  explicit ``jax.lax.psum`` *inside* the body — classic row-parallel TP
  with no forced relayout.

Either way the outputs agree with the single-device planned path to fp32
rounding, not bit for bit: a per-shard call contracts fewer rows, and
both XLA and Mosaic tile a matmul by its shape, so the summation order
can differ (the model-axis psum re-associates the sum as well).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence

import jax
from jax.sharding import PartitionSpec as P

from .executor import planned_tt_linear, shard_execution
from .schema import LayerPlan

def _smap(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


@dataclasses.dataclass(frozen=True)
class ShardDecision:
    """How one planned projection maps onto the installed mesh."""

    axes: tuple[str, ...]     # mesh axes sharding the flattened token dim
    n_shards: int             # product of those axis sizes (1 = replicated)
    model_reduce: bool = False  # split leading input mode over model axis
    tp: int = 1               # model-axis size when model_reduce

    def describe(self, axis_sizes: dict, model_axis: Optional[str]) -> str:
        parts = ",".join(f"{a}={axis_sizes[a]}" for a in self.axes)
        if self.model_reduce:
            red = f"reduce({model_axis}={self.tp})"
            return f"{parts}+{red}" if parts else red
        return parts


def shard_decision(rules, tokens: int,
                   in_modes: Sequence[int]) -> Optional[ShardDecision]:
    """Route choice for a planned projection under ``rules``, or ``None``.

    ``None`` means the mesh cannot take this problem (no mesh object, or
    the token count does not divide the DP axes and no model reduction
    applies) — the caller falls back to the constrained jnp executor.
    """
    if rules is None or rules.mesh is None:
        return None
    axes = rules.token_shard_axes(tokens)
    model_reduce, tp = False, 1
    ma = rules.model_axis
    if rules.tt_model_reduce and ma and ma not in axes:
        tp = int(rules.axis_sizes.get(ma, 1))
        if tp > 1 and in_modes and in_modes[0] % tp == 0:
            model_reduce = True
        else:
            tp = 1
    if not axes and not model_reduce:
        return None
    n = math.prod(rules.axis_sizes[a] for a in axes) if axes else 1
    return ShardDecision(tuple(axes), int(n), model_reduce, tp)


def sharded_tt_linear(
    lp: LayerPlan,
    x2d: jax.Array,
    cores: Sequence[jax.Array],
    in_modes: tuple[int, ...],
    out_modes: tuple[int, ...],
    ranks: tuple[int, ...],
    *,
    rules,
    decision: ShardDecision,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Planned TT projection over the mesh: per-shard Pallas execution.

    ``x2d: (tokens, d_in)`` -> ``(tokens, d_out)``.  Token shards stream
    through the plan's backend at per-shard shapes; cores replicate
    (except the leading input core under ``model_reduce``, which is
    split over the model axis with a psum inside the body).
    """
    in_modes = tuple(in_modes)
    out_modes = tuple(out_modes)
    ranks = tuple(ranks)
    tokens, d_in = int(x2d.shape[0]), int(x2d.shape[1])
    n_cores = len(cores)
    tok_entry = decision.axes if decision.axes else None
    shard_tokens = tokens // decision.n_shards

    if not decision.model_reduce:
        def body(xs, *cs):
            return planned_tt_linear(lp, xs, list(cs), in_modes, out_modes,
                                     ranks, interpret=interpret)

        in_specs = (P(tok_entry, None),) + (P(),) * n_cores
        out_specs = P(tok_entry, None)
        shard_shape = (shard_tokens, d_in)
    else:
        ma = rules.model_axis
        tp = decision.tp
        local_in = (in_modes[0] // tp,) + in_modes[1:]
        # cores are ordered out_modes then in_modes, so the core carrying
        # the leading input mode j1 sits at index len(out_modes); its mode
        # dim is axis 1 both for interior (r, m, r) and final (r, m) cores
        j1 = len(out_modes)
        core_specs = []
        for k in range(n_cores):
            if k == j1:
                core_specs.append(
                    P(None, ma) if k == n_cores - 1 else P(None, ma, None))
            else:
                core_specs.append(P())

        def body(xs, *cs):
            # x columns are row-major over in_modes, so a contiguous
            # 1/tp column block IS a j1-mode slice — no relayout
            y = planned_tt_linear(lp, xs, list(cs), local_in, out_modes,
                                  ranks, interpret=interpret)
            return jax.lax.psum(y, ma)

        in_specs = (P(tok_entry, ma),) + tuple(core_specs)
        out_specs = P(tok_entry, None)
        shard_shape = (shard_tokens, d_in // tp)

    desc = decision.describe(rules.axis_sizes, rules.model_axis)
    fn = _smap(body, rules.mesh, in_specs, out_specs)
    with shard_execution(desc, shard_shape):
        return fn(x2d, *cores)
