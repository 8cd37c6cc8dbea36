"""Production mesh + sharding policy.

``make_production_mesh`` builds the assignment's meshes: ``(16, 16)``
("data", "model") single-pod and ``(2, 16, 16)`` ("pod", "data", "model")
multi-pod.  A FUNCTION, not a module constant — importing this module
never touches jax device state.

``make_rules`` is the per-(arch x shape) sharding policy:
  * batch  -> ("pod", "data") / ("data",)  (pure DP on the pod axis:
    cross-pod links carry only gradient reductions)
  * model  -> TP/EP axis
  * seq    -> sequence parallelism, enabled when attention heads cannot
    shard the model axis (kv_heads % tp != 0) or at >=200k context

``param_shardings`` is the FSDP-style parameter heuristic: largest
divisible dim -> "model", next -> "data" (weight-gathered FSDP under
GSPMD); small tensors (TT cores, norms) replicate.
"""

from __future__ import annotations

import math
from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.config import ModelConfig, ShapeConfig
from repro.sharding import ShardingRules


def _mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> Mesh:
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_test_mesh(devices: Optional[int] = None, multi_pod: bool = False) -> Mesh:
    """Small mesh over however many devices exist (CI / reduced dry-runs)."""
    n = devices or len(jax.devices())
    if multi_pod and n >= 8:
        pod = 2
        rest = n // pod
        model = _largest_pow2_le(int(math.isqrt(rest)))
        data = rest // model
        return _mesh((pod, data, model), ("pod", "data", "model"))
    model = _largest_pow2_le(int(math.isqrt(n)))
    data = n // model
    return _mesh((data, model), ("data", "model"))


def _largest_pow2_le(n: int) -> int:
    p = 1
    while p * 2 <= n:
        p *= 2
    return p


def _decode_cache_gib(cfg: ModelConfig, shape: ShapeConfig, dp: int) -> float:
    """Per-device KV-cache GiB if sharded on batch only (heads replicated)."""
    b_local = max(shape.global_batch // max(dp, 1), 1)
    n_attn = cfg.n_layers
    if cfg.family == "hybrid":
        n_attn = cfg.n_layers // cfg.attn_every if cfg.attn_every else 1
    if cfg.family == "rwkv":
        return 0.0
    per_layer = 2 * b_local * shape.seq_len * cfg.n_kv_heads * cfg.hd * 2
    return n_attn * per_layer / 2**30


def sp_enabled(cfg: ModelConfig, shape: ShapeConfig, tp: int,
               dp: int = 16) -> bool:
    if cfg.family == "rwkv":
        return False  # attention-free: heads always shard
    if shape.step == "decode":
        # Perf iteration (see EXPERIMENTS.md §Perf): seq-sharding the KV
        # cache makes every decode step gather it (measured GB/step of
        # all-gather).  Batch+head sharding is collective-free — use it
        # whenever the cache fits; fall back to SP only when it doesn't.
        if cfg.n_kv_heads % tp == 0:
            return False
        return _decode_cache_gib(cfg, shape, dp) > 12.0
    if cfg.n_kv_heads % tp != 0:
        return True
    return shape.seq_len >= 200_000


def make_rules(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh) -> ShardingRules:
    axis_sizes = dict(mesh.shape)
    batch_axes = tuple(a for a in ("pod", "data") if a in axis_sizes)
    tp = axis_sizes.get("model", 1)
    dp = math.prod(axis_sizes.get(a, 1) for a in batch_axes)
    return ShardingRules(
        axis_sizes=axis_sizes,
        batch_axes=batch_axes,
        model_axis="model" if "model" in axis_sizes else None,
        seq_axis="model" if sp_enabled(cfg, shape, tp, dp) else None,
        mesh=mesh,
    )


# ---------------------------------------------------------------------------
# parameter / cache / input sharding trees
# ---------------------------------------------------------------------------

_REPLICATE_BELOW = 65_536  # elements; TT cores & norms replicate


def _param_pspec(shape: tuple[int, ...], axis_sizes: dict) -> P:
    if math.prod(shape) < _REPLICATE_BELOW or len(shape) < 2:
        return P()
    spec: list = [None] * len(shape)
    order = sorted(range(len(shape)), key=lambda i: -shape[i])
    tp = axis_sizes.get("model", 1)
    if tp > 1:
        for i in order:
            if shape[i] % tp == 0 and shape[i] >= tp:
                spec[i] = "model"
                break
    fsdp = axis_sizes.get("data", 1)
    if fsdp > 1:
        for i in order:
            if spec[i] is None and shape[i] % fsdp == 0 and shape[i] >= fsdp:
                spec[i] = "data"
                break
    return P(*spec)


def param_shardings(params_shapes: Any, mesh: Mesh) -> Any:
    """FSDP/TP heuristic shardings for a param (or optimizer-state) tree."""
    axis_sizes = dict(mesh.shape)

    def one(leaf):
        return NamedSharding(mesh, _param_pspec(tuple(leaf.shape), axis_sizes))

    return jax.tree.map(one, params_shapes)


def _cache_pspec(path: str, shape: tuple[int, ...], cfg: ModelConfig,
                 rules: ShardingRules) -> P:
    """Decode-cache shardings by leaf name.

    KV-style, lane-dense (L, B, S, H*D) self-attention or (L, B, S, H, D)
    cross-attention: batch -> DP; heads -> model when divisible (on the
    flattened H*D, whose contiguous chunks are then whole heads), else
    sequence -> model (SP cache).  State-style: batch -> DP, the
    channel/head dim -> model when divisible.
    """
    ax = rules.axis_sizes
    tp = ax.get("model", 1)
    dp = math.prod(ax.get(a, 1) for a in rules.batch_axes)
    name = path.rsplit("/", 1)[-1].rsplit(".", 1)[-1]

    def batch_spec(b):
        return rules.batch_axes if (dp > 1 and b % dp == 0) else None

    if name in ("k", "v") and len(shape) == 4:
        L, b, s, hd = shape
        bspec = batch_spec(b)
        if tp > 1 and cfg.n_kv_heads % tp == 0:
            return P(None, bspec, None, "model")
        if rules.seq_axis and tp > 1 and s % tp == 0:
            return P(None, bspec, "model", None)
        return P(None, bspec, None, None)
    if name in ("cross_k", "cross_v") and len(shape) == 5:
        L, b, s, h, d = shape
        bspec = batch_spec(b)
        if tp > 1 and h % tp == 0:
            return P(None, bspec, None, "model", None)
        # seq-shard only when the policy enabled SP (cache too big for
        # batch sharding) — otherwise replicate heads: collective-free
        if rules.seq_axis and tp > 1 and s % tp == 0:
            return P(None, bspec, "model", None, None)
        return P(None, bspec, None, None, None)
    if name == "conv" and len(shape) == 4:
        L, b, k, c = shape
        return P(None, batch_spec(b), None,
                 "model" if (tp > 1 and c % tp == 0) else None)
    if name in ("ssm", "wkv") and len(shape) == 5:
        L, b, h = shape[:3]
        return P(None, batch_spec(b),
                 "model" if (tp > 1 and h % tp == 0) else None, None, None)
    if name.startswith("shift") and len(shape) == 3:
        L, b, d = shape
        return P(None, batch_spec(b),
                 "model" if (tp > 1 and d % tp == 0) else None)
    # fallback: batch dim at index 1 if it matches, else replicate
    if len(shape) >= 2:
        return P(None, batch_spec(shape[1]), *([None] * (len(shape) - 2)))
    return P()


def cache_shardings(cfg: ModelConfig, caches_shapes: Any,
                    rules: ShardingRules) -> Any:
    flat = jax.tree_util.tree_flatten_with_path(caches_shapes)[0]
    treedef = jax.tree.structure(caches_shapes)
    shardings = []
    for path, leaf in flat:
        key = "/".join(str(p) for p in path)
        spec = _cache_pspec(key, tuple(leaf.shape), cfg, rules)
        shardings.append(NamedSharding(rules.mesh, spec))
    return jax.tree.unflatten(treedef, shardings)


def batch_shardings(batch_specs: Any, rules: ShardingRules) -> Any:
    """Input batches: leading dim -> DP axes (when divisible), rest replicated."""
    dp = math.prod(rules.axis_sizes.get(a, 1) for a in rules.batch_axes)

    def one(leaf):
        if leaf.ndim >= 1 and dp > 1 and leaf.shape[0] % dp == 0:
            return NamedSharding(rules.mesh,
                                 P(rules.batch_axes, *([None] * (leaf.ndim - 1))))
        return NamedSharding(rules.mesh, P())

    return jax.tree.map(one, batch_specs)


def replicated(tree: Any, mesh: Mesh) -> Any:
    return jax.tree.map(lambda _: NamedSharding(mesh, P()), tree)
