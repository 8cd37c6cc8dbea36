"""Planned execution: route a TT contraction through its LayerPlan backend.

Entry point is :func:`planned_tt_linear` — called by
``repro.nn.linear.linear_apply`` when a plan entry is installed for the
projection.  Three backends:

- ``jnp``        — the pure-jnp reference executor (``kernels/ref.py``)
                   along the plan's path steps: numerical ground truth.
- ``streaming_tt`` — the fused in-VMEM Pallas kernel: cores pinned whole
                   in VMEM, activations streamed in ``block_tokens``
                   blocks, the entire searched path unrolled inside the
                   kernel body (``kernels/streaming_tt.py``).
- ``tt_gemm``    — every pairwise contraction of the path lowered to the
                   dataflow-configurable Pallas GEMM
                   (``kernels/tt_gemm.py``) with the plan's IS/OS/WS grid
                   order and <T_M, T_K, T_N> block shapes.  Any pairwise
                   tensor contraction *is* a GEMM (free-edges x
                   shared-edges reshape), which is the paper's §3.1 view.

**Training.**  A ``pallas_call`` has no autodiff rule, so the Pallas
backends are wrapped in a ``jax.custom_vjp`` whose backward pass
contracts the layer's *gradient networks* (``repro.core.backward``) —
dL/dx and one dL/dG_k per core — along the plan's searched backward
paths (schema v2 ``backward`` entries; inference-only plans fall back to
the MAC-optimal backward path per gradient).  Each backward contraction
is itself routed through a planned kernel: dx may stream through the
same Pallas pipeline as the forward, weight gradients lower to the
Pallas GEMM.  ``launch/train.py --plan`` therefore runs Pallas
end-to-end under ``jax.grad``.

Every planned call appends a record to a trace-time execution log —
``execution_log()`` — so callers (tests, the serve/train drivers) can
assert *which* path/dataflow/kernel actually executed, in which autodiff
``phase`` (``"fwd"`` at forward trace, ``"bwd"`` inside the VJP).  Under
``jit`` the record is appended once per trace, not per step.
"""

from __future__ import annotations

import contextlib
import functools
import math
from typing import Iterator, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core import fusion
from repro.core.backward import backward_networks, grad_input_network
from repro.core.contraction import core_tensors, execute_path
from repro.core.paths import CandidatePath, find_topk_paths
from repro.core.tensor_network import TensorNetwork, tt_linear_network
from repro.kernels import ops, ref

from .schema import BackwardOp, LayerPlan

# ---------------------------------------------------------------------------
# trace-time execution log
# ---------------------------------------------------------------------------

#: ring capacity: long-running serving loops retrace at many token
#: counts; the log keeps the most recent records, and the drop counter
#: (``execution_log_dropped``) lets summary consumers state when their
#: window is partial instead of silently under-counting
_EXEC_LOG_MAX = 4096

_EXEC_LOG: list[dict] = []
_EXEC_DROPPED = 0

#: serving-stream tag stack (``execution_stream``): records appended
#: inside the context carry ``stream`` = the innermost tag, so the serve
#: scheduler's per-phase plan switching is assertable from the log
_STREAM: list[str] = []

#: shard-execution stack (``shard_execution``): records traced inside a
#: shard_map body carry the mesh layout and the per-shard problem shape,
#: so "the kernel ran at per-shard shapes on this mesh" is assertable
_SHARD: list[tuple[str, tuple[int, ...]]] = []


def reset_execution_log() -> None:
    global _EXEC_DROPPED
    _EXEC_LOG.clear()
    _EXEC_DROPPED = 0


def execution_log() -> tuple[dict, ...]:
    """Records of planned executions since the last reset (trace-time).

    At most the newest :data:`_EXEC_LOG_MAX` records are retained;
    :func:`execution_log_dropped` counts the ones that aged out.
    """
    return tuple(_EXEC_LOG)


def execution_log_dropped() -> int:
    """Records evicted from the ring since the last reset."""
    return _EXEC_DROPPED


@contextlib.contextmanager
def execution_stream(name: str) -> Iterator[None]:
    """Tag every execution record traced inside with ``stream=name``.

    The serve engine wraps each prefill/decode call in
    ``execution_stream("prefill"/"decode")`` so the log distinguishes
    which *serving stream* a contraction was traced under — orthogonal
    to the autodiff ``phase`` (fwd/bwd) the record already carries.
    Under ``jit`` a record appears once per trace, so the tag marks the
    stream that *first* traced the shape.
    """
    _STREAM.append(str(name))
    try:
        yield
    finally:
        _STREAM.pop()


@contextlib.contextmanager
def shard_execution(mesh: str, shard_shape: tuple[int, ...]) -> Iterator[None]:
    """Tag records traced inside with their shard_map placement.

    ``mesh`` is a human-readable axis layout (``"data=4"`` or
    ``"data=4+reduce(model=2)"``); ``shard_shape`` is the per-device
    ``(tokens, d_in)`` the kernel actually sees.  The sharded dispatcher
    (:mod:`repro.plan.sharded`) wraps its shard_map call in this context
    — the body traces within, so per-shard kernel records pick up the
    fields.  Single-device records carry ``mesh=""``/``shard_shape=None``.
    """
    _SHARD.append((str(mesh), tuple(int(d) for d in shard_shape)))
    try:
        yield
    finally:
        _SHARD.pop()


def record_execution(
    lp: LayerPlan,
    tokens: int,
    *,
    phase: str = "fwd",
    backend: Optional[str] = None,
    wrt: Optional[str] = None,
    path_steps=None,
    tiling=None,
    segment: Optional[tuple[int, int]] = None,
) -> None:
    """Append one planned-execution record (called at trace time).

    ``tiling`` defaults to the layer plan's forward tiling; backward
    records pass the per-gradient op's.  Logging the blocks makes
    "the kernel tilings follow the plan's (searched) architecture" an
    assertable property, not an inference — the serve driver and
    ``tests/test_hw.py`` both read it.  ``segment`` marks per-segment
    provenance records of a fusion-segmented layer (the step range the
    record covers); the layer-level record carries no segment.
    """
    global _EXEC_DROPPED
    rec = {
        "name": lp.name,
        "backend": backend if backend is not None else lp.backend,
        "dataflow": lp.dataflow,
        "path_index": lp.path_index,
        "path_steps": lp.path_steps if path_steps is None else path_steps,
        "tokens": tokens,
        "phase": phase,
        "stream": _STREAM[-1] if _STREAM else "",
        "mesh": _SHARD[-1][0] if _SHARD else "",
        "shard_shape": list(_SHARD[-1][1]) if _SHARD else None,
        "tiling": (lp.tiling if tiling is None else tiling).to_json(),
    }
    if wrt is not None:
        rec["wrt"] = wrt
    if segment is not None:
        rec["segment"] = [int(segment[0]), int(segment[1])]
    if len(_EXEC_LOG) >= _EXEC_LOG_MAX:
        del _EXEC_LOG[0]
        _EXEC_DROPPED += 1
    _EXEC_LOG.append(rec)


# ---------------------------------------------------------------------------
# path plumbing
# ---------------------------------------------------------------------------

def has_pallas_backward(lp: LayerPlan) -> bool:
    """Whether any of the plan's backward ops names a Pallas backend.

    The auto-compiler can pair a jnp *forward* (small forward GEMMs)
    with Pallas *backward* ops (the weight-gradient GEMMs reduce over
    the whole batch, so they clear ``MIN_KERNEL_MACS`` when the forward
    does not) — such layers still need the custom-VJP route.
    """
    return any(op.backend != "jnp" for op in lp.backward)


def as_candidate_path(tn: TensorNetwork, steps) -> CandidatePath:
    """Reconstruct a CandidatePath (with GEMM shapes) from raw plan steps."""
    steps = tuple(tuple(s) for s in steps)
    gemms = tuple(tn.gemm_sequence(steps))
    return CandidatePath(steps, sum(g.macs for g in gemms), gemms)


def _gemm_contract(lp: LayerPlan, tiling, interpret: Optional[bool]):
    """Pallas-GEMM ``contract_fn`` with the plan's dataflow and blocks."""
    return ops.gemm_contract(
        dataflow=lp.dataflow,
        block_m=tiling.block_m,
        block_k=tiling.block_k,
        block_n=tiling.block_n,
        interpret=interpret,
    )


def _bwd_token_bucket(tokens: int) -> int:
    """Pow2 bucket for the backward-path cache key.

    A serving/decode loop retraces at many distinct token counts; keying
    the derivation cache on the raw count would re-run the path search
    (and grow the cache) once per count.  The MAC-optimal backward
    contraction *order* is stable within a pow2 bucket (asserted by
    ``tests/test_fused_exec.py``), so the bucket is the cache key — the
    returned steps are pure index pairs, valid at any batch size.
    """
    p = 1
    while p < max(1, tokens):
        p *= 2
    return p


@functools.lru_cache(maxsize=256)
def _default_bwd_steps(
    batch: int,
    in_modes: tuple[int, ...],
    out_modes: tuple[int, ...],
    ranks: tuple[int, ...],
) -> tuple[tuple[str, tuple[tuple[int, int], ...]], ...]:
    """MAC-optimal backward path per gradient (fallback for v1 plans).

    ``batch`` should be a :func:`_bwd_token_bucket` value — callers
    bucket before the lookup so the cache stays small under decode-style
    token-count churn (the cap is a backstop, not a working set).
    """
    tn = tt_linear_network(batch, in_modes, out_modes, ranks)
    return tuple(
        (wrt, find_topk_paths(net, k=1)[0].steps)
        for wrt, net in backward_networks(tn)
    )


def _resolve_backward_ops(
    lp: LayerPlan,
    tokens: int,
    in_modes: tuple[int, ...],
    out_modes: tuple[int, ...],
    ranks: tuple[int, ...],
) -> tuple[BackwardOp, ...]:
    """The plan's backward ops, or derived defaults for inference plans.

    Defaults: MAC-optimal path per gradient; dx inherits the forward
    backend (it is the same kind of streaming contraction), weight
    gradients lower to the Pallas GEMM (two streamed operands cannot use
    the streaming kernel), everything stays jnp under a jnp forward.
    A partial ``backward`` list (hand-edited plan installed without the
    driver-side ``check_plan_for_config`` guard) keeps its entries and
    fills the missing gradients with the same defaults.
    """
    planned = {op.wrt: op for op in lp.backward}
    if lp.backend == "jnp":
        dx_backend = grad_backend = "jnp"
    else:
        dx_backend = lp.backend
        grad_backend = "tt_gemm"
    return tuple(
        planned.get(wrt) or BackwardOp(
            wrt=wrt,
            path_index=0,
            path_steps=steps,
            backend=dx_backend if wrt == "dx" else grad_backend,
            tiling=lp.tiling,
        )
        for wrt, steps in _default_bwd_steps(
            _bwd_token_bucket(tokens), in_modes, out_modes, ranks)
    )


# ---------------------------------------------------------------------------
# fusion-segmented execution (tt_gemm backend with LayerPlan.segments)
# ---------------------------------------------------------------------------

def _execute_segmented(
    lp: LayerPlan,
    tn: TensorNetwork,
    tensors: dict,
    out_edges: tuple[str, ...],
    tokens: int,
    interpret: Optional[bool],
) -> jax.Array:
    """Walk the plan's fusion segments over the live work list.

    Multi-step segments execute as ONE ``pallas_call`` with fp32
    VMEM-resident intermediates (``kernels/fused_path.py``); singleton
    segments keep the per-step dataflow-configurable GEMM route, so the
    result is bit-identical to the unsegmented execution (the fused
    kernel replicates the per-step k-block accumulation order).  Each
    segment appends its own provenance record — ``segment=(s, e)`` — in
    addition to the layer-level record ``planned_tt_linear`` wrote.
    """
    steps = tuple(tuple(s) for s in lp.path_steps)
    contract = _gemm_contract(lp, lp.tiling, interpret)
    work: list = [(n.edges, tensors[n.name]) for n in tn.nodes]
    for (s, e) in lp.segments:
        record_execution(lp, tokens, path_steps=steps[s:e], segment=(s, e))
        if e - s >= 2:
            # each lowering resolves the plan's token block itself
            ec, val = ops.fused_segment(
                work, steps[s:e], block_tokens=lp.tiling.block_tokens,
                block_m=lp.tiling.block_m, block_k=lp.tiling.block_k,
                block_n=lp.tiling.block_n, interpret=interpret)
            # replay the per-step removals; interior placeholders are all
            # consumed by the chain, only the final result survives
            for (i, j) in steps[s:e]:
                work = [w for k, w in enumerate(work) if k not in (i, j)]
                work.append(None)
            work[-1] = (ec, val)
        else:
            i, j = steps[s]
            (ea, ta), (eb, tb) = work[i], work[j]
            shared = [x for x in ea if x in eb]
            ax_a = tuple(ea.index(x) for x in shared)
            ax_b = tuple(eb.index(x) for x in shared)
            val = contract(ta, tb, (ax_a, ax_b))
            ec = tuple(x for x in ea if x not in shared) + tuple(
                x for x in eb if x not in shared)
            work = [w for k, w in enumerate(work) if k not in (i, j)]
            work.append((ec, val))
    ec, val = work[-1]
    if tuple(ec) != tuple(out_edges):
        val = jnp.transpose(val, tuple(ec.index(x) for x in out_edges))
    return val


# ---------------------------------------------------------------------------
# forward bodies (shared by the inference path and the custom-VJP wrapper)
# ---------------------------------------------------------------------------

def _forward_planned(
    lp: LayerPlan,
    x2d: jax.Array,
    cores: Sequence[jax.Array],
    in_modes: tuple[int, ...],
    out_modes: tuple[int, ...],
    ranks: tuple[int, ...],
    interpret: Optional[bool],
) -> jax.Array:
    """The plan's forward contraction: ``(tokens, d_in) -> (tokens, d_out)``."""
    tokens = x2d.shape[0]
    if lp.backend == "streaming_tt":
        bt = ops.token_block(lp.tiling.block_tokens, tokens)
        tn_block = tt_linear_network(bt, in_modes, out_modes, ranks)
        path = as_candidate_path(tn_block, lp.path_steps)
        return ops.tt_linear(x2d, list(cores), tn_block, path,
                             block_tokens=bt, interpret=interpret)

    tn = tt_linear_network(tokens, in_modes, out_modes, ranks)
    if lp.backend == "tt_gemm":
        tensors = {"X": x2d.reshape((tokens,) + tuple(in_modes))}
        tensors.update(core_tensors(tn, list(cores)))
        out_edges = ("b",) + tuple(f"i{t + 1}" for t in range(len(out_modes)))
        if fusion.has_fused(lp.segments):
            y = _execute_segmented(lp, tn, tensors, out_edges, tokens,
                                   interpret)
        else:
            y = execute_path(
                tn, lp.path_steps, tensors, out_edges=out_edges,
                contract_fn=_gemm_contract(lp, lp.tiling, interpret))
        return y.reshape(tokens, -1)

    # "jnp": the reference executor along the planned steps
    path = as_candidate_path(tn, lp.path_steps)
    return ref.tt_linear_ref(x2d, list(cores), tn, path)


def _backward_planned(
    lp: LayerPlan,
    x2d: jax.Array,
    cores: Sequence[jax.Array],
    dy2d: jax.Array,
    in_modes: tuple[int, ...],
    out_modes: tuple[int, ...],
    ranks: tuple[int, ...],
    interpret: Optional[bool],
):
    """Contract the layer's gradient networks along the planned backward
    paths, each through its planned backend.  Returns ``(dx2d, dcores)``.
    """
    tokens = x2d.shape[0]
    tn = tt_linear_network(tokens, in_modes, out_modes, ranks)
    core_names = [n.name for n in tn.nodes if n.name != "X"]
    named = dict(zip(core_names, cores))
    node_edges = {n.name: n.edges for n in tn.nodes}
    bwd_ops = {op.wrt: op
               for op in _resolve_backward_ops(lp, tokens, in_modes,
                                               out_modes, ranks)}
    dy = dy2d.astype(x2d.dtype)

    dx2d = None
    dcores: dict[str, jax.Array] = {}
    for wrt, net in backward_networks(tn):
        op = bwd_ops[wrt]
        record_execution(lp, tokens, phase="bwd", backend=op.backend,
                         wrt=wrt, path_steps=op.path_steps,
                         tiling=op.tiling)
        if wrt == "dx" and op.backend == "streaming_tt":
            bt = ops.token_block(op.tiling.block_tokens, tokens)
            net_block = grad_input_network(
                tt_linear_network(bt, in_modes, out_modes, ranks))
            path = as_candidate_path(net_block, op.path_steps)
            dx2d = ops.tt_linear(dy, list(cores), net_block, path,
                                 block_tokens=bt, interpret=interpret)
            continue
        tensors = {n.name: named[n.name] for n in net.nodes
                   if n.name in named}
        if wrt != "dx":
            tensors["X"] = x2d.reshape((tokens,) + tuple(in_modes))
        tensors["dY"] = dy.reshape((tokens,) + tuple(out_modes))
        contract_fn = (_gemm_contract(lp, op.tiling, interpret)
                       if op.backend == "tt_gemm" else None)
        out_edges = node_edges["X"] if wrt == "dx" else node_edges[wrt]
        g = execute_path(net, op.path_steps, tensors, out_edges=out_edges,
                         preferred_dtype=jnp.float32,
                         contract_fn=contract_fn)
        if wrt == "dx":
            dx2d = g.reshape(tokens, -1)
        else:
            dcores[wrt] = g.astype(named[wrt].dtype)
    assert dx2d is not None
    return dx2d.astype(x2d.dtype), tuple(
        dcores[name] for name in core_names)


# ---------------------------------------------------------------------------
# the planned TT-linear entry point
# ---------------------------------------------------------------------------

def planned_tt_linear(
    lp: LayerPlan,
    x2d: jax.Array,
    cores: Sequence[jax.Array],
    in_modes: tuple[int, ...],
    out_modes: tuple[int, ...],
    ranks: tuple[int, ...],
    *,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Apply one planned TT projection to ``x2d: (tokens, d_in)``.

    Returns ``(tokens, d_out)``.  The plan's ``path_steps`` are replayed
    verbatim; the backend decides *how* each step runs.  Pallas backends
    are differentiable: the custom VJP contracts the plan's backward
    networks (see module docstring).  Its ops, forward and backward,
    sit under the name scope ``lp.name``.
    """
    with jax.named_scope(lp.name):
        in_modes = tuple(in_modes)
        out_modes = tuple(out_modes)
        ranks = tuple(ranks)
        tokens = x2d.shape[0]
        record_execution(lp, tokens)

        if lp.backend == "jnp" and not has_pallas_backward(lp):
            # pure-reference layer (jnp forward, no Pallas backward ops):
            # plain jnp is natively differentiable, keep native autodiff
            return _forward_planned(lp, x2d, cores, in_modes, out_modes, ranks,
                                    interpret)

        @jax.custom_vjp
        def f(x2d, cores):
            return _forward_planned(lp, x2d, cores, in_modes, out_modes, ranks,
                                    interpret)

        def fwd(x2d, cores):
            return f(x2d, cores), (x2d, cores)

        def bwd(res, dy2d):
            x2d, cores = res
            return _backward_planned(lp, x2d, cores, dy2d, in_modes, out_modes,
                                     ranks, interpret)

        f.defvjp(fwd, bwd)
        return f(x2d, tuple(cores))
