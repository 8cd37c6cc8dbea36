"""Fully-streaming TT contraction kernel (paper 4.2), TPU-native form.

TT cores are tiny (KBs) — they are pinned whole in VMEM for the entire
kernel (BlockSpec index_map constant in the grid), while activations
stream through in token blocks.  Each grid step contracts one token block
against the pinned operands along a DSE-searched path, entirely in VMEM:
one HBM read of X, one HBM write of Y, zero intermediate spills.  This is
the streaming data-reuse property of the paper's FPGA kernel, re-expressed
as a Pallas pipeline.

The contraction path is a *static* argument.  Steps that join two cores
do not depend on the tokens, so they run once outside the kernel and
their results are pinned like cores; the steps that touch the streamed
value are unrolled at trace time inside the kernel body.  Inside, the
token dim is the minor (lane) axis of every value, so each step is one
2-d ``jnp.dot`` of a pinned ``(free, shared)`` matrix with the streamed
value viewed as ``(shared, free * tokens)`` — the only reshapes and
transposes are of leading axes, which Mosaic lowers when the token block
is a multiple of 128 (:func:`token_block`).

The streamed operand is whichever node has ``kind == "input"`` — the
forward activations ``X``, or the output gradient ``dY`` of a
``repro.core.backward`` dx-network (the backward pass of a TT layer is
itself a streaming TT contraction: same pinned cores, gradient streamed).
:func:`streaming_tt_linear_vjp` packages that into a ``jax.custom_vjp``
so the kernel composes with ``jax.grad``.
"""

from __future__ import annotations

import functools
import math
from typing import Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.backward import GRAD_NODE, backward_networks
from repro.core.contraction import execute_path
from repro.core.paths import CandidatePath, find_topk_paths
from repro.core.tensor_network import Node, TensorNetwork, tt_linear_network

from .tt_gemm import LANE


def _stream_node(tn: TensorNetwork) -> Node:
    """The single streamed (kind == "input") node of the network."""
    inputs = [n for n in tn.nodes if n.kind == "input"]
    if len(inputs) != 1:
        raise ValueError(
            "streaming kernel needs exactly one streamed node, found "
            f"{[n.name for n in inputs]}")
    return inputs[0]


def _stream_layout(tn: TensorNetwork):
    """(stream_name, batch_edge, in_modes, out_edges).

    The streamed node must carry exactly one free (batch) edge, leading;
    its remaining (shared) edges are the flattened inner dim of the 2-d
    operand.  ``out_edges`` orders the result as batch edge first, then
    the weight-side free edges in node order — the row-major layout of
    the 2-d output.
    """
    x = _stream_node(tn)
    free = set(tn.free_edges)
    batch_edges = [e for e in x.edges if e in free]
    if len(batch_edges) != 1 or x.edges[0] != batch_edges[0]:
        raise ValueError(
            f"streamed node {x.name}: need a single leading batch edge, "
            f"got edges {x.edges} (free: {batch_edges})")
    batch = batch_edges[0]
    in_modes = tuple(d for e, d in zip(x.edges, x.dims) if e != batch)
    out_edges = (batch,) + tuple(
        e for n in tn.nodes if n.kind != "input" for e in n.edges if e in free)
    return x.name, batch, in_modes, out_edges


def token_block(block_tokens: int, tokens: int) -> int:
    """The token block the kernel runs: a multiple of ``LANE``.

    Inside the kernel the token dim is the minor (lane) axis of every
    intermediate, and Mosaic only reshapes and transposes such values
    when that axis is a whole number of 128-lane vregs.  The block is the
    plan's ``block_tokens`` rounded up to that, but never more than the
    lane-rounded token count (a decode step of 4 tokens runs one
    128-token block).
    """
    def up(n: int) -> int:
        return -(-max(1, n) // LANE) * LANE

    return min(up(block_tokens), up(tokens))


def _hoisted_contract(ea, ta, eb, tb):
    """A batch-free (core x core) step, evaluated once outside the kernel."""
    shared = [e for e in ea if e in eb]
    t = jnp.tensordot(ta, tb, axes=([ea.index(e) for e in shared],
                                    [eb.index(e) for e in shared]),
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)
    edges = tuple(e for e in ea if e not in shared) + tuple(
        e for e in eb if e not in shared)
    return edges, t


def _chain_layout(t_edges, t_dims, pinned):
    """Static program of a token-minor chain: one entry per pinned operand.

    The streamed value has axes ``(*t_edges, tokens)``.  Contracting it
    with pinned ``(edges, array)`` over their shared edges ``S`` is one
    2-d matmul ``P (F_P, S) @ T (S, F_T * tokens)``: the streamed value
    is permuted to ``(S, F_T, tokens)`` — leading axes only, the lane
    axis stays put — and the result has axes ``(*F_P, *F_T, tokens)``.
    Returns ``(program, mats, edges, dims)``: per step
    ``(perm, n_s, out_dims)``, the pinned operands as ``(F_P, S)``
    matrices, and the final axes.
    """
    program, mats = [], []
    edges, dims = tuple(t_edges), tuple(t_dims)
    for p_edges, p in pinned:
        s = [e for e in edges if e in p_edges]
        f_t = [e for e in edges if e not in s]
        f_p = [e for e in p_edges if e not in s]
        perm = tuple(edges.index(e) for e in s + f_t)
        p_perm = [p_edges.index(e) for e in f_p + s]
        n_s = math.prod(dims[edges.index(e)] for e in s)
        mats.append(jnp.transpose(p, p_perm).reshape(-1, n_s))
        size = dict(zip(edges, dims)) | dict(zip(p_edges, p.shape))
        edges = tuple(f_p + f_t)
        dims = tuple(size[e] for e in edges)
        program.append((perm, n_s, dims))
    return tuple(program), mats, edges, dims


def _chain_kernel(x_ref, *refs, in_dims, program, out_perm, n_out, bt):
    """Token-minor chain body: every contraction is a 2-d ``jnp.dot``."""
    p_refs, o_ref = refs[:-1], refs[-1]
    t = x_ref[...].T.reshape(in_dims + (bt,))
    for p_ref, (perm, n_s, out_dims) in zip(p_refs, program):
        if perm != tuple(range(len(perm))):
            t = jnp.transpose(t, perm + (len(perm),))
        p = p_ref[...]
        if p.dtype != t.dtype:
            p, t = p.astype(jnp.float32), t.astype(jnp.float32)
        y = jnp.dot(p, t.reshape(n_s, -1), preferred_element_type=jnp.float32)
        t = y.reshape(out_dims + (bt,))
    if out_perm != tuple(range(len(out_perm))):
        t = jnp.transpose(t, out_perm + (len(out_perm),))
    o_ref[...] = t.reshape(n_out, bt).T.astype(o_ref.dtype)


def stream_chain(
    x: jax.Array,
    x_edges: Sequence[str],
    x_dims: Sequence[int],
    pinned: Sequence[tuple[tuple[str, ...], jax.Array]],
    out_edges: Sequence[str],
    *,
    block_tokens: int,
    out_dtype,
    interpret: bool,
    name: str,
) -> jax.Array:
    """Contract streamed ``x`` (tokens, prod(x_dims)) with ``pinned`` in order.

    ``x``'s columns are the row-major flattening of axes ``x_edges``
    (sizes ``x_dims``); each pinned ``(edges, array)`` is contracted with
    the running value over all their shared edges.  Returns
    ``(tokens, prod(out dims))`` with columns in ``out_edges`` order.
    The pinned operands are whole-array VMEM blocks; ``x`` streams in
    ``block_tokens`` rows (a ``LANE`` multiple dividing ``tokens``).
    ``name`` names the kernel in the compiled program (``%<name>.N``).
    """
    tokens = x.shape[0]
    if tokens % block_tokens:
        raise ValueError(f"tokens {tokens} not a multiple of {block_tokens}")
    program, mats, edges, dims = _chain_layout(x_edges, x_dims, pinned)
    if sorted(edges) != sorted(out_edges):
        raise ValueError(f"chain leaves edges {edges}, not {tuple(out_edges)}")
    out_perm = tuple(edges.index(e) for e in out_edges)
    n_out = math.prod(dims)
    kernel = functools.partial(
        _chain_kernel, in_dims=tuple(x_dims), program=program,
        out_perm=out_perm, n_out=n_out, bt=block_tokens)
    x_spec = pl.BlockSpec((block_tokens, x.shape[1]), lambda i: (i, 0))
    mat_specs = [pl.BlockSpec(m.shape, lambda i: (0, 0)) for m in mats]
    o_spec = pl.BlockSpec((block_tokens, n_out), lambda i: (i, 0))
    kwargs = {}
    if not interpret:
        from jax.experimental.pallas import tpu as pltpu

        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel",)
        )
    return pl.pallas_call(
        kernel,
        grid=(tokens // block_tokens,),
        in_specs=[x_spec] + mat_specs,
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((tokens, n_out), out_dtype),
        interpret=interpret,
        name=name,
        **kwargs,
    )(x, *mats)


def streaming_tt_linear(
    x: jax.Array,
    cores: Sequence[jax.Array],
    tn: TensorNetwork,
    path: CandidatePath,
    *,
    block_tokens: int = 256,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """Apply a streaming TT contraction to ``x`` (tokens, N_in).

    ``x`` is the network's streamed node flattened to 2-d; tokens must
    divide by ``block_tokens``, a multiple of ``LANE`` on the TPU (see
    :func:`token_block`).  Path steps that join two cores do not depend
    on the tokens: they run once, outside the kernel, and their results
    are pinned like cores.  Every step that touches the streamed value
    runs inside the kernel in path order.
    """
    stream_name, batch, in_modes, out_edges = _stream_layout(tn)
    if math.prod(in_modes) != x.shape[1]:
        raise ValueError("x inner dim does not match network input modes")
    stream = object()  # placeholder for the streamed value in the work list
    x_edges = tuple(e for e in _stream_node(tn).edges if e != batch)
    it = iter(cores)
    work = [(x_edges, stream) if n.name == stream_name else (n.edges, next(it))
            for n in tn.nodes]
    pinned = []
    for (i, j) in path.steps:
        (ea, ta), (eb, tb) = work[i], work[j]
        if ta is stream or tb is stream:
            other = (eb, tb) if ta is stream else (ea, ta)
            s_edges = ea if ta is stream else eb
            pinned.append(other)
            merged = (tuple(e for e in other[0] if e not in s_edges)
                      + tuple(e for e in s_edges if e not in other[0]), stream)
        else:
            merged = _hoisted_contract(ea, ta, eb, tb)
        work = [w for k, w in enumerate(work) if k not in (i, j)]
        work.append(merged)
    return stream_chain(
        x, x_edges, in_modes, pinned, out_edges[1:],
        block_tokens=block_tokens, out_dtype=out_dtype or x.dtype,
        interpret=interpret, name="streaming_tt")


def build_block_network(
    block_tokens: int,
    in_modes: Sequence[int],
    out_modes: Sequence[int],
    ranks: Sequence[int],
) -> TensorNetwork:
    """The per-block tensor network the kernel contracts (batch = block)."""
    return tt_linear_network(block_tokens, tuple(in_modes), tuple(out_modes),
                             tuple(ranks))


# ---------------------------------------------------------------------------
# differentiable wrapper: backward pass along searched gradient networks
# ---------------------------------------------------------------------------

def streaming_tt_linear_vjp(
    x: jax.Array,
    cores: Sequence[jax.Array],
    tn: TensorNetwork,
    path: CandidatePath,
    *,
    bwd_steps: Optional[Mapping[str, Sequence[tuple[int, int]]]] = None,
    block_tokens: int = 256,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """:func:`streaming_tt_linear` under a ``jax.custom_vjp``.

    The backward pass contracts the layer's gradient networks
    (``repro.core.backward``) instead of transposing the kernel:

      * ``dL/dx`` streams ``dY`` through the *same* Pallas kernel against
        the pinned cores (the dx network is itself a streaming TT
        contraction);
      * each ``dL/dG_k`` is contracted by the jnp path executor over the
        whole batch (weight gradients reduce over tokens, so they stream
        two operands and do not fit the single-stream kernel; the plan
        executor routes them through the Pallas GEMM backend instead).

    ``bwd_steps`` optionally pins the DSE-searched backward path per
    gradient (keys ``"dx"`` / core node names); missing entries fall back
    to the MAC-optimal path of that gradient's network.  tokens must be a
    multiple of ``block_tokens`` (the plan executor's padded ``ops``
    wrappers handle ragged shapes).

    This is the *kernel-level* differentiable API (standalone use of the
    streaming kernel under ``jax.grad``).  Planned model execution goes
    through ``repro.plan.executor._backward_planned`` instead, which
    contracts the same ``repro.core.backward`` gradient networks but
    routes each one per the plan's BackwardOp backend/tiling — changes
    to the gradient-contraction contract (edge order, dtype casts,
    padding exactness) must be mirrored there.
    """
    tokens = x.shape[0]
    bwd_steps = dict(bwd_steps or {})
    x_node = _stream_node(tn)
    x_inner = tuple(d for e, d in zip(x_node.edges, x_node.dims)
                    if e != x_node.edges[0])
    core_names = [n.name for n in tn.nodes if n.kind != "input"]
    node_edges = {n.name: n.edges for n in tn.nodes}
    # dx streams per block -> derive from the block-batch network; weight
    # grads reduce over the whole batch -> derive from a full-batch rebind
    dx_net = dict(backward_networks(tn))["dx"]
    full_bnets = [(wrt, net)
                  for wrt, net in backward_networks(_rebatch(tn, tokens))
                  if wrt != "dx"]

    def _path_for(wrt: str, net: TensorNetwork) -> CandidatePath:
        steps = bwd_steps.get(wrt)
        if steps is None:
            return find_topk_paths(net, k=1)[0]
        steps = tuple(tuple(s) for s in steps)
        gemms = tuple(net.gemm_sequence(steps))
        return CandidatePath(steps, sum(g.macs for g in gemms), gemms)

    @jax.custom_vjp
    def f(x, cores):
        return streaming_tt_linear(
            x, list(cores), tn, path, block_tokens=block_tokens,
            out_dtype=out_dtype, interpret=interpret)

    def fwd(x, cores):
        return f(x, cores), (x, cores)

    def bwd(res, g):
        x, cores = res
        named = dict(zip(core_names, cores))
        # dL/dx: dY streamed against the pinned cores — the same kernel
        dx2d = streaming_tt_linear(
            g.astype(x.dtype), list(cores), dx_net, _path_for("dx", dx_net),
            block_tokens=block_tokens, interpret=interpret)
        dcores = {}
        for wrt, net in full_bnets:
            grad_node = next(n for n in net.nodes if n.name == GRAD_NODE)
            tensors = {n.name: named[n.name] for n in net.nodes
                       if n.name in named}
            tensors[x_node.name] = x.reshape((tokens,) + x_inner)
            tensors[grad_node.name] = g.reshape(grad_node.dims)
            dcores[wrt] = execute_path(
                net, _path_for(wrt, net), tensors,
                out_edges=node_edges[wrt], preferred_dtype=jnp.float32,
            ).astype(named[wrt].dtype)
        return dx2d.reshape(x.shape), tuple(dcores[n] for n in core_names)

    f.defvjp(fwd, bwd)
    return f(x, tuple(cores))


def _rebatch(tn: TensorNetwork, tokens: int) -> TensorNetwork:
    """Rebind the streamed node's leading batch edge to ``tokens``."""
    x = _stream_node(tn)
    nodes = [
        Node(n.name, n.edges, (tokens,) + n.dims[1:], n.kind)
        if n.name == x.name else n
        for n in tn.nodes
    ]
    return TensorNetwork(nodes)
