"""Fused path-segment kernel: a contiguous run of contraction-path steps
executed inside ONE ``pallas_call`` with VMEM-resident intermediates.

This bridges the two existing extremes: ``streaming_tt`` contracts the
*whole* path in VMEM (single streamed operand, whole-network working set
must fit), while ``tt_gemm`` launches one kernel per pairwise step and
round-trips every intermediate through HBM.  A fused segment executes the
chain runs found by ``repro.core.fusion.segment_path``: the batch-carrying
chain streams through the grid in token blocks, the batch-free operands
are pinned whole in VMEM (constant index_map), and each interior
intermediate lives in an fp32 VMEM scratch buffer — zero HBM bytes, one
kernel-launch overhead for the whole run.

Dataflow note: inside a segment every step is lowered as an OS-style
fp32 contraction — the per-step WS/IS grid orders cannot be preserved
across a shared 1-d token grid, which is the "falling back to OS inside
a segment" rule the plan compiler and cost model assume.

Two lowerings of the same segment:

* on the TPU (``interpret=False``), :func:`token_minor_segment` runs the
  chain through ``streaming_tt.stream_chain`` — tokens on the lane axis,
  one 2-d dot per step, the only form of these contractions Mosaic
  lowers.  It agrees with the per-step route to fp32 rounding.
* in interpret mode, each chained step replays the per-step kernel's
  *exact* blocked GEMM: the same ``gemm_blocks`` tiles (resolved against
  the full step dims, not the token-blocked kernel-local dims), the same
  sequential k-block partial-sum grouping, and the dot operands are
  materialized behind ``optimization_barrier`` (which Mosaic does not
  lower) so XLA cannot refuse the per-step lowering by folding the
  surrounding transposes into the dot (see ``_chain_step``).  fp32 fused
  execution on the CPU is therefore bit-identical to the per-step
  ``tt_gemm`` route (property-tested); with bf16 operands it is *more*
  precise, because interior intermediates skip the per-step cast back
  to bf16.
"""

from __future__ import annotations

import functools
import math
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .streaming_tt import stream_chain, token_block
from .tt_gemm import _pad_to_block, gemm_blocks, pltpu_accumulator

#: default batch (streamed-token) edge label
BATCH_EDGE = "b"

Src = tuple[str, int]  # ("in", kernel-input position) | ("mid", op index)


def _compile_segment(entries, steps, batch_edge):
    """Symbolic replay of ``steps`` over the work-list ``entries``.

    ``entries`` is the current ``execute_path``-style work list as
    ``(edges, shape)`` pairs; ``steps`` are current-index ``(i, j)`` pairs
    forming a chain (each step after the first consumes the previous
    step's result).  Returns ``(input_positions, ops, mids)``:

      * ``input_positions`` — work-list indices of the original entries
        the segment reads, in first-use order (the kernel input order);
      * ``ops`` — per step ``(a_src, b_src, ax_a, ax_b, (m, k, n))``
        with sources in ``("in", pos)`` / ``("mid", t)`` space and the
        *full* flattened GEMM dims of the step (actual batch size — the
        dims the per-step route clamps its blocks against);
      * ``mids`` — per step ``(edges, dims)`` of its result (actual batch
        size; the caller re-blocks).

    Axis bookkeeping is copied verbatim from
    ``repro.core.contraction.execute_path`` so the fused result is
    element-for-element the sequential one.
    """
    sym: list[tuple[tuple[str, ...], tuple[int, ...], Src]] = []
    for pos, (edges, shape) in enumerate(entries):
        sym.append((tuple(edges), tuple(shape), ("in", pos)))

    input_positions: list[int] = []
    in_slot: dict[int, int] = {}
    ops: list[tuple] = []
    mids: list[tuple[tuple[str, ...], tuple[int, ...]]] = []

    def as_kernel_src(src: Src) -> Src:
        kind, idx = src
        if kind == "mid":
            return src
        if idx not in in_slot:
            in_slot[idx] = len(input_positions)
            input_positions.append(idx)
        return ("in", in_slot[idx])

    for t, (i, j) in enumerate(steps):
        (ea, da, sa), (eb, db, sb) = sym[i], sym[j]
        if t > 0 and ("mid", t - 1) not in (sa, sb):
            raise ValueError(
                f"segment step {t} does not consume the previous result "
                "(not a chain)")
        if sa[0] == "mid" and sb[0] == "mid":
            raise ValueError(f"segment step {t} joins two interior results")
        n_batch = (batch_edge in ea) + (batch_edge in eb)
        if n_batch != 1:
            raise ValueError(
                f"segment step {t}: need exactly one batch-carrying "
                f"operand, found {n_batch}")
        shared = [e for e in ea if e in eb]
        ax_a = tuple(ea.index(e) for e in shared)
        ax_b = tuple(eb.index(e) for e in shared)
        ec = tuple(e for e in ea if e not in shared) + tuple(
            e for e in eb if e not in shared)
        dc = tuple(d for e, d in zip(ea, da) if e not in shared) + tuple(
            d for e, d in zip(eb, db) if e not in shared)
        m_full = math.prod(d for e, d in zip(ea, da) if e not in shared)
        n_full = math.prod(d for e, d in zip(eb, db) if e not in shared)
        k_full = math.prod(da[ax] for ax in ax_a)
        ops.append((as_kernel_src(sa), as_kernel_src(sb), ax_a, ax_b,
                    (m_full, k_full, n_full)))
        mids.append((ec, dc))
        sym = [s for k, s in enumerate(sym) if k not in (i, j)]
        sym.append((ec, dc, ("mid", t)))
    return input_positions, ops, mids


def _chain_step(a, b, ax_a, ax_b, full_dims, block_m, block_k, block_n):
    """One pairwise contraction, mirroring the per-step GEMM route exactly.

    Operands are transposed/flattened to (M, K) @ (K, N) with the same
    axis bookkeeping as ``ops.gemm_contract``, then tiled with the same
    clamped blocks the per-step route would use — ``full_dims`` are the
    step's full (un-token-blocked) flattened GEMM dims, because that is
    what ``gemm_contract`` clamps against.  Each output block accumulates
    its k-blocks *sequentially* from a zero fp32 accumulator (the
    per-step OS grouping; WS/IS agree after the fp32 output fix), and
    every dot sees an ``optimization_barrier``-materialized block of
    exactly the per-step kernel's shape (and the result is materialized
    the same way), so XLA lowers the same GEMM in both routes and the
    fused result is bit-identical to the spill-per-step route.
    """
    m_full, k_full, n_full = full_dims
    a_free = [i for i in range(a.ndim) if i not in ax_a]
    b_free = [i for i in range(b.ndim) if i not in ax_b]
    a_dims = [a.shape[i] for i in a_free]
    b_dims = [b.shape[i] for i in b_free]
    m = math.prod(a_dims)
    n = math.prod(b_dims)
    k = math.prod(a.shape[i] for i in ax_a)
    a2 = jnp.transpose(a, a_free + list(ax_a)).reshape(m, k)
    b2 = jnp.transpose(b, list(ax_b) + b_free).reshape(k, n)
    bm, bk, bn = gemm_blocks(m_full, k_full, n_full, block_m, block_k,
                             block_n)
    a2 = _pad_to_block(_pad_to_block(a2, 0, bm), 1, bk)
    b2 = _pad_to_block(_pad_to_block(b2, 0, bk), 1, bn)
    n_m, n_k, n_n = a2.shape[0] // bm, a2.shape[1] // bk, b2.shape[1] // bn
    rows = []
    for mi in range(n_m):
        cols = []
        for ni in range(n_n):
            acc = jnp.zeros((bm, bn), jnp.float32)
            for kb in range(n_k):
                ab = jax.lax.optimization_barrier(
                    a2[mi * bm:(mi + 1) * bm, kb * bk:(kb + 1) * bk])
                bb = jax.lax.optimization_barrier(
                    b2[kb * bk:(kb + 1) * bk, ni * bn:(ni + 1) * bn])
                acc = acc + jnp.dot(ab, bb,
                                    preferred_element_type=jnp.float32)
            cols.append(acc)
        rows.append(jnp.concatenate(cols, axis=1) if len(cols) > 1
                    else cols[0])
    c = jnp.concatenate(rows, axis=0) if len(rows) > 1 else rows[0]
    # materialized too: else XLA folds the next step's transpose of this
    # result into these dots (transpose(A @ B) -> B^T @ A^T)
    return jax.lax.optimization_barrier(
        c[:m, :n].reshape(tuple(a_dims) + tuple(b_dims)))


def _kernel(*refs, ops, n_in, block_m, block_k, block_n):
    in_vals = [refs[k][...] for k in range(n_in)]
    o_ref = refs[n_in]
    scratch = refs[n_in + 1:]

    def val(src):
        kind, idx = src
        return in_vals[idx] if kind == "in" else scratch[idx][...]

    for t, (a_src, b_src, ax_a, ax_b, full_dims) in enumerate(ops):
        res = _chain_step(val(a_src), val(b_src), ax_a, ax_b, full_dims,
                          block_m, block_k, block_n)
        if t < len(ops) - 1:
            scratch[t][...] = res
        else:
            o_ref[...] = res.astype(o_ref.dtype)


def fused_segment_contract(
    work: Sequence[tuple[tuple[str, ...], jax.Array]],
    steps: Sequence[tuple[int, int]],
    *,
    block_tokens: int = 256,
    block_m: int = 128,
    block_k: int = 128,
    block_n: int = 128,
    batch_edge: str = BATCH_EDGE,
    out_dtype=None,
    interpret: bool = False,
) -> tuple[tuple[str, ...], jax.Array]:
    """Execute the chain run ``steps`` over ``work`` in one ``pallas_call``.

    ``work`` is the live ``execute_path`` work list (``(edges, tensor)``
    pairs); ``steps`` are current-index pairs relative to it.  Returns
    ``(result_edges, result)`` — the same entry the sequential per-step
    route would append, so the caller's bookkeeping is unchanged.  The
    token dim is padded to the block multiple and sliced back (padding
    rows are zeros and the batch edge is never contracted inside a
    segment, so kept rows are exact).  On the TPU the segment runs as
    :func:`token_minor_segment`.
    """
    if not interpret:
        return token_minor_segment(work, steps, block_tokens=block_tokens,
                                   batch_edge=batch_edge,
                                   out_dtype=out_dtype)
    input_positions, ops, mids, stream_slot = _segment_inputs(
        work, steps, batch_edge)
    arrays = [work[p][1] for p in input_positions]
    stream_edges = work[input_positions[stream_slot]][0]
    bpos = stream_edges.index(batch_edge)
    tokens = arrays[stream_slot].shape[bpos]
    block_tokens = min(block_tokens, max(1, tokens))
    padded = arrays[stream_slot]
    padded = _pad_to_block(padded, bpos, block_tokens)
    pt = padded.shape[bpos]
    arrays = list(arrays)
    arrays[stream_slot] = padded
    grid = (pt // block_tokens,)

    def block_dims(edges, dims):
        return tuple(block_tokens if e == batch_edge else d
                     for e, d in zip(edges, dims))

    in_specs = []
    for slot, p in enumerate(input_positions):
        edges = work[p][0]
        shape = tuple(work[p][1].shape)
        if slot == stream_slot:
            bshape = block_dims(edges, shape)
            in_specs.append(pl.BlockSpec(
                bshape,
                functools.partial(
                    lambda g, bp, nd: tuple(g if ax == bp else 0
                                            for ax in range(nd)),
                    bp=bpos, nd=len(bshape))))
        else:
            in_specs.append(pl.BlockSpec(
                shape,
                functools.partial(lambda g, nd=len(shape): (0,) * nd)))

    out_edges, out_dims = mids[-1]
    opos = out_edges.index(batch_edge)
    out_block = block_dims(out_edges, out_dims)
    out_padded = tuple(pt if ax == opos else d
                       for ax, d in enumerate(out_dims))
    out_spec = pl.BlockSpec(
        out_block,
        functools.partial(
            lambda g, op, nd: tuple(g if ax == op else 0
                                    for ax in range(nd)),
            op=opos, nd=len(out_block)))
    out_dtype = out_dtype or arrays[stream_slot].dtype

    scratch_shapes = [
        pltpu_accumulator(block_dims(ec, dc)) for ec, dc in mids[:-1]
    ]
    y = pl.pallas_call(
        functools.partial(_kernel, ops=ops, n_in=len(arrays),
                          block_m=block_m, block_k=block_k,
                          block_n=block_n),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct(out_padded, out_dtype),
        scratch_shapes=scratch_shapes,
        interpret=True,
        name="fused_path",
    )(*arrays)
    if pt != tokens:
        y = jax.lax.slice_in_dim(y, 0, tokens, axis=opos)
    return out_edges, y


def _segment_inputs(work, steps, batch_edge):
    """``_compile_segment`` over ``work`` plus the streamed input's slot."""
    if len(steps) < 2:
        raise ValueError("fused segments need at least two steps")
    entries = [(edges, tuple(t.shape)) for edges, t in work]
    input_positions, ops, mids = _compile_segment(entries, steps, batch_edge)
    slots = [slot for slot, p in enumerate(input_positions)
             if batch_edge in work[p][0]]
    if len(slots) != 1:
        raise ValueError(
            f"segment needs one batch-carrying input, found {len(slots)}")
    return input_positions, ops, mids, slots[0]


def token_minor_segment(
    work: Sequence[tuple[tuple[str, ...], jax.Array]],
    steps: Sequence[tuple[int, int]],
    *,
    block_tokens: int = 256,
    batch_edge: str = BATCH_EDGE,
    out_dtype=None,
    interpret: bool = False,
) -> tuple[tuple[str, ...], jax.Array]:
    """The segment as a token-minor chain (``streaming_tt.stream_chain``).

    The form Mosaic lowers: every step contracts the streamed value with
    one pinned operand as a single 2-d dot, tokens on the lane axis.  Its
    blocking differs from the per-step GEMMs, so results agree with the
    per-step route to fp32 rounding, not bit for bit.  Same arguments
    and result as :func:`fused_segment_contract`.
    """
    input_positions, ops, mids, stream_slot = _segment_inputs(
        work, steps, batch_edge)
    stream_edges, x = work[input_positions[stream_slot]]
    bpos = stream_edges.index(batch_edge)
    tokens = x.shape[bpos]
    x_edges = tuple(e for e in stream_edges if e != batch_edge)
    x_dims = tuple(d for e, d in zip(stream_edges, x.shape)
                   if e != batch_edge)
    bt = token_block(block_tokens, tokens)
    x2 = _pad_to_block(jnp.moveaxis(x, bpos, 0).reshape(tokens, -1), 0, bt)
    pinned = []
    for t, (a_src, b_src, *_rest) in enumerate(ops):
        src = next(s for s in (a_src, b_src)
                   if s[0] == "in" and (t > 0 or s[1] != stream_slot))
        pinned.append(work[input_positions[src[1]]])
    out_edges, out_dims = mids[-1]
    out_nb = tuple(e for e in out_edges if e != batch_edge)
    y = stream_chain(x2, x_edges, x_dims, pinned, out_nb, block_tokens=bt,
                     out_dtype=out_dtype or x.dtype, interpret=interpret,
                     name="fused_path")
    y = y[:tokens].reshape((tokens,) + tuple(
        d for e, d in zip(out_edges, out_dims) if e != batch_edge))
    return out_edges, jnp.moveaxis(y, 0, out_edges.index(batch_edge))


def segment_vmem_bytes(
    work: Sequence[tuple[tuple[str, ...], jax.Array]],
    steps: Sequence[tuple[int, int]],
    *,
    block_tokens: int,
    batch_edge: str = BATCH_EDGE,
) -> int:
    """Working-set bytes the fused call keeps resident (for diagnostics)."""
    entries = [(edges, tuple(t.shape)) for edges, t in work]
    input_positions, _, mids = _compile_segment(entries, steps, batch_edge)

    def blocked(edges, dims, itemsize):
        return itemsize * math.prod(
            block_tokens if e == batch_edge else d
            for e, d in zip(edges, dims))

    total = sum(
        blocked(work[p][0], work[p][1].shape, work[p][1].dtype.itemsize)
        for p in input_positions)
    total += sum(blocked(ec, dc, 4) for ec, dc in mids)
    return total
