"""Pallas TPU GEMM with DSE-selectable dataflow (IS / OS / WS).

The paper's FPGA engine switches dataflows by re-muxing which operand is
pinned in the PE array.  The TPU-native analogue is the *grid iteration
order* of a tiled Pallas matmul: the operand whose BlockSpec ``index_map``
is constant along the innermost grid axis stays VMEM-resident across
consecutive grid steps, while the others stream HBM->VMEM.  The resulting
HBM traffic asymmetry is exactly the IS/OS/WS asymmetry the paper's
simulator models:

  OS  grid=(m, n, k), k innermost  -> C block resident (classic matmul);
                                      A, B stream; C written once.
  WS  grid=(k, n, m), m innermost  -> B (weight) block resident; A streams;
                                      one fp32 C partial per k-fold.
  IS  grid=(m, k, n), n innermost  -> A (input) block resident; B streams;
                                      one fp32 C partial per k-fold.

WS and IS revisit an output block once per k-fold, non-consecutively.  A
TPU Pallas pipeline writes an output block back to HBM when its index
changes and never reads it back, so those kernels write each k-fold's
partial to its own slice of a ``(n_k, M, N)`` fp32 buffer; the folds are
summed afterwards in k order (the spill/refill traffic the simulator
charges as ``C * (2*k_folds - 1)``).

Block shapes are the DSE's tiling decision <T_M, T_K, T_N>.  Mosaic
requires the last two dims of every block to be multiples of (8, 128) or
to span the whole (padded) array dim; :func:`legal_block` resolves a
plan block against one concrete dim under that rule.

Grids must tile the operands exactly; dims that are not block multiples
are zero-padded up and the result sliced back automatically (zero rows
and columns contribute nothing to a matmul), so autotuned tilings never
need caller-side padding logic.
"""

from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DataflowName = Literal["IS", "OS", "WS"]

#: Mosaic's vreg tiling of the last two dims of a block: (sublane, lane)
SUBLANE = 8
LANE = 128


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def legal_block(block: int, dim: int, align: int = SUBLANE) -> int:
    """Resolve a plan block against one runtime dim (power of two, >= 8).

    The result is either >= ``dim`` (one block spans the whole padded
    dim) or a multiple of ``align`` (``SUBLANE`` for a second-minor block
    dim, ``LANE`` for a minor one) — the two block shapes Mosaic lowers.
    A block is never larger than the next power of two above ``dim``:
    padding further would compute only zeros.
    """
    p = max(SUBLANE, _next_pow2(dim))
    b = max(SUBLANE, min(block, p))
    if b >= dim:
        return b
    return max(min(align, p), b - b % align)


def gemm_blocks(m: int, k: int, n: int,
                block_m: int, block_k: int, block_n: int
                ) -> tuple[int, int, int]:
    """Legal ``(block_m, block_k, block_n)`` for an ``(m, k) @ (k, n)`` GEMM.

    ``block_k`` is the lane dim of A's block and ``block_n`` the lane dim
    of B's and C's; ``block_m`` is only ever a sublane dim.
    """
    return (legal_block(block_m, m, SUBLANE), legal_block(block_k, k, LANE),
            legal_block(block_n, n, LANE))


def _os_kernel(a_ref, b_ref, o_ref, acc_ref, *, n_k: int):
    """Output-stationary: k innermost; fp32 accumulator scratch in VMEM."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(k == n_k - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _partial_kernel(a_ref, b_ref, o_ref):
    """WS / IS body: this k-fold's fp32 partial into its own output slice."""
    o_ref[...] = jnp.dot(a_ref[...], b_ref[...],
                         preferred_element_type=jnp.float32)


def _sum_folds(parts: jax.Array) -> jax.Array:
    """Sum ``(n_k, M, N)`` partials in k order (the OS accumulation order)."""
    n_k = parts.shape[0]
    return jax.lax.fori_loop(1, n_k, lambda t, acc: acc + parts[t], parts[0],
                             unroll=max(1, min(n_k - 1, 8)))


def _pad_to_block(x: jax.Array, axis: int, block: int) -> jax.Array:
    pad = (-x.shape[axis]) % block
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def tt_gemm(
    a: jax.Array,
    b: jax.Array,
    *,
    dataflow: DataflowName = "OS",
    block_m: int = 128,
    block_k: int = 128,
    block_n: int = 128,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """``a @ b`` via a dataflow-configurable Pallas kernel.

    Dims that are not multiples of the block shape are zero-padded up to
    the next multiple and the result is sliced back — planned/autotuned
    tilings compose without caller-side padding.  ``interpret=True`` runs
    the kernel body in Python on CPU — the container-side validation
    mode; TPU is the compile target.
    """
    m, k = a.shape
    k2, n = b.shape
    if k != k2:
        raise ValueError(f"inner dims mismatch: {a.shape} @ {b.shape}")
    if m % block_m or k % block_k or n % block_n:
        ap = _pad_to_block(_pad_to_block(a, 0, block_m), 1, block_k)
        bp = _pad_to_block(_pad_to_block(b, 0, block_k), 1, block_n)
        out = tt_gemm(ap, bp, dataflow=dataflow, block_m=block_m,
                      block_k=block_k, block_n=block_n,
                      out_dtype=out_dtype, interpret=interpret)
        return out[:m, :n]
    out_dtype = out_dtype or a.dtype
    n_m, n_k, n_n = m // block_m, k // block_k, n // block_n

    if dataflow == "OS":
        grid = (n_m, n_n, n_k)
        a_spec = pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk))
        b_spec = pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j))
        o_spec = pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j))
        kernel = functools.partial(_os_kernel, n_k=n_k)
        scratch = [pltpu_accumulator((block_m, block_n))]
        dims = ("parallel", "parallel", "arbitrary")
        out_shape = jax.ShapeDtypeStruct((m, n), out_dtype)
    elif dataflow in ("WS", "IS"):
        if dataflow == "WS":
            grid = (n_k, n_n, n_m)
            a_spec = pl.BlockSpec((block_m, block_k), lambda kk, j, i: (i, kk))
            b_spec = pl.BlockSpec((block_k, block_n), lambda kk, j, i: (kk, j))
            o_spec = pl.BlockSpec((None, block_m, block_n),
                                  lambda kk, j, i: (kk, i, j))
        else:
            grid = (n_m, n_k, n_n)
            a_spec = pl.BlockSpec((block_m, block_k), lambda i, kk, j: (i, kk))
            b_spec = pl.BlockSpec((block_k, block_n), lambda i, kk, j: (kk, j))
            o_spec = pl.BlockSpec((None, block_m, block_n),
                                  lambda i, kk, j: (kk, i, j))
        kernel = _partial_kernel
        scratch = []
        dims = ("parallel", "parallel", "parallel")
        out_shape = jax.ShapeDtypeStruct((n_k, m, n), jnp.float32)
    else:
        raise ValueError(f"unknown dataflow {dataflow!r}")

    kwargs = {}
    if not interpret:
        # TPU compile target: annotate which grid axes may be parallelised
        from jax.experimental.pallas import tpu as pltpu

        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=dims
        )

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[a_spec, b_spec],
        out_specs=o_spec,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
        name="tt_gemm",
        **kwargs,
    )(a, b)
    if dataflow != "OS":
        out = _sum_folds(out)
    return out.astype(out_dtype)


def pltpu_accumulator(shape: tuple[int, int]):
    """fp32 VMEM scratch accumulator (works in interpret mode too)."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, jnp.float32)


# ---------------------------------------------------------------------------
# differentiable wrapper: backward GEMMs run the same Pallas kernel
# ---------------------------------------------------------------------------

def tt_gemm_vjp(
    a: jax.Array,
    b: jax.Array,
    *,
    dataflow: DataflowName = "OS",
    block_m: int = 128,
    block_k: int = 128,
    block_n: int = 128,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """``tt_gemm`` with a ``jax.custom_vjp``: differentiable end-to-end.

    A ``pallas_call`` has no transpose rule, so plain autodiff cannot
    cross :func:`tt_gemm`.  The VJP of ``C = A @ B`` is itself two GEMMs
    — ``dA = dC @ B^T`` and ``dB = A^T @ dC`` — and both are issued
    through the *same* dataflow-configurable Pallas kernel, with the
    block shapes permuted to follow the transposed operands and resolved
    by :func:`gemm_blocks` (a forward sublane block can become a backward
    lane block).
    """

    @jax.custom_vjp
    def f(a, b):
        return tt_gemm(a, b, dataflow=dataflow, block_m=block_m,
                       block_k=block_k, block_n=block_n,
                       out_dtype=out_dtype, interpret=interpret)

    def fwd(a, b):
        return f(a, b), (a, b)

    def bwd(res, g):
        a, b = res
        (m, k), n = a.shape, b.shape[1]
        # dA (m, k) = g (m, n) @ B^T (n, k): K axis is n -> block_n
        bm, bk, bn = gemm_blocks(m, n, k, block_m, block_n, block_k)
        da = tt_gemm(g, b.T, dataflow=dataflow, block_m=bm, block_k=bk,
                     block_n=bn, interpret=interpret)
        # dB (k, n) = A^T (k, m) @ g (m, n): M axis is k -> block_k
        bm, bk, bn = gemm_blocks(k, m, n, block_k, block_m, block_n)
        db = tt_gemm(a.T, g, dataflow=dataflow, block_m=bm, block_k=bk,
                     block_n=bn, interpret=interpret)
        return da.astype(a.dtype), db.astype(b.dtype)

    f.defvjp(fwd, bwd)
    return f(a, b)
