"""Jit'd public wrappers around the Pallas kernels.

On CPU containers the kernels execute in ``interpret=True`` mode (Python
evaluation of the kernel body — numerics identical); on TPU backends the
compiled Mosaic kernels run.  ``interpret`` is resolved from the default
backend unless forced.
"""

from __future__ import annotations

import functools
import math
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.core.paths import CandidatePath
from repro.core.tensor_network import TensorNetwork
from . import tt_gemm as _tt_gemm
from . import streaming_tt as _streaming
from . import fused_path as _fused


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


#: zero-pad one axis up to a block multiple (canonical implementation
#: lives beside the kernel whose grid requires it)
_pad_to = _tt_gemm._pad_to_block


@functools.partial(
    jax.jit,
    static_argnames=("dataflow", "block_m", "block_k", "block_n", "interpret",
                     "differentiable"),
)
def gemm(
    a: jax.Array,
    b: jax.Array,
    dataflow: str = "OS",
    block_m: int = 128,
    block_k: int = 128,
    block_n: int = 128,
    interpret: bool | None = None,
    differentiable: bool = False,
) -> jax.Array:
    """Dataflow-configurable GEMM for arbitrary (non-block-multiple) dims.

    The blocks are caps, resolved against ``a @ b``'s dims under
    Mosaic's (8, 128) rule (:func:`tt_gemm.gemm_blocks`).  Padding to
    block multiples (and slicing back) happens inside
    :func:`tt_gemm.tt_gemm` itself — zero rows/columns contribute
    nothing to a matmul.  ``differentiable=True`` routes through
    :func:`tt_gemm.tt_gemm_vjp` (custom-VJP kernel whose backward GEMMs
    are also Pallas calls, each padding its own transposed shapes), so
    the whole call composes with ``jax.grad``.
    """
    interpret = _default_interpret() if interpret is None else interpret
    kernel = _tt_gemm.tt_gemm_vjp if differentiable else _tt_gemm.tt_gemm
    block_m, block_k, block_n = _tt_gemm.gemm_blocks(
        a.shape[0], a.shape[1], b.shape[1], block_m, block_k, block_n)
    return kernel(
        a, b,
        dataflow=dataflow,  # type: ignore[arg-type]
        block_m=block_m, block_k=block_k, block_n=block_n,
        interpret=interpret,
    )


def tt_linear(
    x: jax.Array,
    cores: Sequence[jax.Array],
    tn: TensorNetwork,
    path: CandidatePath,
    block_tokens: int = 256,
    interpret: bool | None = None,
    differentiable: bool = False,
    bwd_steps=None,
) -> jax.Array:
    """Streaming TT-linear; pads the token dim to the block multiple.

    ``differentiable=True`` routes through
    :func:`streaming_tt.streaming_tt_linear_vjp` (custom-VJP kernel: dx
    streams through the same Pallas kernel, weight grads contract their
    searched backward networks); ``bwd_steps`` optionally pins the
    DSE-searched backward path per gradient.  Padding rows are zero, so
    they contribute nothing to the weight gradients and their dx rows
    are sliced away — the padded call is exact under ``jax.grad``.
    """
    interpret = _default_interpret() if interpret is None else interpret
    tokens = x.shape[0]
    xp = _pad_to(x, 0, block_tokens)
    if differentiable:
        y = _streaming.streaming_tt_linear_vjp(
            xp, cores, tn, path, bwd_steps=bwd_steps,
            block_tokens=block_tokens, interpret=interpret
        )
    else:
        y = _streaming.streaming_tt_linear(
            xp, cores, tn, path, block_tokens=block_tokens, interpret=interpret
        )
    return y[:tokens]


def fused_segment(
    work,
    steps,
    block_tokens: int = 256,
    block_m: int = 128,
    block_k: int = 128,
    block_n: int = 128,
    interpret: bool | None = None,
    out_dtype=None,
):
    """Execute a chain run of contraction-path steps in one ``pallas_call``.

    Thin wrapper over :func:`fused_path.fused_segment_contract` resolving
    ``interpret`` from the default backend; ``work`` is the live
    ``execute_path`` work list and ``steps`` the current-index pairs of
    the segment.  Returns ``(result_edges, result)`` — the entry the
    sequential per-step route would have appended.
    """
    interpret = _default_interpret() if interpret is None else interpret
    return _fused.fused_segment_contract(
        work, steps, block_tokens=block_tokens, block_m=block_m,
        block_k=block_k, block_n=block_n,
        out_dtype=out_dtype, interpret=interpret)


def clamp_block(block: int, dim: int, align: int = _tt_gemm.SUBLANE) -> int:
    """Shrink a compile-time block to the runtime dim, legal for Mosaic.

    The DSE tiles for its search-time shapes; a runtime call may carry
    fewer tokens (decode) or contract a smaller intermediate, and padding
    up to the full plan block would compute mostly zeros.  See
    :func:`tt_gemm.legal_block` for the (8, 128) rule.
    """
    return _tt_gemm.legal_block(block, dim, align)


#: legal token (lane) block of the streaming and fused kernels
token_block = _streaming.token_block


def gemm_contract(
    dataflow: str = "OS",
    block_m: int = 128,
    block_k: int = 128,
    block_n: int = 128,
    interpret: bool | None = None,
    differentiable: bool = False,
):
    """A per-step ``contract_fn`` for ``core.contraction.execute_path``
    that lowers each pairwise tensor contraction to the dataflow-
    configurable Pallas GEMM.

    Operands are transposed to (free..., shared...) / (shared..., free...)
    and flattened to (M, K) @ (K, N); the result keeps tensordot's axis
    order (A's free axes then B's), so all edge bookkeeping stays in the
    path executor.  :func:`gemm` resolves the blocks against each step's
    dims.
    """

    def contract(ta: jax.Array, tb: jax.Array, axes) -> jax.Array:
        ax_a, ax_b = axes
        a_free = [i for i in range(ta.ndim) if i not in ax_a]
        b_free = [i for i in range(tb.ndim) if i not in ax_b]
        a_dims = [ta.shape[i] for i in a_free]
        b_dims = [tb.shape[i] for i in b_free]
        m = math.prod(a_dims)
        n = math.prod(b_dims)
        k = math.prod(ta.shape[i] for i in ax_a)
        a2 = jnp.transpose(ta, a_free + list(ax_a)).reshape(m, k)
        b2 = jnp.transpose(tb, list(ax_b) + b_free).reshape(k, n)
        c2 = gemm(a2, b2, dataflow=dataflow, block_m=block_m,
                  block_k=block_k, block_n=block_n, interpret=interpret,
                  differentiable=differentiable)
        return c2.reshape(tuple(a_dims) + tuple(b_dims))

    return contract
