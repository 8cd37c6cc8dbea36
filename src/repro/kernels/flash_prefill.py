"""Blocked causal attention for a prompt's prefill (flash-style, forward).

``_chunked_attention`` materialises float32 scores of every query chunk
against every key, the masked upper triangle included, and writes them
to HBM, reads them back for the softmax and again for probs @ V.  This
kernel keeps each (query block, key block) tile's scores in VMEM and
runs an online softmax over the key blocks: float32 running max,
denominator and accumulator; the probabilities are cast to the value
dtype only as the P @ V operand, as the chunked path casts them.  Key
blocks entirely above the diagonal are skipped: their grid steps compute
nothing, and their K/V block index repeats the last needed one, so the
pipeline fetches nothing for them either.

GQA is native.  Query head ``j`` serves KV head ``j % H_kv`` (the
interleaved grouping of ``_chunked_attention``), so viewed as
``(S, g, H_kv * Dh)`` a KV head's ``g`` query heads are one block
``(block_q, g, Dh)``, folded into ``block_q * g`` rows of the query
tile: each K/V block is read once per KV head, never ``g`` times, and
the causal mask compares key positions with ``row // g``.  A prompt
length that no block divides is masked in the kernel: query rows past
the prompt are never written back, keys past it are masked and their V
rows zeroed (whatever the block's overhang holds).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tt_gemm import LANE, SUBLANE

#: keys per grid step
BLOCK_K = 1024
#: query rows per grid step (positions x g) at Dh 128, fewer in
#: proportion for wider heads: 1024 x 1024 float32 scores and their
#: exponentials are 8 MiB of VMEM
BLOCK_ROWS = 1024
_NEG_INF = -1e30


def flash_blocks(group: int, head_dim: int, seq: int) -> tuple[int, int]:
    """``(block_q, block_k)`` in positions for ``group`` query heads per
    KV head and a prompt of ``seq``: ``block_q * group`` near
    ``BLOCK_ROWS * LANE / head_dim``, ``block_q`` a ``SUBLANE`` multiple;
    ``block_k`` ``BLOCK_K``, or the prompt rounded up to ``LANE`` where
    that is less."""
    rows = BLOCK_ROWS * LANE // head_dim
    bq = max(SUBLANE, rows // group // SUBLANE * SUBLANE)
    return bq, min(BLOCK_K, pl.cdiv(seq, LANE) * LANE)


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            group, bq, bk, seq, scale):
    i, j = pl.program_id(2), pl.program_id(3)
    rows = bq * group
    q_lo, k_lo = i * bq, j * bk
    last = jnp.minimum((q_lo + bq - 1) // bk, pl.num_programs(3) - 1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def step(masked: bool):
        q = q_ref[...].reshape(rows, q_ref.shape[-1])
        k, v = k_ref[...], v_ref[...]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if masked:
            # key c is visible to row r when c <= q_lo + r // g, that is
            # (c - q_lo) * g <= r; keys past the prompt never are
            row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            col = k_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(((col - q_lo) * group <= row) & (col < seq), s,
                          _NEG_INF)
            kv_row = k_lo + jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
            v = jnp.where(kv_row < seq, v, jnp.zeros_like(v))
        m_prev = m_ref[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - m_next[:, :1])
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = alpha[:, :1] * acc_ref[...] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_next

    # a block wholly at or below the diagonal and inside the prompt needs
    # no mask; one crossing either edge does; one above it is skipped
    clean = (k_lo + bk - 1 <= q_lo) & (k_lo + bk <= seq)
    pl.when((j <= last) & clean)(lambda: step(False))
    pl.when((j <= last) & jnp.logical_not(clean))(lambda: step(True))

    @pl.when(j == last)
    def _done():
        out = acc_ref[...] / l_ref[...][:, :1]
        o_ref[...] = out.reshape(o_ref.shape).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def flash_prefill(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  interpret: bool = False) -> jax.Array:
    """Causal attention of ``q`` (B, S, H, Dh) over ``k``, ``v`` (B, S,
    H_kv, Dh), query ``t`` attending keys ``0..t``; returns (B, S, H, Dh)
    in ``q``'s dtype.  ``Dh`` must be a multiple of ``LANE``."""
    b, s, h, dh = q.shape
    hkv = k.shape[2]
    g = h // hkv
    if dh % LANE or h % hkv:
        raise ValueError(f"flash_prefill needs Dh % {LANE} == 0 and whole "
                         f"groups, got Dh {dh}, H {h}, H_kv {hkv}")
    bq, bk = flash_blocks(g, dh, s)
    nq, nk = pl.cdiv(s, bq), pl.cdiv(s, bk)

    def kv_block(bi, hi, i, j):
        # past the diagonal, repeat the last needed block: nothing fetched
        return bi, jnp.minimum(j, ((i + 1) * bq - 1) // bk), hi

    if g > 1:
        qv = q.reshape(b, s, g, hkv * dh)
        q_spec = pl.BlockSpec((None, bq, g, dh), lambda bi, hi, i, j: (bi, i, 0, hi))
    else:
        qv = q.reshape(b, s, hkv * dh)
        q_spec = pl.BlockSpec((None, bq, dh), lambda bi, hi, i, j: (bi, i, hi))
    kv_spec = pl.BlockSpec((None, bk, dh), kv_block)
    rows = bq * g
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"))
    out = pl.pallas_call(
        functools.partial(_kernel, group=g, bq=bq, bk=bk, seq=s,
                          scale=1.0 / math.sqrt(dh)),
        grid=(b, hkv, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(qv.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((rows, LANE), jnp.float32),
                        pltpu.VMEM((rows, LANE), jnp.float32),
                        pltpu.VMEM((rows, dh), jnp.float32)],
        interpret=interpret,
        name="flash_prefill",
        **kwargs,
    )(qv, k.reshape(b, s, hkv * dh), v.reshape(b, s, hkv * dh))
    return out.reshape(b, s, h, dh)
