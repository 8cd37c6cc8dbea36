"""GQA attention with memory-lean chunked softmax and KV-cache decode.

Training/prefill uses a q-chunked attention (lax.scan over query blocks)
so the materialised score tensor is (B, H, q_block, S) rather than
(B, H, S, S) — at 32k context the full score tensor would dominate the
per-device memory budget.  Decode attends one new token against the cache.

All projections go through ``repro.nn.linear`` and are therefore
tensorizable by the DSE.
"""

from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.sharding import get_rules, shard
from .linear import LinearSpec, TTConfig, linear_apply, linear_init
from .rope import apply_rope, rope_for

_NEG_INF = -1e30


def _shard_heads(x: jax.Array, n_heads: int) -> jax.Array:
    """Prefer HEAD-sharded attention internals over sequence sharding.

    With SP on, constraining q/k/v to the seq axis makes every attention
    einsum a cross-device contraction (measured: ~15 GB/layer/device of
    all-to-all at 4k train, tripled by remat).  When the head count
    divides the model axis, resharding seq->heads at the attention
    boundary costs two ~shard-sized all-to-alls per tensor and makes all
    attention math device-local — the Megatron-SP layout, ~100x less
    traffic.  Falls back to seq sharding when heads don't divide.
    """
    rules = get_rules()
    if rules is None:
        return x
    tp = rules.axis_sizes.get(rules.model_axis or "", 1)
    if tp > 1 and n_heads % tp == 0:
        return shard(x, "batch", None, "model", None)
    return shard(x, "batch", "seq", "model", None)


@dataclasses.dataclass(frozen=True)
class AttentionSpec:
    name: str
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    rope: str = "full"           # full | glm2d | none
    qkv_bias: bool = False
    causal: bool = True
    q_chunk: int = 512
    tt: Optional[TTConfig] = None

    @property
    def q_spec(self) -> LinearSpec:
        return LinearSpec(f"{self.name}.wq", self.d_model,
                          self.n_heads * self.head_dim, self.qkv_bias, "attn", self.tt)

    @property
    def k_spec(self) -> LinearSpec:
        return LinearSpec(f"{self.name}.wk", self.d_model,
                          self.n_kv_heads * self.head_dim, self.qkv_bias, "attn", self.tt)

    @property
    def v_spec(self) -> LinearSpec:
        return LinearSpec(f"{self.name}.wv", self.d_model,
                          self.n_kv_heads * self.head_dim, self.qkv_bias, "attn", self.tt)

    @property
    def o_spec(self) -> LinearSpec:
        return LinearSpec(f"{self.name}.wo", self.n_heads * self.head_dim,
                          self.d_model, False, "attn", self.tt)


class KVCache(NamedTuple):
    """Lane-dense K/V: (B, S_max, H_kv * Dh), or stacked over the layers
    as (L, B, S_max, H_kv * Dh).  A position's row is one contiguous
    H_kv * Dh vector, so a decode step writes each lane's row in place."""

    k: jax.Array
    v: jax.Array


def attention_init(rng: jax.Array, spec: AttentionSpec, dtype=jnp.float32) -> dict:
    ks = jax.random.split(rng, 4)
    return {
        "wq": linear_init(ks[0], spec.q_spec, dtype),
        "wk": linear_init(ks[1], spec.k_spec, dtype),
        "wv": linear_init(ks[2], spec.v_spec, dtype),
        "wo": linear_init(ks[3], spec.o_spec, dtype),
    }


def _repeat_kv(x: jax.Array, n_rep: int) -> jax.Array:
    """INTERLEAVED kv repeat: repeated head j serves kv head j % hkv —
    the same convention as the grouped (g-major) einsum form, so flat
    and grouped attention paths are interchangeable."""
    if n_rep == 1:
        return x
    b, s, h, d = x.shape
    return jnp.broadcast_to(x[:, :, None, :, :], (b, s, n_rep, h, d)).reshape(
        b, s, n_rep * h, d
    )


def _chunked_attention(
    q: jax.Array,            # (B, Sq, H, Dh)
    k: jax.Array,            # (B, Sk, Hkv, Dh) — kv heads NOT repeated
    v: jax.Array,
    causal: bool,
    q_chunk: int,
    q_offset: int = 0,
) -> jax.Array:
    """Grouped-GQA attention: q heads are grouped per kv head and contract
    against the raw (un-repeated) K/V — the repeated-KV tensor (and its
    fp32 cast) never materialises.  Scores accumulate in fp32 via
    preferred_element_type; operands stay in model dtype.

    Grouping is INTERLEAVED (q head j serves kv head j % hkv): the head
    dim splits as (g major, hkv minor), so when the head dim is TP-sharded
    the 16-divisible group dim inherits the sharding and all attention
    math stays device-local.  (A (hkv, g)-major split would strand the
    sharding on the tiny kv dim — measured 40x collective regression.)
    """
    b, sq, h, dh = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    scale = 1.0 / math.sqrt(dh)
    # chunks of at most q_chunk rows, as even as they can be; a length it
    # does not divide pads the last chunk's queries (rows cut off below)
    n_chunks = -(-sq // min(q_chunk, sq))
    chunk = -(-sq // n_chunks)
    if n_chunks * chunk > sq:
        q = jnp.pad(q, ((0, 0), (0, n_chunks * chunk - sq), (0, 0), (0, 0)))
    kv_pos = jnp.arange(sk)

    # shardability decides the form: the grouped einsum's score tensor
    # can only head-shard when g divides TP (measured: with g=8 on a
    # 16-way axis the (b,g,hkv,q,k) scores replicate — 64 GiB/device
    # all-gathers).  Otherwise fall back to repeated-KV flat heads (h
    # itself usually divides TP), keeping the fp32-free accumulation.
    rules = get_rules()
    tp = rules.axis_sizes.get(rules.model_axis or "", 1) if rules else 1
    grouped = g > 1 and (tp <= 1 or g % tp == 0)
    if not grouped and g > 1:
        k = _repeat_kv(k, g)
        v = _repeat_kv(v, g)

    if grouped:
        qc = q.reshape(b, n_chunks, chunk, g, hkv, dh).transpose(
            1, 0, 2, 3, 4, 5)                 # (nc, B, chunk, g, Hkv, Dh)
    else:
        qc = q.reshape(b, n_chunks, chunk, h, dh).transpose(1, 0, 2, 3, 4)

    def body(carry, inp):
        qi, idx = inp
        if grouped:                        # (B, chunk, g, Hkv, Dh)
            scores = jnp.einsum("bqghd,bkhd->bghqk", qi, k,
                                preferred_element_type=jnp.float32) * scale
        else:                              # (B, chunk, H, Dh)
            scores = jnp.einsum("bqhd,bkhd->bhqk", qi, k,
                                preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = q_offset + idx * chunk + jnp.arange(chunk)
            mask = kv_pos[None, :] <= q_pos[:, None]
            mask = mask[None, None, None] if grouped else mask[None, None]
            scores = jnp.where(mask, scores, _NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        if grouped:
            out = jnp.einsum("bghqk,bkhd->bqghd", probs.astype(v.dtype), v)
        else:
            out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
        return carry, out

    _, outs = jax.lax.scan(body, None, (qc, jnp.arange(n_chunks)))
    if grouped:
        outs = outs.transpose(1, 0, 2, 3, 4, 5)
    else:
        outs = outs.transpose(1, 0, 2, 3, 4)
    return outs.reshape(b, n_chunks * chunk, h, dh)[:, :sq]


def _write_rows(stack: jax.Array, rows: jax.Array, layer, pos: jax.Array) -> jax.Array:
    """``rows`` (B, s, H_kv, Dh) written into layer ``layer`` of ``stack``
    (L, B, S_max, H_kv * Dh), nothing else touched.

    A (B,) ``pos`` (decode, s == 1) is one scatter of B rows at
    ``(layer, lane, pos[lane])``; a position past the cache is dropped.
    A scalar ``pos`` is one dynamic_update_slice of the s rows there.
    """
    b, s = rows.shape[:2]
    rows = rows.reshape(b, s, stack.shape[-1]).astype(stack.dtype)
    if pos.ndim == 1:
        return stack.at[layer, jnp.arange(b), pos].set(
            rows[:, 0], mode="drop", indices_are_sorted=True,
            unique_indices=True)
    zero = jnp.zeros((), pos.dtype)
    return jax.lax.dynamic_update_slice(stack, rows[None],
                                        (layer, zero, pos, zero))


def _decode_attention(spec: AttentionSpec, q: jax.Array, ks: jax.Array,
                      vs: jax.Array, layer, pos: jax.Array) -> jax.Array:
    """One new token per lane, q (B, 1, H, Dh), against layer ``layer`` of
    the stacked cache over all S_max positions, masked to each lane's
    valid prefix (``pos`` () or (B,), the new token's position).

    The layer's K/V rows are contracted whole, never split into
    (H_kv, Dh): on the TPU a head narrower than 128 lanes would take a
    relayout copy of the layer's whole cache.  Each query head is placed
    on its KV head's block of a row (zeros elsewhere) for the scores, and
    each head's output is the diagonal block of probs @ V — exact, at
    H_kv times a small multiply count, and the cache is read once.
    """
    b = q.shape[0]
    hkv, dh = spec.n_kv_heads, spec.head_dim
    g = spec.n_heads // hkv
    ck, cv = (jax.lax.dynamic_index_in_dim(c, layer, axis=0, keepdims=False)
              for c in (ks, vs))                              # (B, S, Hkv*Dh)
    kv_pos = jnp.arange(ck.shape[1])
    if pos.ndim == 1:    # per-lane valid horizon
        vmask = (kv_pos[None, :] <= pos[:, None])[:, None, None, :]
    else:
        vmask = (kv_pos <= pos)[None, None, None, :]
    scale = 1.0 / math.sqrt(dh)
    own = jnp.eye(hkv, dtype=bool)[:, :, None]   # head h's block of a row
    qg = q.reshape(b, g, hkv, 1, dh)             # interleaved grouping
    qx = jnp.where(own, qg, 0).reshape(b, g, hkv, hkv * dh)
    # fp32 lives only in the score accumulator (no fp32 cache cast)
    scores = jnp.einsum("bghw,bkw->bghk", qx, ck,
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(vmask, scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    full = jnp.einsum("bghk,bkw->bghw", probs.astype(cv.dtype), cv)
    out = jnp.diagonal(full.reshape(b, g, hkv, hkv, dh), axis1=2, axis2=3)
    return jnp.swapaxes(out, -1, -2).reshape(b, 1, spec.n_heads, dh)


def attention_apply(
    spec: AttentionSpec,
    params: dict,
    x: jax.Array,                     # (B, S, D)
    positions: Optional[jax.Array] = None,
    cache: Optional[KVCache] = None,
    cache_pos: Optional[jax.Array] = None,   # () or (B,): #tokens cached
    layer: Optional[jax.Array] = None,
) -> tuple[jax.Array, Optional[KVCache]]:
    """Returns (output, updated_cache).

    Prefill/train: ``cache is None`` — full-sequence chunked attention.
    Decode: ``cache`` given, ``x`` is (B, 1, D); new KV written at
    ``cache_pos`` and attention runs over the valid prefix.  A (B,)
    ``cache_pos`` gives each lane its own write position and valid
    horizon — the continuous-batching decode form, where every slot of
    the fixed-width batch sits at a different sequence offset.  Each
    lane's output depends only on that lane's (cache, token, position),
    so slot contents never leak across requests.

    With ``layer``, ``cache`` holds every layer's stacked (L, B, S_max,
    H_kv * Dh) K/V; this call writes its rows into layer ``layer`` and
    attends over that layer, and the whole stack is returned.  Carried
    through the layer loop, the stack is updated in place: only the new
    rows are written.
    """
    b, s, _ = x.shape
    if positions is None:
        base = cache_pos if cache_pos is not None else 0
        base = jnp.asarray(base)
        if base.ndim == 1:
            positions = base[:, None] + jnp.arange(s)[None, :]
        else:
            positions = base + jnp.arange(s)[None, :]
        positions = jnp.broadcast_to(positions, (b, s))

    q = linear_apply(spec.q_spec, params["wq"], x).reshape(b, s, spec.n_heads, spec.head_dim)
    k = linear_apply(spec.k_spec, params["wk"], x).reshape(b, s, spec.n_kv_heads, spec.head_dim)
    v = linear_apply(spec.v_spec, params["wv"], x).reshape(b, s, spec.n_kv_heads, spec.head_dim)

    with jax.named_scope("attention"):
        rp = rope_for(spec.rope)
        if rp is not None:
            frac, base_f = rp
            q = apply_rope(q, positions, base=base_f, rotary_fraction=frac)
            k = apply_rope(k, positions, base=base_f, rotary_fraction=frac)

        q = _shard_heads(q, spec.n_heads)
        k = _shard_heads(k, spec.n_kv_heads)
        v = _shard_heads(v, spec.n_kv_heads)

        if cache is None:
            out = _chunked_attention(q, k, v, spec.causal, spec.q_chunk)
            new_cache = None
        else:
            idx = jnp.asarray(cache_pos if cache_pos is not None else 0)
            if s > 1 and idx.ndim == 1:
                raise ValueError(
                    "per-lane (B,) cache_pos is decode-only; prefill writes "
                    "one contiguous prompt per call (the serve scheduler "
                    "prefills each request at batch 1)")
            stacked = layer is not None
            ks, vs = cache if stacked else (cache.k[None], cache.v[None])
            layer = layer if stacked else 0
            ks = _write_rows(ks, k, layer, idx)
            vs = _write_rows(vs, v, layer, idx)
            new_cache = KVCache(ks, vs) if stacked else KVCache(ks[0], vs[0])
            if s > 1:
                # prefill-with-cache: the prompt's K/V is written at
                # cache_pos; attend over the local (just-computed) K/V —
                # identical numerics, no per-token cache round-trips
                out = _chunked_attention(q, k, v, spec.causal, spec.q_chunk)
            else:
                out = _decode_attention(spec, q, ks, vs, layer, idx)

    out = out.reshape(b, s, spec.n_heads * spec.head_dim)
    y = linear_apply(spec.o_spec, params["wo"], out)
    return shard(y, "batch", "seq", None), new_cache


def init_kv_cache(spec: AttentionSpec, batch: int, max_seq: int, dtype=jnp.bfloat16) -> KVCache:
    shape = (batch, max_seq, spec.n_kv_heads * spec.head_dim)
    return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))
