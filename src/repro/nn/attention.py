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
    k: jax.Array     # (B, S_max, H_kv, Dh)
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
    chunk = min(q_chunk, sq)
    if sq % chunk:
        chunk = sq  # fall back to a single chunk for ragged sizes
    n_chunks = sq // chunk
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
        return outs.transpose(1, 0, 2, 3, 4, 5).reshape(b, sq, h, dh)
    return outs.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, dh)


def attention_apply(
    spec: AttentionSpec,
    params: dict,
    x: jax.Array,                     # (B, S, D)
    positions: Optional[jax.Array] = None,
    cache: Optional[KVCache] = None,
    cache_pos: Optional[jax.Array] = None,   # () or (B,): #tokens cached
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
        elif s > 1:
            # prefill-with-cache: write the whole prompt's K/V at cache_pos and
            # attend over the local (just-computed) K/V — identical numerics,
            # no per-token cache round-trips
            idx = cache_pos if cache_pos is not None else 0
            if jnp.asarray(idx).ndim == 1:
                raise ValueError(
                    "per-lane (B,) cache_pos is decode-only; prefill writes "
                    "one contiguous prompt per call (the serve scheduler "
                    "prefills each request at batch 1)")
            ck = jax.lax.dynamic_update_slice_in_dim(
                cache.k, k.astype(cache.k.dtype), idx, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(
                cache.v, v.astype(cache.v.dtype), idx, axis=1)
            new_cache = KVCache(ck, cv)
            out = _chunked_attention(q, k, v, spec.causal, spec.q_chunk)
        else:
            idx = cache_pos if cache_pos is not None else 0
            idx = jnp.asarray(idx)
            kv_pos = jnp.arange(cache.k.shape[1])
            if idx.ndim == 1:
                # per-lane positions: one-hot write (bit-exact equivalent of
                # a per-lane dynamic_update_slice) + per-lane valid horizon
                sel = kv_pos[None, :] == idx[:, None]                # (B, S)
                ck = jnp.where(sel[:, :, None, None], k.astype(cache.k.dtype),
                               cache.k)
                cv = jnp.where(sel[:, :, None, None], v.astype(cache.v.dtype),
                               cache.v)
                vmask = (kv_pos[None, :] <= idx[:, None])[:, None, None, None, :]
            else:
                ck = jax.lax.dynamic_update_slice_in_dim(
                    cache.k, k.astype(cache.k.dtype), idx, axis=1)
                cv = jax.lax.dynamic_update_slice_in_dim(
                    cache.v, v.astype(cache.v.dtype), idx, axis=1)
                vmask = (kv_pos <= idx)[None, None, None, None, :]
            new_cache = KVCache(ck, cv)
            hkv = spec.n_kv_heads
            g = spec.n_heads // hkv
            scale = 1.0 / math.sqrt(spec.head_dim)
            qg = q.reshape(b, 1, g, hkv, spec.head_dim)   # interleaved grouping
            # grouped decode: raw cache contracted directly (no repeat, no
            # fp32 cache cast — fp32 lives only in the score accumulator)
            scores = jnp.einsum("bqghd,bkhd->bghqk", qg, ck,
                                preferred_element_type=jnp.float32) * scale
            scores = jnp.where(vmask, scores, _NEG_INF)
            probs = jax.nn.softmax(scores, axis=-1)
            out = jnp.einsum("bghqk,bkhd->bqghd", probs.astype(cv.dtype), cv)
            out = out.reshape(b, 1, spec.n_heads, spec.head_dim)

    out = out.reshape(b, s, spec.n_heads * spec.head_dim)
    y = linear_apply(spec.o_spec, params["wo"], out)
    return shard(y, "batch", "seq", None), new_cache


def init_kv_cache(spec: AttentionSpec, batch: int, max_seq: int, dtype=jnp.bfloat16) -> KVCache:
    shape = (batch, max_seq, spec.n_kv_heads, spec.head_dim)
    return KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))
