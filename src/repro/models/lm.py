"""Unified decoder-only LM: dense / MoE / hybrid (Zamba2) / RWKV6 / VLM.

Layer parameters are stacked on a leading L axis and executed with
``lax.scan`` (HLO size independent of depth); ``scan_layers=False``
unrolls — used by the dry-run's L=1/L=2 cost-extrapolation variants.
``remat`` wraps the scan body with ``jax.checkpoint``.

The hybrid family scans *groups* of ``attn_every`` Mamba layers with the
shared attention block applied once per group inside the scan body —
the parameter set is closed over (not scanned), giving Zamba2's
parameter-sharing semantics for free.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.nn import (
    EmbeddingSpec,
    LinearSpec,
    embedding_apply,
    embedding_init,
    head_apply,
    init_kv_cache,
    init_rwkv_state,
    init_ssm_state,
    linear_apply,
    linear_init,
    rmsnorm,
    rmsnorm_init,
)
from repro.sharding import shard
from .blocks import (
    attn_spec,
    block_apply,
    block_init,
    rwkv_spec,
    shared_attn_apply,
    shared_attn_init,
    ssm_spec,
)
from .config import ModelConfig


def embed_spec(cfg: ModelConfig) -> EmbeddingSpec:
    return EmbeddingSpec("embed", cfg.vocab, cfg.d_model, cfg.tt)


def head_spec(cfg: ModelConfig) -> LinearSpec:
    return LinearSpec("head", cfg.d_model, cfg.vocab, False, "head", cfg.tt)


def _remat(cfg: ModelConfig, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        )
    return jax.checkpoint(fn)


def _hybrid_groups(cfg: ModelConfig) -> tuple[int, int]:
    """(n_groups, remainder_layers) for the hybrid family."""
    g = cfg.attn_every if cfg.attn_every else cfg.n_layers
    return cfg.n_layers // g, cfg.n_layers % g


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def init_params(rng: jax.Array, cfg: ModelConfig) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    k_e, k_b, k_h, k_s = jax.random.split(rng, 4)
    params: dict[str, Any] = {
        "embed": embedding_init(k_e, embed_spec(cfg), dtype),
        "ln_f": rmsnorm_init(cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["head"] = linear_init(k_h, head_spec(cfg), dtype)
    keys = jax.random.split(k_b, cfg.n_layers)
    params["blocks"] = jax.vmap(lambda k: block_init(k, cfg, dtype))(keys)
    if cfg.family == "hybrid":
        params["shared_attn"] = shared_attn_init(k_s, cfg, dtype)
    return params


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

def init_caches(cfg: ModelConfig, batch: int, max_seq: int, dtype=jnp.bfloat16):
    """Stacked per-layer decode caches (family-specific)."""
    def stack(make, n):
        one = make()
        return jax.tree.map(lambda a: jnp.broadcast_to(a, (n,) + a.shape), one)

    if cfg.family in ("dense", "moe", "vlm"):
        return stack(lambda: init_kv_cache(attn_spec(cfg), batch, max_seq, dtype),
                     cfg.n_layers)
    if cfg.family == "hybrid":
        n_groups, rem = _hybrid_groups(cfg)
        return {
            "ssm": stack(lambda: init_ssm_state(ssm_spec(cfg), batch, dtype),
                         cfg.n_layers),
            "attn": stack(lambda: init_kv_cache(attn_spec(cfg), batch, max_seq, dtype),
                          n_groups),
        }
    if cfg.family == "rwkv":
        return stack(lambda: init_rwkv_state(rwkv_spec(cfg), batch, dtype),
                     cfg.n_layers)
    raise ValueError(cfg.family)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _run_blocks(cfg, params, x, positions, caches, cache_pos):
    """Scan/unroll the stacked blocks.  Returns (x, new_caches, aux).

    Recurrent state goes through the loop per layer (``xs`` in, ``ys``
    out); a KV cache is carried whole (``_run_kv_blocks``)."""
    has_cache = caches is not None

    def body(carry, inp):
        x, aux = carry
        p_l, cache_l = inp
        x, new_cache, a = block_apply(cfg, p_l, x, positions, cache_l, cache_pos)
        return (x, aux + a), new_cache

    body = _remat(cfg, body)

    if cfg.family == "hybrid":
        return _run_hybrid(cfg, params, x, positions, caches, cache_pos, body)
    if has_cache and cfg.family != "rwkv":
        return _run_kv_blocks(cfg, params, x, positions, caches, cache_pos)

    blocks = params["blocks"]
    if cfg.scan_layers:
        (x, aux), new_caches = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)),
            (blocks, caches) if has_cache else (blocks, None),
        )
    else:
        aux = jnp.zeros((), jnp.float32)
        new_list = []
        for l in range(cfg.n_layers):
            p_l = jax.tree.map(lambda a: a[l], blocks)
            c_l = jax.tree.map(lambda a: a[l], caches) if has_cache else None
            (x, aux), nc = body((x, aux), (p_l, c_l))
            new_list.append(nc)
        new_caches = (
            jax.tree.map(lambda *xs: jnp.stack(xs), *new_list) if has_cache else None
        )
    return x, (new_caches if has_cache else None), aux


def _run_kv_blocks(cfg, params, x, positions, caches, cache_pos):
    """The attention families' layer loop with a KV cache: the stacked
    (L, B, S_max, H_kv * Dh) cache rides in the carry and layer ``l``
    writes its rows into it in place — a scan's ``ys`` would stack every
    layer's whole cache into a new buffer, copied back afterwards."""

    def body(carry, inp):
        x, aux, kv = carry
        p_l, l = inp
        x, kv, a = block_apply(cfg, p_l, x, positions, kv, cache_pos, l)
        return (x, aux + a, kv), None

    body = _remat(cfg, body)
    blocks = params["blocks"]
    carry = (x, jnp.zeros((), jnp.float32), caches)
    if cfg.scan_layers:
        carry, _ = jax.lax.scan(body, carry, (blocks, jnp.arange(cfg.n_layers)))
    else:
        for l in range(cfg.n_layers):
            carry, _ = body(carry, (jax.tree.map(lambda a: a[l], blocks), l))
    x, aux, caches = carry
    return x, caches, aux


def _run_hybrid(cfg, params, x, positions, caches, cache_pos, body):
    """Groups of ``attn_every`` Mamba layers + shared attention per group."""
    n_groups, rem = _hybrid_groups(cfg)
    g = cfg.attn_every if cfg.attn_every else cfg.n_layers
    blocks = params["blocks"]
    has_cache = caches is not None
    main = jax.tree.map(lambda a: a[: n_groups * g].reshape((n_groups, g) + a.shape[1:]),
                        blocks)
    tail = jax.tree.map(lambda a: a[n_groups * g :], blocks)
    ssm_caches = caches["ssm"] if has_cache else None
    attn_caches = caches["attn"] if has_cache else None
    main_c = (
        jax.tree.map(lambda a: a[: n_groups * g].reshape((n_groups, g) + a.shape[1:]),
                     ssm_caches) if has_cache else None
    )
    tail_c = jax.tree.map(lambda a: a[n_groups * g :], ssm_caches) if has_cache else None

    # the shared attention's stacked KV cache (one layer per group) rides
    # in the carry and group gi writes its rows in place, as in
    # _run_kv_blocks; the Mamba state goes through per layer
    def group_body(carry, inp):
        x, aux, attn = carry
        gp, gc_ssm, gi = inp
        (x, aux), new_ssm = jax.lax.scan(
            body, (x, aux), (gp, gc_ssm) if has_cache else (gp, None)
        )
        x, attn = shared_attn_apply(
            cfg, params["shared_attn"], x, positions, attn, cache_pos, gi
        )
        return (x, aux, attn), new_ssm

    group_body = _remat(cfg, group_body)

    carry = (x, jnp.zeros((), jnp.float32), attn_caches)
    if cfg.scan_layers:
        carry, new_main_ssm = jax.lax.scan(
            group_body, carry,
            (main, main_c, jnp.arange(n_groups)) if has_cache
            else (main, None, None),
        )
    else:
        ssm_list = []
        for gi in range(n_groups):
            gp = jax.tree.map(lambda a: a[gi], main)
            gc_s = jax.tree.map(lambda a: a[gi], main_c) if has_cache else None
            carry, ns = group_body(carry, (gp, gc_s, gi if has_cache else None))
            ssm_list.append(ns)
        new_main_ssm = (
            jax.tree.map(lambda *xs: jnp.stack(xs), *ssm_list) if has_cache else None
        )
    x, aux, new_attn = carry

    new_tail = None
    if rem:
        (x, aux), new_tail = jax.lax.scan(
            body, (x, aux), (tail, tail_c) if has_cache else (tail, None)
        )

    new_caches = None
    if has_cache:
        flat_ssm = jax.tree.map(
            lambda a: a.reshape((n_groups * g,) + a.shape[2:]), new_main_ssm
        )
        if rem:
            flat_ssm = jax.tree.map(
                lambda a, b: jnp.concatenate([a, b], axis=0), flat_ssm, new_tail
            )
        new_caches = {"ssm": flat_ssm, "attn": new_attn}
    return x, new_caches, aux


def forward(
    cfg: ModelConfig,
    params: dict,
    tokens: jax.Array,                       # (B, S) int32
    frontend: Optional[jax.Array] = None,    # (B, P, D) patch/frame embeddings
    caches=None,
    cache_pos=None,
    return_hidden: bool = False,
) -> tuple[jax.Array, Any, jax.Array]:
    """Returns (logits, new_caches, aux_loss).

    Train/prefill: ``caches=None``/given, full sequence.  Decode: S == 1.
    VLM: ``frontend`` prefix tokens are prepended (prefill only).
    ``return_hidden`` skips the LM head (chunked-loss path).
    """
    x = embedding_apply(embed_spec(cfg), params["embed"], tokens)
    n_prefix = 0
    if cfg.family == "vlm" and frontend is not None:
        x = jnp.concatenate([frontend.astype(x.dtype), x], axis=1)
        n_prefix = frontend.shape[1]
    x = shard(x, "batch", "seq", None)
    b, s, _ = x.shape
    base = cache_pos if cache_pos is not None else 0
    base = jnp.asarray(base)
    if base.ndim == 1:
        # per-lane decode offsets (continuous batching): each slot of the
        # fixed-width batch sits at its own sequence position
        positions = base[:, None] + jnp.arange(s)[None, :]
    else:
        positions = base + jnp.arange(s)[None, :]
    positions = jnp.broadcast_to(positions, (b, s))

    x, new_caches, aux = _run_blocks(cfg, params, x, positions, caches, cache_pos)

    x = rmsnorm(params["ln_f"], x)
    if n_prefix:
        x = x[:, n_prefix:]
    if return_hidden:
        return x, new_caches, aux
    logits = apply_head(cfg, params, x)
    return logits, new_caches, aux


def apply_head(cfg: ModelConfig, params: dict, x: jax.Array) -> jax.Array:
    with jax.named_scope("head"):
        if cfg.tie_embeddings:
            logits = head_apply(embed_spec(cfg), params["embed"], x)
        else:
            logits = linear_apply(head_spec(cfg), params["head"], x)
    if logits.ndim == 2:        # chunked-loss path: (tokens, V)
        return shard(logits, "tokens", "model")
    return shard(logits, "batch", None, "model")


# ---------------------------------------------------------------------------
# loss / decode steps
# ---------------------------------------------------------------------------

def cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean token cross-entropy; vocab dim may be model-sharded."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.sum(jax.nn.one_hot(labels, logits.shape[-1], dtype=jnp.float32) * logits,
                 axis=-1)
    return jnp.mean(lse - ll)


def chunked_cross_entropy(
    head_fn, hidden: jax.Array, labels: jax.Array, chunk: int
) -> jax.Array:
    """Fused head + CE, scanned over sequence chunks.

    Bounds the live logits buffer at (B, chunk, V) — used when the vocab
    cannot shard on the model axis (odd vocab sizes).  ``head_fn`` maps
    hidden (B, c, D) -> logits (B, c, V).
    """
    b, s, d = hidden.shape
    c = min(chunk, s)
    if s % c:
        c = s
    n = s // c

    # INTERLEAVED chunking: flatten to the tokens layout (the merged
    # (batch x seq) dim keeps its DP(+SP) sharding), then split the token
    # dim as (T/n major, n minor) — the sharded MAJOR dim survives the
    # reshape, so every chunk stays fully distributed.  (Both contiguous
    # reshapes and traced-index dynamic_slice on a sharded dim force
    # GSPMD into full-tensor rematerialisation — measured as hundreds of
    # GB of all-gather per step before this change.)  Cross-entropy is a
    # token-permutation-invariant mean, so interleaving is exact.
    tokens = b * s
    hf = shard(hidden.reshape(tokens, d), "tokens", None)
    lf = labels.reshape(tokens)
    hs = jnp.swapaxes(hf.reshape(tokens // n, n, d), 0, 1)   # (n, T/n, D)
    ls = jnp.swapaxes(lf.reshape(tokens // n, n), 0, 1)

    @jax.checkpoint  # recompute the head chain in bwd — never stack its
    def body(acc, inp):  # per-chunk intermediates across the scan
        h, lab = inp
        h = shard(h, "tokens", None)
        logits = head_fn(h).astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        ll = jnp.sum(
            jax.nn.one_hot(lab, logits.shape[-1], dtype=jnp.float32) * logits,
            axis=-1,
        )
        return acc + jnp.sum(lse - ll), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (hs, ls))
    return total / (b * s)


def train_loss(cfg: ModelConfig, params: dict, batch: dict) -> jax.Array:
    if cfg.loss_chunk:
        hidden, _, aux = forward(
            cfg, params, batch["tokens"], frontend=batch.get("frontend"),
            return_hidden=True,
        )
        with jax.named_scope("loss"):
            ce = chunked_cross_entropy(
                lambda h: apply_head(cfg, params, h), hidden, batch["labels"],
                cfg.loss_chunk,
            )
        return ce + cfg.aux_loss_weight * aux
    logits, _, aux = forward(
        cfg, params, batch["tokens"], frontend=batch.get("frontend")
    )
    with jax.named_scope("loss"):
        ce = cross_entropy(logits, batch["labels"])
    return ce + cfg.aux_loss_weight * aux


def prefill(cfg: ModelConfig, params: dict, batch: dict, max_seq: int,
            last=None):
    """Run the full prompt, returning (logits (B, V), primed caches).

    The logits are those of position ``last`` (a traced index; the last
    position if None): the serve engine prefills bucket-padded prompts
    and needs the last *real* token's.  Only that row's hidden state goes
    through the head, so a long prompt never makes (B, S, V) logits.

    Attention families write the whole prompt's K/V into the caches in one
    dynamic_update_slice (see ``attention_apply`` s>1-with-cache path);
    state families advance their recurrent state through the scan.
    """
    b, s = batch["tokens"].shape
    caches = init_caches(cfg, b, max_seq, jnp.dtype(cfg.dtype))
    hidden, caches, _ = forward(
        cfg, params, batch["tokens"], frontend=batch.get("frontend"),
        caches=caches, cache_pos=jnp.zeros((), jnp.int32), return_hidden=True,
    )
    idx = hidden.shape[1] - 1 if last is None else last
    row = jax.lax.dynamic_slice_in_dim(hidden, idx, 1, axis=1)   # (B, 1, D)
    return apply_head(cfg, params, row)[:, 0], caches


def decode_step(
    cfg: ModelConfig,
    params: dict,
    token: jax.Array,          # (B, 1) int32
    caches,
    cache_pos: jax.Array,      # () int32 — tokens already cached
):
    """One decode step: returns (logits (B, V), new_caches)."""
    logits, new_caches, _ = forward(
        cfg, params, token, caches=caches, cache_pos=cache_pos
    )
    return logits[:, -1], new_caches


def count_params(params) -> int:
    return sum(int(math.prod(a.shape)) for a in jax.tree.leaves(params))
