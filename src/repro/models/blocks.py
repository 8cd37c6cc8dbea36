"""Per-family transformer blocks with a uniform (x, cache) -> (x, cache, aux)
interface so the layer-scan machinery in ``lm.py`` is family-agnostic.

Block params are plain dicts; stacking a block L times (vmapped init) gives
the scanned parameter tree.  ``cache`` is family-specific: KVCache for
attention blocks, SSMState for Mamba2, RWKVState for RWKV6; ``None`` in
training (no cache threading).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.nn import (
    AttentionSpec,
    MLPSpec,
    MoESpec,
    RWKVSpec,
    SSMSpec,
    attention_apply,
    attention_init,
    mlp_apply,
    mlp_init,
    moe_apply,
    moe_init,
    rmsnorm,
    rmsnorm_init,
    rwkv_channel_mix,
    rwkv_init,
    rwkv_time_mix,
    ssm_apply,
    ssm_init,
)
from repro.nn.rwkv import RWKVState
from .config import ModelConfig


# ---------------------------------------------------------------------------
# spec builders
# ---------------------------------------------------------------------------

def attn_spec(cfg: ModelConfig, name: str = "attn", causal: bool = True) -> AttentionSpec:
    return AttentionSpec(
        name=name,
        d_model=cfg.d_model,
        n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.hd,
        rope=cfg.rope,
        qkv_bias=cfg.qkv_bias,
        causal=causal,
        q_chunk=cfg.q_chunk,
        tt=cfg.tt,
    )


def mlp_spec(cfg: ModelConfig, name: str = "mlp") -> MLPSpec:
    return MLPSpec(name, cfg.d_model, cfg.d_ff, cfg.mlp_kind, cfg.tt)


def moe_spec(cfg: ModelConfig, name: str = "moe") -> MoESpec:
    return MoESpec(
        name=name,
        d_model=cfg.d_model,
        d_ff=cfg.d_ff,
        n_experts=cfg.moe_experts,
        top_k=cfg.moe_top_k,
        n_shared=cfg.moe_shared,
        shared_d_ff=cfg.moe_shared_d_ff,
        capacity_factor=cfg.capacity_factor,
        kind=cfg.mlp_kind,
        tt=cfg.tt,
    )


def ssm_spec(cfg: ModelConfig, name: str = "ssm") -> SSMSpec:
    return SSMSpec(
        name=name,
        d_model=cfg.d_model,
        d_state=cfg.ssm_state,
        head_dim=cfg.ssm_head_dim,
        tt=cfg.tt,
    )


def rwkv_spec(cfg: ModelConfig, name: str = "rwkv") -> RWKVSpec:
    return RWKVSpec(
        name=name,
        d_model=cfg.d_model,
        head_dim=cfg.hd,
        d_ff=cfg.d_ff,
        tt=cfg.tt,
    )


# ---------------------------------------------------------------------------
# blocks — init
# ---------------------------------------------------------------------------

def block_init(rng: jax.Array, cfg: ModelConfig, dtype) -> dict:
    """One decoder block of cfg.family (hybrid = one Mamba layer)."""
    k1, k2 = jax.random.split(rng)
    d = cfg.d_model
    if cfg.family in ("dense", "vlm", "encdec"):
        return {
            "ln1": rmsnorm_init(d, dtype),
            "attn": attention_init(k1, attn_spec(cfg), dtype),
            "ln2": rmsnorm_init(d, dtype),
            "mlp": mlp_init(k2, mlp_spec(cfg), dtype),
        }
    if cfg.family == "moe":
        return {
            "ln1": rmsnorm_init(d, dtype),
            "attn": attention_init(k1, attn_spec(cfg), dtype),
            "ln2": rmsnorm_init(d, dtype),
            "moe": moe_init(k2, moe_spec(cfg), dtype),
        }
    if cfg.family == "hybrid":
        return {
            "ln": rmsnorm_init(d, dtype),
            "ssm": ssm_init(k1, ssm_spec(cfg), dtype),
        }
    if cfg.family == "rwkv":
        return {
            "ln1": rmsnorm_init(d, dtype),
            "tm": rwkv_init(k1, rwkv_spec(cfg), dtype),
            "ln2": rmsnorm_init(d, dtype),
        }
    raise ValueError(cfg.family)


def shared_attn_init(rng: jax.Array, cfg: ModelConfig, dtype) -> dict:
    """Zamba2's shared attention block (one parameter set, applied G times)."""
    return {
        "ln": rmsnorm_init(cfg.d_model, dtype),
        "attn": attention_init(rng, attn_spec(cfg, name="shared_attn"), dtype),
    }


# ---------------------------------------------------------------------------
# blocks — apply
# ---------------------------------------------------------------------------

def block_apply(
    cfg: ModelConfig,
    params: dict,
    x: jax.Array,
    positions: Optional[jax.Array],
    cache,
    cache_pos,
    layer=None,
):
    """Returns (x, new_cache, aux_loss).

    ``layer``: attention families take the stacked KV cache of every
    layer and write this block's rows into layer ``layer`` of it
    (``attention_apply``)."""
    zero = jnp.zeros((), jnp.float32)
    if cfg.family in ("dense", "vlm", "encdec", "moe"):
        h, new_cache = attention_apply(
            attn_spec(cfg), params["attn"], rmsnorm(params["ln1"], x),
            positions, cache, cache_pos, layer,
        )
        x = x + h
        if cfg.family == "moe":
            h2, aux = moe_apply(moe_spec(cfg), params["moe"], rmsnorm(params["ln2"], x))
            return x + h2, new_cache, aux
        h2 = mlp_apply(mlp_spec(cfg), params["mlp"], rmsnorm(params["ln2"], x))
        return x + h2, new_cache, zero
    if cfg.family == "hybrid":
        h, new_state = ssm_apply(ssm_spec(cfg), params["ssm"], rmsnorm(params["ln"], x), cache)
        return x + h, new_state, zero
    if cfg.family == "rwkv":
        h, shift_tm, wkv = rwkv_time_mix(
            rwkv_spec(cfg), params["tm"], rmsnorm(params["ln1"], x), cache
        )
        x = x + h
        h2, shift_cm = rwkv_channel_mix(
            rwkv_spec(cfg), params["tm"], rmsnorm(params["ln2"], x), cache
        )
        x = x + h2
        new_cache = None
        if cache is not None:
            new_cache = RWKVState(shift_tm=shift_tm, shift_cm=shift_cm, wkv=wkv)
        return x, new_cache, zero
    raise ValueError(cfg.family)


def shared_attn_apply(
    cfg: ModelConfig,
    params: dict,
    x: jax.Array,
    positions: Optional[jax.Array],
    cache,
    cache_pos,
    layer=None,
):
    h, new_cache = attention_apply(
        attn_spec(cfg, name="shared_attn"), params["attn"],
        rmsnorm(params["ln"], x), positions, cache, cache_pos, layer,
    )
    return x + h, new_cache
