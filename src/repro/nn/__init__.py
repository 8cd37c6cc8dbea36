"""NN substrate: every projection is dense-or-TT via ``linear.py``."""

from .linear import (
    LinearSpec,
    TTConfig,
    capture_activation_rms,
    install_plan,
    installed_factorizations,
    linear_apply,
    linear_flops,
    linear_init,
    plan_context,
    planned_layer,
    planned_path_index,
)
from .attention import (
    AttentionSpec,
    KVCache,
    attention_apply,
    attention_init,
    flash_prefill_applies,
    init_kv_cache,
)
from .embedding import EmbeddingSpec, embedding_apply, embedding_init, head_apply
from .mlp import MLPSpec, mlp_apply, mlp_init
from .moe import MoESpec, moe_apply, moe_init
from .norms import layernorm, layernorm_init, rmsnorm, rmsnorm_init
from .rope import apply_rope, rope_for, rope_frequencies
from .rwkv import (
    RWKVSpec,
    RWKVState,
    init_rwkv_state,
    rwkv_channel_mix,
    rwkv_init,
    rwkv_time_mix,
)
from .ssm import SSMSpec, SSMState, init_ssm_state, ssm_apply, ssm_init

__all__ = [
    "LinearSpec", "TTConfig", "capture_activation_rms", "install_plan",
    "installed_factorizations", "linear_apply", "linear_flops",
    "linear_init", "plan_context", "planned_layer", "planned_path_index",
    "AttentionSpec", "KVCache", "attention_apply", "attention_init",
    "flash_prefill_applies", "init_kv_cache",
    "EmbeddingSpec", "embedding_apply", "embedding_init", "head_apply",
    "MLPSpec", "mlp_apply", "mlp_init",
    "MoESpec", "moe_apply", "moe_init",
    "layernorm", "layernorm_init", "rmsnorm", "rmsnorm_init",
    "apply_rope", "rope_for", "rope_frequencies",
    "RWKVSpec", "RWKVState", "init_rwkv_state", "rwkv_channel_mix",
    "rwkv_init", "rwkv_time_mix",
    "SSMSpec", "SSMState", "init_ssm_state", "ssm_apply", "ssm_init",
]
