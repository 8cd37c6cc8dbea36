"""Logical-axis sharding rules (no devices needed — pure spec logic)."""

import pytest
from jax.sharding import PartitionSpec as P

from repro.sharding import ShardingRules


def rules(sp=False, multi=False):
    axes = {"pod": 2, "data": 16, "model": 16} if multi else {"data": 16, "model": 16}
    return ShardingRules(
        axis_sizes=axes,
        batch_axes=("pod", "data") if multi else ("data",),
        model_axis="model",
        seq_axis="model" if sp else None,
    )


def test_batch_and_model_resolution():
    r = rules()
    spec = r.partition_spec((256, 4096, 512), ("batch", None, "model"))
    assert spec == P("data", None, "model")


def test_indivisible_dim_replicates():
    r = rules()
    spec = r.partition_spec((10, 4096, 512), ("batch", None, "model"))
    assert spec == P(None, None, "model")
    spec = r.partition_spec((256, 4096, 10), ("batch", None, "model"))
    assert spec == P("data", None, None)


def test_seq_axis_off_means_replicated():
    r = rules(sp=False)
    spec = r.partition_spec((32, 4096, 512), ("batch", "seq", None))
    assert spec == P("data", None, None)


def test_sp_uses_model_once():
    """With SP on, seq takes the model axis; heads cannot reuse it."""
    r = rules(sp=True)
    spec = r.partition_spec((32, 4096, 32, 128), ("batch", "seq", "model", None))
    assert spec == P("data", "model", None, None)


def test_multipod_batch_axes():
    r = rules(multi=True)
    spec = r.partition_spec((256, 4096), ("batch", None))
    assert spec == P(("pod", "data"), None)


def test_tokens_axis_merges_dp_and_sp():
    r = rules(sp=True)
    spec = r.partition_spec((256 * 4096, 16), ("tokens", None))
    assert spec == P(("data", "model"), None)
    r2 = rules(sp=False)
    assert r2.partition_spec((1024, 16), ("tokens", None)) == P(("data",), None)


def test_no_rules_installed_noop():
    import jax.numpy as jnp
    from repro.sharding import shard
    x = jnp.ones((4, 4))
    assert shard(x, "batch", None) is x


def _kv_pspecs(arch, tp, sp):
    """``_cache_pspec`` of each leaf of ``arch``'s decode caches, keyed by
    the path string ``cache_shardings`` gives it."""
    import jax

    from repro.configs import get_config
    from repro.launch.mesh import _cache_pspec
    from repro.models import api

    cfg = get_config(arch, smoke=True)
    r = ShardingRules(axis_sizes={"data": 4, "model": tp},
                      batch_axes=("data",), model_axis="model",
                      seq_axis="model" if sp else None)
    init = api(cfg).init_caches
    caches = jax.eval_shape(lambda: init(8, 64))
    return {"/".join(str(p) for p in path):
            (leaf.shape, _cache_pspec("/".join(str(p) for p in path),
                                      tuple(leaf.shape), cfg, r))
            for path, leaf in jax.tree_util.tree_flatten_with_path(caches)[0]}


@pytest.mark.parametrize("tp,sp,expect", [
    (2, False, P(None, ("data",), None, "model")),   # 2 kv heads: whole heads
    (4, True, P(None, ("data",), "model", None)),    # heads don't divide: SP
    (4, False, P(None, ("data",), None, None)),      # else replicated
])
def test_lane_dense_kv_cache_pspec(tp, sp, expect):
    """The lane-dense (L, B, S, H_kv * Dh) K/V leaves: the model axis on
    the flattened last dim when H_kv divides it (each shard whole heads),
    else on the sequence when SP is on, else none; batch on data."""
    specs = _kv_pspecs("tt-lm-100m", tp, sp)
    assert sorted(specs) == [".k", ".v"]
    for shape, spec in specs.values():
        assert len(shape) == 4
        assert spec == expect


def test_encdec_cross_kv_keeps_head_axis_pspec():
    """encdec's 5-d cross-attention K/V keep their (L, B, S, H, D) branch;
    its self-attention K/V are lane-dense like every attention cache."""
    specs = _kv_pspecs("seamless-m4t-medium", 2, False)
    for key, (shape, spec) in specs.items():
        if key.endswith(("cross_k", "cross_v")):
            assert len(shape) == 5
            assert spec == P(None, ("data",), None, "model", None)
        else:
            assert len(shape) == 4
            assert spec == P(None, ("data",), None, "model")
