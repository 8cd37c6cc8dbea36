"""NN substrate correctness: linear (dense/TT), embedding, attention,
MoE, SSD, WKV — each against an independent reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import tt_svd
from repro.nn import (
    AttentionSpec,
    EmbeddingSpec,
    KVCache,
    LinearSpec,
    MoESpec,
    TTConfig,
    attention_apply,
    attention_init,
    embedding_apply,
    embedding_init,
    head_apply,
    init_kv_cache,
    install_plan,
    linear_apply,
    linear_init,
    moe_apply,
    moe_init,
)
from repro.nn.rope import apply_rope, rope_for
from repro.nn.rwkv import _wkv_chunked
from repro.nn.ssm import _ssd_chunked

TT = TTConfig(enabled=True, d=2, rank=64, min_dim=8,
              targets=("attn", "mlp", "head", "moe", "embed"))


# ---------------------------------------------------------------------------
# linear
# ---------------------------------------------------------------------------

def test_tt_linear_matches_dense_with_svd_cores(rng):
    """Load TT-SVD cores of a dense W into the layer: outputs must match
    the dense matmul (full-rank TT == exact)."""
    d_in, d_out = 16, 24
    spec = LinearSpec("l", d_in, d_out, False, "mlp", TT)
    assert spec.tensorized
    w = rng.normal(size=(d_in, d_out)).astype(np.float32)
    # layer contracts x (in_modes) against cores: W tensor (out_modes, in_modes)
    tt = tt_svd(w.T, spec.out_modes, spec.in_modes, max_rank=64)
    params = {}
    for k, c in enumerate(tt.cores):
        arr = jnp.asarray(c, jnp.float32)
        if k == 0:
            arr = arr[0]            # squeeze boundary rank
        elif k == len(tt.cores) - 1:
            arr = arr[..., 0]
        params[f"core{k}"] = arr
    x = jnp.asarray(rng.normal(size=(5, d_in)), jnp.float32)
    y = linear_apply(spec, params, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x) @ w, rtol=1e-4,
                               atol=1e-4)


def test_tt_linear_all_paths_equivalent(rng):
    spec = LinearSpec("l2", 16, 16, False, "mlp", TT)
    params = linear_init(jax.random.PRNGKey(0), spec)
    x = jnp.asarray(rng.normal(size=(4, 16)), jnp.float32)
    outs = [np.asarray(linear_apply(spec, params, x, path_index=i))
            for i in range(3)]
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], rtol=1e-4, atol=1e-4)


def test_install_plan_changes_selected_path(rng):
    spec = LinearSpec("planned", 16, 16, False, "mlp", TT)
    install_plan({"planned": 1})
    from repro.nn.linear import planned_path_index
    assert planned_path_index("planned") == 1
    install_plan({})


def test_linear_bias_and_dense(rng):
    spec = LinearSpec("d", 8, 4, True, "mlp", None)
    p = linear_init(jax.random.PRNGKey(1), spec)
    x = jnp.asarray(rng.normal(size=(3, 8)), jnp.float32)
    y = linear_apply(spec, p, x)
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(x) @ np.asarray(p["w"]) + np.asarray(p["b"]),
        rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# embedding / head
# ---------------------------------------------------------------------------

def _dense_table(spec, p):
    vm = spec.vocab_modes
    full = p["core0"]
    for k in range(1, len(vm)):
        full = jnp.einsum("...r,rvds->...vds", full, p[f"core{k}"])
    full = full[0, ..., 0]
    perm = [2 * i for i in range(len(vm))] + [2 * i + 1 for i in range(len(vm))]
    return jnp.transpose(full, perm).reshape(spec.vocab, spec.d_model)


@pytest.mark.parametrize("vocab,d_model", [(120, 24), (96, 32), (253, 16)])
def test_tt_embedding_gather_and_head_exact(vocab, d_model, rng):
    tt = TTConfig(enabled=True, d=3, rank=8, min_dim=1, targets=("embed",))
    spec = EmbeddingSpec("e", vocab, d_model, tt)
    p = embedding_init(jax.random.PRNGKey(2), spec)
    table = _dense_table(spec, p)
    ids = jnp.asarray(rng.integers(0, vocab, size=(4, 7)), jnp.int32)
    emb = embedding_apply(spec, p, ids)
    np.testing.assert_allclose(np.asarray(emb), np.asarray(table)[np.asarray(ids)],
                               rtol=1e-5, atol=1e-5)
    x = jnp.asarray(rng.normal(size=(4, 7, d_model)), jnp.float32)
    logits = head_apply(spec, p, x)
    expect = jnp.einsum("bsd,vd->bsv", x, table)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(expect),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def _naive_causal_attention(q, k, v):
    b, s, h, d = q.shape
    scores = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    mask = np.tril(np.ones((s, s), bool))
    scores = np.where(mask[None, None], scores, -1e30)
    probs = np.exp(scores - scores.max(-1, keepdims=True))
    probs = probs / probs.sum(-1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", probs, v)


def test_attention_matches_naive(rng):
    spec = AttentionSpec("a", d_model=16, n_heads=2, n_kv_heads=2, head_dim=8,
                         rope="none", q_chunk=4)
    p = attention_init(jax.random.PRNGKey(3), spec)
    x = jnp.asarray(rng.normal(size=(2, 12, 16)), jnp.float32)
    out, _ = attention_apply(spec, p, x)
    q = np.asarray(x @ p["wq"]["w"]).reshape(2, 12, 2, 8)
    k = np.asarray(x @ p["wk"]["w"]).reshape(2, 12, 2, 8)
    v = np.asarray(x @ p["wv"]["w"]).reshape(2, 12, 2, 8)
    expect = _naive_causal_attention(q, k, v).reshape(2, 12, 16) @ np.asarray(
        p["wo"]["w"])
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-4, atol=1e-4)


def test_gqa_decode_matches_prefill_continuation(rng):
    spec = AttentionSpec("g", d_model=16, n_heads=4, n_kv_heads=2, head_dim=4,
                         rope="full", q_chunk=8)
    p = attention_init(jax.random.PRNGKey(4), spec)
    x = jnp.asarray(rng.normal(size=(1, 9, 16)), jnp.float32)
    full, _ = attention_apply(spec, p, x)
    cache = init_kv_cache(spec, 1, 16, jnp.float32)
    _, cache = attention_apply(spec, p, x[:, :8], cache=cache,
                               cache_pos=jnp.asarray(0, jnp.int32))
    dec, _ = attention_apply(spec, p, x[:, 8:9], cache=cache,
                             cache_pos=jnp.asarray(8, jnp.int32))
    np.testing.assert_allclose(np.asarray(dec[:, 0]), np.asarray(full[:, 8]),
                               rtol=1e-4, atol=1e-4)


_KV_SPEC = AttentionSpec("kv", d_model=16, n_heads=6, n_kv_heads=2,
                         head_dim=8, rope="full")


def _qkv_reference(spec, p, x, positions):
    """q, k, v of ``x`` (B, s, D) at ``positions`` (B, s), heads split."""
    b, s, _ = x.shape
    q, k, v = (linear_apply(ls, p[n], x).reshape(b, s, -1, spec.head_dim)
               for ls, n in ((spec.q_spec, "wq"), (spec.k_spec, "wk"),
                             (spec.v_spec, "wv")))
    frac, base = rope_for(spec.rope)
    return (apply_rope(q, positions, base=base, rotary_fraction=frac),
            apply_rope(k, positions, base=base, rotary_fraction=frac), v)


def _decode_reference(spec, p, x, k4, v4, pos):
    """Decode as written before the cache went lane-dense: the new rows
    put into a (B, S, H_kv, Dh) cache by a one-hot ``where`` over every
    position (per lane for a (B,) ``pos``, else a dynamic_update_slice),
    then grouped attention over the heads.  Returns (out, k4, v4)."""
    b, hkv, dh = x.shape[0], spec.n_kv_heads, spec.head_dim
    kv_pos = jnp.arange(k4.shape[1])
    lane_pos = jnp.broadcast_to(pos, (b,))
    q, k, v = _qkv_reference(spec, p, x, lane_pos[:, None])
    if pos.ndim == 1:
        sel = (kv_pos[None, :] == pos[:, None])[:, :, None, None]
        k4 = jnp.where(sel, k.astype(k4.dtype), k4)
        v4 = jnp.where(sel, v.astype(v4.dtype), v4)
    else:
        k4 = jax.lax.dynamic_update_slice_in_dim(k4, k.astype(k4.dtype), pos, 1)
        v4 = jax.lax.dynamic_update_slice_in_dim(v4, v.astype(v4.dtype), pos, 1)
    g = spec.n_heads // hkv
    qg = q.reshape(b, 1, g, hkv, dh)
    scores = jnp.einsum("bqghd,bkhd->bghqk", qg, k4,
                        preferred_element_type=jnp.float32) * (1 / np.sqrt(dh))
    vmask = (kv_pos[None, :] <= lane_pos[:, None])[:, None, None, None, :]
    probs = jax.nn.softmax(jnp.where(vmask, scores, -1e30), axis=-1)
    out = jnp.einsum("bghqk,bkhd->bqghd", probs.astype(v4.dtype), v4)
    out = linear_apply(spec.o_spec, p["wo"], out.reshape(b, 1, -1))
    return out, k4, v4


def _filled_cache(rng, b, s, dtype):
    """A (B, S, H_kv * Dh) cache of random rows, so that a row left
    untouched is told from one written."""
    w = _KV_SPEC.n_kv_heads * _KV_SPEC.head_dim
    return KVCache(*(jnp.asarray(rng.normal(size=(b, s, w)), dtype)
                     for _ in range(2)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("per_lane", [True, False])
def test_decode_row_write_matches_one_hot_reference(per_lane, dtype, rng):
    """Decode through ``attention_apply`` writes exactly each lane's new
    row of the lane-dense cache (the others bit for bit as they were)
    and returns, bit for bit, the output of the pre-lane-dense decode on
    the same rows: per lane with lanes at position 0 and at
    ``max_seq - 1``, and at one scalar position."""
    spec, b, s = _KV_SPEC, 4, 8
    p = attention_init(jax.random.PRNGKey(7), spec)
    x = jnp.asarray(rng.normal(size=(b, 1, spec.d_model)), jnp.float32)
    cache = _filled_cache(rng, b, s, dtype)
    pos = jnp.asarray([0, 3, s - 1, 5] if per_lane else 5, jnp.int32)
    out, new = attention_apply(spec, p, x, cache=cache, cache_pos=pos)

    heads = (b, s, spec.n_kv_heads, spec.head_dim)
    ref_out, ref_k, ref_v = _decode_reference(
        spec, p, x, cache.k.reshape(heads), cache.v.reshape(heads), pos)
    np.testing.assert_array_equal(np.asarray(new.k.reshape(heads)), ref_k)
    np.testing.assert_array_equal(np.asarray(new.v.reshape(heads)), ref_v)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref_out))
    written = np.zeros((b, s), bool)
    written[np.arange(b), np.broadcast_to(np.asarray(pos), (b,))] = True
    for old, now in ((cache.k, new.k), (cache.v, new.v)):
        np.testing.assert_array_equal(np.asarray(now)[~written],
                                      np.asarray(old)[~written])


def test_prefill_writes_the_prompt_rows(rng):
    """A batch-1 prefill at a scalar position writes the prompt's K/V
    rows there, as the (B, S, H_kv, Dh) dynamic_update_slice did, and
    nothing else."""
    spec, s, at, n = _KV_SPEC, 16, 3, 5
    p = attention_init(jax.random.PRNGKey(8), spec)
    x = jnp.asarray(rng.normal(size=(1, n, spec.d_model)), jnp.float32)
    cache = _filled_cache(rng, 1, s, jnp.bfloat16)
    _, new = attention_apply(spec, p, x, cache=cache,
                             cache_pos=jnp.asarray(at, jnp.int32))
    _, k, v = _qkv_reference(spec, p, x, at + jnp.arange(n)[None, :])
    heads = (1, s, spec.n_kv_heads, spec.head_dim)
    for old, now, rows in ((cache.k, new.k, k), (cache.v, new.v, v)):
        expect = jax.lax.dynamic_update_slice_in_dim(
            old.reshape(heads), rows.astype(old.dtype), at, 1)
        np.testing.assert_array_equal(np.asarray(now.reshape(heads)), expect)


def test_stacked_cache_writes_only_its_layer(rng):
    """With ``layer``, decode writes into that layer of the stacked
    (L, B, S, H_kv * Dh) cache and reads it back: its output and layer
    equal the single-layer call's, the other layers stay as they were."""
    spec, n_layers, b, s = _KV_SPEC, 3, 4, 8
    p = attention_init(jax.random.PRNGKey(9), spec)
    x = jnp.asarray(rng.normal(size=(b, 1, spec.d_model)), jnp.float32)
    layers = [_filled_cache(rng, b, s, jnp.bfloat16) for _ in range(n_layers)]
    stack = KVCache(*(jnp.stack(c) for c in zip(*layers)))
    pos = jnp.asarray([2, 0, s - 1, 6], jnp.int32)
    out, new = attention_apply(spec, p, x, cache=stack, cache_pos=pos,
                               layer=jnp.asarray(1, jnp.int32))
    one_out, one = attention_apply(spec, p, x, cache=layers[1], cache_pos=pos)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(one_out))
    for l in range(n_layers):
        for leaf, now in zip(one if l == 1 else layers[l], new):
            np.testing.assert_array_equal(np.asarray(now[l]), np.asarray(leaf))


def test_chunked_attention_chunk_invariance(rng):
    x = jnp.asarray(rng.normal(size=(1, 16, 16)), jnp.float32)
    outs = []
    for qc in (2, 4, 16):
        spec = AttentionSpec("c", 16, 2, 2, 8, rope="none", q_chunk=qc)
        p = attention_init(jax.random.PRNGKey(5), spec)
        out, _ = attention_apply(spec, p, x)
        outs.append(np.asarray(out))
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("sq,q_chunk", [(13, 4), (15, 8), (11, 10)])
@pytest.mark.parametrize("n_kv", [1, 2])
def test_chunked_attention_ragged_prompt_equals_unchunked(sq, q_chunk, n_kv,
                                                          rng):
    """A prompt length ``q_chunk`` does not divide is still cut into
    chunks of at most ``q_chunk`` queries (the last one padded), and
    gives what one chunk over the whole prompt gives."""
    from repro.nn.attention import _chunked_attention

    q = jnp.asarray(rng.normal(size=(2, sq, 4, 8)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, sq, n_kv, 8)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, sq, n_kv, 8)), jnp.float32)
    whole = _chunked_attention(q, k, v, True, sq)
    chunked = _chunked_attention(q, k, v, True, q_chunk)
    assert chunked.shape == (2, sq, 4, 8)
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)
    scans = [e for e in jax.make_jaxpr(
        lambda q: _chunked_attention(q, k, v, True, q_chunk))(q).eqns
        if e.primitive.name == "scan"]
    assert scans and scans[0].params["length"] == -(-sq // q_chunk)


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------

def test_moe_full_capacity_equals_dense_mixture(rng):
    spec = MoESpec("m", d_model=16, d_ff=32, n_experts=2, top_k=2, n_shared=0,
                   capacity_factor=4.0, router_group=8)
    p = moe_init(jax.random.PRNGKey(6), spec)
    x = jnp.asarray(rng.normal(size=(1, 8, 16)), jnp.float32)
    y, aux = moe_apply(spec, p, x)
    logits = jnp.einsum("bsd,de->bse", x, p["router"])
    pr = jax.nn.softmax(logits, -1)

    def ffn(e, xx):
        up = xx @ p["eu"]["w"][e]
        gate = xx @ p["eg"]["w"][e]
        return (jax.nn.silu(gate) * up) @ p["ed"]["w"][e]

    expect = sum(pr[..., e:e + 1] * ffn(e, x) for e in range(2))
    np.testing.assert_allclose(np.asarray(y), np.asarray(expect), rtol=1e-4,
                               atol=1e-4)
    assert float(aux) > 0


def test_moe_capacity_drops_tokens(rng):
    """With capacity 0-ish the output collapses toward zero (all dropped)."""
    spec = MoESpec("m2", d_model=8, d_ff=16, n_experts=4, top_k=1, n_shared=0,
                   capacity_factor=0.01, router_group=16)
    p = moe_init(jax.random.PRNGKey(7), spec)
    x = jnp.asarray(rng.normal(size=(1, 16, 8)), jnp.float32)
    y, _ = moe_apply(spec, p, x)
    spec_full = MoESpec("m2", d_model=8, d_ff=16, n_experts=4, top_k=1,
                        n_shared=0, capacity_factor=8.0, router_group=16)
    y_full, _ = moe_apply(spec_full, p, x)
    assert float(jnp.sum(jnp.abs(y))) < float(jnp.sum(jnp.abs(y_full)))


# ---------------------------------------------------------------------------
# SSD / WKV recurrences vs sequential references
# ---------------------------------------------------------------------------

def _ssd_ref(x, da, B, C, init=None):
    b, s, h, p = x.shape
    n = B.shape[-1]
    S = np.zeros((b, h, n, p)) if init is None else np.array(init)
    ys = []
    for t in range(s):
        S = S * np.exp(np.array(da[:, t]))[:, :, None, None] + np.einsum(
            "bn,bhp->bhnp", np.array(B[:, t]), np.array(x[:, t]))
        ys.append(np.einsum("bn,bhnp->bhp", np.array(C[:, t]), S))
    return np.stack(ys, 1), S


@pytest.mark.parametrize("chunk", [4, 16, 96])
def test_ssd_chunked_vs_sequential(chunk, rng):
    b, s, h, p, n = 2, 96, 3, 4, 5
    x = jnp.asarray(rng.normal(size=(b, s, h, p)), jnp.float32)
    da = jnp.asarray(-np.abs(rng.normal(size=(b, s, h))) * 0.3, jnp.float32)
    B = jnp.asarray(rng.normal(size=(b, s, n)), jnp.float32)
    C = jnp.asarray(rng.normal(size=(b, s, n)), jnp.float32)
    init = jnp.asarray(rng.normal(size=(b, h, n, p)), jnp.float32)
    y, fin = _ssd_chunked(x, da, B, C, chunk=chunk, init_state=init)
    yr, Sr = _ssd_ref(x, da, B, C, init)
    np.testing.assert_allclose(np.asarray(y), yr, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(fin), Sr, rtol=2e-4, atol=2e-4)


def _wkv_ref(r, k, v, logw, u, init=None):
    b, s, h, n = r.shape
    S = np.zeros((b, h, n, n)) if init is None else np.array(init)
    ys = []
    for t in range(s):
        kv = np.einsum("bhn,bhm->bhnm", np.array(k[:, t]), np.array(v[:, t]))
        ys.append(np.einsum("bhn,bhnm->bhm", np.array(r[:, t]),
                            S + np.array(u)[None, :, :, None] * kv))
        S = np.exp(np.array(logw[:, t]))[..., None] * S + kv
    return np.stack(ys, 1), S


@pytest.mark.parametrize("decay_scale", [0.3, 7.0])
def test_wkv_chunked_vs_sequential(decay_scale, rng):
    b, s, h, n = 2, 64, 3, 4
    r = jnp.asarray(rng.normal(size=(b, s, h, n)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, h, n)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, h, n)), jnp.float32)
    logw = jnp.maximum(jnp.asarray(
        -np.abs(rng.normal(size=(b, s, h, n))) * decay_scale, jnp.float32), -7.5)
    u = jnp.asarray(rng.normal(size=(h, n)), jnp.float32)
    init = jnp.asarray(rng.normal(size=(b, h, n, n)), jnp.float32)
    y, fin = _wkv_chunked(r, k, v, logw, u, chunk=16, init_state=init)
    yr, Sr = _wkv_ref(r, k, v, logw, u, init)
    assert not np.any(np.isnan(np.asarray(y)))
    np.testing.assert_allclose(np.asarray(y), yr, rtol=2e-4, atol=5e-4)
    np.testing.assert_allclose(np.asarray(fin), Sr, rtol=2e-4, atol=5e-4)
