"""Per-architecture smoke tests (reduced same-family configs) +
decode-vs-full-forward consistency — the strongest cache-machinery check."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.models import api
from repro.models import lm as lm_mod


def _batch(cfg, b=2, s=16, seed=1):
    rng = np.random.default_rng(seed)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab, (b, s)), jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab, (b, s)), jnp.int32),
    }
    if cfg.family in ("vlm", "encdec"):
        n = cfg.n_frontend_tokens or 8
        batch["frontend"] = jnp.asarray(
            rng.normal(size=(b, n, cfg.d_model)), jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_smoke_train_step(arch):
    """One forward/train step on CPU: finite loss, finite grads, shapes."""
    cfg = get_config(arch, smoke=True)
    m = api(cfg)
    params = m.init_params(jax.random.PRNGKey(0))
    batch = _batch(cfg)
    loss, grads = jax.value_and_grad(m.train_loss)(params, batch)
    assert np.isfinite(float(loss))
    gnorm = jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2)
                         for g in jax.tree.leaves(grads)))
    assert np.isfinite(float(gnorm)) and float(gnorm) > 0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_smoke_prefill_decode(arch):
    cfg = get_config(arch, smoke=True)
    m = api(cfg)
    params = m.init_params(jax.random.PRNGKey(0))
    batch = _batch(cfg)
    logits, caches = m.prefill(params, batch, 32)
    assert logits.shape == (2, cfg.vocab)
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    logits2, caches2 = m.decode_step(params, tok, caches,
                                     jnp.asarray(16, jnp.int32))
    assert logits2.shape == (2, cfg.vocab)
    assert not bool(jnp.any(jnp.isnan(logits2)))


@pytest.mark.parametrize("arch", ["phi3-medium-14b", "zamba2-1.2b", "rwkv6-7b",
                                  "chatglm3-6b"])
def test_decode_equals_full_forward(arch):
    """prefill(x[:8]) + decode(x[8]) logits == full forward at position 8.

    (MoE archs excluded: capacity-based routing depends on the token GROUP
    — a decoded token routes alone while prefill routes it among its
    neighbours, so exact equality is not a property of capacity MoE.)"""
    cfg = get_config(arch, smoke=True)
    m = api(cfg)
    params = m.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (1, 9)), jnp.int32)
    full_logits, _, _ = lm_mod.forward(cfg, params, toks)
    _, caches = m.prefill(params, {"tokens": toks[:, :8]}, 16)
    dec, _ = m.decode_step(params, toks[:, 8:9], caches,
                           jnp.asarray(8, jnp.int32))
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full_logits[:, -1]),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("arch", ["tt-lm-100m", "chatglm3-6b"])
@pytest.mark.parametrize("real", [5, 8, 13])
def test_prefill_last_real_row_matches_full_forward(arch, real):
    """The engine's prefill of a bucket-padded prompt gives the logits of
    its last real token: the full forward pass's row at ``real - 1``."""
    from repro.serve import ServeEngine

    cfg = get_config(arch, smoke=True)
    m = api(cfg)
    params = m.init_params(jax.random.PRNGKey(0))
    prompt = np.random.default_rng(real).integers(0, cfg.vocab, real)
    eng = ServeEngine(cfg, params, n_slots=1, max_seq=32, prompt_bucket=8)
    padded = eng.padded_len(real)
    assert padded % 8 == 0 and padded >= real
    toks = np.zeros((1, padded), np.int32)
    toks[0, :real] = prompt
    full, _, _ = lm_mod.forward(cfg, params, jnp.asarray(toks))
    row, _ = eng.prefill_request(prompt.tolist())
    assert row.shape == (cfg.vocab,)
    np.testing.assert_allclose(row, np.asarray(full[0, real - 1]),
                               rtol=1e-5, atol=1e-5)
    last, _ = m.prefill(params, {"tokens": jnp.asarray(toks)}, 32)
    np.testing.assert_allclose(np.asarray(last[0]),
                               np.asarray(full[0, padded - 1]),
                               rtol=1e-5, atol=1e-5)


def test_dense_vs_tt_param_count():
    """TT must actually compress: full-size configs, analytic param counts."""
    from repro.models.lm import count_params
    cfg_tt = get_config("chatglm3-6b", smoke=True)
    cfg_dense = get_config("chatglm3-6b", tt=False, smoke=True)
    m_tt, m_dense = api(cfg_tt), api(cfg_dense)
    p_tt = jax.eval_shape(m_tt.init_params, jax.random.PRNGKey(0))
    p_dn = jax.eval_shape(m_dense.init_params, jax.random.PRNGKey(0))
    n_tt = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(p_tt))
    n_dn = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(p_dn))
    assert n_tt < n_dn


def test_loss_chunking_matches_unchunked():
    cfg = get_config("phi3-medium-14b", smoke=True)
    m = api(cfg)
    params = m.init_params(jax.random.PRNGKey(0))
    batch = _batch(cfg)
    full = lm_mod.train_loss(cfg.with_(loss_chunk=0), params, batch)
    chunked = lm_mod.train_loss(cfg.with_(loss_chunk=4), params, batch)
    np.testing.assert_allclose(float(full), float(chunked), rtol=1e-5)


def test_scan_vs_unrolled_layers_identical():
    cfg = get_config("glm4-9b", smoke=True)
    m = api(cfg)
    params = m.init_params(jax.random.PRNGKey(0))
    batch = _batch(cfg)
    l_scan = m.train_loss(params, batch)
    l_unroll = api(cfg.with_(scan_layers=False)).train_loss(params, batch)
    np.testing.assert_allclose(float(l_scan), float(l_unroll), rtol=1e-5)
