"""The blocked causal prefill kernel ``flash_prefill`` (interpret mode on
the CPU) and the one rule that sends a prefill to it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.flash_prefill import BLOCK_K, flash_blocks
from repro.models import api
from repro.nn import AttentionSpec, flash_prefill_applies
from repro.nn.attention import _chunked_attention
from repro.serve import ServeEngine

GLM = "chatglm3-6b"
TT = "tt-lm-100m"
#: serve-docs' padded prompt lengths
DOC_LENS = range(1024, 6145, 512)


def _qkv(seed, s, h, hkv, dh):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return [jax.random.normal(kk, (1, s, n, dh), jnp.float32).astype(
        jnp.bfloat16) for kk, n in zip(ks, (h, hkv, hkv))]


@pytest.mark.parametrize("h,hkv", [(32, 2), (4, 4)])
@pytest.mark.parametrize("s", [BLOCK_K, BLOCK_K + 512, 700, 300])
def test_flash_prefill_matches_chunked_attention(h, hkv, s):
    """ChatGLM's head layout (32 query heads over 2 KV heads of 128) and
    one query head per KV head, at lengths that are a multiple of the key
    block, that end inside a block, and that no block divides.
    bfloat16 operands, float32 scores on both sides: they may differ by
    the rounding of the bfloat16 output and of the probabilities, at most
    two units in the last place of a bfloat16 (rtol 2**-7) over outputs
    of magnitude 1 to 4."""
    q, k, v = _qkv(s * h, s, h, hkv, 128)
    out = ops.flash_prefill(q, k, v, interpret=True)
    want = _chunked_attention(q, k, v, True, 4096)
    assert out.shape == q.shape and out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2**-7, atol=2**-7)


@pytest.mark.parametrize("g,dh,seq,want", [
    (16, 128, 6144, (64, BLOCK_K)),        # chatglm3-6b
    (16, 128, 1100, (64, BLOCK_K)),
    (1, 128, 300, (1024, 384)),            # a block cut to the prompt
    (8, 256, 4096, (64, BLOCK_K)),         # wider heads, fewer rows
])
def test_flash_blocks(g, dh, seq, want):
    assert flash_blocks(g, dh, seq) == want


@pytest.mark.parametrize("arch,engages", [(GLM, True), (TT, False)])
def test_flash_prefill_applies_by_head_width(arch, engages):
    """ChatGLM's 128-wide heads take the kernel at every serve-docs
    length; tt-lm-100m's 64-wide heads never do."""
    from repro.models.blocks import attn_spec

    spec = attn_spec(get_config(arch))
    assert [flash_prefill_applies(spec, s) for s in DOC_LENS] == [
        engages] * len(DOC_LENS)


def test_flash_prefill_applies_only_where_the_kernel_can_run():
    spec = AttentionSpec("a", 256, 2, 1, 128)
    assert flash_prefill_applies(spec, BLOCK_K)
    assert not flash_prefill_applies(spec, BLOCK_K - 1)
    assert not flash_prefill_applies(
        AttentionSpec("a", 256, 2, 1, 128, causal=False), 1024)


def _engine(cfg, **kw):
    params = jax.eval_shape(api(cfg).init_params, jax.random.PRNGKey(0))
    return ServeEngine(cfg, params, n_slots=2, max_seq=1024, **kw)


@pytest.mark.parametrize("head_dim,tokens,has_flash", [
    (64, 512, False), (64, 1024, False), (128, 1024, True)])
def test_lowered_prefill_calls_flash_by_head_width(head_dim, tokens,
                                                   has_flash):
    """tt-lm-100m in bfloat16 as serve-chat serves it: its prefills, up to
    1024 tokens, keep the chunked attention.  The same model with
    128-wide heads calls the kernel, so the check can see it."""
    cfg = get_config(TT).with_(dtype="bfloat16", head_dim=head_dim)
    text = _engine(cfg, prompt_bucket=128).lowered_text(tokens)
    assert ("flash_prefill" in text["prefill"]) == has_flash
    assert "flash_prefill" not in text["decode"]


def test_lowered_train_step_has_no_flash_prefill():
    """Training (no cache) keeps ``_chunked_attention``: the kernel has no
    backward."""
    from repro.launch.steps import make_train_step
    from repro.optim import adamw_init

    cfg = get_config(TT)
    params = jax.eval_shape(api(cfg).init_params, jax.random.PRNGKey(0))
    opt = jax.eval_shape(adamw_init, params)
    tok = jax.ShapeDtypeStruct((4, 1024), jnp.int32)
    batch = {"tokens": tok, "labels": tok}
    text = jax.jit(make_train_step(cfg, lr=lambda s: 1e-3)).lower(
        params, opt, batch).as_text()
    assert "flash_prefill" not in text
