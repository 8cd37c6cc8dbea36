"""A small ChatGLM-shaped model whose TT modes hold large primes, served
through ``ServeEngine`` under its DSE plans and checked against the
benchmark's plain float32 reference (``bench/configs/dense_lm.py``).

chatglm3-6b factorizes d_ff 13696 as (107, 16, 8) and its vocabulary
65024 as (127, 32, 16); here d_ff 296 = (37, 4, 2) and vocab 328 =
(41, 4, 2) put a prime above 31 into the MLP's and the embedding's modes
at smoke size, with the preset's GLM features (partial rotary, q/k/v
bias, grouped KV heads) and three cores per side, as the preset has.
"""

import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import chatglm3_6b, get_config
from repro.core.tensor_network import factorize
from repro.dse_cli import run_dse_plan
from repro.models import api
from repro.nn.embedding import EmbeddingSpec
from repro.nn.linear import TTConfig
from repro.plan import execution_log, reset_execution_log
from repro.serve import ServeEngine

ARCH = "chatglm3-6b"
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SLOTS, MAX_SEQ, BUCKET = 2, 32, 8


def _reference():
    name = "dense_lm_reference"
    if name not in sys.modules:
        path = os.path.join(ROOT, "bench", "configs", "dense_lm.py")
        spec = importlib.util.spec_from_file_location(name, path)
        sys.modules[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[name])
    return sys.modules[name]


@pytest.fixture
def prime_cfg(monkeypatch):
    cfg = get_config(ARCH, smoke=True).with_(
        d_ff=296, vocab=328,
        tt=TTConfig(enabled=True, d=3, rank=4, min_dim=32,
                    targets=("attn", "mlp", "head", "moe", "embed")))
    # the DSE plans the arch's smoke preset: make it this one
    monkeypatch.setattr(chatglm3_6b, "SMOKE", cfg)
    return cfg


def _params(cfg, seed=7):
    """The program's init, with every bias and norm scale moved off its
    initial zero or one so that neither can be dropped unseen."""
    params = api(cfg).init_params(jax.random.PRNGKey(seed))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    out = []
    for (path, a), k in zip(leaves, keys):
        if getattr(path[-1], "key", None) in ("b", "scale"):
            a = a + 0.1 * jax.random.normal(k, a.shape, a.dtype)
        out.append(a)
    return jax.tree_util.tree_unflatten(treedef, out)


def test_prime_mode_model_serves_as_the_reference_computes(prime_cfg):
    cfg = prime_cfg
    assert 37 in factorize(cfg.d_ff, 3) and 41 in EmbeddingSpec(
        "e", cfg.vocab, cfg.d_model, cfg.tt).vocab_modes
    # at this size the search would keep every layer on jnp: put each
    # phase on the kernel the full-size plans give most of its layers
    plans = {phase: run_dse_plan(ARCH, hw="tpu_v5e", smoke=True, phase=phase,
                                 tokens=tokens, serve_slots=SLOTS,
                                 serve_gen=8, plan_backend=backend)[1]
             for phase, tokens, backend in (("prefill", 16, "tt_gemm"),
                                            ("decode", SLOTS, "streaming_tt"))}
    reset_execution_log()
    params = _params(cfg)
    eng = ServeEngine(cfg, params, n_slots=SLOTS, max_seq=MAX_SEQ,
                      prompt_bucket=BUCKET, prefill_plan=plans["prefill"],
                      decode_plan=plans["decode"], arch=ARCH)

    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, cfg.vocab, n).tolist() for n in (11, 6)]
    caches = eng.fresh_caches()
    served, rows = [], []
    for lane, prompt in enumerate(prompts):
        row, small = eng.prefill_request(prompt)
        caches = eng.admit(caches, small, lane)
        served.append([int(np.argmax(row))])
        rows.append([row])
    pos = np.array([len(p) for p in prompts], np.int64)
    for _ in range(4):
        tok = np.array([s[-1] for s in served], np.int64)
        out, caches = eng.decode(tok, pos, caches)
        pos += 1
        for lane in range(SLOTS):
            served[lane].append(int(np.argmax(out[lane])))
            rows[lane].append(out[lane])

    ran = {(r["stream"], r["name"], r["backend"]) for r in execution_log()}
    for stream, backend in (("prefill", "tt_gemm"), ("decode", "streaming_tt")):
        assert {(stream, name, backend)
                for name in ("mlp.wg", "mlp.wu", "mlp.wd")} <= ran

    ref = _reference()
    arch = ref.Arch(n_layers=cfg.n_layers, d_model=cfg.d_model,
                    n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                    head_dim=cfg.hd, vocab=cfg.vocab, rope_fraction=0.5,
                    tt_d=cfg.tt.d)
    for lane, prompt in enumerate(prompts):
        toks = prompt + served[lane][:-1]
        cols = np.arange(len(prompt) - 1, len(toks))
        want = np.asarray(ref.logits_at(
            params, jnp.asarray([toks], jnp.int32),
            np.zeros(len(cols), np.int32), cols, arch=arch, mode="f32"))
        got = np.stack(rows[lane])
        scale = np.abs(want).max()
        np.testing.assert_allclose(got, want, rtol=0, atol=2e-4 * scale)
