"""Compile-only checks of the main path's Mosaic kernels for TPU v5e.

Each test lowers and compiles for a described (not attached) ``v5e:2x2``
topology, so Mosaic refuses here what it would refuse on the chip: an
unsupported in-kernel reshape, a block that breaks the (8, 128) rule, a
primitive it cannot lower.  Nothing runs, so results are not checked —
``chip_smoke.py`` does that on the chip.  The topology is described
inside a fixture, never at import: only the worker that runs this file
loads the TPU compiler.

Covered: ``tt_gemm`` in every dataflow with several k-folds; every layer
of the tt-lm-100m ``tpu_v5e`` prefill plan at 512 tokens and decode plan
at 4 tokens, through ``planned_tt_linear``; one train-plan backward; one
fused path segment; the serving engine's whole bfloat16 decode step at
256 lanes and ``max_seq`` 1536, whose KV cache must be written in place.
For chatglm3-6b at its published widths, whose TT modes hold the primes
107 (d_ff 13696) and 127 (vocab 65024): the head of its ``tpu_v5e``
prefill plan at 6144 tokens and decode plan at 48, and the engine's
whole prefill of a 6144-token prompt and decode step at 48 lanes and
``max_seq`` 6656 under those plans, which must fit one 16-GB chip
beside each other.
Each Mosaic call must carry its kernel's name (``%tt_gemm.N``,
``%streaming_tt.N``, ``%fused_path.N``): the profiler trace keys device
ops by these instruction names.
"""

import dataclasses
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import fusion
from repro.core.paths import find_topk_paths
from repro.core.tensor_network import tt_linear_network
from repro.kernels import ops
from repro.plan import LayerPlan, Tiling
from repro.plan.compiler import base_name
from repro.plan.executor import planned_tt_linear

ARCH = "tt-lm-100m"
LAYERS = ("attn.wq", "attn.wo", "mlp.wg", "mlp.wu", "mlp.wd", "head")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def plans():
    from repro.dse_cli import run_dse_plan

    return {
        "prefill": run_dse_plan(ARCH, hw="tpu_v5e", phase="prefill",
                                serve_slots=4)[1],
        "decode": run_dse_plan(ARCH, hw="tpu_v5e", tokens=4, phase="decode",
                               serve_slots=4)[1],
        "train": run_dse_plan(ARCH, hw="tpu_v5e", mode="train")[1],
    }


def _geometry(arch: str) -> dict:
    """Layer family -> (in_modes, out_modes, ranks, core shapes)."""
    from repro.configs import get_config
    from repro.dse_cli import model_dse_layers

    out = {}
    for name, tn in model_dse_layers(get_config(arch), tokens=8):
        cores = [n for n in tn.nodes if n.kind != "input"]
        out.setdefault(base_name(name), (
            tuple(c.dim_of(e) for c in cores for e in c.edges
                  if e.startswith("j")),
            tuple(c.dim_of(e) for c in cores for e in c.edges
                  if e.startswith("i")),
            tuple(c.dims[-1] for c in cores[:-1]),
            [c.dims for c in cores]))
    return out


@pytest.fixture(scope="module")
def geometry():
    return _geometry(ARCH)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _kernels(compiled) -> set:
    """Names of the Mosaic calls in a compiled program's HLO text."""
    return set(re.findall(r"%([a-z_]+)(?:\.[\w.]+)? = [^\n]*"
                          r'custom_call_target="tpu_custom_call"',
                          compiled.as_text()))


def _layer_args(geom, tokens, sharding, dtype=jnp.float32):
    in_modes, out_modes, ranks, core_dims = geom
    x = jax.ShapeDtypeStruct((tokens, math.prod(in_modes)), dtype,
                             sharding=sharding)
    cores = [jax.ShapeDtypeStruct(d, dtype, sharding=sharding)
             for d in core_dims]
    return in_modes, out_modes, ranks, x, cores


@pytest.mark.parametrize("dataflow", ["OS", "WS", "IS"])
def test_tt_gemm_compiles_with_k_folds(one_chip, dataflow):
    a = jax.ShapeDtypeStruct((512, 1024), jnp.float32, sharding=one_chip)
    b = jax.ShapeDtypeStruct((1024, 384), jnp.float32, sharding=one_chip)
    compiled = _compile(
        lambda a, b: ops.gemm(a, b, dataflow=dataflow, interpret=False),
        a, b)
    assert _kernels(compiled) == {"tt_gemm"}


def _check_layer_compiles(lp, geom, tokens, sharding, dtype=jnp.float32):
    in_modes, out_modes, ranks, x, cores = _layer_args(geom, tokens, sharding,
                                                       dtype)
    compiled = _compile(
        lambda x, cs: planned_tt_linear(lp, x, cs, in_modes, out_modes,
                                        ranks, interpret=False), x, cores)
    if lp.backend != "jnp":
        names = _kernels(compiled)
        fused = {"fused_path"} if fusion.has_fused(lp.segments) else set()
        assert names - fused == {lp.backend} and names >= fused


@pytest.mark.parametrize("phase,tokens", [("prefill", 512), ("decode", 4)])
@pytest.mark.parametrize("layer", LAYERS)
def test_serving_plan_layer_compiles(one_chip, plans, geometry, phase,
                                     tokens, layer):
    lp = next(lp for lp in plans[phase].layers if lp.name == layer)
    _check_layer_compiles(lp, geometry[layer], tokens, one_chip)


GLM = "chatglm3-6b"
#: the serving cell's largest prompt, lanes and cache positions per lane
GLM_PROMPT, GLM_LANES, GLM_MAX_SEQ = 6144, 48, 6656


@pytest.fixture(scope="module")
def glm_geometry():
    return _geometry(GLM)


@pytest.fixture(scope="module")
def glm_plans():
    from repro.dse_cli import run_dse_plan

    return {phase: run_dse_plan(GLM, hw="tpu_v5e", phase=phase,
                                tokens=tokens, serve_slots=GLM_LANES,
                                serve_gen=128)[1]
            for phase, tokens in (("prefill", 2048), ("decode", GLM_LANES))}


@pytest.mark.parametrize("phase,tokens", [("prefill", GLM_PROMPT),
                                          ("decode", GLM_LANES)])
def test_chatglm_plan_layer_compiles_with_prime_modes(one_chip, glm_plans,
                                                      glm_geometry, phase,
                                                      tokens):
    """The plans' head, whose vocab modes hold 127.  The preset's head is
    tied and runs the embedding's chain, so the engine compile below never
    lowers the plan's head kernels; every other planned layer it does."""
    geom = glm_geometry["head"]
    assert 127 in geom[0] + geom[1]
    lp = next(lp for lp in glm_plans[phase].layers if lp.name == "head")
    # in the type the preset serves in
    _check_layer_compiles(lp, geom, tokens, one_chip, jnp.bfloat16)


def test_train_plan_backward_compiles(one_chip, plans, geometry):
    lp = next(lp for lp in plans["train"].layers if lp.name == "attn.wq")
    assert {op.backend for op in lp.backward} >= {"streaming_tt", "tt_gemm"}
    in_modes, out_modes, ranks, x, cores = _layer_args(
        geometry["attn.wq"], 1024, one_chip)

    def loss(x, cs):
        y = planned_tt_linear(lp, x, cs, in_modes, out_modes, ranks,
                              interpret=False)
        return jnp.sum(y * y)

    compiled = _compile(jax.grad(loss, argnums=(0, 1)), x, cores)
    assert _kernels(compiled) == {lp.backend} | {op.backend
                                                for op in lp.backward}


def test_fused_segment_compiles(one_chip):
    in_modes, out_modes, ranks = (12, 8, 8), (40, 40, 20), (16, 16, 16, 16, 8)
    tokens = 512
    tn = tt_linear_network(tokens, in_modes, out_modes, ranks)
    steps = tuple(tuple(s) for s in find_topk_paths(tn, k=1)[0].steps)
    segs = fusion.segment_path(tn, steps, block_tokens=256,
                               budget_bytes=8 * 2**20)
    assert fusion.has_fused(segs)
    lp = LayerPlan(name="head", path_index=0, path_steps=steps,
                   dataflow="OS", partitioning=(1, 1), backend="tt_gemm",
                   tiling=Tiling(block_tokens=256), segments=segs)
    x = jax.ShapeDtypeStruct((tokens, math.prod(in_modes)), jnp.float32,
                             sharding=one_chip)
    cores = [jax.ShapeDtypeStruct(n.dims, jnp.float32, sharding=one_chip)
             for n in tn.nodes if n.kind != "input"]
    compiled = _compile(
        lambda x, cs: planned_tt_linear(lp, x, cs, in_modes, out_modes,
                                        ranks, interpret=False), x, cores)
    assert "fused_path" in _kernels(compiled)


def _instructions(hlo: str):
    """``(computation, in_entry, result_type, opcode, line)`` of every
    instruction in an HLO module's text."""
    comp, entry = None, False
    for line in hlo.splitlines():
        if line[:1] not in ("", " ") and line.rstrip().endswith("{"):
            entry = line.startswith("ENTRY ")
            comp = line.split()[1 if entry else 0]
            continue
        m = re.match(r"\s*(?:ROOT )?%\S+ = ", line)
        if m is None:
            continue
        rest = line[m.end():]
        if rest.startswith("("):          # a tuple type: up to its ")"
            depth = 0
            for i, c in enumerate(rest):
                depth += {"(": 1, ")": -1}.get(c, 0)
                if depth == 0:
                    break
            typ, rest = rest[:i + 1], rest[i + 1:]
        else:
            typ, _, rest = rest.partition(" ")
        yield comp, entry, typ, rest.strip().split("(", 1)[0], line


#: what may produce a whole stacked cache: plumbing, and in-place writes
_IN_PLACE = {"parameter", "get-tuple-element", "tuple", "bitcast", "while",
             "scatter", "dynamic-update-slice"}


def test_serving_decode_step_writes_kv_in_place(topo, one_chip, monkeypatch):
    """The engine's decode step for tt-lm-100m in bfloat16 at the serving
    cell's 256 lanes and ``max_seq`` 1536, under the ``tpu_v5e`` decode
    plan: each layer's new K/V rows go into the donated stacked cache in
    place.  No copy, select or loop fusion may produce a whole stacked
    cache leaf (only plumbing and scatter or dynamic-update-slice
    fusions), no temporary may hold one layer's cache, and both leaves
    are aliased input to output."""
    from repro.configs import get_config
    from repro.dse_cli import run_dse_plan
    from repro.models import api
    from repro.nn import plan_context
    from repro.plan import execution_stream
    from repro.serve import ServeEngine

    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    lanes, max_seq = 256, 1536
    cfg = dataclasses.replace(get_config(ARCH), dtype="bfloat16")
    plan = run_dse_plan(ARCH, hw="tpu_v5e", tokens=lanes, phase="decode",
                        serve_slots=lanes)[1]
    m = api(cfg)
    on_chip = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,  # noqa: E731
                                             sharding=one_chip)
    params = jax.tree.map(on_chip, jax.eval_shape(m.init_params,
                                                  jax.random.PRNGKey(0)))
    eng = ServeEngine(cfg, params, n_slots=lanes, max_seq=max_seq,
                      decode_plan=plan, arch=ARCH)
    caches = jax.tree.map(on_chip, jax.eval_shape(eng.fresh_caches))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32,  # noqa: E731
                                          sharding=one_chip)
    with plan_context(plan), execution_stream("decode"):
        compiled = eng._decode_fn.lower(params, i32(lanes, 1), caches,
                                        i32(lanes)).compile()
    hlo = compiled.as_text()

    leaves = jax.tree.leaves(caches)
    assert len(leaves) == 2 and {a.shape for a in leaves} == {leaves[0].shape}
    stacked = "bf16[" + ",".join(map(str, leaves[0].shape)) + "]"
    roots = {c: op for c, _, _, op, line in _instructions(hlo)
             if line.lstrip().startswith("ROOT ")}
    bad = []
    for comp, _, typ, op, line in _instructions(hlo):
        if stacked not in typ or op in _IN_PLACE:
            continue
        called = re.search(r"calls=(%[\w.\-]+)", line)
        if op == "fusion" and called and roots.get(called.group(1)) in (
                "scatter", "dynamic-update-slice"):
            continue
        bad.append(line.strip()[:160])
    assert not bad, "whole-cache passes:\n" + "\n".join(bad)

    one_layer = math.prod(leaves[0].shape[1:]) * 2
    assert compiled.memory_analysis().temp_size_in_bytes < one_layer

    aliased = {int(p) for p in re.findall(
        r"\{\d*\}: \((\d+), \{\}", hlo.split("\n", 1)[0])}
    cache_params = {int(re.search(r"parameter\((\d+)\)", line).group(1))
                    for _, entry, typ, op, line in _instructions(hlo)
                    if entry and op == "parameter" and typ.startswith(stacked)}
    assert len(cache_params) == 2 and cache_params <= aliased


def test_chatglm_serving_fits_one_chip(one_chip, glm_plans, glm_geometry,
                                      monkeypatch):
    """chatglm3-6b's engine in bfloat16 under its ``tpu_v5e`` plans: the
    prefill of a 6144-token prompt beside the 48-lane decode cache, and
    the decode step, each within 16 GB; the prefill's head makes one row
    of logits, never (1, 6144, 65024); every planned projection runs on
    its plan's kernel, the MLP's with the mode 107 of d_ff 13696."""
    from repro.configs import get_config
    from repro.models import api
    from repro.nn import plan_context
    from repro.plan import execution_stream
    from repro.serve import ServeEngine

    for layer in ("mlp.wg", "mlp.wu", "mlp.wd"):
        assert 107 in sum(glm_geometry[layer][:2], ())
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    cfg = get_config(GLM)
    assert cfg.dtype == "bfloat16" and cfg.tie_embeddings
    m = api(cfg)
    on_chip = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,  # noqa: E731
                                             sharding=one_chip)
    params = jax.tree.map(on_chip, jax.eval_shape(m.init_params,
                                                  jax.random.PRNGKey(0)))
    eng = ServeEngine(cfg, params, n_slots=GLM_LANES, max_seq=GLM_MAX_SEQ,
                      prompt_bucket=512, prefill_plan=glm_plans["prefill"],
                      decode_plan=glm_plans["decode"], arch=GLM)
    caches = jax.tree.map(on_chip, jax.eval_shape(eng.fresh_caches))
    cache_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(caches))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32,  # noqa: E731
                                          sharding=one_chip)
    with plan_context(glm_plans["prefill"]), execution_stream("prefill"):
        pre = eng._prefill_fn.lower(params, i32(1, GLM_PROMPT),
                                    i32()).compile()
    with plan_context(glm_plans["decode"]), execution_stream("decode"):
        dec = eng._decode_fn.lower(params, i32(GLM_LANES, 1), caches,
                                   i32(GLM_LANES)).compile()
    limit = 16e9
    ma = pre.memory_analysis()
    assert (cache_bytes + ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes) < limit
    ma = dec.memory_analysis()
    assert (ma.argument_size_in_bytes + ma.temp_size_in_bytes) < limit
    assert f"{GLM_PROMPT},{cfg.vocab}]" not in pre.as_text()
    for plan, compiled in ((glm_plans["prefill"], pre),
                           (glm_plans["decode"], dec)):
        want = {lp.backend for lp in plan.layers
                if lp.name != "head" and lp.backend != "jnp"}
        assert want and _kernels(compiled) == want
