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
fused path segment.  Each Mosaic call must carry its kernel's name
(``%tt_gemm.N``, ``%streaming_tt.N``, ``%fused_path.N``): the profiler
trace keys device ops by these instruction names.
"""

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


@pytest.fixture(scope="module")
def geometry():
    """Layer family -> (in_modes, out_modes, ranks, core shapes)."""
    from repro.configs import get_config
    from repro.dse_cli import model_dse_layers

    out = {}
    for name, tn in model_dse_layers(get_config(ARCH), tokens=8):
        cores = [n for n in tn.nodes if n.kind != "input"]
        out.setdefault(base_name(name), (
            tuple(c.dim_of(e) for c in cores for e in c.edges
                  if e.startswith("j")),
            tuple(c.dim_of(e) for c in cores for e in c.edges
                  if e.startswith("i")),
            tuple(c.dims[-1] for c in cores[:-1]),
            [c.dims for c in cores]))
    return out


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _kernels(compiled) -> set:
    """Names of the Mosaic calls in a compiled program's HLO text."""
    return set(re.findall(r"%([a-z_]+)(?:\.[\w.]+)? = [^\n]*"
                          r'custom_call_target="tpu_custom_call"',
                          compiled.as_text()))


def _layer_args(geom, tokens, sharding):
    in_modes, out_modes, ranks, core_dims = geom
    x = jax.ShapeDtypeStruct((tokens, math.prod(in_modes)), jnp.float32,
                             sharding=sharding)
    cores = [jax.ShapeDtypeStruct(d, jnp.float32, sharding=sharding)
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


@pytest.mark.parametrize("phase,tokens", [("prefill", 512), ("decode", 4)])
@pytest.mark.parametrize("layer", LAYERS)
def test_serving_plan_layer_compiles(one_chip, plans, geometry, phase,
                                     tokens, layer):
    lp = next(lp for lp in plans[phase].layers if lp.name == layer)
    in_modes, out_modes, ranks, x, cores = _layer_args(
        geometry[layer], tokens, one_chip)
    compiled = _compile(
        lambda x, cs: planned_tt_linear(lp, x, cs, in_modes, out_modes,
                                        ranks, interpret=False), x, cores)
    if lp.backend != "jnp":
        names = _kernels(compiled)
        fused = {"fused_path"} if fusion.has_fused(lp.segments) else set()
        assert names - fused == {lp.backend} and names >= fused


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
