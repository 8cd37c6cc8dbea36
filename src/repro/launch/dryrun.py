"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this proves the distribution config is coherent (sharding
propagates, collectives legal, memory fits) and extracts the roofline
inputs:

  * ``memory_analysis()``  — per-device arg/temp/peak bytes (the full
    scanned model: its memory report is exact),
  * ``cost_analysis()``    — per-device FLOPs / bytes.  XLA counts a scan
    body ONCE, so per-cell totals come from unrolled L=1/L=2 (and
    family-specific) variants extrapolated linearly in depth — exact for
    homogeneous stacks (see ``_cost_variants``),
  * collective bytes       — parsed from the compiled HLO (all-gather /
    all-reduce / reduce-scatter / all-to-all / collective-permute output
    shapes), same depth extrapolation.

Results land incrementally in ``results/dryrun/*.json`` — re-runs skip
existing cells unless ``--force``.

Usage:
  python -m repro.launch.dryrun --arch all --shape all            # single-pod 16x16
  python -m repro.launch.dryrun --arch all --shape all --multi-pod  # 2x16x16
  REPRO_DRYRUN_DEVICES=8 python -m repro.launch.dryrun --test-mesh ...  # CI
"""

import argparse
import json
import math
import os
import re
import time
import traceback
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_config
from repro.models import SHAPES, api, input_specs, shape_applicable
from repro.models.config import ModelConfig, ShapeConfig
from repro.launch.mesh import (
    batch_shardings,
    cache_shardings,
    make_production_mesh,
    make_rules,
    make_test_mesh,
    param_shardings,
    replicated,
)
from repro.launch.steps import make_decode_step, make_prefill_step, make_train_step
from repro.optim import adamw_init
from repro.sharding import use_rules

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}
_SHAPE_RE = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")


def collective_bytes(hlo_text: str) -> dict[str, float]:
    """Per-device bytes moved by each collective kind (output-shape sizes).

    HLO lines look like ``%ag = bf16[8,128]{1,0} all-gather(%x)`` or
    ``(f32[..], f32[..]) all-reduce(..)``; we sum the result-shape bytes of
    every collective op (start/done pairs counted once via -start).
    """
    out: dict[str, float] = {k: 0.0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if "=" not in stripped:
            continue
        lhs, rhs = stripped.split("=", 1)
        rhs = rhs.strip()
        m = re.match(r"^(?:\([^)]*\)|[a-z]+\d*\[[\d,]*\](?:\{[^}]*\})?)\s+([a-z0-9-]+)",
                     rhs)
        if not m:
            continue
        op = m.group(1)
        base = op.removesuffix("-start").removesuffix("-done")
        if base not in _COLLECTIVES:
            continue
        if op.endswith("-done"):
            continue  # counted at -start
        header = rhs[: m.start(1)]
        size = 0.0
        for dt, dims in _SHAPE_RE.findall(header):
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            size += n * _DTYPE_BYTES[dt]
        out[base] += size
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


def _jsonable(x):
    if isinstance(x, (int, float, str, bool)) or x is None:
        return x
    if isinstance(x, dict):
        return {k: _jsonable(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_jsonable(v) for v in x]
    return float(x)


# ---------------------------------------------------------------------------
# lowering one step kind
# ---------------------------------------------------------------------------

def lower_step(cfg: ModelConfig, shape: ShapeConfig, mesh, rules):
    """Returns the lowered computation for the cell's step kind."""
    m = api(cfg)
    specs = input_specs(cfg, shape)
    p_shapes = jax.eval_shape(m.init_params, jax.random.PRNGKey(0))
    p_sh = param_shardings(p_shapes, mesh)

    if shape.step == "train":
        step = make_train_step(cfg)
        opt_shapes = jax.eval_shape(adamw_init, p_shapes)
        opt_sh = param_shardings(opt_shapes, mesh)
        b_sh = batch_shardings(specs, rules)
        fn = jax.jit(
            step,
            in_shardings=(p_sh, opt_sh, b_sh),
            out_shardings=(p_sh, opt_sh, replicated({"loss": 0, "grad_norm": 0}, mesh)),
        )
        return fn.lower(p_shapes, opt_shapes, specs)

    if shape.step == "prefill":
        extra = (cfg.n_frontend_tokens or 256) if cfg.family == "vlm" else 0
        max_seq = shape.seq_len + extra
        step = make_prefill_step(cfg, max_seq=max_seq)
        b_sh = batch_shardings(specs, rules)
        cache_shapes = jax.eval_shape(
            lambda: api(cfg).init_caches(shape.global_batch, max_seq))
        c_sh = cache_shardings(cfg, cache_shapes, rules)
        fn = jax.jit(step, in_shardings=(p_sh, b_sh), out_shardings=(None, c_sh))
        return fn.lower(p_shapes, specs)

    if shape.step == "decode":
        step = make_decode_step(cfg)
        c_sh = cache_shardings(cfg, specs["caches"], rules)
        t_sh = batch_shardings(specs["token"], rules)
        pos_sh = replicated(specs["cache_pos"], mesh)
        fn = jax.jit(
            step,
            in_shardings=(p_sh, t_sh, c_sh, pos_sh),
            out_shardings=(None, c_sh),
        )
        return fn.lower(p_shapes, specs["token"], specs["caches"],
                        specs["cache_pos"])

    raise ValueError(shape.step)


# ---------------------------------------------------------------------------
# depth-extrapolated cost variants
# ---------------------------------------------------------------------------

def _cost_variants(cfg: ModelConfig, shape: ShapeConfig
                   ) -> list[tuple[str, ModelConfig, float]]:
    """(label, unrolled variant, weight) triples; the total cost is the
    weighted sum after solving the per-layer deltas (see extrapolate).

    Variants are SCAN-FREE everywhere (unrolled layers, q_chunk = seq,
    no loss chunking): XLA counts every op exactly once, so the counts
    are exact — these variants are never executed, so their huge
    intermediate shapes cost nothing.  The full scanned model (chunked,
    remat'd) is what memory_analysis reports on.
    """
    base = dict(scan_layers=False, q_chunk=max(shape.seq_len, cfg.q_chunk),
                loss_chunk=0)
    if cfg.family == "hybrid":
        g = cfg.attn_every
        return [
            ("m1", cfg.with_(n_layers=1, attn_every=0, **base), 0.0),
            ("m2", cfg.with_(n_layers=2, attn_every=0, **base), 0.0),
            ("g1", cfg.with_(n_layers=g, attn_every=g, **base), 0.0),
        ]
    if cfg.family == "encdec":
        return [
            ("e1d1", cfg.with_(n_layers=1, encoder_layers=1, **base), 0.0),
            ("e2d1", cfg.with_(n_layers=1, encoder_layers=2, **base), 0.0),
            ("e1d2", cfg.with_(n_layers=2, encoder_layers=1, **base), 0.0),
        ]
    return [
        ("l1", cfg.with_(n_layers=1, **base), 0.0),
        ("l2", cfg.with_(n_layers=2, **base), 0.0),
    ]


def extrapolate(cfg: ModelConfig, values: dict[str, float]) -> float:
    """Combine variant costs into the full-depth estimate."""
    if cfg.family == "hybrid":
        mamba = values["m2"] - values["m1"]
        base = values["m1"] - mamba
        g = cfg.attn_every
        attn = values["g1"] - base - g * mamba
        n_groups = cfg.n_layers // g
        return base + cfg.n_layers * mamba + n_groups * attn
    if cfg.family == "encdec":
        enc = values["e2d1"] - values["e1d1"]
        dec = values["e1d2"] - values["e1d1"]
        base = values["e1d1"] - enc - dec
        return base + cfg.encoder_layers * enc + cfg.n_layers * dec
    per = values["l2"] - values["l1"]
    base = values["l1"] - per
    return base + cfg.n_layers * per


# ---------------------------------------------------------------------------
# cell runner
# ---------------------------------------------------------------------------

def _cost_analysis(compiled) -> dict:
    return compiled.cost_analysis() or {}


def run_cell(
    arch: str,
    shape_name: str,
    *,
    multi_pod: bool = False,
    tt: bool = True,
    test_mesh: bool = False,
    with_cost: bool = True,
    out_dir: str = RESULTS_DIR,
    force: bool = False,
    smoke: bool = False,
) -> dict:
    mesh_tag = ("test" if test_mesh else "") + ("multipod" if multi_pod else "pod")
    tt_tag = "tt" if tt else "dense"
    cell_id = f"{arch}_{shape_name}_{mesh_tag}_{tt_tag}"
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, cell_id + ".json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)

    cfg = get_config(arch, tt=tt, smoke=smoke)
    shape = SHAPES[shape_name]
    result: dict[str, Any] = {
        "cell": cell_id, "arch": arch, "shape": shape_name,
        "multi_pod": multi_pod, "tt": tt, "step": shape.step,
    }
    ok, reason = shape_applicable(cfg, shape)
    if not ok:
        result["status"] = "skipped"
        result["reason"] = reason
        _write(path, result)
        return result

    mesh = make_test_mesh(multi_pod=multi_pod) if test_mesh \
        else make_production_mesh(multi_pod=multi_pod)
    rules = make_rules(cfg, shape, mesh)
    result["mesh"] = {k: int(v) for k, v in mesh.shape.items()}
    result["n_devices"] = int(math.prod(mesh.shape.values()))
    result["sp_enabled"] = rules.seq_axis is not None

    try:
        t0 = time.time()
        with use_rules(rules):
            lowered = lower_step(cfg, shape, mesh, rules)
            compiled = lowered.compile()
        result["compile_s"] = round(time.time() - t0, 1)

        ma = compiled.memory_analysis()
        result["memory"] = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "peak_bytes": int(getattr(ma, "peak_memory_in_bytes", 0)),
        }
        ca = _cost_analysis(compiled)
        result["scanned_cost"] = {
            "flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
        }
        result["scanned_collectives"] = collective_bytes(compiled.as_text())

        if with_cost and not multi_pod:
            variants = _cost_variants(cfg, shape)
            vals_f: dict[str, float] = {}
            vals_b: dict[str, float] = {}
            vals_c: dict[str, float] = {}
            for label, vcfg, _ in variants:
                with use_rules(rules):
                    vlow = lower_step(vcfg, shape, mesh, rules)
                    vcomp = vlow.compile()
                vca = _cost_analysis(vcomp)
                vals_f[label] = float(vca.get("flops", 0.0))
                vals_b[label] = float(vca.get("bytes accessed", 0.0))
                vals_c[label] = collective_bytes(vcomp.as_text())["total"]
            result["variant_flops"] = vals_f
            result["cost"] = {
                "flops_per_device": extrapolate(cfg, vals_f),
                "bytes_per_device": extrapolate(cfg, vals_b),
                "collective_bytes_per_device": extrapolate(cfg, vals_c),
            }
        result["status"] = "ok"
    except Exception as e:  # record the failure, keep sweeping
        result["status"] = "error"
        result["error"] = f"{type(e).__name__}: {e}"
        result["traceback"] = traceback.format_exc()[-4000:]

    _write(path, result)
    return result


def _write(path: str, result: dict) -> None:
    with open(path, "w") as f:
        json.dump(_jsonable(result), f, indent=1)


def main() -> None:
    # a forced host-device count: set before the first device query,
    # which initializes the CPU backend
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count="
        + os.environ.get("REPRO_DRYRUN_DEVICES", "512"))
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--dense", action="store_true", help="dense baseline (no TT)")
    ap.add_argument("--test-mesh", action="store_true")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--no-cost", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out-dir", default=RESULTS_DIR)
    args = ap.parse_args()

    archs = list(ARCH_IDS) if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    for arch in archs:
        for shape in shapes:
            t0 = time.time()
            r = run_cell(
                arch, shape,
                multi_pod=args.multi_pod,
                tt=not args.dense,
                test_mesh=args.test_mesh,
                with_cost=not args.no_cost,
                out_dir=args.out_dir,
                force=args.force,
                smoke=args.smoke,
            )
            status = r.get("status")
            extra = ""
            if status == "ok":
                mem = r["memory"]["peak_bytes"] or (
                    r["memory"]["argument_bytes"] + r["memory"]["temp_bytes"])
                extra = f"peak={mem/2**30:.2f}GiB"
                if "cost" in r:
                    extra += f" flops/dev={r['cost']['flops_per_device']:.3e}"
            elif status == "error":
                extra = r["error"][:120]
            elif status == "skipped":
                extra = r["reason"]
            print(f"[{time.time()-t0:7.1f}s] {r['cell']:60s} {status:8s} {extra}",
                  flush=True)


if __name__ == "__main__":
    main()
