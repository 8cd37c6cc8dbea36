"""Fused-segment execution vs the per-step spill route, wall-clock.

For representative ``tt-lm-100m`` serving shapes (a prefill-sized and a
decode-sized token batch), this benchmark times the per-step ``tt_gemm``
route (one ``pallas_call`` per contraction step, every intermediate
round-tripping HBM) against the fusion-segmented route (chain runs
executed inside one ``pallas_call`` with fp32 VMEM-resident
intermediates), and checks the two routes agree bit-for-bit.

Interpret-mode wall-clock on CPU measures Python-level kernel-body
evaluation plus per-call dispatch — the launch-overhead component the
fused path amortizes is real on every backend; the analytic fused cost
model (``core/cost_table.fused_cost_tables``) carries the HBM-traffic
story.

  PYTHONPATH=src python -m benchmarks.bench_fused_exec
"""

from __future__ import annotations

import numpy as np

from repro.configs import get_config
from repro.core import fusion
from repro.core.paths import find_topk_paths
from repro.kernels import ops
from repro.tune.measure import (
    measure_fused,
    measure_per_step,
    synthesize_network_tensors,
)

from .common import emit

#: (phase, tokens) serving shapes; prefill streams a whole prompt
#: bucket, decode a small slot batch
SHAPES = [("prefill", 256), ("decode", 8)]

VMEM_BUDGET = 8 * 2**20


def _layer_pick(named):
    """One attention + one MLP projection (first of each family)."""
    picked, seen = [], set()
    for name, tn in named:
        fam = name.split(".")[0].split("[")[0]
        if fam not in seen:
            seen.add(fam)
            picked.append((name, tn))
    return picked[:2]


def _routes_bit_identical(tn, steps, segments, block_tokens) -> bool:
    """Execute both routes once on the same tensors, compare bits."""
    tensors = synthesize_network_tensors(tn)
    contract = ops.gemm_contract(interpret=True)

    def seq_step(w, i, j, fn):
        (ea, ta), (eb, tb) = w[i], w[j]
        shared = [x for x in ea if x in eb]
        val = fn(ta, tb, ea, eb, shared)
        ec = tuple(x for x in ea if x not in shared) + tuple(
            x for x in eb if x not in shared)
        w = [q for t, q in enumerate(w) if t not in (i, j)]
        w.append((ec, val))
        return w

    def per_step_fn(ta, tb, ea, eb, shared):
        return contract(ta, tb, (tuple(ea.index(x) for x in shared),
                                 tuple(eb.index(x) for x in shared)))

    plain = [(n.edges, tensors[n.name]) for n in tn.nodes]
    for i, j in steps:
        plain = seq_step(plain, i, j, per_step_fn)
    ec_p, val_p = plain[-1]

    seg = [(n.edges, tensors[n.name]) for n in tn.nodes]
    for s, e in segments:
        if e - s >= 2:
            ec, val = ops.fused_segment(seg, steps[s:e],
                                        block_tokens=block_tokens,
                                        interpret=True)
            for i, j in steps[s:e]:
                seg = [w for t, w in enumerate(seg) if t not in (i, j)]
                seg.append(None)
            seg[-1] = (ec, val)
        else:
            seg = seq_step(seg, *steps[s], per_step_fn)
    ec_s, val_s = seg[-1]

    a, b = np.asarray(val_s), np.asarray(val_p)
    if ec_s != ec_p:
        b = np.transpose(b, [ec_p.index(x) for x in ec_s])
    return bool(np.array_equal(a.view(np.uint32), b.view(np.uint32)))


def _bench_one(phase: str, tokens: int, name: str, tn) -> dict:
    steps = tuple(tuple(s) for s in find_topk_paths(tn, k=4)[0].steps)
    bt = ops.token_block(256, tokens)
    segs = fusion.segment_path(tn, steps, block_tokens=bt,
                               budget_bytes=VMEM_BUDGET)
    per_step_s = measure_per_step(tn, steps, interpret=True)
    fused_s = measure_fused(tn, steps, segs, bt, interpret=True)
    return {
        "phase": phase,
        "layer": name,
        "tokens": tokens,
        "n_steps": len(steps),
        "n_segments": len(segs),
        "n_fused_runs": sum(1 for s, e in segs if e - s >= 2),
        "per_step_ms": per_step_s * 1e3,
        "fused_ms": fused_s * 1e3,
        "speedup": per_step_s / fused_s if fused_s else float("nan"),
        "bit_identical": _routes_bit_identical(tn, steps, segs, bt),
    }


def run() -> list[dict]:
    from repro.dse_cli import model_dse_layers

    cfg = get_config("tt-lm-100m", tt=True, smoke=False)
    rows = []
    for phase, tokens in SHAPES:
        named = model_dse_layers(cfg, tokens=tokens)
        for name, tn in _layer_pick(named):
            rows.append(_bench_one(phase, tokens, name, tn))
    emit("BENCH_fused", rows)
    ok = all(r["bit_identical"] for r in rows)
    best = max(r["speedup"] for r in rows)
    print(f"# fused vs per-step: best speedup {best:.2f}x, "
          f"bit-identical: {ok}")
    return rows


if __name__ == "__main__":
    run()
