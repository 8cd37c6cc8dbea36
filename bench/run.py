#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one cell: set-up (the DSE's plan search, weights made on the
device from the seed, every shape the cell's traffic uses compiled), a
window of ``--seconds`` through the program's own serving scheduler or
train step, then the correctness comparison with the plain reference.
The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics read from a profiler trace of
the window), ``device``, with ``--trace 1`` a ``breakdown`` of device
time and idle gaps, and last ``checks``: each number compared beside its
limit, which also end standard error.

A JAX that finds no TPU, or fewer chips than the cell asks for, exits 1
without a result line; a checkout without the program exits 2.  JAX's
persistent compilation cache is the program's own
(``repro.launch.compile_cache``): ``JAX_COMPILATION_CACHE_DIR`` if set,
else ``.jax_cache`` inside the checkout.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py``: a per-layer metric's reader, or a
    configuration's plain reference."""
    path = os.path.join(BENCH, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} module {name!r} at {path}")
    mod_name = f"bench_{kind}_{name.replace('.', '_')}"
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


_COMPILES: list = []


def _count_compiles() -> list:
    """A one-element list counting backend compilations from now on."""
    if not _COMPILES:
        from jax import monitoring

        _COMPILES.append(0)

        def listen(name, secs, **kw):
            if name == "/jax/core/compile/backend_compile_duration":
                _COMPILES[0] += 1

        monitoring.register_event_duration_secs_listener(listen)
    return _COMPILES


def cell_driver(cell):
    from benchlib.serve import ServeCell
    from benchlib.train import TrainCell

    kinds = {"serve": ServeCell, "train": TrainCell}
    kind = cell.traffic["kind"]
    if kind not in kinds:
        raise ValueError(f"unknown traffic kind {kind!r}; have {sorted(kinds)}")
    return kinds[kind]


def measure(cell, seed: int, seconds: float, trace: bool, *,
            peak=None, controls=()) -> dict:
    """Set up, run the window and check one cell; the result object, and
    under ``"readings"`` the numbers compared (with ``controls``, also
    each control's, for setting limits)."""
    import jax

    from benchlib import trace as trace_mod
    from benchlib import work

    compiles = _count_compiles()
    dev = jax.devices()[0]
    peak = peak or work.peaks(dev.device_kind)
    ref = load_module("configs", cell.config["reference"])
    driver = cell_driver(cell)(cell, seed, ref)
    counter = work.Counter(
        work.projections(driver.init_shapes,
                         cell.config["tt_factorization"]["d"]),
        n_layers=driver.cfg.n_layers, n_heads=driver.cfg.n_heads,
        head_dim=driver.cfg.hd,
        act_bytes=jax.numpy.dtype(driver.cfg.dtype).itemsize,
        param_bytes=jax.numpy.dtype(driver.cfg.dtype).itemsize, peak=peak)
    tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        jax.profiler.start_trace(tdir)
    n_compiles = compiles[0]
    w = driver.window(seconds, counter, trace)
    compiled_in_window = compiles[0] - n_compiles
    if trace:
        jax.profiler.stop_trace()
    setup_s = w["t_start"] - T_PROCESS
    used = jax.devices()[:cell.chips]
    stats = [d.memory_stats() or {} for d in used]
    mem = max((s.get("peak_bytes_in_use", 0) for s in stats), default=0)
    reduced = None
    if trace:
        try:
            reduced = trace_mod.reduce(trace_mod.load_events(tdir))
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
    driver.release()
    readings, limits, failed = driver.check(controls)
    run = types.SimpleNamespace(kind=cell.traffic["kind"], window=w,
                                counter=counter, peak=peak, trace=reduced)
    if trace:
        metrics = {}
        for m in cell.per_layer:
            v = load_module("metrics", m["name"]).read(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = dict(w, setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    checks = {k: {"value": readings[k], "limit": limits[k]} for k in limits}
    correct = all(c["value"] is not None and c["value"] <= c["limit"]
                  for c in checks.values())
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem}
    out = {"correct": correct, "attempted": w["attempted"],
           "failed": failed, "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = {
            "device_ops": trace_mod.top(reduced["ops"]),
            "idle_gaps": trace_mod.top(reduced["gaps"])}
    out["checks"] = checks
    return {"result": out, "readings": readings, "window": w,
            "setup_s": setup_s, "compiled_in_window": compiled_in_window}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchlib import spec

    try:
        cell = spec.load_cell(args.workload)
    except spec.SpecError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"error: no program under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    import jax

    if jax.default_backend() != "tpu":
        print(f"error: JAX finds no TPU (backend {jax.default_backend()!r})",
              file=sys.stderr)
        return 1
    if len(jax.devices()) < cell.chips:
        print(f"error: {cell.chips} chips needed, {len(jax.devices())} found",
              file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    r = measure(cell, args.seed, args.seconds, bool(args.trace))
    print(f"set-up {r['setup_s']!r} s, window {r['window']['window_s']!r} s, "
          f"{r['compiled_in_window']} compilations in the window",
          file=sys.stderr)
    for name, c in r["result"]["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(r["result"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
