"""A cell of ``BENCHMARK.json`` and the data files it names.

A cell is ``<config>.<traffic>``.  Everything that belongs to one
configuration, one traffic mix or one cell sits in a file of its own,
found by name:

  bench/configs/<config>.json    sizes, precision, source, what was cut
  bench/traffic/<traffic>.json   the mix: kind, lengths, lanes, plan shapes
  bench/cells/<cell>.json        the limits of the correctness comparison
  bench/metrics/<metric>.py      one reader per per-layer metric

So a later change adds a cell, a configuration, a mix or a metric by
adding files and entries, never by editing one that is there.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Optional

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


class SpecError(Exception):
    """A cell, or a file it names, is missing or malformed."""


def _load(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing file {os.path.relpath(path, ROOT)}") from None


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    #: end-to-end metric entries of BENCHMARK.json this cell reports
    end_to_end: tuple
    #: per-layer metric entries of BENCHMARK.json this cell reports
    per_layer: tuple


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, benchmark: Optional[dict] = None) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` (or of ``benchmark``)."""
    if benchmark is None:
        benchmark = _load(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in benchmark["workloads"]}
    if name not in cells:
        raise SpecError(f"unknown workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in benchmark["configs"]}
    config = _load(os.path.join(ROOT, configs[w["config"]]["file"]))
    traffic = _load(os.path.join(BENCH, "traffic", f"{w['traffic']}.json"))
    limits = _load(os.path.join(BENCH, "cells", f"{name}.json"))
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        limits=limits,
        end_to_end=tuple(m for m in benchmark["end_to_end"]
                         if _applies(m, name)),
        per_layer=tuple(m for m in benchmark["per_layer"]
                        if _applies(m, name)))


def program_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file.

    Every size the file states must equal the program's: the file holds
    the configuration as it is run, and a program change that moves a
    size fails here instead of measuring another model.  ``changed``
    names the fields the configuration sets apart from the program's
    preset (each also listed in ``reduced``), such as the type it is
    served in.
    """
    from repro.configs import get_config

    cfg = get_config(config["arch"], tt=bool(config["tt"]),
                     smoke=bool(config.get("smoke", False)))
    changed = config.get("changed", {})
    unlisted = sorted(set(changed) - set(config.get("reduced", ())))
    if unlisted:
        raise SpecError(f"configuration {config['name']!r} changes "
                        f"{unlisted} without listing them in reduced")
    if changed:
        cfg = cfg.with_(**changed)
    wrong = {k: (v, getattr(cfg, k)) for k, v in config["sizes"].items()
             if getattr(cfg, k) != v}
    tt = config.get("tt_factorization")
    if tt is not None:
        for k, v in tt.items():
            if getattr(cfg.tt, k) != v:
                wrong[f"tt.{k}"] = (v, getattr(cfg.tt, k))
    if wrong:
        raise SpecError(
            f"configuration {config['name']!r} differs from the program's "
            "(file, program): " + ", ".join(
                f"{k}={a!r}/{b!r}" for k, (a, b) in sorted(wrong.items())))
    return cfg
