"""Smoke-size cells for the benchmark's CPU tests."""

import json
import os

from benchlib.spec import Cell

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
#: stands in for a chip's peaks on the CPU (no device metric is read)
PEAK = {"flops": 1e12, "hbm_bytes_per_s": 1e11}


def smoke_cell(config: str, traffic: str, limits: dict) -> Cell:
    with open(os.path.join(DATA, f"{config}.json")) as f:
        conf = json.load(f)
    with open(os.path.join(DATA, f"{traffic}.json")) as f:
        tr = json.load(f)
    return Cell(name=f"{config}.{traffic}", chips=1, config=conf, traffic=tr,
                limits={"limits": limits}, end_to_end=(), per_layer=())
