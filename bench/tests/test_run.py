"""The command's refusals: no chip, no program, no such cell, a
configuration that changes the preset unlisted."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchlib import spec
from conftest import BENCH, ROOT
from helpers import DATA


def _run(cwd, *args, env=None):
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "bench", "run.py"), *args],
        cwd=cwd, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu", **(env or {})))


ARGS = ("--workload", "tt-lm-100m-bf16.serve-chat", "--seed", "5000000001",
        "--seconds", "1", "--trace", "0")


def test_no_tpu_exits_without_a_result():
    r = _run(ROOT, *ARGS)
    assert r.returncode == 1
    assert r.stdout == ""
    assert "no TPU" in r.stderr


def test_benchmark_files_alone_exit_without_a_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(str(tmp_path), *ARGS)
    assert r.returncode == 2
    assert r.stdout == ""


def test_unknown_workload_exits_without_a_result():
    r = _run(ROOT, "--workload", "no.such", "--seed", "1", "--seconds", "1")
    assert r.returncode == 2
    assert r.stdout == ""


def test_a_change_from_the_preset_must_be_listed_in_reduced():
    with open(os.path.join(DATA, "tt-lm-smoke.json")) as f:
        conf = json.load(f)
    conf["changed"] = {"dtype": "bfloat16"}
    conf["sizes"] = dict(conf["sizes"], dtype="bfloat16")
    with pytest.raises(spec.SpecError, match="without listing"):
        spec.program_config(conf)
    conf["reduced"] = conf["reduced"] + ["dtype"]
    assert spec.program_config(conf).dtype == "bfloat16"
