"""CPU rehearsal of both cells at smoke sizes, and the refusals of
``bench/run.py`` off the chip."""
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from bench import harness
from bench.tests import smoke


@pytest.mark.parametrize("family", ["llama", "mamba2"])
@pytest.mark.parametrize("trace", [False, True])
def test_rehearsal_prints_no_device_metric(family, trace):
    out = harness.run_cell(smoke.cell(family), 2**31 + 5, 2.0, trace,
                           t_start=time.perf_counter(), on_chip=False,
                           log=lambda *_: None)
    assert out["device"]["platform"] == "cpu"
    assert out["metrics"] == {}
    assert "busy_s" not in out["device"] and "breakdown" not in out
    assert out["attempted"] == 12 and out["failed"] == 0
    assert out["correct"]
    assert list(out)[-1] == "checks"


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "mamba2-780m.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_without_an_accelerator():
    r = _run_py(harness.ROOT)
    assert r.returncode != 0
    assert "{" not in r.stdout


def test_run_refuses_without_the_program(tmp_path):
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(harness.ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run_py(tmp_path)
    assert r.returncode != 0
    assert "{" not in r.stdout


def test_every_metric_has_a_reader():
    spec = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(harness.reader(m["name"]))
