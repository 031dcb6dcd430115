"""Each cell end to end on the CPU at tiny sizes (``--rehearse-on-cpu``
skips the look for a chip and drives the rest of a run), and the same run
with the timed path broken underneath: ``correct`` has to come out false.
Nothing here is a device number and the rehearsal prints no metric."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import manifest

RUN = [sys.executable, os.path.join(manifest.HERE, "run.py")]
CELLS = [w["name"] for w in manifest.load_manifest()["workloads"]]
ONE_PER_CONFIG = {w["config"]: w["name"]
                  for w in manifest.load_manifest()["workloads"]}


def run(*args, cwd=manifest.ROOT, env=None):
    r = subprocess.run(RUN + list(args), cwd=cwd, capture_output=True,
                       text=True, timeout=300, env=env)
    return r.returncode, r.stdout.strip().splitlines(), r.stderr


def rehearse(cell, *more):
    code, lines, err = run("--workload", cell, "--seed", str(2**31 + 7),
                           "--seconds", "1", "--rehearse-on-cpu", *more)
    assert code == 0, err[-2000:]
    return json.loads(lines[-1]), lines


@pytest.mark.parametrize("trace", ["0", "1"])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses_correct(cell, trace):
    result, lines = rehearse(cell, "--trace", trace)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0 and result["rehearsal"] is True
    assert result["metrics"] == {} and "breakdown" not in result
    assert result["compilations_in_window"] == 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(result["device"])
    assert any(line.startswith("CHECK responses_not_equal") for line in lines)


@pytest.mark.parametrize("control", ["flip_bit", "stale"])
@pytest.mark.parametrize("cell", sorted(ONE_PER_CONFIG.values()))
def test_a_broken_guarantee_comes_out_not_correct(cell, control):
    result, lines = rehearse(cell, "--trace", "0", "--control", control)
    assert result["correct"] is False and result["failed"] > 0
    assert any("NOT HELD" in line for line in lines)


def test_without_a_tpu_there_is_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    code, lines, _ = run("--workload", CELLS[0], "--seed", "1",
                         "--seconds", "1", "--trace", "0", env=env)
    assert code != 0
    assert not any(line.startswith("{") for line in lines)


def test_without_the_program_there_is_no_result(tmp_path):
    shutil.copy(os.path.join(manifest.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(manifest.HERE, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert r.returncode != 0
    assert not any(line.startswith("{") for line in r.stdout.splitlines())
