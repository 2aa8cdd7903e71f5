"""Each cell end to end at its rehearsal size on the CPU (Pallas kernels
interpreted): sound runs come out correct, and every fault the cell can
have, and the control one precision below the configuration's, come out
not correct. ``--rehearse`` skips the look for a chip and prints no
metric."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
CELLS = ["gauss64-128d.interact-f128", "gauss64-128d.build-stream",
         "qwen2-0.5b.chat-open"]
# the faults each cell's timed path can have: a step that returns its state
# unchanged; the mean taken over half the neighbours; an answer or a served
# token altered where it is produced
FAULTS = {"gauss64-128d.interact-f128": ["unchanged", "half_neighbours",
                                         "altered"],
          "gauss64-128d.build-stream": ["unchanged", "half_neighbours",
                                        "altered"],
          "qwen2-0.5b.chat-open": ["unchanged", "altered"]}
# the control one precision below the configuration's, the number it has
# to fail, and by what factor at least at the rehearsal size: the tiny
# decoder's float8 control reads 0.25-0.47 against the limit of 0.2 set at
# full size (where it reads 0.42 and more)
CONTROLS = {"gauss64-128d.interact-f128": ("bf16", "spmv_err", 3.0),
            "gauss64-128d.build-stream": ("bf16", "spmv_err", 3.0),
            "qwen2-0.5b.chat-open": ("fp8", "logit_gap", 1.0)}


def run_cell(cell, tmp_path, *extra, seed=2**31 + 7):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cell, "--seed",
         str(seed), "--seconds", "1", "--trace", "0", "--rehearse", *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell, tmp_path):
    res = run_cell(cell, tmp_path)
    assert res["correct"], res["checks"]
    assert res["metrics"] == {}
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert res["checks"]["window_compiles"]["value"] == 0


@pytest.mark.parametrize("cell,fault", [(c, f) for c in CELLS
                                        for f in FAULTS[c]])
def test_fault_is_caught(cell, fault, tmp_path):
    res = run_cell(cell, tmp_path, "--fault", fault)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_caught(cell, tmp_path):
    control, number, factor = CONTROLS[cell]
    res = run_cell(cell, tmp_path, "--control", control)
    assert not res["correct"], res["checks"]
    assert res["checks"][number]["value"] > factor * \
        res["checks"][number]["limit"]


def test_no_chip_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert "{" not in out.stdout


def test_without_the_program_no_result(tmp_path):
    """A directory holding only BENCHMARK.json and bench/ is no run."""
    import shutil
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1", "--trace", "0", "--rehearse"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode != 0
    assert "system under test is missing" in out.stderr
    assert out.stdout.strip() == ""


@pytest.mark.parametrize("probe,extra", [("limits", ["--seeds", "5,6"]),
                                         ("sweep", ["--rates", "2,6"])])
def test_probe_at_rehearsal_size(probe, extra, tmp_path):
    """The probes that set the chat cell's rate and limit: one line per
    window, with the numbers the cell compares or its tail."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    out = subprocess.run(
        [sys.executable, "bench/tools/probe.py", probe, "--workload",
         "qwen2-0.5b.chat-open", "--seconds", "1", "--rehearse", *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = [json.loads(x) for x in out.stdout.splitlines()
             if x.startswith("{")]
    assert len(lines) == 2
    want = "logit_gap.fp8" if probe == "limits" else "past_close_s"
    assert all(want in x and x["failed"] == 0 for x in lines)
