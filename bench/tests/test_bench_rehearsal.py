"""A run of one cell rehearsed on the CPU, and the faults its check must catch.

The harness's own functions drive the program's fused forward, with the
kernels in interpret mode, at width 1/16 and 32x32 images in batches of 2:
set-up, the closed-loop window and the comparison of every answer with the
reference.  Then the timed path is broken underneath the same run (half the
batch left out, one answer altered) and replaced by the control, the
reference at three bf16 passes, and ``correct`` must come out false each time.
"""
import os
import shutil
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from bench import control, harness  # noqa: E402
from bench import run as bench_run  # noqa: E402

SEED = 2**31 + 11
WIDTH = 1 / 16


@pytest.fixture(scope="module")
def cell():
    c = harness.load_cell("resnet50_dense.offline")
    c.config = dict(c.config, image_size=32)
    c.traffic = dict(c.traffic, batch=2, pool=2)
    return c


@pytest.fixture(scope="module")
def program(cell):
    return cell.model.program_forward()


def _run(cell, forward):
    result = harness.run_cell(cell, SEED, 0.3, False, time.perf_counter(),
                              devices=jax.devices(), width=WIDTH, forward=forward)
    return bench_run.result_line(result)


def test_a_sound_run_is_correct_and_reports_its_metrics(cell, program):
    line = _run(cell, program)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device",
                          "checks"]
    assert set(line["metrics"]) == {"images_per_s", "setup_s"}
    assert line["device"]["platform"] == "cpu"
    err = line["checks"]["logits_rel_err"]
    assert 0 < err["value"] < err["limit"]


def test_the_control_fails(cell):
    line = _run(cell, control.control_forward(cell))
    assert line["correct"] is False
    err = line["checks"]["logits_rel_err"]
    assert err["value"] > err["limit"]


def test_half_the_batch_left_out_fails(cell, program):
    def half(params, x):
        y = program(params, x)
        h = y.shape[0] // 2
        return jnp.concatenate([y[:h], y[:h]])   # the rest answered by the first half
    line = _run(cell, half)
    assert line["correct"] is False and line["failed"] == line["attempted"]


def test_one_altered_answer_fails(cell, program):
    calls = []

    def altered(params, x):
        y = program(params, x)
        calls.append(1)
        if len(calls) == 5:   # a request inside the window
            y = y.at[1, 7].add(1e-3 * jnp.max(jnp.abs(y)))
        return y
    line = _run(cell, altered)
    assert line["correct"] is False and line["failed"] == 1


def _bench_run(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "resnet50_dense.single_stream",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_run_exits_non_zero_without_a_tpu():
    p = _bench_run(ROOT, {})
    assert p.returncode != 0 and p.stdout == ""
    assert "no TPU" in p.stderr


def test_run_exits_non_zero_with_only_the_benchmarks_files(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _bench_run(tmp_path, {})
    assert p.returncode != 0 and p.stdout == ""
    assert "repro" in p.stderr   # the program under test is not there
