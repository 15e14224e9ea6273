"""The VGG-16 cell: its work, the benchmark's readers on it, and a run
rehearsed on the CPU.

The rehearsal drives the program's fused forward with the kernels in
interpret mode, at width 1/16 and 32x32 images in batches of 2, through the
harness's own set-up, window and check, as ``test_bench_rehearsal.py`` does
for ResNet-50; the control, half a batch left out and one altered answer
must each read ``correct`` false.
"""
import os
import sys
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from bench import control, devtrace, harness  # noqa: E402
from bench import run as bench_run  # noqa: E402

CELL = "vgg16.single_stream"
SEED = 2**31 + 15
WIDTH = 1 / 16


@pytest.fixture(scope="module")
def cell():
    c = harness.load_cell(CELL)
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
    assert set(line["metrics"]) == {"images_per_s", "latency_p95_ms", "setup_s"}
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
        return jnp.concatenate([y[:1], y[:1]])   # the second image answered by the first
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


def _independent_macs(params, size: int) -> int:
    """Multiply-adds per image from the parameter shapes alone: each 3x3 on
    its plane, a 2x2/2 pool after the last conv of each group, then the fcs."""
    macs = 0
    for g in range(1, 6):
        i = 1
        while f"conv{g}_{i}" in params:
            macs += size * size * int(np.prod(params[f"conv{g}_{i}"]["w"].shape))
            i += 1
        size //= 2
    return macs + sum(int(np.prod(params[f"fc{n}"]["w"].shape)) for n in (6, 7, 8))


@pytest.mark.parametrize("batch", [1, 32])
def test_work_equals_an_independent_count_from_the_parameter_shapes(batch):
    c = harness.load_cell(CELL)
    params = jax.eval_shape(lambda k: c.model.build(c.config, k),
                            jax.random.PRNGKey(0))
    layers = c.model.work(c.config, params, batch)
    macs = _independent_macs(params, c.config["image_size"])
    assert sum(layer["flops"] for layer in layers) == 2 * batch * macs
    # Simonyan & Zisserman, configuration D: 15.47e9 multiply-adds per image,
    # 15.35e9 of them in the convs; 138.36e6 parameters
    assert round(macs / 1e7) == 1547
    convs = [x for x in layers if x["name"].startswith("conv")]
    assert round(sum(x["flops"] for x in convs) / 2e7 / batch) == 1535
    n_params = sum(int(np.prod(leaf.shape)) for leaf in jax.tree_util.tree_leaves(params))
    assert round(n_params / 1e4) == 13836
    kinds = [layer["kind"] for layer in layers]
    assert [kinds.count(k) for k in ("stem", "conv3x3", "conv1x1")] == [1, 12, 3]
    fc6 = layers[13]
    assert fc6["name"] == "fc6" and fc6["bytes"] == 4 * (
        batch * (25088 + 4096) + 25088 * 4096 + 4096)


def _ctx(trace_s: dict, requests: int = 40):
    c = harness.load_cell(CELL)
    params = jax.eval_shape(lambda k: c.model.build(c.config, k), jax.random.PRNGKey(0))
    busy = sum(trace_s.values())
    red = devtrace.Reduced(window_s=2.0 * busy, busy_s=busy,
                           category_s=dict(dict.fromkeys(devtrace.CATEGORIES, 0.0),
                                           **trace_s),
                           op_s={}, gaps_s={}, modules=requests, chips=1)
    ctx = SimpleNamespace(trace=red, work=c.model.work(c.config, params, 1),
                          batch=1, chips=1, requests=requests, images_per_s=0.0,
                          flops_per_image=0.0,
                          peaks=harness.peaks_for("TPU v5 lite", "float32"))
    return c, ctx


@pytest.mark.parametrize("metric, kind", [("conv3x3_roofline", "conv3x3"),
                                          ("conv1x1_roofline", "conv1x1"),
                                          ("stem_roofline", "stem")])
def test_the_roofline_readers_read_vgg16_by_kind(metric, kind):
    """Each roofline reader the cell lists reads nothing from an empty trace,
    and with time on its path a share of its kind's least time."""
    c, ctx = _ctx({})
    assert metric in c.readers and c.readers[metric].read(ctx) is None
    c, ctx = _ctx({kind: 1.0})
    assert 0 < c.readers[metric].read(ctx) < 100


def test_conv1x1_roofline_reads_the_classifier_as_a_weight_stream():
    """In VGG-16 only fc6-fc8 run on the 1x1 GEMM path; at batch 1 each is
    bound by its weight bytes, so the reader's share is their bytes over the
    HBM peak, per forward, over the path's device time."""
    c, ctx = _ctx({"conv1x1": 0.04})
    fcs = [x for x in ctx.work if x["kind"] == "conv1x1"]
    assert [x["name"] for x in fcs] == ["fc6", "fc7", "fc8"]
    least = sum(x["bytes"] for x in fcs) / ctx.peaks.hbm
    assert c.readers["conv1x1_roofline"].read(ctx) == pytest.approx(
        100.0 * least * 40 / 0.04)


def test_glue_ms_reads_the_pools_per_forward():
    c, ctx = _ctx({"glue": 0.002, "conv3x3": 0.07})
    assert c.readers["glue_ms"].read(ctx) == pytest.approx(1e3 * 0.002 / 40)
