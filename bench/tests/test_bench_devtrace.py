"""The reduction from a profiler trace to device time per layer.

``testdata/resnet50_b1.xplane.pb.gz`` is a trace of three batch-1 requests
of the fused ResNet-50 forward on a TPU v5 lite, recorded with the
benchmark's ``request.dispatch``/``request.wait`` annotations, and
``resnet50_b1.hlo.txt.gz`` the ENTRY computation of the compiled program that
ran (each Mosaic kernel's body cut down to its kernel's name).
"""
import gzip
import os
import sys
from types import SimpleNamespace

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
from bench import devtrace, harness, roofline  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "testdata")


@pytest.fixture(scope="module")
def hlo():
    with gzip.open(os.path.join(DATA, "resnet50_b1.hlo.txt.gz"), "rt") as f:
        return f.read()


@pytest.fixture(scope="module")
def reduced(hlo, tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    with gzip.open(os.path.join(DATA, "resnet50_b1.xplane.pb.gz"), "rb") as f:
        path.write_bytes(f.read())
    return devtrace.reduce_trace(devtrace.load_xspace(str(path)), hlo,
                                 harness.ANNOTATIONS)


def test_every_kernel_of_the_forward_is_attributed(hlo):
    ops = devtrace.parse_hlo(hlo)
    cats = devtrace.classify(ops)
    kernels = [(cats[n], op.kernel) for n, op in ops.items() if op.kernel]
    assert sorted(set(kernels)) == [
        ("conv1x1", "_mm_act_stationary_kernel"),
        ("conv1x1", "_mm_weight_stationary_kernel"),
        ("conv3x3", "_conv2d_kernel"),
        ("stem", "_mm_act_stationary_kernel")]
    count = {c: sum(1 for k, _ in kernels if k == c) for c in devtrace.CATEGORIES}
    assert count == {"conv3x3": 16, "conv1x1": 36, "stem": 1, "glue": 0}
    # 7 conv5 1x1s have 49 rows at batch 1: weight-stationary
    assert sum(1 for _, k in kernels if k == "_mm_weight_stationary_kernel") == 7


def test_attribution_follows_op_name_and_the_graph(hlo):
    ops = devtrace.parse_hlo(hlo)
    cats = devtrace.classify(ops)
    # the stem's im2col: gathers and the concatenation under jit(_conv2d_jit)
    assert cats["concatenate.49"] == "stem"
    assert ops["concatenate.49"].op_name.endswith("jit(_conv2d_jit)/concatenate")
    # the model's own ops: max pool, mean, fc
    assert cats["reduce_window_max.7"] == "glue"
    assert cats["multiply_reduce_fusion"] == "glue"
    # a layout copy the compiler made of a 1x1's weights feeds that 1x1
    assert ops["copy.10"].op_name == r"params[\'conv2_b1\'][\'c1\']"
    assert cats["copy.10"] == "conv1x1"
    # the input's layout copy feeds the stem's pad
    assert cats["copy.8"] == "stem"
    # a pad under jit(_conv2d_jit) goes to the kernel it feeds
    pads = [n for n, op in ops.items() if op.opcode == "pad"
            and "jit(_conv2d_jit)" in (op.op_name or "")]
    assert pads and {cats[n] for n in pads} <= {"conv3x3", "stem"}
    assert "conv3x3" in {cats[n] for n in pads}


def test_busy_union_idle_share_and_forwards(reduced):
    r = reduced
    assert r.chips == 1 and r.modules == 3 and r.unknown_ops == 0
    assert 0 < r.busy_s < r.window_s
    # no two ops overlap on the core, so busy is the sum of op time
    assert r.busy_s == pytest.approx(sum(r.category_s.values()), rel=1e-9)
    assert r.window_s == pytest.approx(0.01516828, rel=1e-6)
    assert 100 * (1 - r.busy_s / r.window_s) == pytest.approx(21.2531, abs=1e-3)
    # idle time and busy time fill the window
    assert r.busy_s + sum(r.gaps_s.values()) == pytest.approx(r.window_s, rel=1e-9)
    assert set(r.gaps_s) <= {"request.dispatch", "request.wait", "host"}
    per_forward_ms = {k: 1e3 * v / 3 for k, v in r.category_s.items()}
    assert per_forward_ms["stem"] == pytest.approx(3.281, abs=1e-3)
    assert per_forward_ms["conv3x3"] == pytest.approx(0.3587, abs=1e-4)
    assert per_forward_ms["conv1x1"] == pytest.approx(0.3345, abs=1e-4)


def test_breakdown_orders_ops_and_gaps(reduced):
    b = devtrace.breakdown(reduced)
    ops, gaps = b["device_ops"], b["idle_gaps"]
    assert [k for k, _ in ops[:3]] == ["stem", "conv3x3", "conv1x1"]
    assert all(k.startswith("glue:") for k, _ in ops[3:])
    assert len(ops) == 10 and len(gaps) <= 10
    for entries in (ops, gaps):
        secs = [v for _, v in entries]
        assert secs == sorted(secs, reverse=True)


def test_union_merges_overlaps():
    assert devtrace._union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [[0, 3], [5, 9]]


def test_offset_matches_each_forward_to_its_dispatch():
    host = [(0, 10, "request.dispatch"), (10, 50, "request.wait"),
            (55, 65, "request.dispatch"), (65, 100, "request.wait")]
    assert devtrace._offset(host, [8, 63], "request.dispatch") == 2
    assert devtrace._offset(host, [8], "request.dispatch") == 0.0


def test_roofline_share_arithmetic():
    peaks = SimpleNamespace(flops=100.0, hbm=10.0)
    work = [{"kind": "conv3x3", "flops": 200.0, "bytes": 10.0},   # 2 s, flops
            {"kind": "conv3x3", "flops": 100.0, "bytes": 30.0},   # 3 s, bytes
            {"kind": "conv1x1", "flops": 1.0, "bytes": 1.0}]
    assert roofline.least_time(work[0], peaks) == (2.0, "flops")
    assert roofline.least_time(work[1], peaks) == (3.0, "bytes")
    trace = SimpleNamespace(category_s={"conv3x3": 20.0, "conv1x1": 0.0})
    ctx = SimpleNamespace(work=work, peaks=peaks, trace=trace, requests=2)
    assert roofline.share(ctx, "conv3x3") == pytest.approx(50.0)
    assert roofline.share(ctx, "conv1x1") is None   # no device time: silent
    assert roofline.share(ctx, "stem") is None      # no such layer
