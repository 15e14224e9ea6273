"""The reduction by named scope (``bench/scopes.py``), and the benchmark's own
readers left as they were.

``testdata/resnet50_b1_scoped.xplane.pb.gz`` is a trace of three batch-1
requests of the fused ResNet-50 forward on a TPU v5 lite, recorded with the
benchmark's annotations after the program named its layers
(``jax.named_scope`` in ``carla_conv``, ``kernels/ops.py`` and
``resnet50_apply``; each ``pallas_call``'s ``name``), and
``resnet50_b1_scoped.hlo.txt.gz`` the ENTRY computation of the compiled
program that ran, each Mosaic kernel's body cut down to its kernel's name
(``bench/layers.py --save``).  The older trace beside them
(``resnet50_b1.*``) was recorded before the program named anything.
"""
import collections
import gzip
import os
import sys
from types import SimpleNamespace

import jax
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
from bench import devtrace, harness, scopes  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "testdata")
FORWARDS = 3


def _load(stem: str, tmp_path_factory):
    with gzip.open(os.path.join(DATA, f"{stem}.hlo.txt.gz"), "rt") as f:
        hlo = f.read()
    path = tmp_path_factory.mktemp(stem) / "t.xplane.pb"
    with gzip.open(os.path.join(DATA, f"{stem}.xplane.pb.gz"), "rb") as f:
        path.write_bytes(f.read())
    return SimpleNamespace(hlo=hlo, xspace=devtrace.load_xspace(str(path)))


@pytest.fixture(scope="module")
def unnamed(tmp_path_factory):
    return _load("resnet50_b1", tmp_path_factory)


@pytest.fixture(scope="module")
def named(tmp_path_factory):
    t = _load("resnet50_b1_scoped", tmp_path_factory)
    t.red = devtrace.reduce_trace(t.xspace, t.hlo, harness.ANNOTATIONS)
    t.scoped = scopes.reduce_scopes(t.xspace, t.hlo)
    return t


@pytest.fixture(scope="module")
def dense_b1():
    cell = harness.load_cell("resnet50_dense.single_stream")
    params = jax.eval_shape(lambda k: cell.model.build(cell.config, k),
                            jax.random.PRNGKey(0))
    cell.work = cell.model.work(cell.config, params, 1)
    cell.peaks = harness.peaks_for("TPU v5 lite", cell.config["dtype"])
    return cell


# -------------------------------------- the benchmark's readers, unchanged
# Each reader on the older trace, as it read before the program named its
# layers (``forward_mfu`` at a fixed 203.27 images/s: it reads no trace).
UNNAMED_READINGS = {
    "device_idle_share": 21.253134831371778,
    "forward_mfu": 0.7961525097674721,
    "conv3x3_roofline": 19.17853354156935,
    "conv1x1_roofline": 47.5961339507463,
    "stem_roofline": 0.1433329794554779,
    "glue_ms": 0.007398666666666665,
}


@pytest.mark.parametrize("metric", list(UNNAMED_READINGS))
def test_benchmark_readers_read_the_unnamed_trace_as_before(metric, unnamed, dense_b1):
    red = devtrace.reduce_trace(unnamed.xspace, unnamed.hlo, harness.ANNOTATIONS)
    ctx = SimpleNamespace(
        trace=red, work=dense_b1.work, batch=1, chips=1, requests=FORWARDS,
        images_per_s=203.27, peaks=dense_b1.peaks,
        flops_per_image=sum(layer["flops"] for layer in dense_b1.work))
    assert dense_b1.readers[metric].read(ctx) == pytest.approx(
        UNNAMED_READINGS[metric], rel=1e-12)


# ------------------------------------------------------- reading the names
def test_scope_of_reads_the_name_stack():
    def op(op_name, kernel=None):
        return devtrace.HloOp("x", "fusion", op_name, [], kernel)
    assert scopes.scope_of(op(
        "jit(<unknown>)/conv1/jit(_conv2d_jit)/im2col/jit(_pad)/pad")) == "conv1/im2col"
    assert scopes.scope_of(op(
        "jit(<unknown>)/conv1/jit(_conv2d_jit)/gemm/_mm_act_stationary_kernel/pallas_call",
        "_mm_act_stationary_kernel")) == "conv1/gemm"
    assert scopes.scope_of(op(
        "jit(<unknown>)/conv3_b0_3x3/jit(_conv2d_jit)/_conv2d_kernel/pallas_call",
        "_conv2d_kernel")) == "conv3_b0_3x3"
    assert scopes.scope_of(op("jit(<unknown>)/head/reduce_sum")) == "head"
    # the program before it named anything: under the forward, no scope
    assert scopes.scope_of(op("jit(<unknown>)/jit(_conv2d_jit)/pallas_call",
                              "_conv2d_kernel")) == scopes.UNSCOPED
    # ops the compiler made: an argument's name, or none
    assert scopes.scope_of(op(r"params[\'conv2_b1\'][\'c1\']")) is None
    assert scopes.scope_of(op(None)) is None


def test_reduction_is_silent_on_a_program_that_names_nothing(unnamed):
    assert scopes.reduce_scopes(unnamed.xspace, unnamed.hlo) is None


# ---------------------------------------------- the trace of a named program
def test_every_conv_of_the_forward_has_device_time(named, dense_b1):
    sc = named.scoped
    convs = [layer["name"] for layer in dense_b1.work if layer["kind"] != "fc"]
    assert len(convs) == 53
    layer_s = sc.layer_s()
    assert all(layer_s.get(name, 0) > 0 for name in convs)
    assert sc.kernel_count == sc.scoped_kernels == 53
    assert set(sc.kernels) == set(convs)
    assert layer_s["maxpool"] > 0 and layer_s["head"] > 0
    assert sc.forwards == FORWARDS and sc.unknown_ops == 0 and sc.outside_ops == 0


def test_stem_splits_into_its_im2col_and_its_gemm(named):
    sc, red = named.scoped, named.red
    im2col = scopes.im2col_ms(sc)
    gemm = sc.per_forward_ms(sc.scope_s["conv1/gemm"])
    stem = 1e3 * red.category_s["stem"] / FORWARDS
    assert im2col + gemm == pytest.approx(stem, rel=0.01)
    assert im2col > 50 * gemm            # the patches, not the GEMM, take the time
    assert set(k for k in sc.scope_s if k.startswith("conv1")) == {"conv1/im2col",
                                                                   "conv1/gemm"}


def _kind(layer: str, scoped) -> str:
    """The category ``devtrace.classify`` gives a layer's ops."""
    kernel = scoped.kernels.get(layer)
    if any(k.startswith(f"{layer}/im2col") for k in scoped.scope_s):
        return "stem"
    if kernel == devtrace.CONV2D_KERNEL:
        return "conv3x3"
    return "conv1x1" if kernel else "glue"


def test_per_scope_totals_agree_with_the_categories(named):
    sc, red = named.scoped, named.red
    by_kind = collections.Counter()
    for layer, s in sc.layer_s().items():
        by_kind[_kind(layer, sc)] += s
    for kind in devtrace.CATEGORIES:
        assert by_kind[kind] == pytest.approx(red.category_s[kind], rel=0.01), kind


def test_gaps_inside_forwards_are_part_of_the_idle_time(named):
    sc, red = named.scoped, named.red
    gap = scopes.forward_gap_ms(sc)
    idle_ms = 1e3 * (red.window_s - red.busy_s) / FORWARDS
    assert 0 <= gap <= idle_ms
    # a forward is its ops and its gaps
    busy = sum(sc.scope_s.values())
    assert sc.forward_s == pytest.approx(busy + sc.gap_s, rel=1e-6)


def test_weight_stationary_layers_are_the_conv5_1x1s(named, dense_b1):
    sc = named.scoped
    ws = sorted(n for n, k in sc.kernels.items() if k == scopes.WEIGHT_STATIONARY)
    assert len(ws) == 7 and all(n.startswith("conv5_") for n in ws)
    share = scopes.kernel_roofline(sc, dense_b1.work, dense_b1.peaks,
                                   scopes.WEIGHT_STATIONARY)
    assert 0 < share < 100
    assert scopes.kernel_roofline(sc, dense_b1.work, dense_b1.peaks,
                                  "_no_such_kernel") is None
