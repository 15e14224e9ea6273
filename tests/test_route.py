"""``core.route.route``: the one decision of which Pallas kernel runs a conv.

The contract: for every distinct conv shape the five benchmark cells run
(ResNet-50 dense and pruned at batch 1 and 32, VGG-16's convs and fc6-fc8
at batch 1), the ``pallas_call`` that ``kernels.ops`` traces is the kernel
``route`` names, in the stationarity it names, folded as it says.  Tracing
with ``jax.make_jaxpr`` on shapes runs nothing, so real widths cost
milliseconds.  Then the spans: ``carla_conv``'s span reports what ran, as
its one ``kernels.*`` child measured it, beside the ASIC's dataflow.
"""
import os
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.core import carla, plan_conv
from repro.core.modes import Dataflow, Stationarity
from repro.core.networks import (
    resnet50_conv_layers,
    resnet50_projection_shortcuts,
    vgg16_conv_layers,
)
from repro.core.route import (
    conv2d_gemm_shape,
    conv2d_kernel_shapes,
    route,
    tile_util_conv2d,
    tile_util_gemm,
)
from repro.kernels import ops, ref
from repro.kernels.conv2d import row_block
from repro.models.cnn import VGG_FC
from repro.observability import trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELLS = ("resnet50_dense.single_stream", "resnet50_dense.offline",
         "resnet50_pruned50.single_stream", "resnet50_pruned50.offline",
         "vgg16.single_stream")
KERNEL = {
    ("conv2d", None): "_conv2d_kernel",
    ("im2col_gemm", Stationarity.ACTIVATION_STATIONARY):
        "_mm_act_stationary_kernel",
    ("gemm", Stationarity.ACTIVATION_STATIONARY): "_mm_act_stationary_kernel",
    ("gemm", Stationarity.WEIGHT_STATIONARY): "_mm_weight_stationary_kernel",
}


def _cell_convs() -> list[tuple]:
    """``(x shape, w shape, stride, padding)`` of each distinct conv of the
    five cells, from the layer tables (checked against the cells' forwards
    by ``test_route_cases_are_the_convs_the_cells_run``)."""
    convs = []
    for sparse in (False, True):
        for batch in (1, 32):
            for l in (resnet50_conv_layers(sparse)
                      + resnet50_projection_shortcuts(sparse)):
                convs.append(((batch, l.IL, l.IL, l.IC),
                              (l.FL, l.FL, l.IC, l.K), l.S, l.Z))
    for l in vgg16_conv_layers():
        convs.append(((1, l.IL, l.IL, l.IC), (3, 3, l.IC, l.K), 1, 1))
    cin = 7 * 7 * 512
    for k in (*VGG_FC, 1000):
        convs.append(((1, 1, 1, cin), (1, 1, cin, k), 1, 0))
        cin = k
    return sorted(set(convs))


CELL_CONVS = _cell_convs()


def _conv_id(conv) -> str:
    (b, h, w, c), (fh, fw, _, k), s, p = conv
    return f"b{b}_{h}x{w}x{c}_{fh}x{fw}x{k}_s{s}p{p}"


def _pallas_calls(jaxpr):
    """``(name, operand shapes)`` of every ``pallas_call`` in a jaxpr."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn.params["name"], [v.aval.shape for v in eqn.invars]
            continue
        for p in eqn.params.values():
            if isinstance(p, jax.extend.core.ClosedJaxpr):
                yield from _pallas_calls(p.jaxpr)
            elif isinstance(p, jax.extend.core.Jaxpr):
                yield from _pallas_calls(p)


@pytest.mark.parametrize("conv", CELL_CONVS, ids=map(_conv_id, CELL_CONVS))
def test_route_is_what_runs(conv):
    x_shape, w_shape, stride, padding = conv
    r = route(x_shape, w_shape, stride, padding)
    x = jax.ShapeDtypeStruct(x_shape, jnp.float32)
    if r.kernel == "gemm":
        w = jax.ShapeDtypeStruct(w_shape[2:], jnp.float32)
        fwd = lambda x, w: ops.conv1x1(x, w, stride=stride, impl="pallas")
    else:
        w = jax.ShapeDtypeStruct(w_shape, jnp.float32)
        fwd = lambda x, w: ops.conv2d(x, w, stride=stride, padding=padding,
                                      impl="pallas")
    ((name, operands),) = _pallas_calls(jax.make_jaxpr(fwd)(x, w).jaxpr)
    assert name == KERNEL[r.kernel, r.stationarity]
    if r.kernel == "conv2d":
        # the kernel sees the folded conv: f*C lanes, f*K output lanes
        xk, wk, _ = conv2d_kernel_shapes(x_shape, w_shape, stride, padding)
        assert xk[3] == r.col_fold * x_shape[3]
        assert operands[1] == wk
    else:
        assert r.col_fold == 1


def test_route_cases_are_the_convs_the_cells_run(monkeypatch):
    """Every conv each cell's forward dispatches, recorded while tracing it
    on shapes, is one of ``test_route_is_what_runs``' cases, and each case
    is run by some cell."""
    sys.path.insert(0, ROOT)
    from bench import harness
    seen = set()

    def spy(x, w, plan, stride, padding, impl, epilogue):
        seen.add((x.shape, w.shape, stride, padding))
        return ref.conv2d_ref(x, w, stride=stride, padding=padding)

    monkeypatch.setattr(carla, "_dispatch", spy)
    for name in CELLS:
        cell = harness.load_cell(name)
        cfg, batch = cell.config, cell.traffic["batch"]
        params = jax.eval_shape(lambda k: cell.model.build(cfg, k),
                                jax.random.PRNGKey(0))
        x = jax.ShapeDtypeStruct(
            (batch, cfg["image_size"], cfg["image_size"], cfg["in_channels"]),
            jnp.float32)
        jax.eval_shape(cell.model.program_forward(), params, x)
    assert seen == set(CELL_CONVS)


@pytest.mark.parametrize("x_shape,w_shape,stride,padding,dataflow,want", [
    # a 1x1 over 784 rows: feature-stationary on the ASIC and on the TPU
    ((1, 28, 28, 8), (1, 1, 8, 16), 1, 0,
     Dataflow.CONV1X1_FEATURE_STATIONARY,
     ("gemm", Stationarity.ACTIVATION_STATIONARY, 1)),
    # conv5's 1x1 at batch 1 (49 rows): weight-stationary on both
    ((1, 7, 7, 2048), (1, 1, 2048, 512), 1, 0,
     Dataflow.CONV1X1_WEIGHT_STATIONARY,
     ("gemm", Stationarity.WEIGHT_STATIONARY, 1)),
    # the same at batch 32 (1,568 rows): the ASIC's rule reads the plane,
    # the TPU's the rows
    ((32, 7, 7, 2048), (1, 1, 2048, 512), 1, 0,
     Dataflow.CONV1X1_WEIGHT_STATIONARY,
     ("gemm", Stationarity.ACTIVATION_STATIONARY, 1)),
    # the stem and VGG-16's conv1_1: the im2col GEMM
    ((1, 224, 224, 3), (7, 7, 3, 64), 2, 3, Dataflow.CONV7X7_ROW_DECOMPOSED,
     ("im2col_gemm", Stationarity.ACTIVATION_STATIONARY, 1)),
    ((1, 224, 224, 3), (3, 3, 3, 64), 1, 1, Dataflow.CONV3X3_SERIAL_ACC,
     ("im2col_gemm", Stationarity.ACTIVATION_STATIONARY, 1)),
    # VGG-16's conv1_2: the conv2d kernel, 2 columns folded
    ((1, 224, 224, 64), (3, 3, 64, 64), 1, 1, Dataflow.CONV3X3_SERIAL_ACC,
     ("conv2d", None, 2)),
])
def test_plan_carries_the_route_beside_the_asic_dataflow(
        x_shape, w_shape, stride, padding, dataflow, want):
    plan = plan_conv(x_shape, w_shape, stride, padding)
    assert plan.dataflow == dataflow
    assert plan.route == want == route(x_shape, w_shape, stride, padding)


def test_strided_conv_routes_to_its_im2col_gemm():
    """A strided conv runs its im2col GEMM, and tile_util describes that
    GEMM, its zero taps (147 of 192 columns are real) counted as padding;
    the carla_conv span reports what its child measured."""
    key = jax.random.PRNGKey(5)
    x = jax.random.normal(key, (1, 16, 16, 3))
    w = jax.random.normal(jax.random.fold_in(key, 1), (7, 7, 3, 8))
    m, c, k = conv2d_gemm_shape(x.shape, w.shape, 2, 3)
    with trace.capture() as tr:
        out = carla.carla_conv(x, w, stride=2, padding=3, impl="pallas")
    sp = tr.spans[0]
    (ksp,) = sp.children
    assert ksp.attrs["kernel"] == "im2col_gemm"
    assert ksp.attrs["stationarity"] == "weight_stationary"   # 64 rows
    for s in (sp, ksp):
        assert s.attrs["tile_util"] == pytest.approx(
            7 * 7 * 3 / c * tile_util_gemm(m, c, k,
                                           Stationarity.WEIGHT_STATIONARY))
    assert sp.attrs["dataflow"] == Dataflow.CONV7X7_ROW_DECOMPOSED.value
    assert sp.attrs["kernel"] == "im2col_gemm"
    want = ref.conv2d_ref(x, w, stride=2, padding=3)
    assert float(jnp.max(jnp.abs(out - want))) < 1e-3


def test_tile_util_math(monkeypatch):
    # conv2d: cin=24 -> bc=128 clamps to 24, k=16 -> bk=16 (9 * 24 = 216
    # columns: too wide for the im2col route; 24 does not divide 128, so no
    # fold): each block takes 128 of the MXU's lanes
    assert tile_util_conv2d((1, 14, 14, 24), (3, 3, 24, 16)) \
        == pytest.approx((24 * 16) / (128 * 128))
    # cin=16 folds 8 columns into 128 lanes: 2 column taps of 8 columns
    # hold the 3 taps (zero taps), and 12 columns take 2 groups, padded to 8
    assert tile_util_conv2d((1, 14, 14, 16), (3, 3, 16, 16)) \
        == pytest.approx((9 * 16 * 16 * 12 * 12) / (3 * 2 * 128 * 128 * 12 * 8))
    ws, act = Stationarity.WEIGHT_STATIONARY, Stationarity.ACTIVATION_STATIONARY
    # gemm WS: the block divides K, and a whole C is one block: no padding
    assert tile_util_gemm(7, 64, 30, ws) == 1.0
    # ... but C blocks pad C: 300 in blocks of 128 under a 64 KiB budget
    monkeypatch.setattr(sys.modules["repro.kernels.matmul"], "WS_BUDGET",
                        2**16)
    assert tile_util_gemm(3, 300, 40, ws) == pytest.approx(300 / 384)
    # gemm AS: M, C and K pad to bm = 128, bc = 512 and bk = 128
    assert tile_util_gemm(200, 600, 130, act) == pytest.approx(
        (200 * 600 * 130) / (256 * 1024 * 256))
    # whole axes below a block do not
    assert tile_util_gemm(100, 60, 30, act) == 1.0


def test_conv2d_dispatch_matches_ref_and_records_span():
    """A 24-channel 3x3 (216 patch columns, too wide for the im2col route;
    24 does not divide 128 lanes) runs the conv2d kernel unfolded; the
    carla_conv span and its kernel child carry the same measurements."""
    key = jax.random.PRNGKey(2)
    x = jax.random.normal(key, (1, 10, 10, 24))
    w = jax.random.normal(jax.random.fold_in(key, 1), (3, 3, 24, 16))
    with trace.capture() as tr:
        out = carla.carla_conv(x, w, padding=1, impl="pallas")
    want = ref.conv2d_ref(x, w, stride=1, padding=1)
    assert float(jnp.max(jnp.abs(out - want))) < 1e-3
    sp = tr.spans[0]
    (ksp,) = sp.children
    assert sp.attrs["kernel"] == ksp.attrs["kernel"] == "conv2d"
    assert sp.attrs["col_fold"] == ksp.attrs["col_fold"] == 1
    assert sp.attrs["tile_util"] == ksp.attrs["tile_util"] == pytest.approx(
        (24 * 16) / (128 * 128))
    assert sp.attrs["bytes_touched"] == ksp.attrs["bytes_touched"]


def test_repro_impl_env_overrides_dispatch(monkeypatch):
    """REPRO_IMPL forces the engine and the span records it."""
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 8, 8, 4))
    w = jax.random.normal(jax.random.PRNGKey(4), (3, 3, 4, 8))
    monkeypatch.setenv("REPRO_IMPL", "pallas")
    with trace.capture() as tr:
        out_p = ops.conv2d(x, w, padding=1, impl="ref")   # env wins
    assert tr.spans[0].attrs["impl"] == "pallas"
    monkeypatch.setenv("REPRO_IMPL", "ref")
    with trace.capture() as tr:
        out_r = ops.conv2d(x, w, padding=1, impl="pallas")
    assert tr.spans[0].attrs["impl"] == "ref"
    assert float(jnp.max(jnp.abs(out_p - out_r))) < 1e-4
    monkeypatch.delenv("REPRO_IMPL")
    assert ops._resolve("auto") in ("pallas", "ref")


def test_b32_conv5_1x1_span_reports_what_ran():
    """ResNet-50's conv5 1x1 at batch 32: a 7x7 plane, so the ASIC's rule
    reads weight-stationary, but 1,568 rows run the activation-stationary
    GEMM, and the span says so, with that GEMM's tile_util."""
    key = jax.random.PRNGKey(6)
    x = jax.random.normal(key, (32, 7, 7, 2048))
    w = jax.random.normal(jax.random.fold_in(key, 1), (2048, 512)) / 45
    with trace.capture() as tr:
        out = carla.carla_conv(x, w, impl="pallas")
    (sp,) = tr.spans
    assert sp.attrs["dataflow"] == Dataflow.CONV1X1_WEIGHT_STATIONARY.value
    assert sp.attrs["kernel"] == "gemm"
    assert sp.attrs["stationarity"] == "activation_stationary"
    assert sp.attrs["tile_util"] == pytest.approx(tile_util_gemm(
        32 * 49, 2048, 512, Stationarity.ACTIVATION_STATIONARY))
    assert sp.attrs["tile_util"] == pytest.approx(1568 / 1664)  # 13 x 128
    want = ref.matmul_ref(x.reshape(-1, 2048), w)
    assert float(jnp.max(jnp.abs(out.reshape(-1, 512) - want))) < 1e-3


def test_row_blocked_3x3_parent_and_child_count_the_halo(monkeypatch):
    """A 3x3 in row blocks: the carla_conv span's bytes_touched is its
    child's, the halo rows each block past the first re-reads included."""
    monkeypatch.setattr(sys.modules["repro.kernels.conv2d"], "VMEM_BUDGET",
                        2**20)
    key = jax.random.PRNGKey(7)
    x = jax.random.normal(key, (1, 40, 40, 24))
    w = jax.random.normal(jax.random.fold_in(key, 1), (3, 3, 24, 16)) / 15
    th = row_block(x.shape, w.shape, padding=1)
    n_r = -(-40 // th)
    assert n_r > 1
    with trace.capture() as tr:
        out = carla.carla_conv(x, w, padding=1, impl="pallas")
    (sp,) = tr.spans
    (ksp,) = sp.children
    plain = 4 * (x.size + w.size + out.size)
    halo = 4 * (n_r - 1) * 2 * 42 * 24
    assert sp.attrs["bytes_touched"] == ksp.attrs["bytes_touched"] \
        == plain + halo
    assert ksp.attrs["row_blocks"] == n_r
    want = ref.conv2d_ref(x, w, padding=1)
    assert float(jnp.max(jnp.abs(out - want))) < 1e-4
