"""Dataflow-planner edge cases: large filters, the 1x1 stationarity boundary,
strided 1x1 dispatch, and the 2-D weight convenience path of carla_conv."""
import jax
import jax.numpy as jnp
import pytest

from repro.core import carla_conv, plan_conv, select_dataflow
from repro.core.cost_model import layer_cost
from repro.core.modes import NUM_PES, ConvLayer, Dataflow


# ----------------------- select_dataflow: FL = 5 / 7 --------------------------
@pytest.mark.parametrize("fl,z", [(5, 2), (7, 3)])
def test_large_filters_row_decompose(fl, z):
    layer = ConvLayer("big", IL=56, IC=16, K=32, FL=fl, S=1, Z=z)
    assert select_dataflow(layer) == Dataflow.CONV7X7_ROW_DECOMPOSED
    # the decomposed cost model must still produce a sane, bounded PUF
    c = layer_cost(layer)
    assert 0 < c.puf <= 1.0 + 1e-9
    assert c.dram_out == layer.OL ** 2 * layer.K


def test_resnet_conv1_is_row_decomposed():
    conv1 = ConvLayer("conv1", IL=224, IC=3, K=64, FL=7, S=2, Z=3)
    assert select_dataflow(conv1) == Dataflow.CONV7X7_ROW_DECOMPOSED


# ------------------- 1x1 weight-stationary boundary ---------------------------
def test_1x1_boundary_exactly_num_pes():
    """OL*OL == NUM_PES (14*14 == 196): 'close to or greater' -> features stay
    resident; strictly below flips to weight-stationary."""
    at = ConvLayer("b", IL=14, IC=64, K=128, FL=1)
    assert at.OL * at.OL == NUM_PES
    assert select_dataflow(at) == Dataflow.CONV1X1_FEATURE_STATIONARY

    below = ConvLayer("b", IL=13, IC=64, K=128, FL=1)
    assert below.OL * below.OL < NUM_PES
    assert select_dataflow(below) == Dataflow.CONV1X1_WEIGHT_STATIONARY


def test_1x1_stride_crosses_boundary():
    """Stride-2 shrinks OL: a 14x14 input (feature-stationary at stride 1)
    becomes 7x7 = 49 features < 196 PEs -> weight-stationary."""
    strided = ConvLayer("s", IL=14, IC=64, K=128, FL=1, S=2)
    assert strided.OL == 7
    assert select_dataflow(strided) == Dataflow.CONV1X1_WEIGHT_STATIONARY


# --------------------- carla_conv numeric edge paths --------------------------
def _ref_1x1(x, w2d, stride):
    return jnp.einsum("bhwc,ck->bhwk", x[:, ::stride, ::stride, :], w2d)


def test_carla_conv_stride2_1x1():
    """The transition-block 1x1/2 (original ResNet variant) — subsampling
    happens before the GEMM, and the result matches the dense reference."""
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (2, 14, 14, 32))
    w = jax.random.normal(jax.random.fold_in(key, 1), (1, 1, 32, 64))
    plan = plan_conv(x.shape, w.shape, stride=2)
    assert plan.dataflow == Dataflow.CONV1X1_WEIGHT_STATIONARY
    got = carla_conv(x, w, stride=2)
    want = _ref_1x1(x, w[0, 0], 2)
    assert got.shape == (2, 7, 7, 64)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4


def test_carla_conv_2d_weight_reshape_path():
    """(C, K) weights are promoted to (1, 1, C, K) — both spellings must hit
    the same 1x1 dispatch and produce identical outputs."""
    key = jax.random.PRNGKey(7)
    x = jax.random.normal(key, (1, 28, 28, 16))
    w2d = jax.random.normal(jax.random.fold_in(key, 1), (16, 24))
    got2d = carla_conv(x, w2d)
    got4d = carla_conv(x, w2d[None, None])
    assert got2d.shape == (1, 28, 28, 24)
    assert jnp.array_equal(got2d, got4d)
    assert float(jnp.max(jnp.abs(got2d - _ref_1x1(x, w2d, 1)))) < 1e-4


def test_carla_conv_3x3_matches_reference():
    """The serial-accumulation dispatch stays numerically a plain conv."""
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (1, 8, 8, 4))
    w = jax.random.normal(jax.random.fold_in(key, 1), (3, 3, 4, 8))
    got = carla_conv(x, w, stride=1, padding=1)
    want = jax.lax.conv_general_dilated(
        x, w, (1, 1), [(1, 1), (1, 1)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4


@pytest.mark.parametrize("stride,padding,kernel", [(2, 3, "im2col_gemm"),
                                                   (1, 3, "conv2d")])
def test_pallas_conv2d_span_records_the_kernel_that_ran(stride, padding,
                                                        kernel):
    """A strided conv (the 7x7/2 stem) runs as an im2col GEMM on the pallas
    path; the plan keeps the analytic 7x7 dataflow and the kernel span says
    which kernel ran."""
    from repro.kernels.ref import conv2d_ref
    from repro.observability import trace
    key = jax.random.PRNGKey(11)
    x = jax.random.normal(key, (2, 20, 20, 3))
    w = jax.random.normal(jax.random.fold_in(key, 1), (7, 7, 3, 16))
    with trace.capture() as tr:
        got = carla_conv(x, w, stride=stride, padding=padding, impl="pallas")
    (sp,) = tr.spans
    assert sp.attrs["dataflow"] == Dataflow.CONV7X7_ROW_DECOMPOSED.value
    (kernel_sp,) = sp.children
    assert kernel_sp.attrs["kernel"] == kernel
    want = conv2d_ref(x, w, stride=stride, padding=padding)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4


# ------------- strided conv2d: space-to-depth im2col parity -------------------
# (h, c, k, fl, stride, pad): strides 2 and 3, filters 3/5/7, paddings below
# stride - 1 (the padded input is cropped), and sizes where
# (h + 2p - fl) % stride != 0 (rows no output reads)
S2D_CASES = [
    (224, 3, 64, 7, 2, 3),   # ResNet-50's stem
    (17, 3, 8, 7, 2, 3),     # odd size, (17 + 6 - 7) % 2 == 0
    (16, 4, 8, 3, 2, 1),
    (16, 4, 8, 3, 2, 0),     # crop; (16 - 3) % 2 == 1
    (15, 2, 8, 5, 3, 2),
    (16, 5, 8, 5, 3, 1),     # crop; (16 + 2 - 5) % 3 == 1
    (13, 3, 8, 3, 3, 0),     # crop; (13 - 3) % 3 == 1
    (18, 3, 8, 5, 2, 2),     # (18 + 4 - 5) % 2 == 1
]


@pytest.mark.parametrize("fused", [False, True])
@pytest.mark.parametrize("h,c,k,fl,s,p", S2D_CASES)
def test_strided_conv2d_space_to_depth_matches_reference(h, c, k, fl, s, p,
                                                         fused):
    """The strided route builds its patches by space-to-depth and its weights
    with zero taps, so a conv of any stride, filter and padding equals the
    plain conv, with and without the fused scale/bias/residual/ReLU."""
    from repro.core.fuse import Epilogue
    from repro.kernels import ops
    from repro.kernels.ref import conv2d_ref
    key = jax.random.PRNGKey(h * 10 + fl + s)
    x = jax.random.normal(key, (2 if h < 64 else 1, h, h, c))
    w = jax.random.normal(jax.random.fold_in(key, 1), (fl, fl, c, k))
    oh = (h + 2 * p - fl) // s + 1
    kw = {}
    if fused:
        kw = dict(scale=jax.random.normal(jax.random.fold_in(key, 2), (k,)),
                  bias=jax.random.normal(jax.random.fold_in(key, 3), (k,)),
                  residual=jax.random.normal(jax.random.fold_in(key, 4),
                                             (x.shape[0], oh, oh, k)),
                  relu=True)
    got = ops.conv2d(x, w, stride=s, padding=p, impl="pallas",
                     epilogue=Epilogue(**kw))
    want = conv2d_ref(x, w, stride=s, padding=p, **kw)
    assert got.shape == want.shape == (x.shape[0], oh, oh, k)
    err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    assert err < 1e-5, err


def test_strided_im2col_has_no_gather():
    """The stem's patches are slices of a space-to-depth of its input: 16
    unit-stride windows of 12 channels (192 GEMM columns, 45 of them zero
    taps), and no gather, which a strided index would lower to."""
    from repro.kernels import ops
    x = jnp.zeros((1, 224, 224, 3))
    w = jnp.zeros((7, 7, 3, 64))
    p, wf = ops._im2col(x, w, 2, 3)
    assert p.shape == (1, 112, 112, 192) and wf.shape == (192, 64)
    jaxpr = str(jax.make_jaxpr(lambda x, w: ops._im2col(x, w, 2, 3))(x, w))
    assert "gather" not in jaxpr
    assert jaxpr.count(" slice[") == 16
