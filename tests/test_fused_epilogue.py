"""Fused conv epilogues: numerical parity, BN folding, and the bytes ledger.

The fused path (scale/bias + residual + ReLU applied at the kernel flush)
must be bit-comparable (fp32 atol) to the unfused op sequence across all
four CARLA dataflows and both execution engines, and the telemetry must
record what was fused plus the HBM round-trips the fusion eliminated.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.core import (
    Epilogue,
    apply_epilogue,
    carla_conv,
    epilogue_dram_delta,
    epilogue_dram_delta_bytes,
    fold_bn,
    fold_bn_into_conv,
    plan_conv,
)
from repro.core.modes import WORD_BYTES, ConvLayer, Dataflow
from repro.kernels import ops, ref
from repro.observability import trace


def _err(a, b):
    return float(jnp.max(jnp.abs(a.astype(jnp.float32) -
                                 b.astype(jnp.float32))))


# One conv shape per dataflow (mirrors core.networks.smoke_conv_layers).
DATAFLOW_CASES = {
    Dataflow.CONV3X3_SERIAL_ACC: dict(il=14, ic=8, k=16, fl=3, s=1, z=1),
    Dataflow.CONV1X1_FEATURE_STATIONARY: dict(il=28, ic=16, k=8, fl=1, s=1, z=0),
    Dataflow.CONV1X1_WEIGHT_STATIONARY: dict(il=7, ic=16, k=8, fl=1, s=1, z=0),
    Dataflow.CONV7X7_ROW_DECOMPOSED: dict(il=28, ic=3, k=8, fl=7, s=2, z=3),
}


def _operands(case, batch=2, seed=0):
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (batch, case["il"], case["il"], case["ic"]))
    w = jax.random.normal(jax.random.fold_in(key, 1),
                          (case["fl"], case["fl"], case["ic"], case["k"]))
    w = w * (case["fl"] ** 2 * case["ic"]) ** -0.5
    return x, w


def _epilogue(kind, k, out_shape, seed=0):
    key = jax.random.PRNGKey(seed + 99)
    scale = 1.0 + 0.2 * jax.random.normal(key, (k,))
    bias = 0.3 * jax.random.normal(jax.random.fold_in(key, 1), (k,))
    residual = jax.random.normal(jax.random.fold_in(key, 2), out_shape)
    return {
        "none": Epilogue(),
        "bias": Epilogue(bias=bias),
        "scale_bias": Epilogue(scale=scale, bias=bias),
        "scale_bias_relu": Epilogue(scale=scale, bias=bias, relu=True),
        "relu": Epilogue(relu=True),
        "full": Epilogue(scale=scale, bias=bias, relu=True, residual=residual),
        "residual": Epilogue(residual=residual),
    }[kind]


@pytest.mark.parametrize("dataflow", list(DATAFLOW_CASES))
@pytest.mark.parametrize("impl", ["ref", "pallas"])
@pytest.mark.parametrize("kind", ["none", "bias", "scale_bias",
                                  "scale_bias_relu", "full", "residual"])
def test_fused_matches_unfused(dataflow, impl, kind):
    case = DATAFLOW_CASES[dataflow]
    x, w = _operands(case)
    plan = plan_conv(x.shape, w.shape, stride=case["s"], padding=case["z"])
    assert plan.dataflow == dataflow          # the case really hits this mode

    unfused = carla_conv(x, w, stride=case["s"], padding=case["z"], impl=impl)
    ep = _epilogue(kind, case["k"], unfused.shape)
    fused = carla_conv(x, w, stride=case["s"], padding=case["z"], impl=impl,
                       epilogue=ep)
    want = apply_epilogue(unfused, ep)
    assert fused.shape == want.shape
    assert _err(fused, want) < 1e-4


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_no_epilogue_identity(impl):
    """epilogue=None and epilogue=Epilogue() are the plain conv, exactly."""
    case = DATAFLOW_CASES[Dataflow.CONV3X3_SERIAL_ACC]
    x, w = _operands(case)
    base = carla_conv(x, w, padding=1, impl=impl)
    noop = carla_conv(x, w, padding=1, impl=impl, epilogue=Epilogue())
    assert jnp.array_equal(base, noop)


def test_ref_oracles_accept_epilogue():
    """kernels.ref mirrors the fused semantics (the kernels' ground truth)."""
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (2, 8, 8, 4))
    w = jax.random.normal(jax.random.fold_in(key, 1), (3, 3, 4, 6))
    sc = jax.random.normal(jax.random.fold_in(key, 2), (6,))
    bi = jax.random.normal(jax.random.fold_in(key, 3), (6,))
    res = jax.random.normal(jax.random.fold_in(key, 4), (2, 8, 8, 6))
    got = ref.conv2d_ref(x, w, padding=1, scale=sc, bias=bi, relu=True,
                         residual=res)
    want = jnp.maximum(
        ref.conv2d_ref(x, w, padding=1) * sc + bi + res, 0.0)
    assert _err(got, want) < 1e-5

    xf = x.reshape(-1, 4)
    rf = jax.random.normal(jax.random.fold_in(key, 5), (xf.shape[0], 6))
    w2 = w[0, 0]
    got = ref.matmul_ref(xf, w2, scale=sc, bias=bi, relu=True, residual=rf)
    want = jnp.maximum(ref.matmul_ref(xf, w2) * sc + bi + rf, 0.0)
    assert _err(got, want) < 1e-5


# ------------------------------ BN folding ------------------------------------
def test_fold_bn_matches_unfolded():
    key = jax.random.PRNGKey(11)
    k = 9
    scale = jax.random.normal(key, (k,))
    bias = jax.random.normal(jax.random.fold_in(key, 1), (k,))
    mean = jax.random.normal(jax.random.fold_in(key, 2), (k,))
    var = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 3), (k,)))
    y = jax.random.normal(jax.random.fold_in(key, 4), (5, k))

    eff_s, eff_b = fold_bn(scale, bias, mean, var, eps=1e-5)
    want = scale * (y - mean) / jnp.sqrt(var + 1e-5) + bias
    assert _err(y * eff_s + eff_b, want) < 1e-5


@pytest.mark.parametrize("w_shape", [(3, 3, 4, 9), (4, 9)])
def test_fold_bn_into_conv(w_shape):
    key = jax.random.PRNGKey(13)
    k = w_shape[-1]
    w = jax.random.normal(key, w_shape)
    scale = 1.0 + 0.2 * jax.random.normal(jax.random.fold_in(key, 1), (k,))
    bias = jax.random.normal(jax.random.fold_in(key, 2), (k,))
    mean = jax.random.normal(jax.random.fold_in(key, 3), (k,))
    var = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 4), (k,)))
    x = jax.random.normal(jax.random.fold_in(key, 5), (2, 8, 8, 4))

    wf, bf = fold_bn_into_conv(w, scale, bias, mean, var)
    if w.ndim == 2:
        raw = ref.conv1x1_ref(x, w)
        folded = ref.conv1x1_ref(x, wf, bias=bf)
    else:
        raw = ref.conv2d_ref(x, w, padding=1)
        folded = ref.conv2d_ref(x, wf, padding=1, bias=bf)
    want = scale * (raw - mean) / jnp.sqrt(var + 1e-5) + bias
    assert _err(folded, want) < 1e-4


def test_bn_as_pure_epilogue():
    """Inference BN == a scale/bias epilogue on the conv (end to end)."""
    key = jax.random.PRNGKey(17)
    x = jax.random.normal(key, (1, 10, 10, 4))
    w = jax.random.normal(jax.random.fold_in(key, 1), (3, 3, 4, 8)) * 0.3
    scale = 1.0 + 0.1 * jax.random.normal(jax.random.fold_in(key, 2), (8,))
    bias = 0.1 * jax.random.normal(jax.random.fold_in(key, 3), (8,))
    mean = jax.random.normal(jax.random.fold_in(key, 4), (8,))
    var = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 5), (8,)))

    eff_s, eff_b = fold_bn(scale, bias, mean, var)
    fused = carla_conv(x, w, padding=1,
                       epilogue=Epilogue(scale=eff_s, bias=eff_b))
    raw = carla_conv(x, w, padding=1)
    want = scale * (raw - mean) / jnp.sqrt(var + 1e-5) + bias
    assert _err(fused, want) < 1e-4


# ------------------------------ Epilogue type ---------------------------------
def test_epilogue_tag_and_op_count():
    one = jnp.ones((4,))
    res = jnp.zeros((1, 2, 2, 4))
    assert Epilogue().tag == "none" and Epilogue().is_noop
    assert Epilogue().n_fused_ops == 0
    assert Epilogue(scale=one, bias=one).tag == "scale+bias"
    assert Epilogue(scale=one, bias=one).n_fused_ops == 1   # one FMA pass
    assert Epilogue(bias=one, relu=True).tag == "bias+relu"
    full = Epilogue(scale=one, bias=one, relu=True, residual=res)
    assert full.tag == "scale+bias+residual+relu"
    assert full.n_fused_ops == 3 and not full.is_noop


# --------------------------- telemetry + bytes ledger -------------------------
def test_carla_span_records_epilogue():
    case = DATAFLOW_CASES[Dataflow.CONV3X3_SERIAL_ACC]
    x, w = _operands(case)
    base = carla_conv(x, w, padding=1)
    ep = _epilogue("full", case["k"], base.shape)
    with trace.capture() as tr:
        out = carla_conv(x, w, padding=1, epilogue=ep)
    (sp,) = tr.spans
    assert sp.attrs["epilogue"] == "scale+bias+residual+relu"
    saved = sp.attrs["epilogue_hbm_saved"]
    assert saved == 2 * 3 * out.size * out.dtype.itemsize
    # bytes_touched covers conv operands + epilogue operands
    expected = sum(a.size * a.dtype.itemsize
                   for a in (x, w, out, ep.scale, ep.bias, ep.residual))
    assert sp.attrs["bytes_touched"] == expected
    # the unfused dispatch records epilogue="none" and no savings
    with trace.capture() as tr:
        carla_conv(x, w, padding=1)
    (sp,) = tr.spans
    assert sp.attrs["epilogue"] == "none"
    assert "epilogue_hbm_saved" not in sp.attrs


def test_strided_1x1_bytes_counts_subsampled_view():
    """A 1x1/2 conv reads only the strided view — the traced byte count must
    not charge the full pre-stride feature map (ops.py and carla_conv)."""
    key = jax.random.PRNGKey(5)
    x = jax.random.normal(key, (2, 14, 14, 32))
    w = jax.random.normal(jax.random.fold_in(key, 1), (1, 1, 32, 64))
    with trace.capture() as tr:
        out = carla_conv(x, w, stride=2)
    (sp,) = tr.spans
    rows = 2 * 7 * 7
    expected = (rows * 32 * x.dtype.itemsize
                + w.size * w.dtype.itemsize + out.size * out.dtype.itemsize)
    assert sp.attrs["bytes_touched"] == expected
    (kernel_sp,) = sp.children
    assert kernel_sp.name == "kernels.conv1x1"
    assert kernel_sp.attrs["bytes_touched"] == expected
    # unstrided dispatch still charges the full input
    with trace.capture() as tr:
        out1 = carla_conv(x, w, stride=1)
    (sp1,) = tr.spans
    assert sp1.attrs["bytes_touched"] == sum(
        a.size * a.dtype.itemsize for a in (x, w, out1))


def test_fused_touches_fewer_bytes_than_unfused_sequence():
    """The acceptance invariant, at dispatch level: fused bytes < unfused
    bytes (conv + separate scale/bias + relu + residual round-trips)."""
    for dataflow, case in DATAFLOW_CASES.items():
        x, w = _operands(case)
        base = carla_conv(x, w, stride=case["s"], padding=case["z"])
        ep = _epilogue("full", case["k"], base.shape)
        with trace.capture() as tr:
            out = carla_conv(x, w, stride=case["s"], padding=case["z"],
                             epilogue=ep)
        (sp,) = tr.spans
        fused_bytes = sp.attrs["bytes_touched"]
        out_b = out.size * out.dtype.itemsize
        unfused_bytes = (fused_bytes                       # same operand reads
                         + 2 * out_b * ep.n_fused_ops)     # + HBM round-trips
        assert fused_bytes < unfused_bytes, dataflow
        assert sp.attrs["epilogue_hbm_saved"] == unfused_bytes - fused_bytes


# ------------------------------- cost model -----------------------------------
def test_epilogue_dram_delta():
    layer = ConvLayer("l", IL=14, IC=8, K=16, FL=3, S=1, Z=1)
    out_words = layer.OL ** 2 * layer.K
    assert epilogue_dram_delta(layer) == 0
    assert epilogue_dram_delta(layer, scale_bias=True) == 2 * out_words
    assert epilogue_dram_delta(layer, scale_bias=True, relu=True,
                               residual=True) == 6 * out_words
    assert epilogue_dram_delta_bytes(layer, relu=True) == \
        2 * out_words * WORD_BYTES


# ------------------------------ model forwards --------------------------------
def test_resnet50_fused_forward_parity():
    from repro.models.cnn import resnet50_apply, resnet50_init
    key = jax.random.PRNGKey(0)
    params = resnet50_init(key, width=0.0625, num_classes=10)
    # non-trivial BN so fusion actually changes the math
    bns = [params["bn1"]]
    for blk in params.values():
        if isinstance(blk, dict) and "scale" not in blk:
            bns += [v for v in blk.values()
                    if isinstance(v, dict) and "scale" in v]
    for i, bn in enumerate(bns):
        k2 = jax.random.fold_in(key, 1000 + i)
        bn["scale"] = 1.0 + 0.1 * jax.random.normal(k2, bn["scale"].shape)
        bn["bias"] = 0.1 * jax.random.normal(jax.random.fold_in(k2, 1),
                                             bn["bias"].shape)
    x = jax.random.normal(jax.random.fold_in(key, 7), (2, 32, 32, 3))
    fused = resnet50_apply(params, x, impl="ref", fused=True)
    unfused = resnet50_apply(params, x, impl="ref", fused=False)
    assert fused.shape == (2, 10)
    assert _err(fused, unfused) < 1e-4


def test_resnet50_fused_residual_rides_last_conv():
    """With tracing on, each bottleneck's closing 1x1 must carry the
    residual in its fused epilogue (and every conv must carry relu/BN)."""
    from repro.models.cnn import resnet50_apply, resnet50_init
    key = jax.random.PRNGKey(1)
    params = resnet50_init(key, width=0.0625, num_classes=10)
    x = jax.random.normal(key, (1, 32, 32, 3))
    with trace.capture() as tr:
        resnet50_apply(params, x, impl="ref", fused=True)
    spans = [s for root in tr.spans for s in root.walk()
             if s.name == "carla_conv"]
    assert len(spans) == 49 + 4           # 49 counted layers + 4 projections
    tags = [s.attrs["epilogue"] for s in spans]
    assert tags.count("scale+bias+residual+relu") == 16   # one per bottleneck
    assert all(t != "none" for t in tags)


def test_vgg16_fused_forward_parity():
    from repro.models.cnn import vgg16_apply, vgg16_init
    key = jax.random.PRNGKey(2)
    params = vgg16_init(key, width=0.0625, num_classes=10, image_size=32)
    x = jax.random.normal(key, (1, 32, 32, 3))
    fused = vgg16_apply(params, x, impl="ref", fused=True)
    unfused = vgg16_apply(params, x, impl="ref", fused=False)
    assert fused.shape == (1, 10)     # fc8's logits
    assert _err(fused, unfused) < 1e-5
