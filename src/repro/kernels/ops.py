"""jit'd wrappers + reconfigurable dispatch over the Pallas kernels.

``impl`` selects the execution engine:
  * ``"pallas"`` — the Pallas TPU kernels, compiled by Mosaic on a TPU and
                   interpreted elsewhere (this module is the one place that
                   decides ``interpret``, from the platform);
  * ``"ref"``    — the pure-jnp oracles (XLA-compiled; fast on CPU, and what
                   the LM models use so that 512-device dry-runs lower to
                   plain HLO convolutions/GEMMs);
  * ``"auto"``   — pallas on TPU backends, ref elsewhere.

The ``REPRO_IMPL`` environment variable overrides all of it, so a whole
program can be forced onto ``pallas`` or ``ref`` without editing call sites.
The resolved impl is recorded as the ``impl`` span attribute.

Which kernel runs a conv on the pallas path, in which GEMM stationarity and
with which column fold, is ``core.route.route``'s decision, from the shapes
alone; ``conv2d`` and ``conv1x1`` branch on it, and each kernel runs its own
default blocks.  ``gemm`` takes an explicit ``stationarity=`` from its
callers, else ``core.modes.select_stationarity``'s, the rule ``route`` uses.

``conv2d``/``conv1x1``/``gemm`` accept an ``epilogue=`` (``core.fuse.Epilogue``):
folded-BN scale/bias, residual add, and ReLU are applied inside the kernel's
flush step, so the output feature map is written to HBM exactly once instead
of round-tripping once per element-wise op.  Telemetry spans record which
epilogue was fused (``epilogue=`` attr) and the HBM bytes the fusion saved
vs. the unfused op sequence (``epilogue_hbm_saved``).

Every public entry point is telemetry-instrumented: when the global tracer is
enabled (``observability.trace``) and the call is eager, the dispatch records
operand shapes, FLOPs, wall time under ``block_until_ready``, the bytes it
touched, what ran (``kernel``, ``stationarity``, ``col_fold``) and
``tile_util`` (logical FLOPs / padded FLOPs under the kernel's blocks).
These spans are the one record of them: ``carla_conv``'s span copies them
from its child.  When tracing is disabled (the default), or the call is
traced inside an outer ``jax.jit``, the jitted function is invoked directly:
no span objects, no clock reads, no ``block_until_ready``.  There the record
is the name of the ops: the im2col route's patches run under
``jax.named_scope("im2col")`` and its GEMM under ``"gemm"``, the column
fold under ``"col_fold"``, inside the layer's scope that ``carla_conv``
opens, and each Pallas kernel keeps its ``pallas_call`` name.

A strided conv (``stride > 1``, ResNet-50's 7x7/2 stem) and a unit-stride
one whose patch row ``FH*FW*C`` fits one 128-lane tile (VGG-16's conv1_1,
3 channels) run as one GEMM on the matmul kernels, over patches built by
space-to-depth and unit-stride slices (:func:`_im2col`), never by strided
indexing, which XLA lowers to one gather per tap.  Every other conv runs
the conv2d kernel, in row blocks where the whole plane does not fit VMEM.
One whose C is below 128 lanes and divides 128 (VGG-16's conv1_2 and
conv2_1, ResNet-50's conv2 3x3s, the pruned conv3 3x3s) runs it with
``f = 128 // C`` adjacent output columns folded into the lanes
(:func:`_conv2d_folded`): the padded input read as ``f*C`` lanes, the
weights refolded with zero taps, so each dot fills the MXU's 128 lanes of
C and of K where 64 channels filled a quarter of it.  The kernel and its
row blocks then see the folded conv (``core.route.conv2d_kernel_shapes``).
An eager span records ``col_fold`` (f, or 1) and ``row_blocks`` and counts
the halo rows that blocks re-read in ``bytes_touched``, and a 1x1's span
records the GEMM's ``c_blocks``.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from repro.core.fuse import Epilogue
from repro.core.modes import Stationarity, select_stationarity
from repro.core.route import (
    conv2d_kernel_shapes,
    route,
    tile_util_conv2d,
    tile_util_gemm,
)
from repro.observability import trace
from . import ref as _ref
from .conv1d import conv1d_causal as _conv1d_pallas
from .conv2d import conv2d as _conv2d_pallas, row_block
from .matmul import (
    BC as _GEMM_BC,
    matmul_act_stationary,
    matmul_weight_stationary,
    ws_blocks,
)

_NO_EPILOGUE = Epilogue()


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve(impl: str) -> str:
    """Resolve ``auto`` (and the ``REPRO_IMPL`` env override) to pallas/ref."""
    impl = os.environ.get("REPRO_IMPL") or impl
    if impl == "auto":
        return "pallas" if _on_tpu() else "ref"
    return impl


def _nbytes(*arrays) -> int:
    return sum(a.size * a.dtype.itemsize for a in arrays if a is not None)


def _epilogue_attrs(sp, ep: Epilogue, out) -> None:
    """Record the fused-epilogue ledger on a kernel/dispatch span."""
    sp.attrs["epilogue"] = ep.tag
    if ep.n_fused_ops:
        # Each fused element-wise pass would have read+written the full
        # output feature map through HBM; the fused flush does neither.
        sp.attrs["epilogue_hbm_saved"] = \
            2 * ep.n_fused_ops * out.size * out.dtype.itemsize


def _im2col(x, w, stride: int, padding: int):
    """Patches and weights of a strided conv as one GEMM, by space-to-depth.

    The padded input is folded into ``S*S*C`` channels, one per (row phase,
    column phase, channel), so each group of taps ``(r, q)`` of the filter,
    ``TH = ceil(FH/S)`` by ``TW = ceil(FW/S)`` of them, is a unit-stride
    window of the folded input: reshapes, one transpose and static slices,
    no strided index (which XLA lowers to a gather).  The weights are padded
    with zero taps to ``(S*TH, S*TW)`` and folded the same way.  Returns
    ``(B, OH, OW, TH*TW*S*S*C)`` patches and ``(TH*TW*S*S*C, K)`` weights.
    """
    b, h, wd, c = x.shape
    fh, fw, _, k = w.shape
    s = stride
    oh = (h - fh + 2 * padding) // s + 1
    ow = (wd - fw + 2 * padding) // s + 1
    th, tw = -(-fh // s), -(-fw // s)
    hp, wp = s * (oh + th - 1), s * (ow + tw - 1)
    # the bottom and right pads may be negative: rows no output reads
    xp = jax.lax.pad(x, jnp.zeros((), x.dtype),
                     ((0, 0, 0), (padding, hp - h - padding, 0),
                      (padding, wp - wd - padding, 0), (0, 0, 0)))
    xs = (xp.reshape(b, hp // s, s, wp // s, s * c)
          .transpose(0, 1, 3, 2, 4).reshape(b, hp // s, wp // s, s * s * c))
    p = jnp.concatenate([xs[:, r:r + oh, q:q + ow, :]
                         for r in range(th) for q in range(tw)], axis=-1)
    wz = jnp.pad(w, ((0, s * th - fh), (0, s * tw - fw), (0, 0), (0, 0)))
    wf = (wz.reshape(th, s, tw, s, c, k).transpose(0, 2, 1, 3, 4, 5)
          .reshape(th * tw * s * s * c, k))
    return p, wf


def _col_fold(x, w, scale, bias, residual, padding: int, rows: int):
    """The operands of a conv folded along W into the lanes, for the conv2d
    kernel at padding 0 (:func:`~repro.core.route.conv2d_kernel_shapes`),
    with ``rows`` more zero rows below the plane (and the residual): the
    kernel's last row block, which it would otherwise pad in a second copy.

    ``f`` adjacent output columns become one column group of ``f*K`` lanes.
    The input, padded once, is read as ``f*C`` lanes per column group (a
    reshape; in HBM, where 32 or 64 channels take 128 lanes a column, a
    layout copy), and column tap ``t`` of the folded weights holds
    ``W'[r, t, (p, c), (q, k)] = w[r, f*t + p - q, c, k]``, zero outside
    ``[0, FW)``: for each output phase ``q`` the taps shifted by ``q``, from
    pads, reshapes and one concatenate.  Scale and bias repeat ``f`` times
    along the lanes; the residual is padded to ``f * OWf`` columns and folded
    as the output is.
    """
    b, _, wd, c = x.shape
    fh, fw, _, k = w.shape
    xs, ws, _ = conv2d_kernel_shapes(x.shape, w.shape, 1, padding)
    f, twf = xs[3] // c, ws[1]
    owf = xs[2] - twf + 1
    xp = jnp.pad(x, ((0, 0), (padding, padding + rows),
                     (padding, f * xs[2] - wd - padding), (0, 0)))
    wf = jnp.concatenate(
        [jnp.pad(w, ((0, 0), (q, f * twf - fw - q), (0, 0), (0, 0)))
         .reshape(fh, twf, f * c, k) for q in range(f)], axis=-1)
    scale, bias = (None if v is None else jnp.tile(v, f) for v in (scale, bias))
    if residual is not None:
        _, oh, ow, _ = residual.shape
        residual = jnp.pad(residual, ((0, 0), (0, rows), (0, f * owf - ow),
                                      (0, 0))).reshape(b, oh + rows, owf, f * k)
    return xp.reshape(b, -1, xs[2], xs[3]), wf, scale, bias, residual


def _conv2d_folded(x, w, scale, bias, residual, *, relu: bool, padding: int):
    """A conv whose C fills half or a quarter of the MXU's 128 lanes, run on
    the conv2d kernel with ``route(...).col_fold`` adjacent output columns
    folded into the lanes, so that each dot contracts and writes all 128;
    the fold and the unfold run under ``jax.named_scope("col_fold")``."""
    b, h, wd, _ = x.shape
    fh, fw, _, k = w.shape
    oh, ow = h + 2 * padding - fh + 1, wd + 2 * padding - fw + 1
    xs, ws, _ = conv2d_kernel_shapes(x.shape, w.shape, 1, padding)
    th = row_block(xs, ws, padding=0, has_res=residual is not None)
    with jax.named_scope("col_fold"):
        x, w, scale, bias, residual = _col_fold(
            x, w, scale, bias, residual, padding, -(-oh // th) * th - oh)
    out = _conv2d_pallas(x, w, padding=0, scale=scale, bias=bias, relu=relu,
                         residual=residual, interpret=not _on_tpu())
    with jax.named_scope("col_fold"):
        return out.reshape(b, out.shape[1], -1, k)[:, :oh, :ow]


@functools.partial(jax.jit,
                   static_argnames=("stride", "padding", "impl", "relu"))
def _conv2d_jit(x, w, scale=None, bias=None, residual=None, *,
                relu: bool = False, stride: int = 1, padding: int = 0,
                impl: str = "auto"):
    if _resolve(impl) != "pallas":
        return _ref.conv2d_ref(x, w, stride=stride, padding=padding,
                               scale=scale, bias=bias, relu=relu,
                               residual=residual).astype(x.dtype)
    r = route(x.shape, w.shape, stride, padding)
    if r.kernel == "im2col_gemm":
        # Strided convs (ResNet-50's 7x7/2 stem) run as one GEMM over im2col
        # patches: Mosaic refuses a strided slice inside the conv2d kernel,
        # and the patch columns fill lanes that a 3-channel input block would
        # not.  For the stem, 16 slices of a (B, 115, 115, 12) space-to-depth
        # give 192 columns, 45 of them zero taps inside the 256 lanes that
        # 147 would occupy anyway.  VGG-16's conv1_1 (3x3, 3 channels) gives
        # 27 columns from 9 slices, against 9 contractions over 3 lanes.
        k = w.shape[-1]
        with jax.named_scope("im2col"):
            p, wf = _im2col(x, w, stride, padding)
        b, oh, ow, kk = p.shape
        rf = residual.reshape(b * oh * ow, k) if residual is not None else None
        with jax.named_scope("gemm"):
            out = _tiled_matmul(p.reshape(b * oh * ow, kk), wf,
                                scale, bias, relu, rf, r.stationarity)
        return out.reshape(b, oh, ow, k)
    if r.col_fold > 1:
        return _conv2d_folded(x, w, scale, bias, residual, relu=relu,
                              padding=padding)
    return _conv2d_pallas(x, w, padding=padding, scale=scale, bias=bias,
                          relu=relu, residual=residual,
                          interpret=not _on_tpu())


def conv2d(x, w, *, stride: int = 1, padding: int = 0, impl: str = "auto",
           epilogue: Epilogue | None = None):
    """General NHWC conv; CARLA 3x3/7x7 serial-accumulation dataflow.

    On the pallas path a strided conv runs as an im2col GEMM and a conv of
    32 or 64 channels as its column fold (``core.route.route``); an
    unpadded 1x1 is :func:`conv1x1`.  The span's ``kernel`` attribute
    records which kernel ran, ``col_fold`` the fold.
    """
    ep = epilogue or _NO_EPILOGUE
    impl = _resolve(impl)
    r = route(x.shape, w.shape, stride, padding)
    if r.kernel == "gemm":
        return conv1x1(x, w[0, 0], stride=stride, impl=impl, epilogue=epilogue)
    if not trace.timed(x):
        return _conv2d_jit(x, w, ep.scale, ep.bias, ep.residual, relu=ep.relu,
                           stride=stride, padding=padding, impl=impl)
    fh, fw, _, k = w.shape
    with trace.span("kernels.conv2d", impl=impl,
                    x_shape=list(x.shape), w_shape=list(w.shape),
                    stride=stride, padding=padding,
                    dtype=str(x.dtype)) as sp:
        out = _conv2d_jit(x, w, ep.scale, ep.bias, ep.residual, relu=ep.relu,
                          stride=stride, padding=padding, impl=impl)
        jax.block_until_ready(out)
        b, oh, ow, _ = out.shape
        halo = 0
        if impl == "pallas":
            sp.attrs["kernel"] = r.kernel
            if r.kernel == "im2col_gemm":
                sp.attrs["stationarity"] = r.stationarity.value
            else:
                # the conv the kernel runs: folded, where col_fold > 1
                xk, wk, pk = conv2d_kernel_shapes(x.shape, w.shape, stride,
                                                  padding)
                sp.attrs["col_fold"] = r.col_fold
                th = row_block(xk, wk, padding=pk,
                               has_res=ep.residual is not None)
                n_r = -(-oh // th)
                sp.attrs["row_blocks"] = n_r
                # each block past the first re-reads fh - 1 padded rows
                halo = ((n_r - 1) * (fh - 1) * (xk[2] + 2 * pk)
                        * xk[3] * x.dtype.itemsize)
        sp.attrs["flops"] = 2 * b * oh * ow * k * fh * fw * x.shape[-1]
        sp.attrs["bytes_touched"] = halo + _nbytes(x, w, out, ep.scale,
                                                   ep.bias, ep.residual)
        sp.attrs["tile_util"] = tile_util_conv2d(
            x.shape, w.shape, stride=stride, padding=padding,
            has_res=ep.residual is not None)
        _epilogue_attrs(sp, ep, out)
    return out


@functools.partial(jax.jit, static_argnames=("stride", "impl", "relu"))
def _conv1x1_jit(x, w, scale=None, bias=None, residual=None, *,
                 relu: bool = False, stride: int = 1, impl: str = "auto"):
    st = route(x.shape, (1, 1, *w.shape), stride).stationarity
    if stride != 1:
        x = x[:, ::stride, ::stride, :]
    b, h, wd, c = x.shape
    k = w.shape[-1]
    xf = x.reshape(b * h * wd, c)
    rf = residual.reshape(b * h * wd, k) if residual is not None else None
    if _resolve(impl) == "pallas":
        out = _tiled_matmul(xf, w, scale, bias, relu, rf, st)
    else:
        out = _ref.matmul_ref(xf, w, scale=scale, bias=bias, relu=relu,
                              residual=rf).astype(x.dtype)
    return out.reshape(b, h, wd, k)


def _tiled_matmul(xf, w, scale, bias, relu, rf, stationarity: Stationarity):
    """The pallas GEMM in the given stationarity, at the kernel's blocks."""
    mm = (matmul_weight_stationary
          if stationarity == Stationarity.WEIGHT_STATIONARY
          else matmul_act_stationary)
    return mm(xf, w, scale=scale, bias=bias, relu=relu, residual=rf,
              interpret=not _on_tpu())


def conv1x1(x, w, *, stride: int = 1, impl: str = "auto",
            epilogue: Epilogue | None = None):
    """Pointwise conv via the dual-stationarity GEMM (paper §III.B/C)."""
    ep = epilogue or _NO_EPILOGUE
    impl = _resolve(impl)
    if not trace.timed(x):
        return _conv1x1_jit(x, w, ep.scale, ep.bias, ep.residual, relu=ep.relu,
                            stride=stride, impl=impl)
    b, h, wd, c = x.shape
    rows = b * -(-h // stride) * -(-wd // stride)   # x[:, ::s, ::s] row count
    st = route(x.shape, (1, 1, *w.shape), stride).stationarity
    with trace.span("kernels.conv1x1", impl=impl,
                    x_shape=list(x.shape), w_shape=list(w.shape),
                    stride=stride, stationarity=st.value,
                    dtype=str(x.dtype)) as sp:
        out = _conv1x1_jit(x, w, ep.scale, ep.bias, ep.residual, relu=ep.relu,
                           stride=stride, impl=impl)
        jax.block_until_ready(out)
        sp.attrs["flops"] = 2 * rows * c * w.shape[-1]
        # A strided 1x1 subsamples BEFORE the GEMM, so only the strided view
        # of the input is ever read — count those rows, not the full fmap.
        sp.attrs["bytes_touched"] = (rows * c * x.dtype.itemsize
                                     + _nbytes(w, out, ep.scale, ep.bias,
                                               ep.residual))
        sp.attrs["tile_util"] = tile_util_gemm(rows, c, w.shape[-1], st)
        if impl == "pallas":
            sp.attrs["kernel"] = "gemm"
            sp.attrs["c_blocks"] = _c_blocks(c, w.shape[-1], st)
        _epilogue_attrs(sp, ep, out)
    return out


def _c_blocks(c: int, k: int, st: Stationarity) -> int:
    """Blocks of the GEMM's reduction axis over C that the kernel runs."""
    if st == Stationarity.WEIGHT_STATIONARY:
        bc = ws_blocks(c, k)[1]
    else:
        bc = min(_GEMM_BC, c)
    return -(-c // bc)


@functools.partial(jax.jit, static_argnames=("impl", "stationarity", "relu"))
def _gemm_jit(x, w, scale=None, bias=None, residual=None, *,
              relu: bool = False, impl: str = "auto",
              stationarity: Stationarity):
    if _resolve(impl) == "pallas":
        return _tiled_matmul(x, w, scale, bias, relu, residual, stationarity)
    return _ref.matmul_ref(x, w, scale=scale, bias=bias, relu=relu,
                           residual=residual).astype(x.dtype)


def gemm(x, w, *, impl: str = "auto",
         stationarity: Stationarity | None = None,
         epilogue: Epilogue | None = None):
    """(M, C) @ (C, K) with CARLA stationarity planning: ``stationarity``,
    else ``select_stationarity`` of the rows."""
    ep = epilogue or _NO_EPILOGUE
    impl = _resolve(impl)
    st = (stationarity if stationarity is not None
          else select_stationarity(x.shape[0]))
    if not trace.timed(x):
        return _gemm_jit(x, w, ep.scale, ep.bias, ep.residual, relu=ep.relu,
                         impl=impl, stationarity=st)
    with trace.span("kernels.gemm", impl=impl,
                    x_shape=list(x.shape), w_shape=list(w.shape),
                    stationarity=st.value, dtype=str(x.dtype)) as sp:
        out = _gemm_jit(x, w, ep.scale, ep.bias, ep.residual, relu=ep.relu,
                        impl=impl, stationarity=st)
        jax.block_until_ready(out)
        sp.attrs["flops"] = 2 * x.shape[0] * x.shape[1] * w.shape[-1]
        sp.attrs["bytes_touched"] = _nbytes(x, w, out, ep.scale, ep.bias,
                                            ep.residual)
        sp.attrs["tile_util"] = tile_util_gemm(x.shape[0], x.shape[1],
                                               w.shape[-1], st)
        _epilogue_attrs(sp, ep, out)
    return out


@functools.partial(jax.jit, static_argnames=("impl",))
def _conv1d_jit(x, w, *, impl: str = "auto"):
    if _resolve(impl) == "pallas":
        return _conv1d_pallas(x, w, interpret=not _on_tpu())
    return _ref.conv1d_causal_ref(x, w).astype(x.dtype)


def conv1d_causal(x, w, *, impl: str = "auto"):
    """Depthwise causal conv1d (Mamba2 short conv / RWKV token shift)."""
    impl = _resolve(impl)
    if not trace.timed(x):
        return _conv1d_jit(x, w, impl=impl)
    with trace.span("kernels.conv1d_causal", impl=impl,
                    x_shape=list(x.shape), w_shape=list(w.shape),
                    dtype=str(x.dtype)) as sp:
        out = _conv1d_jit(x, w, impl=impl)
        jax.block_until_ready(out)
        sp.attrs["flops"] = 2 * x.size * w.shape[0]
        sp.attrs["bytes_touched"] = _nbytes(x, w, out)
    return out
