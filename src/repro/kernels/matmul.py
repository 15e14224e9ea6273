"""CARLA dual-stationarity GEMM — the paper's 1x1-mode operand swap on TPU.

Two Pallas kernels implementing the same GEMM ``(M, C) @ (C, K)`` with opposite
residency choices, mirroring the paper's §III.B / §III.C reconfiguration:

* **activation-stationary** (§III.B analogue): the activation row-block
  ``(bm, C)`` is fetched into VMEM *once* per M-block (its BlockSpec index map
  ignores the k and c grid axes, so Pallas keeps it resident) while weight
  tiles ``(bc, bk)`` stream past it.  The output tile is accumulated
  output-stationary in an fp32 VMEM scratch, exactly like CARLA's partial
  results living in the wide SRAM pair.  Use when M (tokens) >= one MXU tile:
  training / prefill.

* **weight-stationary** (§III.C analogue): M is tiny (batch-1 conv5 1x1s,
  a classifier at a small batch), so the activation is resident and the
  weights stream through exactly once — Eq (11)'s "each filter weight is
  only fetched once".  When a whole-C column block ``(C, bk)`` fits VMEM
  (ResNet-50's conv5 1x1s), the whole ``(M, C)`` activation stays resident
  and each block is one grid step.  When it does not (VGG-16's fc6, C =
  25088), a reduction axis over C streams ``(bc, bk)`` weight blocks, whole
  weight rows where 128 of them fit, past ``(M, bc)`` slices of the
  activation into an fp32 accumulator.  :func:`ws_blocks` chooses.  Use
  when M < one MXU tile.

Both kernels accept the same fused epilogue as ``conv2d``: per-column
scale/bias (folded BN), a residual operand, and ReLU, applied on the fp32
accumulator in the flush step so the output crosses HBM exactly once (the
1x1 convs of a bottleneck block route here via ``ops.conv1x1``).

``kernels.ops`` runs the variant ``core.route.route`` picks (by
``core.modes.select_stationarity``, the software twin of CARLA's controller)
and decides ``interpret`` from the platform.  Grid pipelining
double-buffers the streamed operand, the TPU analogue of the paper's paired
wide/narrow SRAMs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# MXU-aligned blocks of the activation-stationary kernel: the ones every
# dispatch of ``kernels.ops`` runs, and the ones ``core.route.tile_util_gemm``
# counts.  The keyword arguments below exist for tests of ragged blocks.
BM, BK, BC = 128, 128, 512
# VMEM for the weight-stationary kernel's streamed weight blocks, fp32,
# double-buffered: blocks of up to 4 MiB keep the DMA engine busy between
# grid steps.
WS_BUDGET = 8 * 2**20


def _pad_to(x: jnp.ndarray, axis: int, mult: int) -> jnp.ndarray:
    rem = (-x.shape[axis]) % mult
    if rem == 0:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, rem)
    return jnp.pad(x, pads)


def _pack_scale_bias(scale, bias, k: int, bk: int) -> jnp.ndarray:
    """Stack (scale, bias) into one fp32 (2, K-padded) operand (defaults 1/0)."""
    sc = jnp.ones((k,), jnp.float32) if scale is None else scale.astype(jnp.float32)
    bi = jnp.zeros((k,), jnp.float32) if bias is None else bias.astype(jnp.float32)
    return _pad_to(jnp.stack([sc, bi]), 1, bk)


def mxu_dot(a, b):
    """a @ b with an fp32 accumulator.  fp32 operands contract at full fp32
    precision: Mosaic's default rounds them to bf16, which on a v5e put
    ResNet-50's logits ~3e-3 (relative) off the fp32 reference."""
    precision = lax.Precision.HIGHEST if a.dtype == jnp.float32 else None
    return jnp.dot(a, b, precision=precision,
                   preferred_element_type=jnp.float32)


def _epilogue(y, sb_ref, res_ref, relu: bool):
    """Apply the fused epilogue to an fp32 tile right before writeback."""
    if sb_ref is not None:
        y = y * sb_ref[0][None, :] + sb_ref[1][None, :]
    if res_ref is not None:
        y = y + res_ref[...].astype(jnp.float32)
    if relu:
        y = jnp.maximum(y, 0.0)
    return y


# --------------------------- activation-stationary ---------------------------
def _mm_act_stationary_kernel(*refs, n_c: int, bc: int,
                              has_sb: bool, has_res: bool, relu: bool):
    """grid = (M/bm, K/bk, C/bc); c innermost is the reduction axis."""
    it = iter(refs)
    x_ref, w_ref = next(it), next(it)
    sb_ref = next(it) if has_sb else None
    res_ref = next(it) if has_res else None
    o_ref, acc_ref = next(it), next(it)

    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Slice the resident activation block; stream the weight tile past it.
    # One channel block is read whole: a slice narrower than 128 lanes at a
    # traced offset is refused by Mosaic (C=64/32 1x1s).
    xs = x_ref[...] if n_c == 1 else x_ref[:, pl.ds(c * bc, bc)]
    acc_ref[...] += mxu_dot(xs, w_ref[...])

    @pl.when(c == n_c - 1)
    def _flush():
        y = _epilogue(acc_ref[...], sb_ref, res_ref, relu)
        o_ref[...] = y.astype(o_ref.dtype)


def matmul_act_stationary(x: jnp.ndarray, w: jnp.ndarray, *,
                          bm: int = BM, bk: int = BK, bc: int = BC,
                          scale: jnp.ndarray | None = None,
                          bias: jnp.ndarray | None = None,
                          relu: bool = False,
                          residual: jnp.ndarray | None = None,
                          interpret: bool) -> jnp.ndarray:
    """(M, C) @ (C, K); activation row-block VMEM-resident, weights stream."""
    m, c = x.shape
    c2, k = w.shape
    assert c == c2, (x.shape, w.shape)
    bm, bk, bc = min(bm, m), min(bk, k), min(bc, c)
    xp = _pad_to(_pad_to(x, 0, bm), 1, bc)
    wp = _pad_to(_pad_to(w, 0, bc), 1, bk)
    mp, cp = xp.shape
    kp = wp.shape[1]
    n_c = cp // bc

    has_sb = scale is not None or bias is not None
    has_res = residual is not None
    operands = [xp, wp]
    in_specs = [
        # resident: index map ignores (k, c) -> fetched once per m block
        pl.BlockSpec((bm, cp), lambda i, j, l: (i, 0)),
        # streamed weight tiles
        pl.BlockSpec((bc, bk), lambda i, j, l: (l, j)),
    ]
    if has_sb:
        operands.append(_pack_scale_bias(scale, bias, k, bk))
        in_specs.append(pl.BlockSpec((2, bk), lambda i, j, l: (0, j)))
    if has_res:
        assert residual.shape == (m, k), (residual.shape, (m, k))
        operands.append(_pad_to(_pad_to(residual, 0, bm), 1, bk))
        in_specs.append(pl.BlockSpec((bm, bk), lambda i, j, l: (i, j)))

    out = pl.pallas_call(
        functools.partial(_mm_act_stationary_kernel, n_c=n_c, bc=bc,
                          has_sb=has_sb, has_res=has_res, relu=relu),
        grid=(mp // bm, kp // bk, n_c),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bk), lambda i, j, l: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, kp), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bk), jnp.float32)],
        # the name is a contract: a profiler trace and the compiled text
        # name the kernel by it, and the benchmark's reduction matches it
        name="_mm_act_stationary_kernel",
        interpret=interpret,
    )(*operands)
    return out[:m, :k]


# ---------------------------- weight-stationary ------------------------------
def _weight_vmem(rows: int, cols: int) -> int:
    """VMEM of a double-buffered fp32 (rows, cols) weight block, tiled (8, 128)."""
    return 2 * 4 * (-(-rows // 8) * 8) * (-(-cols // 128) * 128)


def ws_blocks(c: int, k: int, bk: int | None = None) -> tuple[int, int]:
    """``(bk, bc)`` of the weight-stationary GEMM; ``bc == c`` is one C block.

    Unless a ``bk`` is given, ``bk`` divides K (a whole-K block where
    128 does not), so the weights are not copied to a padded array on each
    call.  The whole C is one block when its ``(C, bk)`` weight block fits
    :data:`WS_BUDGET`.  Otherwise the kernel streams whole weight rows
    (``bk = K``) where 128 of them fit, each block then one contiguous
    stretch of HBM, in the most rows that fit, preferring a count that
    divides C."""
    if bk is None:
        bk = min(BK, k)
        if k % bk:
            bk = k
    bk = min(bk, k)
    if _weight_vmem(c, bk) <= WS_BUDGET:
        return bk, c
    if _weight_vmem(128, k) <= WS_BUDGET:
        bk = k
    fit = max(128, WS_BUDGET // _weight_vmem(128, bk) * 128)
    divisors = [d for d in range(128, fit + 1, 128) if c % d == 0]
    return bk, max(divisors) if divisors else fit


def _mm_weight_stationary_kernel(*refs, n_c: int, has_sb: bool,
                                 has_res: bool, relu: bool):
    """grid = (K/bk,) with x whole and each weight block fetched once, or
    (K/bk, C/bc), c innermost, into an fp32 accumulator."""
    it = iter(refs)
    x_ref, w_ref = next(it), next(it)
    sb_ref = next(it) if has_sb else None
    res_ref = next(it) if has_res else None
    o_ref = next(it)
    if n_c == 1:
        y = mxu_dot(x_ref[...], w_ref[...])
        o_ref[...] = _epilogue(y, sb_ref, res_ref, relu).astype(o_ref.dtype)
        return
    acc_ref = next(it)
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += mxu_dot(x_ref[...], w_ref[...])

    @pl.when(c == n_c - 1)
    def _flush():
        y = _epilogue(acc_ref[...], sb_ref, res_ref, relu)
        o_ref[...] = y.astype(o_ref.dtype)


def matmul_weight_stationary(x: jnp.ndarray, w: jnp.ndarray, *,
                             bk: int | None = None,
                             scale: jnp.ndarray | None = None,
                             bias: jnp.ndarray | None = None,
                             relu: bool = False,
                             residual: jnp.ndarray | None = None,
                             interpret: bool) -> jnp.ndarray:
    """(M, C) @ (C, K) with small M: a weight stream (:func:`ws_blocks`)."""
    m, c = x.shape
    c2, k = w.shape
    assert c == c2, (x.shape, w.shape)
    bk, bc = ws_blocks(c, k, bk)
    wp = _pad_to(_pad_to(w, 1, bk), 0, bc)
    cp, kp = wp.shape
    n_c = cp // bc

    def spec(block, index):
        """A BlockSpec on the grid that runs: with one C block the grid has
        no C axis."""
        if n_c == 1:
            return pl.BlockSpec(block, lambda j: index(j, 0))
        return pl.BlockSpec(block, index)

    has_sb = scale is not None or bias is not None
    has_res = residual is not None
    operands = [_pad_to(x, 1, bc), wp]
    in_specs = [
        spec((m, bc), lambda j, l: (0, l)),     # activations, a C block each
        spec((bc, bk), lambda j, l: (l, j)),    # weights stream once
    ]
    if has_sb:
        operands.append(_pack_scale_bias(scale, bias, k, bk))
        in_specs.append(spec((2, bk), lambda j, l: (0, j)))
    if has_res:
        assert residual.shape == (m, k), (residual.shape, (m, k))
        operands.append(_pad_to(residual, 1, bk))
        in_specs.append(spec((m, bk), lambda j, l: (0, j)))

    out = pl.pallas_call(
        functools.partial(_mm_weight_stationary_kernel, n_c=n_c,
                          has_sb=has_sb, has_res=has_res, relu=relu),
        grid=(kp // bk,) if n_c == 1 else (kp // bk, n_c),
        in_specs=in_specs,
        out_specs=spec((m, bk), lambda j, l: (0, j)),
        out_shape=jax.ShapeDtypeStruct((m, kp), x.dtype),
        scratch_shapes=([] if n_c == 1
                        else [pltpu.VMEM((m, bk), jnp.float32)]),
        # the name is a contract: a profiler trace and the compiled text
        # name the kernel by it, and the benchmark's reduction matches it
        name="_mm_weight_stationary_kernel",
        interpret=interpret,
    )(*operands)
    return out[:, :k]
