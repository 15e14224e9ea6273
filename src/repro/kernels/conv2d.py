"""CARLA 3x3-mode convolution on TPU — output-stationary serial accumulation.

The paper's §III.A dataflow, transplanted to the TPU memory hierarchy:

* **Output-stationary accumulation**: the output tile lives in an fp32 VMEM
  scratch across the whole reduction (filter taps x input-channel blocks) —
  CARLA's partial results living in the wide SRAM until a sub-out-fmap is done.
* **Serial accumulation over filter rows**: the kernel loops filter rows
  (outer) then columns (inner), accumulating shifted input-window GEMMs — the
  MXU-era analogue of the 3-PE accumulator chain.  The ASIC needed to split
  rows into <=3-tap pieces (§III.D, 21 pieces for 7x7) because a CU has 3
  cascaded PEs; the MXU has no such register-width limit, so each row is one
  loop level and the 7x7 decomposition lives only in the analytic model.
* **Unit stride only**: every tap window is a plain shifted slice.  Mosaic
  refuses a strided in-kernel slice, so ``kernels.ops`` runs strided convs
  (ResNet-50's 7x7/2 stem) as an im2col GEMM on the matmul kernels instead,
  its patches built by space-to-depth and unit-stride slices in XLA.  A
  3x3 over 3 channels (VGG-16's conv1_1) runs that way too: its 27 patch
  columns fill one lane tile, where this kernel would contract 3 lanes.
* **Full lanes, by folding columns**: a channel block narrower than the
  MXU's 128 lanes wastes the rest of them in both the contraction and the
  output (C = K = 64 uses a quarter of the MXU).  For a conv whose C is 32
  or 64, ``kernels.ops`` folds ``f = 128 / C`` adjacent output columns into
  the lanes before calling this kernel (``core.route.col_fold``): the
  input becomes ``f*C`` lanes per column group, the weights ``(FH, TWf,
  f*C, f*K)`` with the zero taps of the fold, and the output ``f*K`` lanes
  per group.  This kernel runs the folded conv as any other, at padding 0.
* **Feedback-path reuse, in row blocks with a halo**: a grid step holds
  ``th`` output rows and reads the ``th + FH - 1`` padded input rows they
  need (its own rows and a halo of ``FH - 1``) into VMEM *once* per (batch,
  row block, channel block), then re-reads them for every tap: within a
  block the halo rows are never re-fetched from HBM, which is the economics
  of the paper's pipeline feedback paths.  A plane in row blocks takes its
  whole C in one channel block.  ``th`` is the most rows whose
  step fits :data:`VMEM_BUDGET` (:func:`row_block`, the one place that
  chooses it): every ResNet-50 3x3 (56x56 and below) takes the whole plane
  in one block, VGG-16's 224x224 and 112x112 layers take several (the
  image decomposition of arXiv 1709.05116), and each block past the first
  re-reads ``FH - 1`` rows of the block above it.
* **Paired-SRAM overlap**: Pallas grid pipelining double-buffers the streamed
  weight tiles while compute proceeds.
* **Fused flush epilogue**: on the last reduction step the kernel can apply a
  per-channel scale/bias (inference-folded BN), a residual add, and ReLU
  *directly on the fp32 VMEM accumulator* before the single HBM writeback.
  Unfused, each of those element-wise steps is a full read+write round-trip
  of the output feature map through HBM; fused, the feature map crosses the
  HBM boundary exactly once — the TPU twin of CARLA keeping partial results
  on-chip until a sub-out-fmap is complete, and of MMIE-style in-pipeline
  activation before writeback.  The scale/bias ride in as one tiny (2, K)
  operand; the residual streams in with the same block map as the output, so
  it is read once (it would be read once by the unfused add too).

Zero padding is applied by index arithmetic in the wrapper (pad once in HBM);
the paper's MUX-based zero-pad insertion is register-level micro-architecture
with no TPU analogue — the *goal* (no wasted work on pads) holds here by
construction.

Layout: NHWC activations, HWIO weights, fp32 accumulation (MXU native).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .matmul import mxu_dot

# Channel tiles: the ones every dispatch of ``kernels.ops`` runs, and the ones
# ``core.route.tile_util_conv2d`` counts.  ``conv2d``'s ``bk``/``bc`` keyword
# arguments exist for tests of ragged tiles.
BK = 128   # output-channel tile
BC = 128   # input-channel tile


# VMEM one grid step may hold: the input rows and the weight tile, each
# double-buffered, lane-padded to 128, and the fp32 accumulator with the
# output (and residual) block, double-buffered, counted at their ``bk``
# columns.  Against the v5e compiler, whose scoped VMEM is 16 MiB, this count
# put the largest row blocks that compiled at 14.2-15.6 MB and the smallest
# refused at 17.4 MB (VGG-16's conv1_2 and conv2_1 shapes); 14 MiB leaves
# room below that.
VMEM_BUDGET = 14 * 2**20


def _sublanes(n: int) -> int:
    return -(-n // 8) * 8


def _lanes(n: int) -> int:
    return -(-n // 128) * 128


def row_block(x_shape, w_shape, *, padding: int, bk: int = BK, bc: int = BC,
              has_res: bool = False) -> int:
    """Output rows per grid step: the whole plane when its step fits
    :data:`VMEM_BUDGET`, else the most rows that fit with the whole C in one
    channel block, evened out over the blocks so that the last one is at
    most a few rows short (it is padded).

    Row blocks take the whole C because the v5e compiler reserves far more
    VMEM for element-offset rows read in several channel blocks than for
    one: at 112x112, C = 256 and 64 rows, 26.2 MB in two blocks of 128
    against under 16 MiB in one of 256."""
    _, h, wd, cin = x_shape
    fh, fw, _, k = w_shape
    oh, ow = h - fh + 2 * padding + 1, wd - fw + 2 * padding + 1
    bk = min(bk, k)

    def rows(bc: int) -> int:
        weights = 2 * fh * fw * _sublanes(bc) * _lanes(bk) * 4
        in_row = 2 * _sublanes(wd + 2 * padding) * _lanes(bc) * 4
        out_row = (1 + 2 + 2 * has_res) * _sublanes(ow) * bk * 4
        return (VMEM_BUDGET - weights - (fh - 1) * in_row) // (in_row + out_row)

    if rows(min(bc, cin)) >= oh:
        return oh
    n = -(-oh // max(rows(cin), 1))
    return -(-oh // n)


def step_tiles(x_shape, w_shape, *, padding: int, bk: int = BK, bc: int = BC,
               has_res: bool = False) -> tuple[int, int, int]:
    """``(th, bk, bc)`` of a grid step: :func:`row_block`'s rows and the
    channel tiles clamped to the layer, the whole C when the plane is in
    row blocks.  The one place that decides them."""
    cin, k = x_shape[3], w_shape[3]
    oh = x_shape[1] - w_shape[0] + 2 * padding + 1
    th = row_block(x_shape, w_shape, padding=padding, bk=bk, bc=bc,
                   has_res=has_res)
    return th, min(bk, k), cin if th < oh else min(bc, cin)


def _conv2d_kernel(*refs, fh: int, fw: int, n_c: int, c_axis: int,
                   has_sb: bool, has_res: bool, relu: bool):
    """grid = (B, OH/th, K/bk, C/bc), or (B, K/bk, C/bc) with one row block;
    c innermost (reduction axis).

    refs = (x_ref, w_ref, [sb_ref], [res_ref], o_ref, acc_ref):
      x_ref:   (1, th + fh - 1, WP, bc) padded input rows of the block and
               its halo (VMEM-resident across taps)
      w_ref:   (fh, fw, bc, bk) weight tile (streamed)
      sb_ref:  (2, bk) fp32 — row 0 scale, row 1 bias (when has_sb)
      res_ref: (1, th, OW, bk) residual block (when has_res)
      o_ref:   (1, th, OW, bk); acc_ref: fp32 (th, OW, bk) scratch.
    """
    it = iter(refs)
    x_ref, w_ref = next(it), next(it)
    sb_ref = next(it) if has_sb else None
    res_ref = next(it) if has_res else None
    o_ref, acc_ref = next(it), next(it)

    c = pl.program_id(c_axis)

    @pl.when(c == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    th, ow, bk = acc_ref.shape
    # Serial accumulation: filter rows outer (the CU chain), columns inner.
    for r in range(fh):
        for s in range(fw):
            window = x_ref[0, r:r + th, s:s + ow, :]          # (th, OW, bc)
            acc_ref[...] += mxu_dot(window.reshape(th * ow, -1),
                                    w_ref[r, s]).reshape(th, ow, bk)

    @pl.when(c == n_c - 1)
    def _flush():
        # Fused epilogue: applied on the fp32 accumulator, then ONE writeback.
        y = acc_ref[...]
        if has_sb:
            y = y * sb_ref[0][None, None, :] + sb_ref[1][None, None, :]
        if has_res:
            y = y + res_ref[0].astype(jnp.float32)
        if relu:
            y = jnp.maximum(y, 0.0)
        o_ref[0] = y.astype(o_ref.dtype)


def _pack_scale_bias(scale, bias, k: int, kpad: int):
    """Stack (scale, bias) into one fp32 (2, K+kpad) operand (defaults 1/0)."""
    sc = jnp.ones((k,), jnp.float32) if scale is None else scale.astype(jnp.float32)
    bi = jnp.zeros((k,), jnp.float32) if bias is None else bias.astype(jnp.float32)
    sb = jnp.stack([sc, bi])
    return jnp.pad(sb, ((0, 0), (0, kpad)))


def conv2d(x: jnp.ndarray, w: jnp.ndarray, *, padding: int = 0,
           bk: int = BK, bc: int = BC,
           scale: jnp.ndarray | None = None, bias: jnp.ndarray | None = None,
           relu: bool = False, residual: jnp.ndarray | None = None,
           interpret: bool) -> jnp.ndarray:
    """Unit-stride conv.  x: (B, H, W, C), w: (FH, FW, C, K) -> (B, OH, OW, K).

    scale/bias ((K,)), residual ((B, OH, OW, K)) and relu are fused into the
    flush step — see the module docstring's fused-flush design note.  Each
    grid step computes :func:`step_tiles`' ``th`` output rows; a last block
    that runs past the plane is computed on zero rows and cut.
    """
    b, h, wd, cin = x.shape
    fh, fw, cin2, k = w.shape
    assert cin == cin2, (x.shape, w.shape)
    oh = h - fh + 2 * padding + 1
    ow = wd - fw + 2 * padding + 1
    has_sb = scale is not None or bias is not None
    has_res = residual is not None
    th, bk, bc = step_tiles(x.shape, w.shape, padding=padding, bk=bk, bc=bc,
                            has_res=has_res)
    n_r = -(-oh // th)
    ohp = n_r * th          # rows computed, the last block's padding included
    # Pad: spatial zero-pads (once, in HBM; the bottom also covers the last
    # block's window) + channel pads to tile multiples.
    cpad = (-cin) % bc
    kpad = (-k) % bk
    xp = jnp.pad(x, ((0, 0), (padding, padding + ohp - oh),
                     (padding, padding), (0, cpad)))
    wp = jnp.pad(w, ((0, 0), (0, 0), (0, cpad), (0, kpad)))
    hp, wp_ = xp.shape[1], xp.shape[2]
    n_c = (cin + cpad) // bc
    n_k = (k + kpad) // bk

    def spec(block, index):
        """A BlockSpec on the grid that runs: with one row block the grid
        has no row axis, as before row blocks existed."""
        if n_r == 1:
            return pl.BlockSpec(block, lambda i, j, l: index(i, 0, j, l))
        return pl.BlockSpec(block, index)

    if n_r == 1:
        # the whole plane: one input block per (b, c) visit
        x_spec = spec((1, hp, wp_, bc), lambda i, r, j, l: (i, 0, 0, l))
    else:
        # rows [r*th, r*th + th + fh - 1): the block's own and its halo,
        # addressed by element offset since consecutive windows overlap
        # (Mosaic takes element offsets on every axis or on none)
        x_spec = spec((pl.Element(1), pl.Element(th + fh - 1),
                       pl.Element(wp_), pl.Element(bc)),
                      lambda i, r, j, l: (i, r * th, 0, 0))
    operands = [xp, wp]
    in_specs = [
        x_spec,
        # weight tile: streamed
        spec((fh, fw, bc, bk), lambda i, r, j, l: (0, 0, l, j)),
    ]
    if has_sb:
        operands.append(_pack_scale_bias(scale, bias, k, kpad))
        in_specs.append(spec((2, bk), lambda i, r, j, l: (0, j)))
    if has_res:
        assert residual.shape == (b, oh, ow, k), (residual.shape, (b, oh, ow, k))
        operands.append(jnp.pad(residual, ((0, 0), (0, ohp - oh), (0, 0),
                                           (0, kpad))))
        in_specs.append(spec((1, th, ow, bk), lambda i, r, j, l: (i, r, 0, j)))

    grid = (b, n_r, n_k, n_c) if n_r > 1 else (b, n_k, n_c)
    out = pl.pallas_call(
        functools.partial(_conv2d_kernel, fh=fh, fw=fw, n_c=n_c,
                          c_axis=len(grid) - 1,
                          has_sb=has_sb, has_res=has_res, relu=relu),
        grid=grid,
        in_specs=in_specs,
        out_specs=spec((1, th, ow, bk), lambda i, r, j, l: (i, r, 0, j)),
        out_shape=jax.ShapeDtypeStruct((b, ohp, ow, k + kpad), x.dtype),
        scratch_shapes=[pltpu.VMEM((th, ow, bk), jnp.float32)],
        # the name is a contract: a profiler trace and the compiled text
        # name the kernel by it, and the benchmark's reduction matches it
        name="_conv2d_kernel",
        interpret=interpret,
    )(*operands)
    return out[:, :oh, :, :k]
