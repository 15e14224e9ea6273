"""Which Pallas kernel runs a conv, decided from its shapes alone.

CARLA's controller picks a dataflow per layer from the layer's shape
(``core.modes.select_dataflow``, the ASIC's rule, which the analytic model
and the fidelity gate read).  On a TPU the same decision is which of the
three Pallas kernels runs the conv, and how: :func:`route` makes it, and it
is the only code that does.  ``kernels.ops`` branches on it to run a conv,
and ``core.carla.plan_conv`` carries it in the plan beside the ASIC's
dataflow, so the two cannot disagree.

* ``gemm``: a 1x1 (unpadded) is a GEMM over its pixels
  (``kernels.matmul``), in the stationarity ``modes.select_stationarity``
  picks from its rows.
* ``im2col_gemm``: a strided conv, or one whose patch row fits one 128-lane
  tile (:func:`runs_as_gemm`), is one GEMM over im2col patches
  (:func:`conv2d_gemm_shape`), in the stationarity its rows pick.
* ``conv2d``: every other conv runs the conv2d kernel, with :func:`col_fold`
  adjacent output columns folded into the lanes where its C is 32 or 64;
  :func:`conv2d_kernel_shapes` gives the conv the kernel then sees.

:func:`tile_util_conv2d` and :func:`tile_util_gemm` count the MXU work the
route pads in (logical FLOPs / padded FLOPs under the kernels' own blocks),
the TPU analogue of the paper's PUF.
"""
from __future__ import annotations

from typing import NamedTuple

from .modes import Stationarity, select_stationarity

LANES = 128     # the MXU's width, and the lanes a channel block occupies


class Route(NamedTuple):
    """What the Pallas path runs for one conv."""

    kernel: str                         # "conv2d" | "im2col_gemm" | "gemm"
    stationarity: Stationarity | None   # the GEMM's; None on the conv2d kernel
    col_fold: int                       # output columns in the lanes (1: none)


def route(x_shape, w_shape, stride: int = 1, padding: int = 0) -> Route:
    """The kernel, the GEMM's stationarity and the column fold of a conv of
    input ``(B, H, W, C)`` and weights ``(FH, FW, C, K)``."""
    b, h, wd, _ = x_shape
    fh, fw, _, _ = w_shape
    if fh * fw == 1 and padding == 0:
        rows = b * -(-h // stride) * -(-wd // stride)   # x[:, ::s, ::s]
        return Route("gemm", select_stationarity(rows), 1)
    if runs_as_gemm(w_shape, stride):
        m = conv2d_gemm_shape(x_shape, w_shape, stride, padding)[0]
        return Route("im2col_gemm", select_stationarity(m), 1)
    return Route("conv2d", None, col_fold(x_shape, w_shape, stride))


def runs_as_gemm(w_shape, stride: int) -> bool:
    """Whether the Pallas path runs a conv (not a 1x1) as an im2col GEMM:
    a strided one, since Mosaic refuses a strided slice inside the conv2d
    kernel, or a unit-stride one whose patch row ``FH*FW*C`` fits one
    128-lane tile (VGG-16's conv1_1: 27 columns), which the conv2d kernel
    would contract 3 lanes at a time."""
    fh, fw, cin, _ = w_shape
    return stride > 1 or (fh * fw > 1 and fh * fw * cin <= 128)


def col_fold(x_shape, w_shape, stride: int) -> int:
    """How many adjacent output columns the Pallas path folds into the
    lanes of a conv on the conv2d kernel: ``128 // C`` for a unit-stride
    conv of more than one tap whose C is below 128 and divides it (2 for
    VGG-16's conv1_2 and ResNet-50's conv2 3x3s, 4 for the pruned conv2
    3x3s' 32 channels), else 1.  Folded, each dot contracts and writes 128
    lanes, where C = K = 64 would fill a quarter of the MXU."""
    fh, fw, _, _ = w_shape
    cin = x_shape[3]
    if (stride != 1 or fh * fw == 1 or runs_as_gemm(w_shape, stride)
            or cin >= LANES or LANES % cin):
        return 1
    return LANES // cin


def conv2d_kernel_shapes(x_shape, w_shape, stride: int, padding: int):
    """``(x_shape, w_shape, padding)`` of the conv the conv2d kernel runs
    (``kernels.ops._col_fold``): with :func:`col_fold` ``f > 1``, the input
    padded once and read as ``f*C`` lanes of ``OWf + TWf - 1`` column
    groups, ``OWf = ceil(OW / f)`` rounded up to a multiple of 8 (so the
    kernel's ``(th, OWf, 128)`` windows flatten without a relayout), the
    weights ``(FH, TWf, f*C, f*K)`` with ``TWf = ceil((f + FW - 1) / f)``
    column taps, and padding 0; else the conv's own."""
    f = col_fold(x_shape, w_shape, stride)
    if f == 1:
        return tuple(x_shape), tuple(w_shape), padding
    b, h, wd, cin = x_shape
    fh, fw, _, k = w_shape
    ow = wd + 2 * padding - fw + 1
    twf = -(-(f + fw - 1) // f)
    owf = _ceil_to(-(-ow // f), 8)
    return ((b, h + 2 * padding, owf + twf - 1, f * cin),
            (fh, twf, f * cin, f * k), 0)


def conv2d_gemm_shape(x_shape, w_shape, stride: int,
                      padding: int) -> tuple[int, int, int]:
    """(M, C, K) of the im2col GEMM a conv runs as on the Pallas path
    (:func:`runs_as_gemm`): one row per output pixel, one column per (tap,
    input channel) of the filter padded with zero taps to
    ``stride * ceil(F / stride)`` a side (``kernels.ops._im2col``): 192
    columns for the 7x7/2 stem, not 147; 27 for VGG-16's conv1_1."""
    b, h, w, cin = x_shape
    fh, fw, _, k = w_shape
    oh = (h - fh + 2 * padding) // stride + 1
    ow = (w - fw + 2 * padding) // stride + 1
    return (b * oh * ow,
            _ceil_to(fh, stride) * _ceil_to(fw, stride) * cin, k)


def _ceil_to(n: int, m: int) -> int:
    return -(-n // m) * m


# ---------------------------------------------------------------------------
# tile_util — padding waste, the TPU analogue of the paper's PUF.  They read
# the kernels' own blocks; imported in the functions, since importing
# ``repro.kernels`` here would cycle through ``kernels.ops``.
# ---------------------------------------------------------------------------
def tile_util_conv2d(x_shape, w_shape, *, stride: int = 1, padding: int = 0,
                     has_res: bool = False) -> float:
    """Logical FLOPs / the MXU's FLOPs under the conv kernel's tiling: each
    channel block of C and K takes 128 lanes of the MXU (a block of 64
    fills half of each), a last row block that runs past the plane computes
    padded rows, and a folded conv (:func:`col_fold`) computes its zero taps
    and padded column groups.  For a conv that runs as an im2col GEMM, the
    padded FLOPs of that GEMM's tiling, whose zero taps count as padding."""
    from repro.kernels.conv2d import step_tiles
    r = route(x_shape, w_shape, stride, padding)
    if r.kernel == "im2col_gemm":
        m, c, k = conv2d_gemm_shape(x_shape, w_shape, stride, padding)
        taps = w_shape[0] * w_shape[1] * w_shape[2]
        return taps / c * tile_util_gemm(m, c, k, r.stationarity)
    fh, fw, cin, k = w_shape
    oh = x_shape[1] - fh + 2 * padding + 1
    ow = x_shape[2] - fw + 2 * padding + 1
    xk, wk, pk = conv2d_kernel_shapes(x_shape, w_shape, stride, padding)
    _, th, bk, bc = step_tiles(xk, wk, padding=pk, has_res=has_res)
    owk = xk[2] - wk[1] + 2 * pk + 1
    mxu = (wk[0] * wk[1] * -(-wk[2] // bc) * _ceil_to(bc, LANES)
           * -(-wk[3] // bk) * _ceil_to(bk, LANES) * _ceil_to(oh, th) * owk)
    return fh * fw * cin * k * oh * ow / mxu


def tile_util_gemm(m: int, c: int, k: int,
                   stationarity: Stationarity) -> float:
    """Logical FLOPs / padded FLOPs for the GEMM under either stationarity."""
    from repro.kernels.matmul import BC, BK, BM, ws_blocks
    if stationarity == Stationarity.WEIGHT_STATIONARY:
        # (M, C) resident; K and, in C blocks, C are padded
        bk, bc = ws_blocks(c, k)
        return (k * c) / (_ceil_to(k, bk) * _ceil_to(c, bc))
    bm, bk, bc = min(BM, m), min(BK, k), min(BC, c)
    return (m * c * k) / (_ceil_to(m, bm) * _ceil_to(c, bc) * _ceil_to(k, bk))
