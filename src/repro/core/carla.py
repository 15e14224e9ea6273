"""CARLA public API: reconfigurable convolution with per-layer mode dispatch.

``carla_conv`` is the paper's accelerator as a composable JAX op: given any
NHWC convolution, it plans the layer (:func:`plan_conv`) and runs the plan.
The plan carries both of the controller's decisions.  ``dataflow`` is the
mode the ASIC would have used (``core.modes.select_dataflow``), with the
analytic cost (cycles / DRAM accesses / PUF) the ASIC model predicts for
that layer, so a network built from ``carla_conv`` carries its own
performance model, exactly like the paper's evaluation methodology.
``route`` is what runs on the TPU (``core.route.route``): the Pallas
kernel, the GEMM's stationarity and the column fold, the same function
``kernels.ops`` branches on.

Passing ``epilogue=Epilogue(scale, bias, relu, residual)`` fuses folded-BN,
the shortcut add, and the activation into the kernel's flush step (see
``core.fuse``): the output feature map is written to HBM once instead of
round-tripping once per element-wise op — the TPU analogue of the paper's
on-chip partial-result residency.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.observability import trace
from .cost_model import LayerCost, layer_cost
from .fuse import Epilogue
from .modes import ConvLayer, Dataflow, select_dataflow
from .route import Route, route
from .sparsity import SparsityTag


_NO_EPILOGUE = Epilogue()

# What the kernel span measures of the dispatch it ran; the carla_conv span
# copies them from its child rather than computing them a second time.
_MEASURED = ("kernel", "stationarity", "col_fold", "images_per_step",
             "bytes_touched", "tile_util", "epilogue_hbm_saved")


@dataclass(frozen=True)
class ConvPlan:
    layer: ConvLayer
    dataflow: Dataflow          # the analytic controller rule's choice
    cost: LayerCost
    route: Route                # what the Pallas path runs


def plan_conv(x_shape: tuple[int, ...], w_shape: tuple[int, ...],
              stride: int = 1, padding: int = 0,
              name: str = "conv") -> ConvPlan:
    """Controller decision + analytic cost for a conv of the given shapes:
    the ASIC's ``dataflow`` and ``cost``, and the TPU's ``route``."""
    _, h, _, cin = x_shape
    fh, _, _, k = w_shape
    layer = ConvLayer(name, IL=h, IC=cin, K=k, FL=fh, S=stride, Z=padding)
    return ConvPlan(layer, select_dataflow(layer), layer_cost(layer),
                    route(x_shape, w_shape, stride, padding))


def _dispatch(x, w, plan: ConvPlan, stride: int, padding: int, impl: str,
              epilogue: Epilogue | None):
    if plan.route.kernel == "gemm":
        # Both 1x1 modes are the dual-stationarity GEMM.
        return ops.conv1x1(x, w[0, 0], stride=stride, impl=impl,
                           epilogue=epilogue)

    # 3x3 serial accumulation and 7x7 row decomposition share the
    # tap-accumulation kernel (the MXU removes the 3-tap register limit that
    # forced the ASIC's 21-piece split; see kernels/conv2d.py docstring).
    return ops.conv2d(x, w, stride=stride, padding=padding, impl=impl,
                      epilogue=epilogue)


def carla_conv(x: jnp.ndarray, w: jnp.ndarray, *, stride: int = 1,
               padding: int = 0, impl: str = "auto",
               epilogue: Epilogue | None = None,
               name: str = "conv",
               sparsity: SparsityTag | None = None) -> jnp.ndarray:
    """Reconfigurable convolution: dispatches on the controller's mode choice.

    x: (B, H, W, C); w: (FH, FW, C, K) (use (1, 1, C, K) or (C, K) for 1x1).
    epilogue: optional fused flush (folded-BN scale/bias, residual add, ReLU)
    applied on the fp32 accumulator before the single HBM writeback.
    sparsity: for a structured-pruned layer, the dense twin's channel counts
    (``core.sparsity.SparsityTag``) — the span then records ``keep_fraction``
    and ``dense_twin_macs`` so pruned-vs-dense is measurable per layer.

    The dispatch always runs under ``jax.named_scope(name)``, so inside a
    jitted forward every device op of the layer carries its name in the
    compiled program and in a profiler trace; that costs nothing at run time.
    With tracing enabled (``observability.trace``) an eager dispatch also
    records a ``carla_conv`` span carrying both sides of the paper's ledger:
    the dataflow the controller picked with its analytic ``LayerCost``
    (cycles / DRAM bytes / PUF), the epilogue combination that was fused
    (``epilogue=`` attr), and what its one ``kernels.*`` child span measured
    of the kernel that ran: ``kernel``, ``stationarity``, ``col_fold``,
    ``images_per_step``, ``bytes_touched``, ``tile_util`` and
    ``epilogue_hbm_saved``.  A dispatch traced inside a jit opens no span.
    """
    if w.ndim == 2:
        w = w[None, None]
    plan = plan_conv(x.shape, w.shape, stride, padding, name=name)

    with jax.named_scope(name):
        if not trace.timed(x):
            return _dispatch(x, w, plan, stride, padding, impl, epilogue)
        return _timed_dispatch(x, w, plan, stride, padding, impl, epilogue,
                               sparsity)


def _timed_dispatch(x, w, plan: ConvPlan, stride: int, padding: int,
                    impl: str, epilogue: Epilogue | None,
                    sparsity: SparsityTag | None):
    """An eager dispatch inside a ``carla_conv`` span (see ``carla_conv``)."""
    cost = plan.cost
    sparse_attrs = {}
    if sparsity is not None:
        sparse_attrs = {
            "pruned": True,
            "keep_fraction": sparsity.keep_fraction(plan.layer.IC,
                                                    plan.layer.K),
            "dense_twin_macs": sparsity.dense_twin(plan.layer).macs,
        }
    with trace.span(
            "carla_conv", layer=plan.layer.name,
            dataflow=plan.dataflow.value,
            epilogue=(epilogue or _NO_EPILOGUE).tag,
            x_shape=list(x.shape), w_shape=list(w.shape),
            stride=stride, padding=padding, batch=int(x.shape[0]),
            macs=cost.macs, dense_macs=plan.layer.dense_macs,
            analytic_cycles=cost.cycles,
            analytic_time_ms=cost.time_s * 1e3,
            analytic_dram_bytes=cost.dram_bytes,
            analytic_puf=cost.puf,
            **sparse_attrs) as sp:
        out = _dispatch(x, w, plan, stride, padding, impl, epilogue)
        jax.block_until_ready(out)
        (child,) = sp.children
        sp.attrs.update({k: child.attrs[k] for k in _MEASURED
                         if k in child.attrs})
    return out
