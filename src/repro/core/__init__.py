"""CARLA core: the paper's contribution as composable JAX modules."""
from . import route
from .carla import ConvPlan, carla_conv, plan_conv
from .cost_model import (
    LayerCost,
    NetworkCost,
    epilogue_dram_delta,
    epilogue_dram_delta_bytes,
    layer_cost,
    network_cost,
    resnet50_cost,
    vgg16_cost,
)
from .fuse import Epilogue, apply_epilogue, fold_bn, fold_bn_into_conv
from .modes import (
    ConvLayer,
    Dataflow,
    Stationarity,
    select_dataflow,
    select_stationarity,
)
from .networks import (
    resnet50_conv_layers,
    resnet50_projection_shortcuts,
    smoke_conv_layers,
    sparse_conv_layers,
    vgg16_conv_layers,
)
from .sparsity import (
    SparsityTag,
    prune_bn,
    prune_conv_weights,
    prune_plan,
    topk_channel_mask,
)

__all__ = [
    "ConvLayer", "ConvPlan", "Dataflow", "Epilogue", "LayerCost",
    "NetworkCost", "SparsityTag", "Stationarity",
    "apply_epilogue", "carla_conv",
    "epilogue_dram_delta", "epilogue_dram_delta_bytes", "fold_bn",
    "fold_bn_into_conv", "layer_cost",
    "network_cost", "plan_conv", "prune_bn", "prune_conv_weights",
    "prune_plan", "route",
    "resnet50_conv_layers", "resnet50_projection_shortcuts", "resnet50_cost",
    "select_dataflow", "select_stationarity", "smoke_conv_layers",
    "sparse_conv_layers", "topk_channel_mask",
    "vgg16_conv_layers", "vgg16_cost",
]
