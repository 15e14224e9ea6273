"""Pallas TPU kernels + jnp oracles.

Every kernel takes ``interpret`` with no default; ``kernels.ops`` decides it
from the platform (compiled by Mosaic on a TPU, interpreted elsewhere).
"""
from . import ops, ref
from .conv1d import conv1d_causal
from .conv2d import conv2d
from .decode_attention import decode_attention
from .flash_attention import flash_attention_fused
from .matmul import matmul_act_stationary, matmul_weight_stationary

__all__ = [
    "conv1d_causal", "conv2d", "decode_attention",
    "flash_attention_fused",
    "matmul_act_stationary", "matmul_weight_stationary", "ops", "ref",
]
