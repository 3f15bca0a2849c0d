"""Ops layer: native (C++) host-side runtime pieces and Pallas TPU kernels.

The reference's native machinery all lives in libraries below its Python
surface — NCCL collectives, cuDNN kernels, the DDP C++ reducer (SURVEY.md
§2B). Here the TPU compute path is XLA-lowered (convs/matmuls hit the MXU
without hand-written kernels; Pallas kernels where XLA underperforms), and
the host-side runtime pieces — topology introspection and a Gloo-style CPU
ring allreduce fallback for host coordination off-TPU — are native C++
(`tpu_dp/ops/native/`), bound via ctypes.

The Pallas kernels, a module each: `xent` (fused softmax cross-entropy),
`conv_block` (BN-apply + ReLU + conv chains), `qk_norm_rope` (per-head
RMSNorm and RoPE of q and k), `flash_block_diffusion` (flash attention under
a mask that is a function of the two indices: block-diffusion, causal),
`ssd_scan` (the selective state-space scan in its chunked form) and
`grouped_products` (the expert layers' grouped matrix products). The token
models import the last four from their modules.
"""

from tpu_dp.ops import native
from tpu_dp.ops._partition import interpret_kernels
from tpu_dp.ops.conv_block import (
    fused_affine_relu_conv,
    fused_affine_relu_conv_emit,
    fused_conv_bn,
)
from tpu_dp.ops.xent import mean_softmax_xent, softmax_xent

__all__ = [
    "native",
    "fused_affine_relu_conv",
    "fused_affine_relu_conv_emit",
    "fused_conv_bn",
    "interpret_kernels",
    "mean_softmax_xent",
    "softmax_xent",
]
