"""Tuned Pallas TPU kernels — WPK's generated-code lane.

The paper's compute hot-spots are exactly these: convolution (its headline
benchmark), the matmul family, and fused operators produced by graph fusion.
Each kernel module pairs with `ref.py` (pure-jnp oracle) and is exposed via
`ops.py` (jit-friendly wrappers with tuned-config dispatch).  On a TPU the
kernels compile natively with the tuned BlockSpec tiling; on any other
backend they run in the Pallas interpreter (`tpu.py` decides).
"""

from repro.kernels import ops, ref
from repro.kernels.ops import (
    attention,
    attention_decode,
    conv2d,
    fused_elementwise,
    matmul,
)

__all__ = [
    "ops",
    "ref",
    "matmul",
    "conv2d",
    "attention",
    "attention_decode",
    "fused_elementwise",
]
