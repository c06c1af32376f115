"""Fused elementwise-chain Pallas kernel.

The fusion pass (§2.1) collapses chains of elementwise operators into one
`fused_elementwise` node; this kernel is its single-launch implementation —
"complete the whole computation within only one kernel launch to eliminate
the intermediate data movement overhead" (paper §1).  One block streams
HBM→VMEM→HBM exactly once regardless of chain length.

Tunable: block_rows (how many rows of the flattened (R, C) view per step).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tpu
from repro.kernels.ref import apply_activation

_BINARY = {"add": jnp.add, "mul": jnp.multiply, "sub": jnp.subtract, "div": jnp.divide}


def _chain_kernel(*refs, chain: Sequence[Dict[str, Any]], n_extra: int):
    x_ref, extra_refs, o_ref = refs[0], refs[1 : 1 + n_extra], refs[-1]
    x = x_ref[...]
    ei = 0
    for stage in chain:
        op = stage["op"] if isinstance(stage, dict) else stage
        if op in _BINARY:
            x = _BINARY[op](x, extra_refs[ei][...])
            ei += 1
        else:
            x = apply_activation(x, op)
    o_ref[...] = x


def fused_elementwise(
    x: jnp.ndarray,
    chain: Sequence[Dict[str, Any]],
    extras: Sequence[jnp.ndarray] = (),
    *,
    block_rows: int = 256,
) -> jnp.ndarray:
    """Apply `chain` to x in one kernel.  All extras must be broadcastable to
    x's shape; we require same-shape here (the fusion pass guarantees it)."""
    orig_shape = x.shape
    flat = x.reshape(-1)
    extras = [e.reshape(-1) for e in extras]
    n = flat.shape[0]
    # pick a lane-friendly 2-D view
    cols = 128
    rows = -(-n // cols)
    pad = rows * cols - n
    if pad:
        flat = jnp.pad(flat, (0, pad))
        extras = [jnp.pad(e, (0, pad)) for e in extras]
    x2 = flat.reshape(rows, cols)
    extras2 = [e.reshape(rows, cols) for e in extras]
    br = min(block_rows, rows)
    rt = -(-rows // br)
    if rows % br:
        extra_rows = rt * br - rows
        x2 = jnp.pad(x2, ((0, extra_rows), (0, 0)))
        extras2 = [jnp.pad(e, ((0, extra_rows), (0, 0))) for e in extras2]

    kernel = functools.partial(_chain_kernel, chain=tuple(
        tuple(sorted(s.items())) and s for s in chain), n_extra=len(extras2))
    spec = pl.BlockSpec((br, cols), lambda i: (i, 0))
    out = pl.pallas_call(
        kernel,
        grid=(rt,),
        in_specs=[spec] * (1 + len(extras2)),
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
        **tpu.pallas_call_args(),
    )(x2, *extras2)
    return out.reshape(-1)[:n].reshape(orig_shape)
