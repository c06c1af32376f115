"""Tuned Pallas TPU matmul — the WPK "generated code" for the matmul family.

Schedule knobs (from `MatmulTemplate`): block sizes (bm, bn, bk), grid-major
`order` ('mn' keeps an A row-band resident across the n sweep, 'nm' keeps a
B column-band resident), and `k_unroll` (compiler hint only — the MXU
pipeline depth; it does not change the math).

The K axis is the innermost ('arbitrary') grid dimension with an f32 VMEM
accumulator; the epilogue optionally fuses bias + activation (the graph
fusion pass emits `fused_matmul` nodes that land here — one kernel launch for
matmul+bias+act, the paper's in-placed fused-operator implementation).

Inputs are padded to block multiples by the `ops.py` wrapper, so zero
K-padding contributes nothing to the accumulator and M/N padding is sliced
off the output.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tpu
from repro.kernels.ref import apply_activation


def _matmul_kernel(x_ref, w_ref, b_ref, o_ref, acc_ref, *, kt: int,
                   activation: Optional[str], out_dtype):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], w_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(k == kt - 1)
    def _epilogue():
        out = acc_ref[...]
        if b_ref is not None:
            out = out + b_ref[...].astype(jnp.float32)
        o_ref[...] = apply_activation(out, activation).astype(out_dtype)


def matmul_padded(
    x: jnp.ndarray,          # (M, K), M % bm == 0, K % bk == 0
    w: jnp.ndarray,          # (K, N), N % bn == 0
    bias: Optional[jnp.ndarray],  # (1, N) or None
    *,
    bm: int,
    bn: int,
    bk: int,
    order: str = "mn",
    k_unroll: int = 1,       # schedule hint; no effect on semantics
    activation: Optional[str] = None,
    out_dtype=None,
) -> jnp.ndarray:
    m, k = x.shape
    _, n = w.shape
    mt, nt, kt = m // bm, n // bn, k // bk
    out_dtype = out_dtype or x.dtype

    if order == "mn":
        grid = (mt, nt, kt)
        xmap = lambda i, j, kk: (i, kk)
        wmap = lambda i, j, kk: (kk, j)
        omap = lambda i, j, kk: (i, j)
        bmap = lambda i, j, kk: (0, j)
    else:  # 'nm': n-major grid
        grid = (nt, mt, kt)
        xmap = lambda j, i, kk: (i, kk)
        wmap = lambda j, i, kk: (kk, j)
        omap = lambda j, i, kk: (i, j)
        bmap = lambda j, i, kk: (0, j)

    in_specs = [
        pl.BlockSpec((bm, bk), xmap),
        pl.BlockSpec((bk, bn), wmap),
    ]
    args = [x, w]
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, bn), bmap))
        args.append(bias)

    kernel = functools.partial(
        _matmul_kernel if bias is not None else _matmul_nobias_kernel,
        kt=kt, activation=activation, out_dtype=out_dtype,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), omap),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        **tpu.pallas_call_args(("parallel", "parallel", "arbitrary")),
    )(*args)


def _matmul_nobias_kernel(x_ref, w_ref, o_ref, acc_ref, *, kt, activation, out_dtype):
    _matmul_kernel(x_ref, w_ref, None, o_ref, acc_ref, kt=kt,
                   activation=activation, out_dtype=out_dtype)
