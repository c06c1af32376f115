"""Tuned Pallas TPU conv2d — the paper's headline operator, rethought for TPU.

The paper's CUDA template carves the output into per-thread tiles
(T_x/T_y/T_z × Tile_x/Tile_y/Tile_z).  TPU has no thread blocks; the natural
mapping is an *implicit GEMM*: the kernel keeps one whole input image
resident in VMEM (HBM→VMEM once — the in-kernel im2col never materialises
the M×K patch matrix in HBM), carves the output into
(row_block rows × bn output channels) VMEM tiles, and drives the MXU with
(OW × Kh·Kw·Cin) @ (Kh·Kw·Cin × bn) dots assembled from statically-unrolled
Kh×Kw shifted slices.

Schedule knobs (from `Conv2dTemplate`): bn (output-channel block),
row_block (output rows per grid step, sharing one halo), plus bm/bk/order
which shape the *fallback* GEMM path used when the image does not fit VMEM
(`ops.conv2d` falls back to XLA patch extraction + the tuned matmul kernel).

Padding (SAME) is applied by the wrapper, so the kernel only sees VALID
convolutions on pre-padded inputs.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tpu
from repro.kernels.ref import apply_activation


def _conv_kernel(x_ref, w_ref, b_ref, o_ref, *, kh: int, kw: int, stride: int,
                 row_block: int, ow: int, activation: Optional[str], out_dtype):
    """One grid step: out rows [i*row_block, ...) × out channels block j for
    image n.  x_ref: (1, Hp, Wp, Cin); w_ref: (Kh*Kw*Cin, bn);
    o_ref: (1, row_block, OW, bn)."""
    i = pl.program_id(1)
    x = x_ref[0]                      # (Hp, Wp, Cin)
    cin = x.shape[-1]

    for r in range(row_block):        # static unroll over the row block
        base = (i * row_block + r) * stride
        # Assemble the (OW, Kh*Kw*Cin) patch matrix for this output row.
        cols = []
        for dh in range(kh):
            row = jax.lax.dynamic_slice_in_dim(x, base + dh, 1, axis=0)[0]  # (Wp, Cin)
            for dw in range(kw):
                span = (ow - 1) * stride + 1
                seg = jax.lax.dynamic_slice(row, (dw, 0), (span, cin))
                if stride > 1:
                    seg = seg[::stride]
                cols.append(seg)                          # (OW, Cin)
        patches = jnp.concatenate(cols, axis=-1)          # (OW, Kh*Kw*Cin)
        acc = jnp.dot(patches, w_ref[...], preferred_element_type=jnp.float32)
        if b_ref is not None:
            acc = acc + b_ref[...].astype(jnp.float32)
        o_ref[0, r] = apply_activation(acc, activation).astype(out_dtype)


def conv2d_direct(
    x: jnp.ndarray,                 # (N, Hp, Wp, Cin) — already padded
    w: jnp.ndarray,                 # (Kh, Kw, Cin, Cout)
    bias: Optional[jnp.ndarray],    # (1, Cout) or None
    *,
    stride: int = 1,
    bn: int = 128,
    row_block: int = 4,
    activation: Optional[str] = None,
    out_dtype=None,
) -> jnp.ndarray:
    n, hp, wp, cin = x.shape
    kh, kw, _, cout = w.shape
    oh = (hp - kh) // stride + 1
    ow = (wp - kw) // stride + 1
    out_dtype = out_dtype or x.dtype

    bn = min(bn, max(128, cout))
    # pad channel dim of weights/bias to bn multiple
    cout_p = -(-cout // bn) * bn
    w2 = jnp.reshape(w, (kh * kw * cin, cout))
    if cout_p != cout:
        w2 = jnp.pad(w2, ((0, 0), (0, cout_p - cout)))
        if bias is not None:
            bias = jnp.pad(bias, ((0, 0), (0, cout_p - cout)))
    # pad rows to row_block multiple
    oh_p = -(-oh // row_block) * row_block
    hp_need = (oh_p - 1) * stride + kh
    if hp_need > hp:
        x = jnp.pad(x, ((0, 0), (0, hp_need - hp), (0, 0), (0, 0)))
        hp = hp_need

    grid = (n, oh_p // row_block, cout_p // bn)
    kernel = functools.partial(
        _conv_kernel if bias is not None else _conv_nobias_kernel,
        kh=kh, kw=kw, stride=stride, row_block=row_block, ow=ow,
        activation=activation, out_dtype=out_dtype,
    )
    in_specs = [
        pl.BlockSpec((1, hp, wp, cin), lambda nn, i, j: (nn, 0, 0, 0)),
        pl.BlockSpec((kh * kw * cin, bn), lambda nn, i, j: (0, j)),
    ]
    args = [x, w2]
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, bn), lambda nn, i, j: (0, j)))
        args.append(bias)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, row_block, ow, bn), lambda nn, i, j: (nn, i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((n, oh_p, ow, cout_p), out_dtype),
        **tpu.pallas_call_args(),
    )(*args)
    return out[:, :oh, :, :cout]


def _conv_nobias_kernel(x_ref, w_ref, o_ref, **kw):
    _conv_kernel(x_ref, w_ref, None, o_ref, **kw)
