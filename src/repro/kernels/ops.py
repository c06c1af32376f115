"""Public jit'd wrappers over the tuned Pallas kernels.

These are the functions the WPK runtime engine and the model zoo call.  Each
wrapper:

  * accepts the tuned `config` dict produced by the automated searches
    (None -> a safe aligned default),
  * handles padding/reshaping so the kernels only ever see block-aligned
    shapes (zero K/KV padding is mathematically inert; M/N padding is sliced
    off),
  * falls back to the XLA lowering where a kernel is out of its envelope
    (e.g. image too large for whole-image VMEM residency in conv2d_direct).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref as _ref
from repro.kernels.attention import (
    flash_attention_padded,
    flash_decode_paged,
    flash_decode_padded,
    flash_prefill_paged,
)
from repro.kernels.conv2d import conv2d_direct
from repro.kernels.fused import fused_elementwise as _fused_elementwise
from repro.kernels.matmul import matmul_padded

Config = Optional[Dict[str, Any]]

_DEF_MM = {"bm": 128, "bn": 128, "bk": 128, "order": "mn", "k_unroll": 1}
_DEF_ATT = {"block_q": 128, "block_kv": 128}


def _pad_to(x: jnp.ndarray, axis: int, mult: int) -> jnp.ndarray:
    size = x.shape[axis]
    target = -(-size // mult) * mult
    if target == size:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, target - size)
    return jnp.pad(x, pads)


def matmul(
    x: jnp.ndarray,                # (..., K)
    w: jnp.ndarray,                # (K, N)
    bias: Optional[jnp.ndarray] = None,
    *,
    config: Config = None,
    activation: Optional[str] = None,
    out_dtype=None,
) -> jnp.ndarray:
    cfg = dict(_DEF_MM, **(config or {}))
    lead = x.shape[:-1]
    k = x.shape[-1]
    n = w.shape[-1]
    x2 = x.reshape(-1, k)
    m = x2.shape[0]
    bm = min(cfg["bm"], max(8, m))
    bn, bk = cfg["bn"], cfg["bk"]
    x2 = _pad_to(_pad_to(x2, 0, bm), 1, bk)
    w2 = _pad_to(_pad_to(w, 0, bk), 1, bn)
    b2 = None
    if bias is not None:
        b2 = _pad_to(bias.reshape(1, -1), 1, bn)
    out = matmul_padded(
        x2, w2, b2, bm=bm, bn=bn, bk=bk, order=cfg.get("order", "mn"),
        k_unroll=cfg.get("k_unroll", 1), activation=activation,
        out_dtype=out_dtype or x.dtype)
    return out[:m, :n].reshape(*lead, n)


def conv2d(
    x: jnp.ndarray,                   # NCHW or NHWC
    w: jnp.ndarray,                   # OIHW (NCHW) or HWIO (NHWC)
    bias: Optional[jnp.ndarray] = None,
    *,
    stride: int = 1,
    padding: str = "SAME",
    layout: str = "NHWC",
    activation: Optional[str] = None,
    config: Config = None,
    vmem_limit: int = 64 * 1024 * 1024,
) -> jnp.ndarray:
    cfg = {**_DEF_MM, "row_block": 4, **(config or {})}
    if layout == "NCHW":
        x = jnp.transpose(x, (0, 2, 3, 1))
        w = jnp.transpose(w, (2, 3, 1, 0))
    n, h, wd, cin = x.shape
    kh, kw, _, cout = w.shape

    if padding == "SAME":
        oh = -(-h // stride)
        ow = -(-wd // stride)
        pad_h = max(0, (oh - 1) * stride + kh - h)
        pad_w = max(0, (ow - 1) * stride + kw - wd)
        x = jnp.pad(x, ((0, 0), (pad_h // 2, pad_h - pad_h // 2),
                        (pad_w // 2, pad_w - pad_w // 2), (0, 0)))

    img_bytes = x.shape[1] * x.shape[2] * cin * x.dtype.itemsize
    if img_bytes <= vmem_limit:
        out = conv2d_direct(
            x, w, bias.reshape(1, -1) if bias is not None else None,
            stride=stride, bn=cfg["bn"], row_block=cfg.get("row_block", 4),
            activation=activation)
    else:
        # Fallback: XLA patch extraction + tuned Pallas GEMM.
        patches = jax.lax.conv_general_dilated_patches(
            x, (kh, kw), (stride, stride), "VALID",
            dimension_numbers=jax.lax.conv_dimension_numbers(
                x.shape, w.shape, ("NHWC", "HWIO", "NHWC")))
        po, ph, pw_, pc = patches.shape
        out = matmul(
            patches.reshape(-1, pc),
            w.transpose(2, 0, 1, 3).reshape(pc, cout),
            bias, config=cfg, activation=activation,
        ).reshape(po, ph, pw_, cout)

    if layout == "NCHW":
        out = jnp.transpose(out, (0, 3, 1, 2))
    return out


def attention(
    q: jnp.ndarray,                  # (B, Sq, H, D)
    k: jnp.ndarray,                  # (B, Skv, Hkv, D)
    v: jnp.ndarray,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    config: Config = None,
) -> jnp.ndarray:
    cfg = dict(_DEF_ATT, **(config or {}))
    b, sq, h, d = q.shape
    _, skv, hkv, _ = k.shape
    q_per_kv = h // hkv
    bq = min(cfg["block_q"], max(128, sq))
    bkv = min(cfg["block_kv"], max(128, skv))

    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * hkv, skv, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * hkv, skv, d)
    sq_p = -(-sq // bq) * bq
    skv_p = -(-skv // bkv) * bkv
    qf = _pad_to(qf, 1, bq)
    kf = _pad_to(kf, 1, bkv)
    vf = _pad_to(vf, 1, bkv)
    if skv_p != skv and not causal:
        # mask the padded tail by pushing keys to -inf via a causal=False trick:
        # zero-pad keys produce logits*scale = 0; safer to slice after ref-style
        # handling — we instead rely on causal masking or exact multiples in
        # production paths; for the general case fall back to the oracle.
        return _ref.attention_ref(q, k, v, causal=causal, scale=scale)
    out = flash_attention_padded(
        qf, kf, vf, block_q=bq, block_kv=bkv, causal=causal, scale=scale,
        q_per_kv=q_per_kv)
    out = out[:, :sq].reshape(b, h, sq, d).transpose(0, 2, 1, 3)
    return out


def attention_decode(
    q: jnp.ndarray,        # (B, H, D)
    k_cache: jnp.ndarray,  # (B, S, Hkv, D)
    v_cache: jnp.ndarray,
    lengths: jnp.ndarray,  # (B,)
    *,
    scale: Optional[float] = None,
    config: Config = None,
) -> jnp.ndarray:
    cfg = dict(_DEF_ATT, **(config or {}))
    b, h, d = q.shape
    _, s, hkv, _ = k_cache.shape
    bkv = min(cfg["block_kv"], max(128, s))
    group = h // hkv

    outs = []
    for g in range(hkv):  # per-KV-head grouping keeps the cache un-replicated
        qg = q[:, g * group : (g + 1) * group]          # (B, group, D)
        kg = _pad_to(k_cache[:, :, g], 1, bkv)          # (B, S_p, D)
        vg = _pad_to(v_cache[:, :, g], 1, bkv)
        outs.append(flash_decode_padded(qg, kg, vg, lengths, block_kv=bkv,
                                        scale=scale))
    return jnp.concatenate(outs, axis=1)


def attention_decode_paged(
    q: jnp.ndarray,             # (B, H, D)
    k_pool: jnp.ndarray,        # (num_blocks, block_size, Hkv, D)
    v_pool: jnp.ndarray,
    lengths: jnp.ndarray,       # (B,) valid context lengths (incl. new token)
    block_tables: jnp.ndarray,  # (B, nbt) physical block ids per slot
    *,
    scale: Optional[float] = None,
    config: Config = None,
) -> jnp.ndarray:
    """Paged decode attention over the block pool (continuous-batching lane).

    Same per-KV-head grouping as `attention_decode`, but the cache argument
    is the shared physical pool + per-slot block tables instead of a dense
    per-sequence cache, so admission of a new request only rewrites the
    (host-built) tables — shapes, and therefore the compiled program, are
    invariant."""
    del config  # block geometry is fixed by the pool; nothing to tune yet
    b, h, d = q.shape
    hkv = k_pool.shape[2]
    group = h // hkv

    outs = []
    for g in range(hkv):
        qg = q[:, g * group : (g + 1) * group]          # (B, group, D)
        kg = k_pool[:, :, g]                            # (nb, bs, D)
        vg = v_pool[:, :, g]
        outs.append(flash_decode_paged(qg, kg, vg, lengths, block_tables,
                                       scale=scale))
    return jnp.concatenate(outs, axis=1)


def attention_prefill_packed(
    q: jnp.ndarray,             # (1, C, H, D) packed chunk queries
    k_pool: jnp.ndarray,        # (num_blocks, block_size, Hkv, D)
    v_pool: jnp.ndarray,
    seg_tables: jnp.ndarray,    # (S, nbt) per-segment physical block ids
    seg_info: jnp.ndarray,      # (S, 3) int32 [row_offset, seg_len, kv_start]
    *,
    scale: Optional[float] = None,
    config: Config = None,
) -> jnp.ndarray:
    """Segment-packed paged prefill attention over the block pool (the
    prefill lane of the unified serving step).

    Same per-KV-head grouping as `attention_decode_paged`, but the query
    buffer carries contiguous prompt segments from up to S requests: each
    row attends causally to every committed row of its OWN request (earlier
    chunks included, co-packed neighbours masked) through its segment's
    scalar-prefetched block table.  The descriptors are traced data —
    packing geometry never recompiles.  The tuned `config` contributes
    `block_q` (prompt positions per query tile); together with the segment
    count it fixes the kernel's block_q x max-segments grid, the knobs the
    plan's `prefill_chunk` stage races."""
    cfg = dict(_DEF_ATT, **(config or {}))
    _, c, h, d = q.shape
    hkv = k_pool.shape[2]
    group = h // hkv
    bq = min(cfg.get("block_q") or c, c)
    info = jnp.asarray(seg_info, jnp.int32)

    outs = []
    for g in range(hkv):  # per-KV-head grouping keeps the pool un-replicated
        qg = q[0, :, g * group: (g + 1) * group]        # (C, group, D)
        outs.append(flash_prefill_paged(
            qg, k_pool[:, :, g], v_pool[:, :, g], seg_tables, info,
            block_q=bq, scale=scale))
    return jnp.concatenate(outs, axis=1)[None]          # (1, C, H, D)


def attention_prefill_paged(
    q: jnp.ndarray,             # (1, C, H, D) one request's chunk queries
    k_pool: jnp.ndarray,        # (num_blocks, block_size, Hkv, D)
    v_pool: jnp.ndarray,
    block_tables: jnp.ndarray,  # (1, nbt) physical block ids
    chunk_start,                # scalar int32: rows committed before the chunk
    chunk_len,                  # scalar int32: real rows in this chunk
    *,
    scale: Optional[float] = None,
    config: Config = None,
) -> jnp.ndarray:
    """Single-request chunked prefill — the S=1 special case of
    `attention_prefill_packed` (kept as the stable entry point for callers
    that carry one request's chunk per step)."""
    zero = jnp.zeros((), jnp.int32)
    seg_info = jnp.stack([zero, jnp.asarray(chunk_len, jnp.int32),
                          jnp.asarray(chunk_start, jnp.int32)])[None]
    return attention_prefill_packed(
        q, k_pool, v_pool, block_tables, seg_info,
        scale=scale, config=config)


def fused_elementwise(
    x: jnp.ndarray,
    chain: Sequence[Dict[str, Any]],
    extras: Sequence[jnp.ndarray] = (),
    *,
    config: Config = None,
) -> jnp.ndarray:
    cfg = config or {}
    return _fused_elementwise(x, chain, extras,
                              block_rows=cfg.get("block_rows", 256))
