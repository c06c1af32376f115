"""Tuned Pallas TPU flash attention (prefill + decode).

Online-softmax attention with VMEM-resident running max/denominator/output
accumulator — no B·H·Sq·Skv logits tensor ever touches HBM (the XLA
"vendor" lowering materialises it; that is exactly the gap WPK's backend
selection exploits for long sequences).

Schedule knobs (from `AttentionTemplate`): block_q, block_kv.  The grid is
(B·H, Sq/block_q, Skv/block_kv) with the KV axis innermost ('arbitrary');
causal masking skips fully-masked KV blocks via `pl.when` so the causal
prefill does ~half the work.

GQA is handled by the wrapper (`ops.attention`): the KV head index map
divides by the group size — KV blocks are *shared* across the query heads of
a group, not materialised per head.

The decode variant (single query token against a long cache) uses the same
online softmax with block_q folded away and a `length` scalar masking the
unwritten cache tail.

The *paged* variants serve the continuous-batching runtime: the KV argument
is the shared physical block pool and a scalar-prefetched block table
indirects each grid step to its physical block — `flash_decode_paged` for
one query row per slot, `flash_prefill_paged` for a *segment-packed* prompt
chunk (the block-table-aware prefill kernel of the unified token-budget
step): the chunk's query rows carry contiguous prompt segments from up to S
requests, each segment's `(row_offset, seg_len, kv_start)` descriptor and
block table travel in the scalar-prefetch lane, and the kernel masks
cross-segment attention, so packing geometry is data and never recompiles.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tpu

NEG_INF = -1e30


def _scratch(shape, dtype=jnp.float32):
    return pltpu.VMEM(shape, dtype)


# ---------------------------------------------------------------- prefill
def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  kt: int, block_q: int, block_kv: int, scale: float,
                  causal: bool, out_dtype):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _body():
        q = q_ref[0]                                   # (bq, d)
        k = k_ref[0]                                   # (bkv, d)
        v = v_ref[0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            kpos = ki * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev = m_ref[...]                            # (bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    if causal:
        # Skip KV blocks entirely above the diagonal.
        @pl.when(ki * block_kv <= qi * block_q + block_q - 1)
        def _():
            _body()
    else:
        _body()

    @pl.when(ki == kt - 1)
    def _finish():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l).astype(out_dtype)


def flash_attention_padded(
    q: jnp.ndarray,   # (BH, Sq, D)  — Sq % block_q == 0
    k: jnp.ndarray,   # (BHkv, Skv, D)
    v: jnp.ndarray,
    *,
    block_q: int = 512,
    block_kv: int = 512,
    causal: bool = True,
    scale: Optional[float] = None,
    q_per_kv: int = 1,
) -> jnp.ndarray:
    bh, sq, d = q.shape
    _, skv, _ = k.shape
    qt, kt = sq // block_q, skv // block_kv
    scale = scale if scale is not None else 1.0 / np.sqrt(d)

    kernel = functools.partial(
        _flash_kernel, kt=kt, block_q=block_q, block_kv=block_kv,
        scale=scale, causal=causal, out_dtype=q.dtype)

    return pl.pallas_call(
        kernel,
        grid=(bh, qt, kt),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_kv, d), lambda b, i, j, q_per_kv=q_per_kv: (b // q_per_kv, j, 0)),
            pl.BlockSpec((1, block_kv, d), lambda b, i, j, q_per_kv=q_per_kv: (b // q_per_kv, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[
            _scratch((block_q, 1)),
            _scratch((block_q, 1)),
            _scratch((block_q, d)),
        ],
        **tpu.pallas_call_args(),
    )(q, k, v)


# ---------------------------------------------------------------- decode
def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                   *, kt: int, block_kv: int, scale: float, out_dtype):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[0, 0]

    @pl.when(ki * block_kv < length)
    def _body():
        q = q_ref[0]                                   # (H, D)
        k = k_ref[0]                                   # (bkv, D)
        v = v_ref[0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (H, bkv)
        kpos = ki * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < length, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == kt - 1)
    def _finish():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l).astype(out_dtype)


def _paged_decode_kernel(len_ref, bt_ref, q_ref, k_ref, v_ref, o_ref,
                         m_ref, l_ref, acc_ref, *, nbt: int, block_size: int,
                         scale: float, out_dtype):
    """One (slot, logical-block) grid step of paged decode attention.

    The physical KV block this step reads was selected by the BlockSpec
    index map from the scalar-prefetched block table — the kernel body only
    ever sees a dense (block_size, D) tile, so the online softmax is
    identical to the monolithic decode kernel."""
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[b]

    @pl.when(j * block_size < length)
    def _body():
        q = q_ref[0]                                   # (H, D)
        k = k_ref[0]                                   # (block_size, D)
        v = v_ref[0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        kpos = j * block_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < length, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == nbt - 1)
    def _finish():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l).astype(out_dtype)


def _paged_prefill_kernel(info_ref, bt_ref, q_ref, k_ref, v_ref, o_ref,
                          m_ref, l_ref, acc_ref, *, ns: int, nbt: int,
                          block_size: int, block_q: int, group: int,
                          scale: float, out_dtype):
    """One (query-tile, segment, logical-block) grid step of segment-packed
    paged prefill attention — `_paged_decode_kernel` generalised from 1
    query row to a chunk of `block_q` prompt positions (x `group` query
    heads each) carrying contiguous segments from up to `ns` requests.

    The physical KV block this step reads was selected by the BlockSpec
    index map from segment s's scalar-prefetched block table, so each
    segment's rows attend to every previously *committed* row of their OWN
    request (earlier chunks + the segment's rows, scattered before the
    kernel runs) and never to a co-packed neighbour's: rows outside the
    segment's [q0, q0+qn) row span are masked to NEG_INF for this (s, j)
    step.  Causality is positional within the segment: chunk row r sits at
    absolute position `kv_start + r - q0` of its request and masks
    strictly-future key rows, which also hides whatever stale data lives
    beyond the request's committed length.  A row's running max stays at
    NEG_INF through foreign segments' blocks (every entry masked -> p==1
    garbage), and the first in-segment block rescales that garbage by
    alpha = exp(NEG_INF - m_real) == 0 exactly, so packing is invisible to
    the online softmax; rows past the packed fill never see an unmasked
    block and are discarded by the caller."""
    i = pl.program_id(0)
    s = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when((s == 0) & (j == 0))
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q0 = info_ref[s, 0]          # segment's first row within the chunk
    qn = info_ref[s, 1]          # segment length in rows (0 = idle slot)
    kv0 = info_ref[s, 2]         # committed rows before this segment's chunk
    total = kv0 + qn             # committed rows once this segment lands
    tile0 = i * block_q
    # largest absolute position any of this tile's rows can hold in s's
    # request (rows beyond the segment are masked in the body)
    qpos_max = kv0 + tile0 + block_q - 1 - q0

    # Skip (segment, block) steps that cannot contribute: idle segment
    # slots, tiles that hold none of the segment's rows, blocks entirely
    # above the tile's diagonal, and blocks holding no committed row.
    @pl.when((qn > 0) & (tile0 < q0 + qn) & (tile0 + block_q > q0)
             & (j * block_size <= qpos_max) & (j * block_size < total))
    def _body():
        q = q_ref[...]                                 # (block_q*group, D)
        k = k_ref[0]                                   # (block_size, D)
        v = v_ref[0]
        st = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        row = jax.lax.broadcasted_iota(jnp.int32, st.shape, 0)
        qrow = tile0 + row // group                    # row within the chunk
        qpos = kv0 + qrow - q0                         # row's own-request pos
        kpos = j * block_size + jax.lax.broadcasted_iota(jnp.int32, st.shape, 1)
        ok = (qrow >= q0) & (qrow < q0 + qn) & (kpos <= qpos)
        st = jnp.where(ok, st, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(st, -1, keepdims=True))
        p = jnp.exp(st - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, -1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when((s == ns - 1) & (j == nbt - 1))
    def _finish():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_ref[...] / l).astype(out_dtype)


def flash_prefill_paged(
    q: jnp.ndarray,             # (C, G, D) one KV-head group's chunk queries
    k_pool: jnp.ndarray,        # (num_blocks, block_size, D) one KV head's pool
    v_pool: jnp.ndarray,
    seg_tables: jnp.ndarray,    # (S, nbt) int32 per-segment physical block ids
    seg_info: jnp.ndarray,      # (S, 3) int32 [row_offset, seg_len, kv_start]
    *,
    block_q: Optional[int] = None,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Block-table-aware segment-packed prefill flash attention.

    `flash_decode_paged` generalised from one query row to a packed prompt
    chunk: the (C, G, D) query buffer carries contiguous prompt segments
    from up to S requests (segment s occupies chunk rows
    [seg_info[s,0], seg_info[s,0]+seg_info[s,1])), the grid walks
    (query tiles x segments x each segment's *logical* blocks), and segment
    s's scalar-prefetched table indirects to its request's physical pool
    blocks, so every row attends to all previously committed KV of its OWN
    request — earlier chunks included, co-packed neighbours excluded —
    without materialising a gathered contiguous cache.  The descriptors
    ride in the scalar-prefetch lane, so packing geometry is *data*, never
    a new compile; a single-request chunk is just S=1 (or idle descriptor
    rows with seg_len 0).  `block_q` (prompt positions per query tile) is
    the schedule knob the plan's `prefill_chunk` stage tunes — together
    with the segment axis it defines the kernel's block_q x max-segments
    grid."""
    c, g, d = q.shape
    _, block_size, _ = k_pool.shape
    ns, nbt = seg_tables.shape
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    bq = min(block_q or c, c)
    c_pad = -(-c // bq) * bq
    qf = q.reshape(c * g, d)
    if c_pad != c:
        qf = jnp.pad(qf, ((0, (c_pad - c) * g), (0, 0)))
    rows = bq * g

    kernel = functools.partial(
        _paged_prefill_kernel, ns=ns, nbt=nbt, block_size=block_size,
        block_q=bq, group=g, scale=scale, out_dtype=q.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,      # segment descriptors + block tables
        grid=(c_pad // bq, ns, nbt),
        in_specs=[
            pl.BlockSpec((rows, d), lambda i, s, j, info, bt: (i, 0)),
            pl.BlockSpec((1, block_size, d),
                         lambda i, s, j, info, bt: (bt[s, j], 0, 0)),
            pl.BlockSpec((1, block_size, d),
                         lambda i, s, j, info, bt: (bt[s, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((rows, d), lambda i, s, j, info, bt: (i, 0)),
        scratch_shapes=[
            _scratch((rows, 1)),
            _scratch((rows, 1)),
            _scratch((rows, d)),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((c_pad * g, d), q.dtype),
        **tpu.pallas_call_args(),
    )(seg_info.astype(jnp.int32), seg_tables.astype(jnp.int32),
      qf, k_pool, v_pool)
    return out.reshape(c_pad, g, d)[:c]


def flash_decode_paged(
    q: jnp.ndarray,             # (B, H, D) single new token per sequence
    k_pool: jnp.ndarray,        # (num_blocks, block_size, D) one KV head's pool
    v_pool: jnp.ndarray,
    lengths: jnp.ndarray,       # (B,) int32 valid context lengths
    block_tables: jnp.ndarray,  # (B, nbt) int32 physical block ids
    *,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Block-table-aware flash decode: the grid walks each slot's *logical*
    blocks and the scalar-prefetched table indirects to physical pool blocks,
    so the kernel never materialises a gathered contiguous cache."""
    b, h, d = q.shape
    _, block_size, _ = k_pool.shape
    nbt = block_tables.shape[1]
    scale = scale if scale is not None else 1.0 / np.sqrt(d)

    kernel = functools.partial(
        _paged_decode_kernel, nbt=nbt, block_size=block_size, scale=scale,
        out_dtype=q.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,      # lengths + block table drive the DMA
        grid=(b, nbt),
        in_specs=[
            pl.BlockSpec((1, h, d), lambda bb, j, lens, bt: (bb, 0, 0)),
            pl.BlockSpec((1, block_size, d),
                         lambda bb, j, lens, bt: (bt[bb, j], 0, 0)),
            pl.BlockSpec((1, block_size, d),
                         lambda bb, j, lens, bt: (bt[bb, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, d), lambda bb, j, lens, bt: (bb, 0, 0)),
        scratch_shapes=[
            _scratch((h, 1)),
            _scratch((h, 1)),
            _scratch((h, d)),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        **tpu.pallas_call_args(),
    )(lengths.astype(jnp.int32), block_tables.astype(jnp.int32), q,
      k_pool, v_pool)


def flash_decode_padded(
    q: jnp.ndarray,        # (B, H, D) single new token per sequence
    k: jnp.ndarray,        # (B, Skv, D) one KV head's cache (GQA grouped out)
    v: jnp.ndarray,
    lengths: jnp.ndarray,  # (B,) int32 valid cache lengths
    *,
    block_kv: int = 512,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    b, h, d = q.shape
    _, skv, _ = k.shape
    kt = skv // block_kv
    scale = scale if scale is not None else 1.0 / np.sqrt(d)

    kernel = functools.partial(_decode_kernel, kt=kt, block_kv=block_kv,
                               scale=scale, out_dtype=q.dtype)
    lengths2d = lengths.astype(jnp.int32).reshape(b, 1)
    return pl.pallas_call(
        kernel,
        grid=(b, kt),
        in_specs=[
            pl.BlockSpec((1, 1), lambda bb, j: (bb, 0)),
            pl.BlockSpec((1, h, d), lambda bb, j: (bb, 0, 0)),
            pl.BlockSpec((1, block_kv, d), lambda bb, j: (bb, j, 0)),
            pl.BlockSpec((1, block_kv, d), lambda bb, j: (bb, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, d), lambda bb, j: (bb, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        scratch_shapes=[
            _scratch((h, 1)),
            _scratch((h, 1)),
            _scratch((h, d)),
        ],
        **tpu.pallas_call_args(),
    )(lengths2d, q, k, v)
