"""GQA attention with RoPE / M-RoPE / qk-norm, KV cache, cross-attention.

Three entry points sharing parameters:
  * `attn_forward`  — full-sequence (train / prefill); optionally returns the
    freshly built KV for cache initialisation;
  * `attn_decode`   — one new token against a (B, S_max, Hkv, D) cache,
    scatter-updating the cache at each sequence's current length;
  * cross-attention — pass `kv_override` (encoder K/V) to `attn_forward`.

The XLA einsum path is the default (it is what the multi-pod dry-run lowers);
`repro.kernels.ops.attention[_decode]` is the tuned-Pallas lane selected by
the WPK plan on TPU.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain
from repro.models.common import (
    Params,
    apply_mrope,
    apply_rope,
    dense,
    dense_init,
    norm_init,
    rms_norm,
)
from repro.serve.trace import scope


def attn_init(rng, cfg: ModelConfig, d_model: Optional[int] = None) -> Params:
    d = d_model or cfg.d_model
    hd = cfg.hd
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    p: Params = {
        "wq": dense_init(k1, d, cfg.n_heads * hd),
        "wk": dense_init(k2, d, cfg.n_kv_heads * hd),
        "wv": dense_init(k3, d, cfg.n_kv_heads * hd),
        "wo": dense_init(k4, cfg.n_heads * hd, d),
    }
    if cfg.qk_norm:
        p["q_norm"] = norm_init(hd)
        p["k_norm"] = norm_init(hd)
    return p


def attn_logical_axes(cfg: ModelConfig) -> Params:
    p = {
        "wq": {"w": ("embed", "heads")},
        "wk": {"w": ("embed", "kv_heads")},
        "wv": {"w": ("embed", "kv_heads")},
        "wo": {"w": ("heads", "embed")},
    }
    if cfg.qk_norm:
        p["q_norm"] = {"scale": (None,)}
        p["k_norm"] = {"scale": (None,)}
    return p


def _project_qkv(p: Params, cfg: ModelConfig, x: jnp.ndarray,
                 positions: Optional[jnp.ndarray]):
    # q/k norm and rotary positions belong to the projection's scope
    with scope("qkv_proj"):
        b, s, _ = x.shape
        hd, hq, hkv = cfg.hd, cfg.n_heads, cfg.n_kv_heads
        # role-tagged: the serve plan's stage `qkv_proj` choice dispatches
        # these
        q = dense(p["wq"], x, role="qkv_proj").reshape(b, s, hq, hd)
        k = dense(p["wk"], x, role="qkv_proj").reshape(b, s, hkv, hd)
        v = dense(p["wv"], x, role="qkv_proj").reshape(b, s, hkv, hd)
        if cfg.qk_norm:
            q = rms_norm(p["q_norm"], q)
            k = rms_norm(p["k_norm"], k)
        if positions is not None:
            if cfg.mrope_sections:
                sec = cfg.mrope_sections
                q = apply_mrope(q, positions, sec, cfg.rope_theta)
                k = apply_mrope(k, positions, sec, cfg.rope_theta)
            else:
                q = apply_rope(q, positions, cfg.rope_theta)
                k = apply_rope(k, positions, cfg.rope_theta)
        q = constrain(q, ("batch", None, "heads", None))
        k = constrain(k, ("batch", None, "kv_heads", None))
        v = constrain(v, ("batch", None, "kv_heads", None))
        return q, k, v


_CHUNKED_KV_THRESHOLD = 1024
_KV_CHUNK = 1024


def _sdpa_direct(q, k, v, causal: bool, q_per_kv: int) -> jnp.ndarray:
    """einsum attention, GQA grouped so the KV is never repeated in HBM."""
    b, sq, h, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    scale = 1.0 / np.sqrt(d)
    qg = q.reshape(b, sq, hkv, q_per_kv, d)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((sq, skv), bool), k=skv - sq)
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
    return out.reshape(b, sq, h, d)


def _sdpa_chunked(q, k, v, causal: bool, q_per_kv: int,
                  kv_chunk: int = _KV_CHUNK) -> jnp.ndarray:
    """Online-softmax attention scanned over KV chunks — the XLA-path
    flash-attention equivalent.  Peak logits temp drops from O(Sq*Skv) to
    O(Sq*kv_chunk); `jax.checkpoint` on the chunk body keeps backward at the
    same footprint (recompute per chunk).  Numerics match `_sdpa_direct` to
    ~1e-6 (same f32 accumulation)."""
    b, sq, h, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    scale = 1.0 / np.sqrt(d)
    nc = skv // kv_chunk
    assert skv % kv_chunk == 0, (skv, kv_chunk)
    qg = q.reshape(b, sq, hkv, q_per_kv, d)
    kc = k.reshape(b, nc, kv_chunk, hkv, d).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, nc, kv_chunk, hkv, d).transpose(1, 0, 2, 3, 4)
    qpos = jnp.arange(sq) + (skv - sq)  # query absolute positions

    @jax.checkpoint
    def body(carry, inp):
        m_prev, l_prev, acc = carry
        k_blk, v_blk, ci = inp
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k_blk).astype(jnp.float32) * scale
        if causal:
            kpos = ci * kv_chunk + jnp.arange(kv_chunk)
            mask = qpos[:, None] >= kpos[None, :]
            s = jnp.where(mask[None, None, None], s, -1e30)
        m_cur = jnp.max(s, -1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, -1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p.astype(v_blk.dtype), v_blk)
        return (m_new, l_new, acc), None

    from repro.models import runmode
    m0 = jnp.full((b, hkv, q_per_kv, sq), -1e30, jnp.float32)
    l0 = jnp.zeros((b, hkv, q_per_kv, sq), jnp.float32)
    a0 = jnp.zeros((b, hkv, q_per_kv, sq, d), jnp.float32)
    (m, l, acc), _ = runmode.layer_scan(body, (m0, l0, a0),
                                        (kc, vc, jnp.arange(nc)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, d).astype(q.dtype)


def _kv_chunk_for(skv: int, target: int = _KV_CHUNK) -> int:
    """Largest divisor of skv that is <= target (>= 64 to stay MXU-friendly)."""
    best = 0
    for c in range(min(target, skv), 63, -1):
        if skv % c == 0:
            best = c
            break
    return best


def _sdpa(q, k, v, causal: bool, q_per_kv: int) -> jnp.ndarray:
    skv = k.shape[1]
    if skv >= _CHUNKED_KV_THRESHOLD:
        chunk = _kv_chunk_for(skv)
        if chunk and skv // chunk > 1:
            return _sdpa_chunked(q, k, v, causal, q_per_kv, kv_chunk=chunk)
    return _sdpa_direct(q, k, v, causal, q_per_kv)


def attn_forward(
    p: Params,
    cfg: ModelConfig,
    x: jnp.ndarray,                         # (B, S, d)
    *,
    positions: Optional[jnp.ndarray] = None,
    causal: bool = True,
    kv_override: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
    return_kv: bool = False,
    backend: str = "xla",                   # 'pallas*' -> tuned flash kernel
    backend_config: Optional[Dict[str, Any]] = None,
):
    b, s, _ = x.shape
    if kv_override is not None:             # cross-attention
        hd = cfg.hd
        q = dense(p["wq"], x).reshape(b, s, cfg.n_heads, hd)
        if cfg.qk_norm:
            q = rms_norm(p["q_norm"], q)
        k, v = kv_override
        causal = False
    else:
        q, k, v = _project_qkv(p, cfg, x, positions)
    if backend.startswith("pallas") and kv_override is None:
        from repro.kernels import ops as K
        out = K.attention(q, k, v, causal=causal, config=backend_config)
    else:
        out = _sdpa(q, k, v, causal, cfg.q_per_kv)
    out = constrain(out, ("batch", None, "heads", None))
    y = dense(p["wo"], out.reshape(b, s, cfg.n_heads * cfg.hd))
    if return_kv:
        return y, (k, v)
    return y


def cross_kv(p: Params, cfg: ModelConfig, enc: jnp.ndarray):
    """Precompute encoder K/V once for all decoder steps."""
    b, s, _ = enc.shape
    hd = cfg.hd
    k = dense(p["wk"], enc).reshape(b, s, cfg.n_kv_heads, hd)
    v = dense(p["wv"], enc).reshape(b, s, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        k = rms_norm(p["k_norm"], k)
    return k, v


# ----------------------------------------------------------------- decode
def init_cache(cfg: ModelConfig, batch: int, max_seq: int, n_layers: int,
               dtype=jnp.bfloat16) -> Dict[str, Any]:
    shape = (n_layers, batch, max_seq, cfg.n_kv_heads, cfg.hd)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
    }


def cache_logical_axes() -> Dict[str, Any]:
    ax = ("layers", "batch", "kv_seq", "kv_heads", None)
    return {"k": ax, "v": ax}


def attn_decode(
    p: Params,
    cfg: ModelConfig,
    x: jnp.ndarray,                 # (B, 1, d)
    k_cache: jnp.ndarray,           # (B, S_max, Hkv, hd)
    v_cache: jnp.ndarray,
    lengths: jnp.ndarray,           # (B,) current lengths (position of new tok)
):
    b = x.shape[0]
    q, k_new, v_new = _project_qkv(p, cfg, x, lengths[:, None])
    if cfg.mrope_sections:
        # decode positions for M-RoPE: all three streams equal (text token)
        pos3 = jnp.broadcast_to(lengths[:, None, None], (b, 1, 3))
        q, k_new, v_new = _project_qkv(p, cfg, x, pos3)
    bidx = jnp.arange(b)
    k_cache = k_cache.at[bidx, lengths].set(k_new[:, 0])
    v_cache = v_cache.at[bidx, lengths].set(v_new[:, 0])

    scale = 1.0 / np.sqrt(cfg.hd)
    hkv, g = cfg.n_kv_heads, cfg.q_per_kv
    qg = q.reshape(b, hkv, g, cfg.hd)
    logits = jnp.einsum("bhgd,bkhd->bhgk", qg, k_cache).astype(jnp.float32) * scale
    pos = jnp.arange(k_cache.shape[1])[None, None, None, :]
    logits = jnp.where(pos <= lengths[:, None, None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bhgk,bkhd->bhgd", probs, v_cache)
    y = dense(p["wo"], out.reshape(b, 1, cfg.n_heads * cfg.hd))
    return y, k_cache, v_cache


# ------------------------------- paged (block-table) segment-packed prefill
def packed_row_map(seg_info, c: int):
    """Per-row segment assignment for a packed prompt chunk.

    `seg_info` is the (S, 3) int32 descriptor array [row_offset, seg_len,
    kv_start]: segment s occupies the contiguous chunk rows
    [row_offset, row_offset + seg_len) and its first row sits at absolute
    position `kv_start` of its own request (segments are packed from row 0
    in order; idle descriptor rows carry seg_len 0 with row_offset at the
    fill level, so offsets stay monotone).  Returns

        sid   (C,) int32 — each chunk row's segment index (clamped),
        pos   (C,) int32 — the row's absolute position in its OWN request,
        valid (C,) bool  — whether the row carries a real prompt token.

    All of it is arithmetic on traced data: packing geometry never changes
    the compiled program."""
    info = jnp.asarray(seg_info, jnp.int32)
    ns = info.shape[0]
    q0, qn, kv0 = info[:, 0], info[:, 1], info[:, 2]
    r = jnp.arange(c, dtype=jnp.int32)
    seg_end = q0 + qn
    sid = jnp.minimum(jnp.sum((r[:, None] >= seg_end[None, :]).astype(jnp.int32),
                              axis=1), ns - 1)
    valid = (r >= q0[sid]) & (r < seg_end[sid])
    pos = kv0[sid] + (r - q0[sid])
    return sid, jnp.where(valid, pos, 0), valid


def _own_segment(per_seg: jnp.ndarray, sid: jnp.ndarray, axis: int):
    """`per_seg[sid[r]]` for every chunk row r: `per_seg` stacks one result
    per segment on axis 0, and `axis` is the row axis of `per_seg[0]`.  A
    select over the static segment count: it fuses with its consumer, and
    ran faster than a `take_along_axis` gather on a TPU v5e."""
    shape = [1] * (per_seg.ndim - 1)
    shape[axis] = -1
    row_sid = sid.reshape(shape)
    out = per_seg[0]
    for s in range(1, per_seg.shape[0]):
        out = jnp.where(row_sid == s, per_seg[s], out)
    return out


def attention_prefill_packed_xla(
    q: jnp.ndarray,                 # (1, C, H, hd) packed chunk queries
    k_pool: jnp.ndarray,            # (num_blocks, block_size, Hkv, hd)
    v_pool: jnp.ndarray,
    seg_tables: jnp.ndarray,        # (S, nbt) per-segment physical block ids
    seg_info: jnp.ndarray,          # (S, 3) [row_offset, seg_len, kv_start]
) -> jnp.ndarray:
    """The XLA lane of segment-packed prefill attention: the same contract
    as `kernels.ops.attention_prefill_packed`, (1, C, H, hd) out.

    Each SEGMENT's logical cache view is gathered from the pool once,
    (S, nbt * block_size, Hkv, hd); every row is scored against all S views
    and keeps its own segment's logits and PV output.  The view is always
    the full table and the mask purely positional, so each row's float
    program is the same for every chunk split AND every packing — chunked,
    unchunked, packed and single-segment prefill all agree bitwise on this
    lane."""
    _, c, h, hd = q.shape
    ns, nbt = seg_tables.shape
    kv_len = nbt * k_pool.shape[1]
    hkv = k_pool.shape[2]
    sid, pos, _ = packed_row_map(seg_info, c)
    k_ctx = k_pool[seg_tables].reshape(ns, kv_len, hkv, hd)
    v_ctx = v_pool[seg_tables].reshape(ns, kv_len, hkv, hd)
    qg = q.reshape(c, hkv, h // hkv, hd)
    scale = 1.0 / np.sqrt(hd)
    logits = _own_segment(jnp.einsum("qhgd,skhd->shgqk", qg, k_ctx), sid,
                          axis=2).astype(jnp.float32) * scale
    kpos = jnp.arange(kv_len)[None, None, None, :]
    logits = jnp.where(kpos <= pos[None, None, :, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v_ctx.dtype)
    out = _own_segment(jnp.einsum("hgqk,skhd->sqhgd", probs, v_ctx), sid,
                       axis=0)
    return out.reshape(1, c, h, hd)


def attn_prefill_packed(
    p: Params,
    cfg: ModelConfig,
    x: jnp.ndarray,                 # (1, C, d) — packed prompt segments
    k_pool: jnp.ndarray,            # (num_blocks, block_size, Hkv, hd)
    v_pool: jnp.ndarray,
    seg_tables: jnp.ndarray,        # (S, nbt) per-segment physical block ids
    positions: jnp.ndarray,         # (1, C[, 3]) absolute RoPE positions
    seg_info: jnp.ndarray,          # (S, 3) [row_offset, seg_len, kv_start]
    *,
    backend: str = "xla",
    backend_config=None,
):
    """Segment-packed prefill attention against the *paged* KV pool.

    The chunk's K/V rows are scattered straight into each row's OWN
    request's blocks (row r of segment s lands at absolute position
    `kv_start_s + r - row_offset_s`; padding rows beyond the packed fill
    divert to the reserved null-sink block), then each query row attends
    causally to every committed row of its request — earlier chunks
    included, co-packed neighbours excluded — either through the XLA lane,
    which gathers each segment's table once and lets every row attend to
    its own segment's view, or through the segment-aware Pallas kernel
    (`backend='pallas_attention'`, `kernels.ops.attention_prefill_packed`).
    Packing geometry is carried by the traced descriptor array, so every
    packing of every step reuses one program — and because each row of
    the XLA lane reduces over the SAME full-width table view of its own
    request regardless of how rows are grouped into segments, packed and
    unpacked schedules compute identical float programs per row
    (byte-identical token streams)."""
    b, c, _ = x.shape
    block_size = k_pool.shape[1]
    nbt = seg_tables.shape[1]
    q, k_new, v_new = _project_qkv(p, cfg, x, positions)

    # incremental commit: each row scatters into its own segment's blocks
    with scope("kv_write"):
        sid, pos, valid = packed_row_map(seg_info, c)
        row_tables = jnp.asarray(seg_tables, jnp.int32)[sid]      # (C, nbt)
        blk = row_tables[jnp.arange(c),
                         jnp.clip(pos // block_size, 0, nbt - 1)]
        blk = jnp.where(valid, blk, 0)              # padding -> null sink
        off = pos % block_size
        k_pool = k_pool.at[blk, off].set(k_new[0].astype(k_pool.dtype))
        v_pool = v_pool.at[blk, off].set(v_new[0].astype(v_pool.dtype))

    with scope("attention"):
        if backend.startswith("pallas"):
            from repro.kernels import ops as K
            out = K.attention_prefill_packed(
                q, k_pool, v_pool, seg_tables, seg_info,
                config=backend_config)
        else:
            out = attention_prefill_packed_xla(q, k_pool, v_pool, seg_tables,
                                               seg_info)
    with scope("o_proj"):
        y = dense(p["wo"], out.reshape(b, c, cfg.n_heads * cfg.hd))
    return y, k_pool, v_pool


# ---------------------------------------------------- paged (block-table) decode
def attn_decode_paged(
    p: Params,
    cfg: ModelConfig,
    x: jnp.ndarray,                 # (B, 1, d) — B is the slot count
    k_pool: jnp.ndarray,            # (num_blocks, block_size, Hkv, hd)
    v_pool: jnp.ndarray,
    block_tables: jnp.ndarray,      # (B, nbt) physical block ids per slot
    lengths: jnp.ndarray,           # (B,) current context lengths (new token pos)
    *,
    backend: str = "xla",
):
    """Decode attention against the *paged* KV pool.

    The new token's K/V rows are scattered into each slot's current block
    (inactive slots carry all-null tables and write harmlessly into the
    reserved sink block 0), then attention runs either as an XLA
    gather+einsum over the slot's logical view of the pool, or through the
    block-table-aware Pallas kernel (`backend='pallas_attention'`) that
    indirects via scalar-prefetched tables without gathering."""
    b = x.shape[0]
    block_size = k_pool.shape[1]
    q, k_new, v_new = _project_qkv(p, cfg, x, lengths[:, None])
    if cfg.mrope_sections:
        pos3 = jnp.broadcast_to(lengths[:, None, None], (b, 1, 3))
        q, k_new, v_new = _project_qkv(p, cfg, x, pos3)

    with scope("kv_write"):
        bidx = jnp.arange(b)
        blk = block_tables[bidx, lengths // block_size]  # (B,) physical
        off = lengths % block_size
        k_pool = k_pool.at[blk, off].set(k_new[:, 0].astype(k_pool.dtype))
        v_pool = v_pool.at[blk, off].set(v_new[:, 0].astype(v_pool.dtype))

    hkv, g = cfg.n_kv_heads, cfg.q_per_kv
    with scope("attention"):
        if backend.startswith("pallas"):
            from repro.kernels import ops as K
            out = K.attention_decode_paged(
                q.reshape(b, cfg.n_heads, cfg.hd), k_pool, v_pool,
                lengths + 1, block_tables)
            out = out.reshape(b, hkv, g, cfg.hd)
        else:
            # XLA lane: gather each slot's logical cache view from the pool.
            nbt = block_tables.shape[1]
            k_ctx = k_pool[block_tables].reshape(b, nbt * block_size, hkv,
                                                 cfg.hd)
            v_ctx = v_pool[block_tables].reshape(b, nbt * block_size, hkv,
                                                 cfg.hd)
            scale = 1.0 / np.sqrt(cfg.hd)
            qg = q.reshape(b, hkv, g, cfg.hd)
            logits = jnp.einsum("bhgd,bkhd->bhgk", qg,
                                k_ctx).astype(jnp.float32) * scale
            pos = jnp.arange(nbt * block_size)[None, None, None, :]
            logits = jnp.where(pos <= lengths[:, None, None, None], logits,
                               -1e30)
            probs = jax.nn.softmax(logits, axis=-1).astype(v_ctx.dtype)
            out = jnp.einsum("bhgk,bkhd->bhgd", probs, v_ctx)
    with scope("o_proj"):
        y = dense(p["wo"], out.reshape(b, 1, cfg.n_heads * cfg.hd))
    return y, k_pool, v_pool
