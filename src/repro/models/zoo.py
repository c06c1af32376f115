"""Model assembly: one `Model` API over six architecture families.

  dense / moe / vlm : decoder-only transformer (scan-over-layers)
  ssm               : Mamba2 (SSD)
  hybrid            : Zamba2 (Mamba2 backbone + one shared attention block)
  encdec            : Whisper (encoder + cross-attending decoder)

All params are plain pytrees with a parallel `logical_axes()` tree consumed
by `repro.distributed.sharding`.  `forward` is the training/prefill path
(scan over stacked layer params, remat-policy aware); `prefill`/`decode_step`
are the serving path with explicit caches.  Modality frontends (vision
patches, audio frames) are STUBS per the assignment: `input_specs` provides
pre-computed embeddings.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain
from repro.models import attention as A
from repro.models import mamba as M
from repro.models import mlp as F
from repro.models import runmode
from repro.models.common import (
    Params,
    chunked_softmax_xent,
    cross_entropy,
    embed,
    embed_init,
    layer_norm,
    layer_norm_init,
    norm_init,
    lm_head_logits,
    rms_norm,
    dense,
    dense_init,
)
from repro.serve.trace import scope

Batch = Dict[str, jnp.ndarray]


def _norm_init(cfg: ModelConfig, d: int):
    return layer_norm_init(d) if cfg.norm == "layer" else norm_init(d)


def _norm(cfg: ModelConfig, p, x):
    return layer_norm(p, x) if cfg.norm == "layer" else rms_norm(p, x)


def _remat(cfg: ModelConfig, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)


def _scoped_layer_out(cfg: ModelConfig, bp: Params, x, y):
    """The serving layers' tail: x + attention output `y`, then the MLP
    block with its residual.  Every op carries a scope of its own (a
    residual add sits in the scope of the projection feeding it, so a
    fusion rooted at the add is charged to that projection), leaving
    exactly ".../layers" to what the layer scan itself emits."""
    with scope("o_proj"):
        x = x + y
    with scope("norm"):
        h = _norm(cfg, bp["mlp_norm"], x)
    if cfg.family == "moe":
        y = F.moe_apply(bp["moe"], cfg, h, cfg.act)
    else:
        y = F.mlp_apply(bp["mlp"], h, cfg.act)
    with scope("mlp_down"):
        return x + y


# ===================================================================== blocks
def block_init(rng, cfg: ModelConfig) -> Params:
    k1, k2 = jax.random.split(rng)
    p = {
        "attn_norm": _norm_init(cfg, cfg.d_model),
        "attn": A.attn_init(k1, cfg),
        "mlp_norm": _norm_init(cfg, cfg.d_model),
    }
    if cfg.family == "moe":
        p["moe"] = F.moe_init(k2, cfg)
    else:
        p["mlp"] = F.mlp_init(k2, cfg.d_model, cfg.d_ff, gated=cfg.act == "silu")
    return p


def block_logical_axes(cfg: ModelConfig) -> Params:
    norm_ax = {"scale": (None,)} if cfg.norm == "rms" else {"scale": (None,), "bias": (None,)}
    p = {
        "attn_norm": dict(norm_ax),
        "attn": A.attn_logical_axes(cfg),
        "mlp_norm": dict(norm_ax),
    }
    if cfg.family == "moe":
        p["moe"] = F.moe_logical_axes(cfg)
    else:
        p["mlp"] = F.mlp_logical_axes(gated=cfg.act == "silu")
    return p


def block_forward(p: Params, cfg: ModelConfig, x, positions, causal=True):
    h = _norm(cfg, p["attn_norm"], x)
    x = x + A.attn_forward(p["attn"], cfg, h, positions=positions, causal=causal)
    h = _norm(cfg, p["mlp_norm"], x)
    if cfg.family == "moe":
        x = x + F.moe_apply(p["moe"], cfg, h, cfg.act)
    else:
        x = x + F.mlp_apply(p["mlp"], h, cfg.act)
    return constrain(x, ("batch", "seq", "embed"))


def block_decode(p: Params, cfg: ModelConfig, x, kc, vc, lengths):
    h = _norm(cfg, p["attn_norm"], x)
    y, kc, vc = A.attn_decode(p["attn"], cfg, h, kc, vc, lengths)
    x = x + y
    h = _norm(cfg, p["mlp_norm"], x)
    if cfg.family == "moe":
        x = x + F.moe_apply(p["moe"], cfg, h, cfg.act)
    else:
        x = x + F.mlp_apply(p["mlp"], h, cfg.act)
    return x, kc, vc


# ============================================================= decoder-only LM
class DecoderLM:
    """dense / moe / vlm families."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # ----------------------------------------------------------------- init
    def init(self, rng) -> Params:
        cfg = self.cfg
        k_emb, k_blocks, k_final = jax.random.split(rng, 3)
        block_keys = jax.random.split(k_blocks, cfg.n_layers)
        blocks = jax.vmap(lambda k: block_init(k, cfg))(block_keys)
        return {
            "embed": embed_init(k_emb, cfg.vocab, cfg.d_model),
            "blocks": blocks,
            "final_norm": _norm_init(cfg, cfg.d_model),
            "lm_head": dense_init(k_final, cfg.d_model, cfg.vocab),
        }

    def logical_axes(self) -> Params:
        cfg = self.cfg
        blocks = jax.tree.map(
            lambda ax: ("layers",) + ax,
            block_logical_axes(cfg),
            is_leaf=lambda x: isinstance(x, tuple) and all(
                a is None or isinstance(a, str) for a in x))
        norm_ax = {"scale": (None,)} if cfg.norm == "rms" else {"scale": (None,), "bias": (None,)}
        return {
            "embed": {"emb": ("vocab", "embed_tbl")},
            "blocks": blocks,
            "final_norm": norm_ax,
            "lm_head": {"w": ("embed_vec", "vocab")},
        }

    # ------------------------------------------------------------ positions
    def _positions(self, batch: Batch, b: int, s: int):
        return self._position_ids(b, jnp.arange(s))

    def _position_ids(self, b: int, idx: jnp.ndarray):
        """RoPE position ids for sequence indices `idx` (any offset — the
        chunked-prefill path passes `chunk_start + arange(C)`, so a chunk's
        rows encode the same positions the full prompt would)."""
        cfg = self.cfg
        s = idx.shape[0]
        if not cfg.mrope_sections:
            return jnp.broadcast_to(idx, (b, s))
        # M-RoPE (qwen2-vl): vision tokens index a (t=0, h, w) grid; text
        # tokens use (t, t, t) offset past the vision span.
        nv = cfg.n_vision_tokens
        grid = max(1, int(np.sqrt(nv)))
        vis_h = (idx // grid).clip(0, grid - 1)
        vis_w = (idx % grid)
        t_text = jnp.maximum(idx - nv, 0) + grid  # text clock starts after grid
        is_vis = idx < nv
        pt = jnp.where(is_vis, 0, t_text)
        ph = jnp.where(is_vis, vis_h, t_text)
        pw = jnp.where(is_vis, vis_w, t_text)
        pos3 = jnp.stack([pt, ph, pw], -1)          # (S, 3)
        return jnp.broadcast_to(pos3, (b, s, 3))

    def _embed_inputs(self, params: Params, batch: Batch) -> jnp.ndarray:
        cfg = self.cfg
        x = embed(params["embed"], batch["tokens"], jnp.dtype(cfg.dtype))
        if cfg.family == "vlm" and "vision_embeds" in batch:
            nv = batch["vision_embeds"].shape[1]
            vis = batch["vision_embeds"].astype(x.dtype)
            x = jnp.concatenate([vis, x[:, nv:]], axis=1)
        return constrain(x, ("batch", "seq", "embed"))

    # -------------------------------------------------------------- forward
    def hidden(self, params: Params, batch: Batch) -> jnp.ndarray:
        cfg = self.cfg
        x = self._embed_inputs(params, batch)
        b, s, _ = x.shape
        positions = self._positions(batch, b, s)

        body = _remat(cfg, functools.partial(self._scan_body, cfg, positions))
        x, _ = runmode.layer_scan(body, x, params["blocks"])
        return _norm(cfg, params["final_norm"], x)

    def forward(self, params: Params, batch: Batch) -> jnp.ndarray:
        return lm_head_logits(params["lm_head"],
                              self.hidden(params, batch))

    @staticmethod
    def _scan_body(cfg, positions, x, bp):
        return block_forward(bp, cfg, x, positions), None

    def loss(self, params: Params, batch: Batch):
        x = self.hidden(params, batch)
        l = chunked_softmax_xent(x, params["lm_head"]["w"],
                                 batch["labels"], batch.get("mask"))
        return l, {"loss": l}

    # -------------------------------------------------------------- serving
    def init_cache(self, batch: int, max_seq: int) -> Dict[str, Any]:
        cfg = self.cfg
        cache = A.init_cache(cfg, batch, max_seq, cfg.n_layers,
                             jnp.dtype(cfg.dtype))
        cache["lengths"] = jnp.zeros((batch,), jnp.int32)
        return cache

    def cache_logical_axes(self):
        ax = A.cache_logical_axes()
        ax["lengths"] = ("batch",)
        return ax

    def prefill(self, params: Params, batch: Batch, max_seq: int):
        """Run the full prompt, build the cache, return last-position logits."""
        cfg = self.cfg
        x = self._embed_inputs(params, batch)
        b, s, _ = x.shape
        positions = self._positions(batch, b, s)

        def body(x, bp):
            h = _norm(cfg, bp["attn_norm"], x)
            y, (k, v) = A.attn_forward(bp["attn"], cfg, h, positions=positions,
                                       causal=True, return_kv=True)
            x = x + y
            h = _norm(cfg, bp["mlp_norm"], x)
            if cfg.family == "moe":
                x = x + F.moe_apply(bp["moe"], cfg, h, cfg.act)
            else:
                x = x + F.mlp_apply(bp["mlp"], h, cfg.act)
            return x, (k, v)

        x, (ks, vs) = runmode.layer_scan(_remat(cfg, body), x, params["blocks"])
        x = _norm(cfg, params["final_norm"], x)
        logits = lm_head_logits(params["lm_head"], x[:, -1:])

        cache = self.init_cache(b, max_seq)
        cache["k"] = jax.lax.dynamic_update_slice(
            cache["k"], ks.astype(cache["k"].dtype), (0, 0, 0, 0, 0))
        cache["v"] = jax.lax.dynamic_update_slice(
            cache["v"], vs.astype(cache["v"].dtype), (0, 0, 0, 0, 0))
        cache["lengths"] = jnp.full((b,), s, jnp.int32)
        return logits, cache

    def decode_step(self, params: Params, cache: Dict[str, Any],
                    tokens: jnp.ndarray):
        """tokens: (B, 1) -> logits (B, 1, V), updated cache."""
        cfg = self.cfg
        x = embed(params["embed"], tokens, jnp.dtype(cfg.dtype))
        lengths = cache["lengths"]

        def body(x, layer):
            bp, kc, vc = layer
            x, kc, vc = block_decode(bp, cfg, x, kc, vc, lengths)
            return x, (kc, vc)

        x, (ks, vs) = runmode.layer_scan(body, x, (params["blocks"], cache["k"], cache["v"]))
        x = _norm(cfg, params["final_norm"], x)
        logits = lm_head_logits(params["lm_head"], x)
        new_cache = dict(cache, k=ks, v=vs, lengths=lengths + 1)
        return logits, new_cache

    # ------------------------------------------------- paged serving path
    def decode_step_paged(self, params: Params, k_pool: jnp.ndarray,
                          v_pool: jnp.ndarray, block_tables: jnp.ndarray,
                          lengths: jnp.ndarray, tokens: jnp.ndarray,
                          *, attn_backend: str = "xla"):
        """One decode step over the slot batch against the paged KV pool.

        k_pool/v_pool: (L, num_blocks, block_size, Hkv, hd); tokens: (B, 1).
        Block tables and lengths have static shapes in the slot count, so
        admitting a request into the in-flight batch is a pure data update —
        the jitted program is reused as-is."""
        cfg = self.cfg
        with scope("embed"):
            x = embed(params["embed"], tokens, jnp.dtype(cfg.dtype))

        def body(x, layer):
            bp, kp, vp = layer
            with scope("norm"):
                h = _norm(cfg, bp["attn_norm"], x)
            y, kp, vp = A.attn_decode_paged(
                bp["attn"], cfg, h, kp, vp, block_tables, lengths,
                backend=attn_backend)
            return _scoped_layer_out(cfg, bp, x, y), (kp, vp)

        with scope("layers"):
            x, (ks, vs) = runmode.layer_scan(
                body, x, (params["blocks"], k_pool, v_pool))
        with scope("final_norm"):
            x = _norm(cfg, params["final_norm"], x)
        logits = lm_head_logits(params["lm_head"], x)
        return logits, ks, vs

    def prefill_packed_paged(self, params: Params, k_pool: jnp.ndarray,
                             v_pool: jnp.ndarray, seg_tables: jnp.ndarray,
                             tokens: jnp.ndarray, seg_info: jnp.ndarray,
                             *, attn_backend: str = "xla",
                             attn_config: Optional[Dict[str, Any]] = None):
        """A segment-packed prompt chunk against the paged KV pool — the
        prefill lane of the unified serving step.

        tokens: (1, C) carrying contiguous prompt segments from up to S
        requests; `seg_info` is the (S, 3) descriptor array [row_offset,
        seg_len, kv_start] and `seg_tables` (S, nbt) each segment's block
        table (idle descriptor rows: seg_len 0, all-null table).  Each
        layer scatters every row's K/V into its OWN segment's blocks
        (padding rows divert to the null sink) and attends causally over
        everything its request committed so far — never a co-packed
        neighbour — so a prompt split across steps or packed beside others
        computes exactly the single-shot prefill.  The descriptors are
        traced data: every packing of every step is a pure data update to
        ONE compiled program — admission never compiles.

        Returns (logits (1, S, V) at each segment's last real row — the
        first sampled token of every segment that completes its prompt
        this step — ks, vs)."""
        cfg = self.cfg
        with scope("embed"):
            x = embed(params["embed"], tokens, jnp.dtype(cfg.dtype))
            b, c, _ = x.shape
            _, pos, _ = A.packed_row_map(seg_info, c)  # pos 0 on padding
            positions = self._position_ids(b, pos)

        def body(x, layer):
            bp, kp, vp = layer
            with scope("norm"):
                h = _norm(cfg, bp["attn_norm"], x)
            y, kp, vp = A.attn_prefill_packed(
                bp["attn"], cfg, h, kp, vp, seg_tables, positions,
                seg_info, backend=attn_backend,
                backend_config=attn_config)
            return _scoped_layer_out(cfg, bp, x, y), (kp, vp)

        with scope("layers"):
            x, (ks, vs) = runmode.layer_scan(
                body, x, (params["blocks"], k_pool, v_pool))
        with scope("final_norm"):
            x = _norm(cfg, params["final_norm"], x)
        with scope("lm_head"):
            info = jnp.asarray(seg_info, jnp.int32)
            last = jnp.clip(info[:, 0] + info[:, 1] - 1, 0, c - 1)  # (S,)
            x_last = x[:, last]                                    # (1, S, d)
            logits = lm_head_logits(params["lm_head"], x_last)
        return logits, ks, vs

    @staticmethod
    def paged_cache_logical_axes():
        ax = ("layers", None, None, "kv_heads", None)
        return {"k": ax, "v": ax}


# ===================================================================== Mamba2
class MambaLM:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    def init(self, rng) -> Params:
        cfg = self.cfg
        k_emb, k_blocks, k_final = jax.random.split(rng, 3)
        keys = jax.random.split(k_blocks, cfg.n_layers)

        def one(k):
            return {"norm": _norm_init(cfg, cfg.d_model),
                    "mamba": M.mamba_init(k, cfg)}

        return {
            "embed": embed_init(k_emb, cfg.vocab, cfg.d_model),
            "blocks": jax.vmap(one)(keys),
            "final_norm": _norm_init(cfg, cfg.d_model),
            "lm_head": dense_init(k_final, cfg.d_model, cfg.vocab),
        }

    def logical_axes(self) -> Params:
        cfg = self.cfg
        block = {"norm": {"scale": (None,)},
                 "mamba": M.mamba_logical_axes(cfg)}
        blocks = jax.tree.map(
            lambda ax: ("layers",) + ax, block,
            is_leaf=lambda x: isinstance(x, tuple) and all(
                a is None or isinstance(a, str) for a in x))
        return {
            "embed": {"emb": ("vocab", "embed_tbl")},
            "blocks": blocks,
            "final_norm": {"scale": (None,)},
            "lm_head": {"w": ("embed_vec", "vocab")},
        }

    def hidden(self, params: Params, batch: Batch) -> jnp.ndarray:
        cfg = self.cfg
        x = embed(params["embed"], batch["tokens"], jnp.dtype(cfg.dtype))
        x = constrain(x, ("batch", "seq", "embed"))

        def body(x, bp):
            h = _norm(cfg, bp["norm"], x)
            return x + M.mamba_forward(bp["mamba"], cfg, h), None

        x, _ = runmode.layer_scan(_remat(cfg, body), x, params["blocks"])
        return _norm(cfg, params["final_norm"], x)

    def forward(self, params: Params, batch: Batch) -> jnp.ndarray:
        return lm_head_logits(params["lm_head"],
                              self.hidden(params, batch))

    def loss(self, params, batch):
        x = self.hidden(params, batch)
        l = chunked_softmax_xent(x, params["lm_head"]["w"],
                                 batch["labels"], batch.get("mask"))
        return l, {"loss": l}

    def init_cache(self, batch: int, max_seq: int) -> Dict[str, Any]:
        cfg = self.cfg
        conv, h = M.mamba_init_state(cfg, batch)
        L = cfg.n_layers
        return {
            "conv": jnp.broadcast_to(conv, (L,) + conv.shape),
            "ssm": jnp.broadcast_to(h, (L,) + h.shape),
            "lengths": jnp.zeros((batch,), jnp.int32),
        }

    def cache_logical_axes(self):
        return {"conv": ("layers", "batch", None, "conv_dim"),
                "ssm": ("layers", "batch", "ssm_heads", None, "ssm_state"),
                "lengths": ("batch",)}

    def prefill(self, params: Params, batch: Batch, max_seq: int):
        cfg = self.cfg
        x = embed(params["embed"], batch["tokens"], jnp.dtype(cfg.dtype))
        b, s, _ = x.shape

        def body(x, bp):
            h = _norm(cfg, bp["norm"], x)
            y, (conv, hstate) = M.mamba_forward(bp["mamba"], cfg, h,
                                                return_state=True)
            return x + y, (conv.astype(jnp.float32), hstate)

        x, (convs, hs) = runmode.layer_scan(body, x, params["blocks"])
        x = _norm(cfg, params["final_norm"], x)
        logits = lm_head_logits(params["lm_head"], x[:, -1:])
        cache = {"conv": convs, "ssm": hs,
                 "lengths": jnp.full((b,), s, jnp.int32)}
        return logits, cache

    def decode_step(self, params: Params, cache, tokens):
        cfg = self.cfg
        x = embed(params["embed"], tokens, jnp.dtype(cfg.dtype))

        def body(x, layer):
            bp, conv, h = layer
            hin = _norm(cfg, bp["norm"], x)
            y, conv, h = M.mamba_decode(bp["mamba"], cfg, hin, conv, h)
            return x + y, (conv, h)

        x, (convs, hs) = runmode.layer_scan(
            body, x, (params["blocks"], cache["conv"], cache["ssm"]))
        x = _norm(cfg, params["final_norm"], x)
        logits = lm_head_logits(params["lm_head"], x)
        return logits, dict(cache, conv=convs, ssm=hs,
                            lengths=cache["lengths"] + 1)

    # ------------------------------------------------- continuous serving
    # Slot-pooled counterparts of DecoderLM's `prefill_packed_paged` /
    # `decode_step_paged`: the per-request state (conv window + SSM state)
    # is FIXED-SIZE, so instead of paged block tables each request owns one
    # row of a (layers, num_slots, ...) pool and every index below is
    # traced data — admission never compiles (see serve/statecache.py).

    def prefill_chunk_slots(self, params: Params, conv_pool: jnp.ndarray,
                            ssm_pool: jnp.ndarray, state_idx: jnp.ndarray,
                            tokens: jnp.ndarray, seg_len: jnp.ndarray,
                            seg_start: jnp.ndarray):
        """One prompt segment against the slot-pooled state cache — the
        prefill lane of the ssm unified serving step.

        tokens: (1, C) holding the segment's rows at offset 0 (rows past
        `seg_len` are padding — `mamba_chunk_forward` makes them exact
        identities); `state_idx` the request's pool row; `seg_start` the
        prompt offset of row 0.  seg_start == 0 selects ZERO carries
        in-program instead of the pool row, so a freshly claimed slot needs
        no zeroing pass (and no second executable).  Chunking a prompt in
        C-token segments reproduces `prefill` bitwise provided C is a
        multiple of `cfg.ssm_chunk` (the serve runtime rounds its chunk
        width up to guarantee that).

        Returns (logits (1, 1, V) at the segment's last real row, conv_pool,
        ssm_pool)."""
        cfg = self.cfg
        with scope("embed"):
            x = embed(params["embed"], tokens, jnp.dtype(cfg.dtype))
        state_idx = jnp.asarray(state_idx, jnp.int32)
        seg_len = jnp.asarray(seg_len, jnp.int32)
        fresh = jnp.asarray(seg_start, jnp.int32) == 0

        def body(x, layer):
            bp, cc, hc = layer
            with scope("norm"):
                hin = _norm(cfg, bp["norm"], x)
            y, cc, hc = M.mamba_chunk_forward(bp["mamba"], cfg, hin,
                                              cc[None], hc[None], seg_len)
            with scope("out_proj"):
                return x + y, (cc[0], hc[0])

        # the state pool's row read and write-back belong to the scan
        with scope("layers"):
            conv_c = conv_pool[:, state_idx]             # (L, W-1, conv_dim)
            ssm_c = ssm_pool[:, state_idx]               # (L, nh, hd, n)
            conv_c = jnp.where(fresh, jnp.zeros_like(conv_c), conv_c)
            ssm_c = jnp.where(fresh, jnp.zeros_like(ssm_c), ssm_c)
            x, (convs, hs) = runmode.layer_scan(
                body, x, (params["blocks"], conv_c, ssm_c))
            conv_pool = conv_pool.at[:, state_idx].set(convs)
            ssm_pool = ssm_pool.at[:, state_idx].set(hs)
        with scope("final_norm"):
            x = _norm(cfg, params["final_norm"], x)
        with scope("lm_head"):
            last = jnp.clip(seg_len - 1, 0, x.shape[1] - 1)
            x_last = jax.lax.dynamic_slice_in_dim(x, last, 1, axis=1)
            logits = lm_head_logits(params["lm_head"], x_last)
        return logits, conv_pool, ssm_pool

    def decode_step_slots(self, params: Params, conv_pool: jnp.ndarray,
                          ssm_pool: jnp.ndarray, state_idx: jnp.ndarray,
                          tokens: jnp.ndarray):
        """One decode token for every serving slot against the slot-pooled
        state cache.  state_idx: (B,) pool rows — idle/prefilling slots
        point at the NULL row 0, whose reads and colliding write-backs are
        garbage by construction and never reach a real request's row.
        tokens: (B, 1).  Returns (logits (B, 1, V), conv_pool, ssm_pool)."""
        cfg = self.cfg
        with scope("embed"):
            x = embed(params["embed"], tokens, jnp.dtype(cfg.dtype))
        state_idx = jnp.asarray(state_idx, jnp.int32)

        def body(x, layer):
            bp, cv, h = layer
            with scope("norm"):
                hin = _norm(cfg, bp["norm"], x)
            y, cv, h = M.mamba_decode(bp["mamba"], cfg, hin, cv, h)
            with scope("out_proj"):
                return x + y, (cv, h)

        # the state pool's row gather and write-back belong to the scan
        with scope("layers"):
            conv = conv_pool[:, state_idx]               # (L, B, W-1, conv)
            ssm = ssm_pool[:, state_idx]                 # (L, B, nh, hd, n)
            x, (convs, hs) = runmode.layer_scan(
                body, x, (params["blocks"], conv, ssm))
            conv_pool = conv_pool.at[:, state_idx].set(convs)
            ssm_pool = ssm_pool.at[:, state_idx].set(hs)
        with scope("final_norm"):
            x = _norm(cfg, params["final_norm"], x)
        logits = lm_head_logits(params["lm_head"], x)
        return logits, conv_pool, ssm_pool

    @staticmethod
    def slot_state_logical_axes():
        return {"conv": ("layers", None, None, "conv_dim"),
                "ssm": ("layers", None, "ssm_heads", None, "ssm_state")}


# ===================================================================== Zamba2
class HybridLM:
    """Mamba2 backbone with ONE shared attention block applied every
    `attn_every` layers (Zamba2's parameter-shared attention; the shared
    block sees concat(hidden, original_embeddings) through a down-projection).
    """

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.n_shared_uses = cfg.n_layers // cfg.attn_every

    def _group_sizes(self):
        cfg = self.cfg
        sizes = [cfg.attn_every] * (cfg.n_layers // cfg.attn_every)
        rem = cfg.n_layers % cfg.attn_every
        if rem:
            sizes.append(rem)
        return sizes

    def init(self, rng) -> Params:
        cfg = self.cfg
        k_emb, k_blocks, k_sh, k_final, k_proj = jax.random.split(rng, 5)
        keys = jax.random.split(k_blocks, cfg.n_layers)

        def one(k):
            return {"norm": _norm_init(cfg, cfg.d_model),
                    "mamba": M.mamba_init(k, cfg)}

        shared = {
            "in_proj": dense_init(k_proj, 2 * cfg.d_model, cfg.d_model),
            "attn_norm": _norm_init(cfg, cfg.d_model),
            "attn": A.attn_init(k_sh, cfg),
            "mlp_norm": _norm_init(cfg, cfg.d_model),
            "mlp": F.mlp_init(k_sh, cfg.d_model, cfg.d_ff),
        }
        return {
            "embed": embed_init(k_emb, cfg.vocab, cfg.d_model),
            "blocks": jax.vmap(one)(keys),
            "shared": shared,
            "final_norm": _norm_init(cfg, cfg.d_model),
            "lm_head": dense_init(k_final, cfg.d_model, cfg.vocab),
        }

    def logical_axes(self) -> Params:
        cfg = self.cfg
        block = {"norm": {"scale": (None,)}, "mamba": M.mamba_logical_axes(cfg)}
        blocks = jax.tree.map(
            lambda ax: ("layers",) + ax, block,
            is_leaf=lambda x: isinstance(x, tuple) and all(
                a is None or isinstance(a, str) for a in x))
        return {
            "embed": {"emb": ("vocab", "embed_tbl")},
            "blocks": blocks,
            "shared": {
                "in_proj": {"w": ("embed", "embed")},
                "attn_norm": {"scale": (None,)},
                "attn": A.attn_logical_axes(cfg),
                "mlp_norm": {"scale": (None,)},
                "mlp": F.mlp_logical_axes(),
            },
            "final_norm": {"scale": (None,)},
            "lm_head": {"w": ("embed_vec", "vocab")},
        }

    def _shared_apply(self, sp, x, x0, positions):
        cfg = self.cfg
        xin = dense(sp["in_proj"], jnp.concatenate([x, x0], -1))
        h = _norm(cfg, sp["attn_norm"], xin)
        y = A.attn_forward(sp["attn"], cfg, h, positions=positions, causal=True)
        xin = xin + y
        h = _norm(cfg, sp["mlp_norm"], xin)
        return x + xin + F.mlp_apply(sp["mlp"], h, cfg.act)

    def hidden(self, params: Params, batch: Batch) -> jnp.ndarray:
        cfg = self.cfg
        x = embed(params["embed"], batch["tokens"], jnp.dtype(cfg.dtype))
        x0 = x
        b, s, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))

        def mamba_body(x, bp):
            h = _norm(cfg, bp["norm"], x)
            return x + M.mamba_forward(bp["mamba"], cfg, h), None

        off = 0
        for gsize in self._group_sizes():
            group = jax.tree.map(lambda a: a[off:off + gsize], params["blocks"])
            x, _ = runmode.layer_scan(_remat(cfg, mamba_body), x, group)
            off += gsize
            if gsize == cfg.attn_every:   # full group -> shared attention
                x = self._shared_apply(params["shared"], x, x0, positions)
        return _norm(cfg, params["final_norm"], x)

    def forward(self, params: Params, batch: Batch) -> jnp.ndarray:
        return lm_head_logits(params["lm_head"],
                              self.hidden(params, batch))

    def loss(self, params, batch):
        x = self.hidden(params, batch)
        l = chunked_softmax_xent(x, params["lm_head"]["w"],
                                 batch["labels"], batch.get("mask"))
        return l, {"loss": l}

    # Serving: mamba states per layer + KV cache per shared-block use.
    def init_cache(self, batch: int, max_seq: int):
        cfg = self.cfg
        conv, h = M.mamba_init_state(cfg, batch)
        L = cfg.n_layers
        kv = A.init_cache(cfg, batch, max_seq, self.n_shared_uses,
                          jnp.dtype(cfg.dtype))
        return {
            "conv": jnp.broadcast_to(conv, (L,) + conv.shape),
            "ssm": jnp.broadcast_to(h, (L,) + h.shape),
            "k": kv["k"], "v": kv["v"],
            "lengths": jnp.zeros((batch,), jnp.int32),
        }

    def cache_logical_axes(self):
        return {"conv": ("layers", "batch", None, "conv_dim"),
                "ssm": ("layers", "batch", "ssm_heads", None, "ssm_state"),
                "k": ("layers", "batch", "kv_seq", "kv_heads", None),
                "v": ("layers", "batch", "kv_seq", "kv_heads", None),
                "lengths": ("batch",)}

    def prefill(self, params: Params, batch: Batch, max_seq: int):
        cfg = self.cfg
        # Prefill runs the forward path while accumulating every cache.
        x = embed(params["embed"], batch["tokens"], jnp.dtype(cfg.dtype))
        x0 = x
        b, s, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        cache = self.init_cache(b, max_seq)

        def mamba_body(x, bp):
            h = _norm(cfg, bp["norm"], x)
            y, (conv, hstate) = M.mamba_forward(bp["mamba"], cfg, h,
                                                return_state=True)
            return x + y, (conv.astype(jnp.float32), hstate)

        convs, ssms, use = [], [], 0
        off = 0
        for gsize in self._group_sizes():
            group = jax.tree.map(lambda a: a[off:off + gsize], params["blocks"])
            x, (cv, hs) = runmode.layer_scan(mamba_body, x, group)
            convs.append(cv)
            ssms.append(hs)
            off += gsize
            if gsize == cfg.attn_every:
                sp = params["shared"]
                xin = dense(sp["in_proj"], jnp.concatenate([x, x0], -1))
                h = _norm(cfg, sp["attn_norm"], xin)
                y, (k, v) = A.attn_forward(sp["attn"], cfg, h,
                                           positions=positions, causal=True,
                                           return_kv=True)
                xin = xin + y
                h = _norm(cfg, sp["mlp_norm"], xin)
                x = x + xin + F.mlp_apply(sp["mlp"], h, cfg.act)
                cache["k"] = cache["k"].at[use, :, :s].set(k.astype(cache["k"].dtype))
                cache["v"] = cache["v"].at[use, :, :s].set(v.astype(cache["v"].dtype))
                use += 1
        x = _norm(cfg, params["final_norm"], x)
        logits = lm_head_logits(params["lm_head"], x[:, -1:])
        cache["conv"] = jnp.concatenate(convs, 0)
        cache["ssm"] = jnp.concatenate(ssms, 0)
        cache["lengths"] = jnp.full((b,), s, jnp.int32)
        return logits, cache

    def decode_step(self, params: Params, cache, tokens):
        cfg = self.cfg
        x = embed(params["embed"], tokens, jnp.dtype(cfg.dtype))
        x0 = x
        lengths = cache["lengths"]

        def mamba_body(x, layer):
            bp, conv, h = layer
            hin = _norm(cfg, bp["norm"], x)
            y, conv, h = M.mamba_decode(bp["mamba"], cfg, hin, conv, h)
            return x + y, (conv, h)

        convs, ssms, use = [], [], 0
        off = 0
        new_k, new_v = cache["k"], cache["v"]
        for gsize in self._group_sizes():
            layer = (jax.tree.map(lambda a: a[off:off + gsize], params["blocks"]),
                     cache["conv"][off:off + gsize],
                     cache["ssm"][off:off + gsize])
            x, (cv, hs) = runmode.layer_scan(mamba_body, x, layer)
            convs.append(cv)
            ssms.append(hs)
            off += gsize
            if gsize == cfg.attn_every:
                sp = params["shared"]
                xin = dense(sp["in_proj"], jnp.concatenate([x, x0], -1))
                h = _norm(cfg, sp["attn_norm"], xin)
                y, kc, vc = A.attn_decode(sp["attn"], cfg, h,
                                          new_k[use], new_v[use], lengths)
                new_k = new_k.at[use].set(kc)
                new_v = new_v.at[use].set(vc)
                xin = xin + y
                h = _norm(cfg, sp["mlp_norm"], xin)
                x = x + xin + F.mlp_apply(sp["mlp"], h, cfg.act)
                use += 1
        x = _norm(cfg, params["final_norm"], x)
        logits = lm_head_logits(params["lm_head"], x)
        return logits, dict(cache, conv=jnp.concatenate(convs, 0),
                            ssm=jnp.concatenate(ssms, 0), k=new_k, v=new_v,
                            lengths=lengths + 1)


# ===================================================================== Whisper
class EncDecLM:
    """Whisper-style encoder-decoder.  The audio conv frontend is a stub:
    `batch['audio_embeds']` carries pre-computed frame embeddings."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    def _enc_block_init(self, k):
        cfg = self.cfg
        return {
            "attn_norm": _norm_init(cfg, cfg.d_model),
            "attn": A.attn_init(k, cfg),
            "mlp_norm": _norm_init(cfg, cfg.d_model),
            "mlp": F.mlp_init(k, cfg.d_model, cfg.d_ff, gated=False),
        }

    def _dec_block_init(self, k):
        cfg = self.cfg
        k1, k2, k3 = jax.random.split(k, 3)
        return {
            "self_norm": _norm_init(cfg, cfg.d_model),
            "self_attn": A.attn_init(k1, cfg),
            "cross_norm": _norm_init(cfg, cfg.d_model),
            "cross_attn": A.attn_init(k2, cfg),
            "mlp_norm": _norm_init(cfg, cfg.d_model),
            "mlp": F.mlp_init(k3, cfg.d_model, cfg.d_ff, gated=False),
        }

    def init(self, rng) -> Params:
        cfg = self.cfg
        ke, kenc, kdec, kf = jax.random.split(rng, 4)
        enc_keys = jax.random.split(kenc, cfg.n_enc_layers)
        dec_keys = jax.random.split(kdec, cfg.n_layers)
        return {
            "embed": embed_init(ke, cfg.vocab, cfg.d_model),
            "enc_pos": jax.random.normal(ke, (cfg.enc_seq, cfg.d_model),
                                         jnp.float32) * 0.01,
            "encoder": jax.vmap(self._enc_block_init)(enc_keys),
            "enc_norm": _norm_init(cfg, cfg.d_model),
            "decoder": jax.vmap(self._dec_block_init)(dec_keys),
            "final_norm": _norm_init(cfg, cfg.d_model),
            "lm_head": dense_init(kf, cfg.d_model, cfg.vocab),
        }

    def logical_axes(self) -> Params:
        cfg = self.cfg
        norm_ax = {"scale": (None,)} if cfg.norm == "rms" else {"scale": (None,), "bias": (None,)}
        enc_block = {
            "attn_norm": dict(norm_ax), "attn": A.attn_logical_axes(cfg),
            "mlp_norm": dict(norm_ax), "mlp": F.mlp_logical_axes(gated=False),
        }
        dec_block = {
            "self_norm": dict(norm_ax), "self_attn": A.attn_logical_axes(cfg),
            "cross_norm": dict(norm_ax), "cross_attn": A.attn_logical_axes(cfg),
            "mlp_norm": dict(norm_ax), "mlp": F.mlp_logical_axes(gated=False),
        }
        is_ax = lambda x: isinstance(x, tuple) and all(
            a is None or isinstance(a, str) for a in x)
        return {
            "embed": {"emb": ("vocab", "embed_tbl")},
            "enc_pos": (None, "embed"),
            "encoder": jax.tree.map(lambda ax: ("layers",) + ax, enc_block, is_leaf=is_ax),
            "enc_norm": dict(norm_ax),
            "decoder": jax.tree.map(lambda ax: ("layers",) + ax, dec_block, is_leaf=is_ax),
            "final_norm": dict(norm_ax),
            "lm_head": {"w": ("embed_vec", "vocab")},
        }

    def encode(self, params: Params, audio_embeds: jnp.ndarray) -> jnp.ndarray:
        cfg = self.cfg
        x = audio_embeds.astype(jnp.dtype(cfg.dtype))
        x = x + params["enc_pos"].astype(x.dtype)[None, : x.shape[1]]

        def body(x, bp):
            h = _norm(cfg, bp["attn_norm"], x)
            x = x + A.attn_forward(bp["attn"], cfg, h, positions=None, causal=False)
            h = _norm(cfg, bp["mlp_norm"], x)
            return x + F.mlp_apply(bp["mlp"], h, "gelu"), None

        x, _ = runmode.layer_scan(_remat(cfg, body), x, params["encoder"])
        return _norm(cfg, params["enc_norm"], x)

    def _dec_body(self, cfg, positions, enc_kv_l, x, bp_and_kv):
        bp, (ek, ev) = bp_and_kv
        h = _norm(cfg, bp["self_norm"], x)
        x = x + A.attn_forward(bp["self_attn"], cfg, h, positions=positions,
                               causal=True)
        h = _norm(cfg, bp["cross_norm"], x)
        x = x + A.attn_forward(bp["cross_attn"], cfg, h, kv_override=(ek, ev))
        h = _norm(cfg, bp["mlp_norm"], x)
        return x + F.mlp_apply(bp["mlp"], h, "gelu"), None

    def hidden(self, params: Params, batch: Batch) -> jnp.ndarray:
        cfg = self.cfg
        enc = self.encode(params, batch["audio_embeds"])
        x = embed(params["embed"], batch["tokens"], jnp.dtype(cfg.dtype))
        b, s, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        # precompute per-layer cross KV (scan over decoder layers)
        enc_kv = jax.vmap(lambda bp: A.cross_kv(bp["cross_attn"], cfg, enc))(
            params["decoder"])
        body = _remat(cfg, functools.partial(self._dec_body, cfg, positions, None))
        x, _ = runmode.layer_scan(body, x, (params["decoder"], enc_kv))
        return _norm(cfg, params["final_norm"], x)

    def forward(self, params: Params, batch: Batch) -> jnp.ndarray:
        return lm_head_logits(params["lm_head"],
                              self.hidden(params, batch))

    def loss(self, params, batch):
        x = self.hidden(params, batch)
        l = chunked_softmax_xent(x, params["lm_head"]["w"],
                                 batch["labels"], batch.get("mask"))
        return l, {"loss": l}

    def init_cache(self, batch: int, max_seq: int):
        cfg = self.cfg
        kv = A.init_cache(cfg, batch, max_seq, cfg.n_layers, jnp.dtype(cfg.dtype))
        enc_shape = (cfg.n_layers, batch, cfg.enc_seq, cfg.n_kv_heads, cfg.hd)
        return {
            "k": kv["k"], "v": kv["v"],
            "ek": jnp.zeros(enc_shape, jnp.dtype(cfg.dtype)),
            "ev": jnp.zeros(enc_shape, jnp.dtype(cfg.dtype)),
            "lengths": jnp.zeros((batch,), jnp.int32),
        }

    def cache_logical_axes(self):
        ax = ("layers", "batch", "kv_seq", "kv_heads", None)
        return {"k": ax, "v": ax, "ek": ax, "ev": ax, "lengths": ("batch",)}

    def prefill(self, params: Params, batch: Batch, max_seq: int):
        cfg = self.cfg
        enc = self.encode(params, batch["audio_embeds"])
        x = embed(params["embed"], batch["tokens"], jnp.dtype(cfg.dtype))
        b, s, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        enc_kv = jax.vmap(lambda bp: A.cross_kv(bp["cross_attn"], cfg, enc))(
            params["decoder"])

        def body(x, bp_and_kv):
            bp, (ek, ev) = bp_and_kv
            h = _norm(cfg, bp["self_norm"], x)
            y, (k, v) = A.attn_forward(bp["self_attn"], cfg, h,
                                       positions=positions, causal=True,
                                       return_kv=True)
            x = x + y
            h = _norm(cfg, bp["cross_norm"], x)
            x = x + A.attn_forward(bp["cross_attn"], cfg, h, kv_override=(ek, ev))
            h = _norm(cfg, bp["mlp_norm"], x)
            return x + F.mlp_apply(bp["mlp"], h, "gelu"), (k, v)

        x, (ks, vs) = runmode.layer_scan(body, x, (params["decoder"], enc_kv))
        x = _norm(cfg, params["final_norm"], x)
        logits = lm_head_logits(params["lm_head"], x[:, -1:])
        cache = self.init_cache(b, max_seq)
        cache["k"] = cache["k"].at[:, :, :s].set(ks.astype(cache["k"].dtype))
        cache["v"] = cache["v"].at[:, :, :s].set(vs.astype(cache["v"].dtype))
        cache["ek"] = enc_kv[0].astype(cache["ek"].dtype)
        cache["ev"] = enc_kv[1].astype(cache["ev"].dtype)
        cache["lengths"] = jnp.full((b,), s, jnp.int32)
        return logits, cache

    def decode_step(self, params: Params, cache, tokens):
        cfg = self.cfg
        x = embed(params["embed"], tokens, jnp.dtype(cfg.dtype))
        lengths = cache["lengths"]

        def body(x, layer):
            bp, kc, vc, ek, ev = layer
            h = _norm(cfg, bp["self_norm"], x)
            y, kc, vc = A.attn_decode(bp["self_attn"], cfg, h, kc, vc, lengths)
            x = x + y
            h = _norm(cfg, bp["cross_norm"], x)
            x = x + A.attn_forward(bp["cross_attn"], cfg, h, kv_override=(ek, ev))
            h = _norm(cfg, bp["mlp_norm"], x)
            return x + F.mlp_apply(bp["mlp"], h, "gelu"), (kc, vc)

        x, (ks, vs) = runmode.layer_scan(
            body, x, (params["decoder"], cache["k"], cache["v"],
                      cache["ek"], cache["ev"]))
        x = _norm(cfg, params["final_norm"], x)
        logits = lm_head_logits(params["lm_head"], x)
        return logits, dict(cache, k=ks, v=vs, lengths=lengths + 1)


# ===================================================================== factory
def build_model(cfg: ModelConfig):
    if cfg.family in ("dense", "moe", "vlm"):
        return DecoderLM(cfg)
    if cfg.family == "ssm":
        return MambaLM(cfg)
    if cfg.family == "hybrid":
        return HybridLM(cfg)
    if cfg.family == "encdec":
        return EncDecLM(cfg)
    raise ValueError(f"unknown family {cfg.family}")
