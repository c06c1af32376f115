"""Plain reference of the served decoder models, and its lower-precision
control.

The forward pass follows the published description of a Llama-style
decoder as the configuration file states it (HF key names): token
embedding times `embedding_multiplier`; per layer RMSNorm, grouped-query
attention with RoPE (split-half rotation, `rope_theta`), q/k RMSNorm over
the head dimension for `model_type` "qwen3", causal softmax scaled by
`attention_multiplier` (default 1/sqrt(head_dim)), the residual branch
scaled by `residual_multiplier`; SwiGLU MLP; final RMSNorm; untied output
head, logits divided by `logits_scaling`.  Everything runs in float32 with
`Precision.HIGHEST` matmuls, over one batch of right-padded sequences
(causal attention keeps padding away from real rows), layer by layer under
`lax.scan`, attention in query blocks.

It imports nothing of the system under test.  The weights are the
benchmark's own (see `run.make_weights`), read by the names of the
parameter tree the benchmark made them in.

`precision="fp8"` is the control: every matmul's inputs rounded to
float8_e4m3 with a per-row (activations) and per-column (weights) scale,
accumulated in float32 — the step below the bfloat16 the configurations
state.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 256
ROW_BLOCK = 512


def _fp8(x, axis):
    """Round to float8_e4m3 under a scale that maps the largest magnitude
    along `axis` to the format's largest finite value."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, 448.0 / amax, 1.0)
    q = (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q / scale


def _matmul(x, w, precision: str):
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if precision == "fp8":
        x = _fp8(x, -1)
        w = _fp8(w, 0)
    return jnp.matmul(x, w, precision=HIGHEST)


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x, pos, theta):
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos[..., None].astype(jnp.float32) * freqs          # (B, T, d/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, scale):
    """Causal attention, float32, in blocks of Q_BLOCK query rows.
    q (B, T, H, D); k, v (B, T, Hkv, D); T a multiple of Q_BLOCK."""
    b, t, h, d = q.shape
    hkv = k.shape[2]
    g = h // hkv
    qb = q.reshape(b, t // Q_BLOCK, Q_BLOCK, hkv, g, d).transpose(1, 0, 2, 3,
                                                                   4, 5)
    kpos = jnp.arange(t)

    def block(args):
        i, qi = args
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qi, k,
                       precision=HIGHEST) * scale
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhgqk,bkhd->bqhgd", p, v, precision=HIGHEST)

    out = jax.lax.map(block, (jnp.arange(t // Q_BLOCK), qb))
    return out.transpose(1, 0, 2, 3, 4, 5).reshape(b, t, h, d)


def _hyper(cfg: Dict):
    hd = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    return dict(
        hd=hd,
        h=cfg["num_attention_heads"],
        hkv=cfg["num_key_value_heads"],
        eps=cfg["rms_norm_eps"],
        theta=float(cfg["rope_theta"]),
        qk_norm=cfg["model_type"] == "qwen3",
        emb_mult=cfg.get("embedding_multiplier", 1.0),
        res_mult=cfg.get("residual_multiplier", 1.0),
        attn_scale=cfg.get("attention_multiplier", 1.0 / math.sqrt(hd)),
        logit_div=cfg.get("logits_scaling", 1.0),
    )


@functools.partial(jax.jit, static_argnames=("hp", "precision"))
def _forward(params, tokens, rows, cols, *, hp, precision):
    """Logits (float32) at the (rows, cols) positions of `tokens` (B, T)."""
    hp = dict(hp)
    mm = functools.partial(_matmul, precision=precision)
    b, t = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(t), (b, t))
    x = params["embed"]["emb"][tokens].astype(jnp.float32) * hp["emb_mult"]

    def layer(x, p):
        a = p["attn"]
        h = _rms(x, p["attn_norm"]["scale"], hp["eps"])
        q = mm(h, a["wq"]["w"]).reshape(b, t, hp["h"], hp["hd"])
        k = mm(h, a["wk"]["w"]).reshape(b, t, hp["hkv"], hp["hd"])
        v = mm(h, a["wv"]["w"]).reshape(b, t, hp["hkv"], hp["hd"])
        if hp["qk_norm"]:
            q = _rms(q, a["q_norm"]["scale"], hp["eps"])
            k = _rms(k, a["k_norm"]["scale"], hp["eps"])
        q = _rope(q, pos, hp["theta"])
        k = _rope(k, pos, hp["theta"])
        o = _attention(q, k, v, hp["attn_scale"]).reshape(b, t, -1)
        x = x + hp["res_mult"] * mm(o, a["wo"]["w"])
        m = p["mlp"]
        h = _rms(x, p["mlp_norm"]["scale"], hp["eps"])
        u = jax.nn.silu(mm(h, m["w_gate"]["w"])) * mm(h, m["w_in"]["w"])
        x = x + hp["res_mult"] * mm(u, m["w_out"]["w"])
        return x, None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    x = _rms(x[rows, cols], params["final_norm"]["scale"], hp["eps"])
    return mm(x, params["lm_head"]["w"]) / hp["logit_div"]


def padded_batch(seqs: Sequence[np.ndarray], batch: int, width: int):
    """Right-pad `seqs` into a (batch, width) int32 array (extra rows are
    all padding: they only keep the compiled shape fixed)."""
    out = np.zeros((batch, width), np.int32)
    for i, s in enumerate(seqs):
        out[i, :len(s)] = s
    return out


def logits_at(cfg: Dict, params, seqs: Sequence[np.ndarray],
              positions: Sequence[np.ndarray], *, batch: int, width: int,
              precision: str = "f32") -> List[np.ndarray]:
    """Reference logits of each sequence at its `positions` (the rows whose
    next token was served), one float32 (len(positions[i]), vocab) array
    per sequence."""
    tokens = padded_batch(seqs, batch, width)
    n = sum(len(p) for p in positions)
    # the row count is padded to a multiple of ROW_BLOCK so that few
    # shapes ever compile
    rows = np.zeros(-(-n // ROW_BLOCK) * ROW_BLOCK, np.int32)
    cols = np.zeros_like(rows)
    rows[:n] = np.concatenate([np.full(len(p), i, np.int32)
                               for i, p in enumerate(positions)])
    cols[:n] = np.concatenate([np.asarray(p, np.int32) for p in positions])
    hp = tuple(sorted(_hyper(cfg).items()))
    out = np.asarray(_forward(params, jnp.asarray(tokens), jnp.asarray(rows),
                              jnp.asarray(cols), hp=hp, precision=precision))
    splits = np.cumsum([len(p) for p in positions])[:-1]
    return np.split(out[:n], splits)


def served_gaps(ref_logits: np.ndarray, served: np.ndarray) -> np.ndarray:
    """How far each served token's reference logit lies below the
    reference's best at its position."""
    return ref_logits.max(axis=1) - ref_logits[np.arange(len(served)), served]
