"""Shared model building blocks (pure-JAX, pytree params).

Everything here is a pair of functions: `init_*(rng, ...) -> params` and a
pure apply.  No flax/haiku — params are plain dicts so that sharding rules,
checkpointing and the WPK backend dispatch stay transparent.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.sharding import constrain
from repro.serve.trace import scope

Params = Dict[str, Any]


def dense_init(rng, d_in: int, d_out: int, scale: Optional[float] = None) -> Params:
    scale = scale if scale is not None else 1.0 / np.sqrt(d_in)
    return {"w": jax.random.normal(rng, (d_in, d_out), jnp.float32) * scale}


def dense(p: Params, x: jnp.ndarray, role: Optional[str] = None,
          activation: Optional[str] = None) -> jnp.ndarray:
    """Projection `x @ w`, routable through the WPK plan's matmul lanes.

    `role` names the projection against the serve plan's stage matmul table
    ('qkv_proj' / 'mlp_up' / 'mlp_down' / 'lm_head'); inside an active
    `kernels.dispatch.matmul_dispatch` context the chosen lane (XLA vs tuned
    Pallas) runs instead of the plain dot.  `activation` is fused into the
    tuned kernel's epilogue (applied after the dot on the XLA lane — same
    numerics).  A role also names the projection's scope in the compiled
    program (`repro.serve.trace.scope`).  With no role/activation this is
    exactly `x @ w`."""
    w = p["w"].astype(x.dtype)
    if role is None and activation is None:
        return x @ w
    from repro.kernels.dispatch import dispatch_dense
    if role is None:
        return dispatch_dense(role, x, w, activation=activation)
    with scope(role):
        return dispatch_dense(role, x, w, activation=activation)


def norm_init(d: int) -> Params:
    return {"scale": jnp.ones((d,), jnp.float32)}


def rms_norm(p: Params, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"]).astype(x.dtype)


def layer_norm_init(d: int) -> Params:
    return {"scale": jnp.ones((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)}


def layer_norm(p: Params, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.var(xf, -1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"] + p["bias"]).astype(x.dtype)


# ------------------------------------------------------------------- RoPE
def rope_freqs(head_dim: int, theta: float = 10000.0) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float = 10000.0) -> jnp.ndarray:
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                               # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, D/2)
    angles = angles[..., None, :]                              # (..., S, 1, D/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


def apply_mrope(x: jnp.ndarray, positions_3d: jnp.ndarray,
                sections: Tuple[int, int, int], theta: float = 10000.0) -> jnp.ndarray:
    """Multimodal RoPE (qwen2-vl): the head dim is partitioned into
    (temporal, height, width) sections, each rotated by its own position
    stream.  positions_3d: (..., S, 3).  sections are in *pairs* (sum = D/2).
    For text tokens all three streams are equal, reducing to standard RoPE.
    """
    d = x.shape[-1]
    assert sum(sections) == d // 2, (sections, d)
    freqs = rope_freqs(d, theta)                               # (D/2,)
    # select the position stream per frequency-pair index (static mapping)
    sec_ids = np.repeat(np.arange(3), np.array(sections))      # (D/2,)
    pos = positions_3d.astype(jnp.float32)[..., sec_ids]       # (..., S, D/2)
    angles = pos * freqs                                       # (..., S, D/2)
    angles = angles[..., None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.astype(x.dtype)


def embed_init(rng, vocab: int, d: int) -> Params:
    return {"emb": jax.random.normal(rng, (vocab, d), jnp.float32) * 0.01}


def embed(p: Params, tokens: jnp.ndarray, dtype) -> jnp.ndarray:
    return jnp.take(p["emb"].astype(dtype), tokens, axis=0)


def unembed(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    logits = x @ p["emb"].astype(x.dtype).T
    return constrain(logits, ("batch", None, "vocab"))


def lm_head_logits(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    """LM head projection `x @ w` (w: (d_model, vocab)), role-tagged so the
    serve plan's `lm_head` stage choice dispatches it (see kernels.dispatch)."""
    with scope("lm_head"):
        return constrain(dense(p, x, role="lm_head"),
                         ("batch", None, "vocab"))


def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray,
                  mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    nll = logz - gold
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def chunked_softmax_xent(x: jnp.ndarray, w_lm: jnp.ndarray,
                         labels: jnp.ndarray,
                         mask: Optional[jnp.ndarray] = None,
                         chunk: int = 512) -> jnp.ndarray:
    """Cross-entropy over sequence chunks WITHOUT materialising the full
    (B, S, V) logits tensor: each chunk's logits are computed, reduced to a
    scalar, and (via jax.checkpoint) recomputed in backward.  This is the
    difference between a ~200 GiB and a ~1 GiB loss temp at
    (B=256, S=4096, V=152k) — see EXPERIMENTS.md §Perf."""
    from repro.models import runmode
    b, s, d = x.shape
    if s % chunk or s <= chunk:
        logits = constrain((x @ w_lm.astype(x.dtype)), ("batch", None, "vocab"))
        return cross_entropy(logits, labels, mask)
    nc = s // chunk
    xc = x.reshape(b, nc, chunk, d).transpose(1, 0, 2, 3)
    lc = labels.reshape(b, nc, chunk).transpose(1, 0, 2)
    mc = (mask.reshape(b, nc, chunk).transpose(1, 0, 2)
          if mask is not None else jnp.ones_like(lc, jnp.float32))

    @jax.checkpoint
    def body(carry, inp):
        xb, lb, mb = inp
        logits = constrain((xb @ w_lm.astype(xb.dtype)),
                           ("batch", None, "vocab")).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, -1)
        gold = jnp.take_along_axis(logits, lb[..., None], -1)[..., 0]
        nll = (logz - gold) * mb
        return (carry[0] + jnp.sum(nll), carry[1] + jnp.sum(mb)), None

    (tot, cnt), _ = runmode.layer_scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (xc, lc, mc))
    return tot / jnp.maximum(cnt, 1.0)
