"""MLP + Mixture-of-Experts layers.

The MoE uses capacity-based scatter dispatch (GShard-style) formulated as
gather/scatter + batched einsum so it (a) compiles on any mesh, (b) shards
experts over the `model` axis (EP — XLA inserts the all-to-alls at the
resharding boundary), and (c) has compiled FLOPs ≈ top-k active FLOPs ×
capacity_factor, keeping the roofline analysis honest (no dense all-experts
overcounting).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain
from repro.models.common import Params, dense, dense_init
from repro.serve.trace import scope


def _act(x, kind: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}[kind](x)


# ------------------------------------------------------------------ dense MLP
def mlp_init(rng, d: int, f: int, gated: bool = True) -> Params:
    k1, k2, k3 = jax.random.split(rng, 3)
    p = {"w_in": dense_init(k1, d, f), "w_out": dense_init(k2, f, d)}
    if gated:
        p["w_gate"] = dense_init(k3, d, f)
    return p


def mlp_logical_axes(gated: bool = True) -> Params:
    p = {"w_in": {"w": ("embed", "ffn")}, "w_out": {"w": ("ffn", "embed")}}
    if gated:
        p["w_gate"] = {"w": ("embed", "ffn")}
    return p


def mlp_apply(p: Params, x: jnp.ndarray, act: str = "silu") -> jnp.ndarray:
    # role-tagged for the serve plan's stage matmul dispatch; the activation
    # rides into the tuned kernel's epilogue (XLA lane applies it after).
    # The gate product sits in the up projection's scope too, so a fusion
    # rooted at it is charged to mlp_up.
    with scope("mlp_up"):
        if "w_gate" in p:
            h = dense(p["w_in"], x, role="mlp_up")
            h = dense(p["w_gate"], x, role="mlp_up", activation=act) * h
        else:
            h = dense(p["w_in"], x, role="mlp_up", activation=act)
        h = constrain(h, ("batch", None, "ffn"))
    return dense(p["w_out"], h, role="mlp_down")


# ------------------------------------------------------------------ MoE
def moe_init(rng, cfg: ModelConfig) -> Params:
    d, e, f = cfg.d_model, cfg.n_experts, cfg.d_expert
    k1, k2, k3, k4, k5 = jax.random.split(rng, 5)
    scale = 1.0 / np.sqrt(d)
    p: Params = {
        "router": dense_init(k1, d, e),
        "w_in": jax.random.normal(k2, (e, d, f), jnp.float32) * scale,
        "w_gate": jax.random.normal(k3, (e, d, f), jnp.float32) * scale,
        "w_out": jax.random.normal(k4, (e, f, d), jnp.float32) / np.sqrt(f),
    }
    if cfg.n_shared_experts:
        p["shared"] = mlp_init(k5, d, cfg.n_shared_experts * f)
        p["shared_gate"] = dense_init(k5, d, 1)
    return p


def moe_logical_axes(cfg: ModelConfig) -> Params:
    p = {
        "router": {"w": ("embed", None)},
        "w_in": ("experts", "embed", "expert_ffn"),
        "w_gate": ("experts", "embed", "expert_ffn"),
        "w_out": ("experts", "expert_ffn", "embed"),
    }
    if cfg.n_shared_experts:
        p["shared"] = mlp_logical_axes()
        p["shared_gate"] = {"w": ("embed", None)}
    return p


def moe_capacity(cfg: ModelConfig, tokens_per_group: int) -> int:
    cap = int(np.ceil(tokens_per_group * cfg.top_k / cfg.n_experts
                      * cfg.capacity_factor))
    return max(4, -(-cap // 4) * 4)


def moe_apply(p: Params, cfg: ModelConfig, x: jnp.ndarray,
              act: str = "silu") -> jnp.ndarray:
    """x: (B, S, d).  Groups = sequences (decode: the whole batch is one
    group).  Returns (B, S, d) plus auxiliary-loss-free routing (inference
    framework — no load-balancing loss term needed for the forward).
    """
    b, s, d = x.shape
    squeeze = False
    if s == 1:                     # decode: group across the batch instead
        x = x.reshape(1, b, d)
        b, s = 1, b
        squeeze = True
    e, k = cfg.n_experts, cfg.top_k
    cap = moe_capacity(cfg, s)

    logits = dense(p["router"], x)                      # (B, S, E)
    probs = jax.nn.softmax(logits.astype(jnp.float32), -1)
    top_p, top_i = jax.lax.top_k(probs, k)              # (B, S, k)
    if cfg.norm_topk:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    top_p = top_p.astype(x.dtype)

    # Position of each (token, choice) inside its expert's capacity queue.
    flat_i = top_i.reshape(b, s * k)                    # (B, T)
    onehot = jax.nn.one_hot(flat_i, e, dtype=jnp.int32) # (B, T, E)
    pos_all = jnp.cumsum(onehot, axis=1) - onehot       # tokens before me
    pos = jnp.take_along_axis(pos_all, flat_i[..., None], -1)[..., 0]  # (B, T)
    keep = pos < cap

    xs = jnp.repeat(x, k, axis=1)                       # (B, T, d) token copies
    xs = constrain(xs, ("batch", "moe_tokens", None))
    weights = top_p.reshape(b, s * k)

    def scatter_one(e_idx, c_idx, keep_b, xs_b):
        buf = jnp.zeros((e, cap, d), xs_b.dtype)
        return buf.at[e_idx, jnp.where(keep_b, c_idx, cap - 1)].add(
            xs_b * keep_b[:, None].astype(xs_b.dtype), mode="drop")

    # NOTE: mode='drop' + clamped index keeps dropped tokens out of the buf.
    expert_in = jax.vmap(scatter_one)(flat_i, pos, keep, xs)   # (B, E, cap, d)
    expert_in = constrain(expert_in, ("batch", "experts", None, None))

    h = jnp.einsum("becd,edf->becf", expert_in, p["w_in"].astype(x.dtype))
    g = jnp.einsum("becd,edf->becf", expert_in, p["w_gate"].astype(x.dtype))
    h = _act(g, act) * h
    y = jnp.einsum("becf,efd->becd", h, p["w_out"].astype(x.dtype))
    y = constrain(y, ("batch", "experts", None, None))

    # Gather each (token, choice) result back and combine with router weights.
    def gather_one(y_b, e_idx, c_idx):
        return y_b[e_idx, c_idx]                        # (T, d)

    out_tk = jax.vmap(gather_one)(y, flat_i, jnp.minimum(pos, cap - 1))
    out_tk = constrain(out_tk.astype(x.dtype), ("batch", "moe_tokens", None))
    out_tk = out_tk * (weights * keep.astype(x.dtype))[..., None]
    out = out_tk.reshape(b, s, k, d).sum(axis=2)

    if cfg.n_shared_experts:
        gate = jax.nn.sigmoid(dense(p["shared_gate"], x).astype(jnp.float32))
        out = out + mlp_apply(p["shared"], x, act) * gate.astype(x.dtype)

    if squeeze:
        out = out.reshape(s, 1, d)
    return out
