"""Mamba2 (state-space duality) block — chunked SSD for train/prefill,
O(1)-state recurrence for decode.

Follows the minimal SSD reference of Dao & Gu (arXiv:2405.21060, Listing 1):
the sequence is split into chunks of Q tokens; within a chunk the output is
a (masked) quadratic form computed on the MXU, across chunks a tiny scan
propagates the (n_heads, head_dim, d_state) states.  This is the TPU-native
rendering of the paper['s] "SSM as matmuls" insight — every heavy op below
is an einsum.

Decode keeps two small carries per layer: the depthwise-conv window (last
`conv_width-1` inputs) and the SSM state h: h' = exp(dt*A) h + dt * B ⊗ x.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain
from repro.models.common import Params, dense, dense_init, norm_init, rms_norm
from repro.serve.trace import scope


def _dims(cfg: ModelConfig):
    d_in = cfg.ssm_expand * cfg.d_model
    nheads = d_in // cfg.ssm_head_dim
    conv_dim = d_in + 2 * cfg.ssm_state
    return d_in, nheads, conv_dim


def mamba_init(rng, cfg: ModelConfig) -> Params:
    d = cfg.d_model
    d_in, nh, conv_dim = _dims(cfg)
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    return {
        "in_proj": dense_init(k1, d, 2 * d_in + 2 * cfg.ssm_state + nh),
        "conv_w": jax.random.normal(k2, (cfg.conv_width, conv_dim), jnp.float32) * 0.2,
        "conv_b": jnp.zeros((conv_dim,), jnp.float32),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, nh).astype(jnp.float32)),
        "D": jnp.ones((nh,), jnp.float32),
        "dt_bias": jnp.log(jnp.expm1(
            jnp.exp(jax.random.uniform(k3, (nh,), jnp.float32,
                                       np.log(1e-3), np.log(1e-1))))),
        "norm": norm_init(d_in),
        "out_proj": dense_init(k4, d_in, d),
    }


def mamba_logical_axes(cfg: ModelConfig) -> Params:
    return {
        "in_proj": {"w": ("embed", "conv_dim")},
        "conv_w": (None, "conv_dim"),
        "conv_b": ("conv_dim",),
        "A_log": ("ssm_heads",),
        "D": ("ssm_heads",),
        "dt_bias": ("ssm_heads",),
        "norm": {"scale": (None,)},
        "out_proj": {"w": ("conv_dim", "embed")},
    }


def _split_proj(cfg: ModelConfig, zxbcdt: jnp.ndarray):
    d_in, nh, _ = _dims(cfg)
    n = cfg.ssm_state
    z = zxbcdt[..., :d_in]
    xbc = zxbcdt[..., d_in : 2 * d_in + 2 * n]
    dt = zxbcdt[..., 2 * d_in + 2 * n :]
    return z, xbc, dt


def _causal_conv(w, b, xbc: jnp.ndarray, act=jax.nn.silu) -> jnp.ndarray:
    """Depthwise causal conv via static shifts (window is 4)."""
    width = w.shape[0]
    out = xbc * w[-1].astype(xbc.dtype)
    for i in range(1, width):
        shifted = jnp.pad(xbc, ((0, 0), (i, 0), (0, 0)))[:, : xbc.shape[1]]
        out = out + shifted * w[-1 - i].astype(xbc.dtype)
    return act(out + b.astype(xbc.dtype))


def _causal_conv_carry(w, b, xbc: jnp.ndarray, carry: jnp.ndarray,
                       act=jax.nn.silu) -> jnp.ndarray:
    """`_causal_conv` continued from a previous segment: the last `W-1`
    raw inputs of that segment (`carry`, (B, W-1, conv_dim)) stand in for
    the zero left-padding.  Same shift-and-accumulate order as
    `_causal_conv`, so a fresh (all-zero) carry is bitwise identical to the
    from-scratch conv — that equivalence is what lets chunked serve-time
    prefill reproduce `mamba_forward` exactly."""
    width = w.shape[0]
    s = xbc.shape[1]
    ext = jnp.concatenate([carry.astype(xbc.dtype), xbc], axis=1)
    out = xbc * w[-1].astype(xbc.dtype)
    for i in range(1, width):
        shifted = ext[:, width - 1 - i : width - 1 - i + s]
        out = out + shifted * w[-1 - i].astype(xbc.dtype)
    return act(out + b.astype(xbc.dtype))


def _segsum(x: jnp.ndarray) -> jnp.ndarray:
    """(..., Q) -> (..., Q, Q) lower-triangular segment sums:
    out[i, j] = sum_{j < t <= i} x[t]; -inf above the diagonal."""
    q = x.shape[-1]
    cs = jnp.cumsum(x, -1)
    out = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((q, q), bool), 0)
    return jnp.where(mask, out, -jnp.inf)


def ssd_chunked(x, dt, A, B, C, chunk: int, h0=None):
    """SSD scan.  x: (b, s, h, p); dt: (b, s, h); A: (h,);
    B, C: (b, s, n).  Returns y: (b, s, h, p), final state (b, h, p, n).

    `h0` (optional, (b, h, p, n)) seeds the inter-chunk recurrence —
    serve-time chunked prefill threads the previous segment's state through
    it.  The default (None -> zeros) is the exact value the scan used
    before the parameter existed, so existing callers are bitwise
    unchanged."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    q = min(chunk, s)
    assert s % q == 0, (s, q)
    nc = s // q

    xc = x.reshape(b, nc, q, h, p)
    dtc = dt.reshape(b, nc, q, h)
    Bc = B.reshape(b, nc, q, n)
    Cc = C.reshape(b, nc, q, n)
    dA = dtc * A                                             # (b, nc, q, h)
    dA_cs = jnp.cumsum(dA, axis=2)

    # 1. intra-chunk (quadratic in q — all MXU work)
    L = jnp.exp(_segsum(dA.transpose(0, 1, 3, 2)))           # (b, nc, h, q, q)
    scores = jnp.einsum("bcqn,bckn->bcqk", Cc, Bc)           # (b, nc, q, q)
    xdt = xc * dtc[..., None]                                # (b, nc, q, h, p)
    y_diag = jnp.einsum("bcqk,bchqk,bckhp->bcqhp", scores, L, xdt)
    # 2. chunk states
    decay_states = jnp.exp(dA_cs[:, :, -1:, :] - dA_cs)      # (b, nc, q, h)
    states = jnp.einsum("bckn,bckh,bckhp->bchpn", Bc, decay_states, xdt)

    # 3. inter-chunk recurrence over nc (tiny scan)
    chunk_decay = jnp.exp(dA_cs[:, :, -1, :])                # (b, nc, h)

    def step(h_prev, inp):
        decay, st = inp                                      # (b,h), (b,h,p,n)
        h_new = h_prev * decay[..., None, None] + st
        return h_new, h_prev

    if h0 is None:
        h0 = jnp.zeros((b, h, p, n), x.dtype)
    h_final, h_prevs = jax.lax.scan(
        step, h0, (chunk_decay.transpose(1, 0, 2), states.transpose(1, 0, 2, 3, 4)))
    h_prevs = h_prevs.transpose(1, 0, 2, 3, 4)               # (b, nc, h, p, n)

    # 4. contribution of previous-chunk states
    state_decay = jnp.exp(dA_cs)                             # (b, nc, q, h)
    y_off = jnp.einsum("bcqn,bchpn,bcqh->bcqhp", Cc, h_prevs, state_decay)

    y = (y_diag + y_off).reshape(b, s, h, p)
    return y, h_final


def mamba_forward(p: Params, cfg: ModelConfig, x: jnp.ndarray,
                  return_state: bool = False):
    """x: (B, S, d) -> (B, S, d) (+ decode carries)."""
    d_in, nh, conv_dim = _dims(cfg)
    n, hd = cfg.ssm_state, cfg.ssm_head_dim
    b, s, _ = x.shape

    zxbcdt = dense(p["in_proj"], x)
    z, xbc_raw, dt = _split_proj(cfg, zxbcdt)
    xbc = _causal_conv(p["conv_w"], p["conv_b"], xbc_raw)
    xs = xbc[..., :d_in].reshape(b, s, nh, hd)
    Bmat = xbc[..., d_in : d_in + n]
    Cmat = xbc[..., d_in + n :]
    xs = constrain(xs, ("batch", None, "ssm_heads", None))

    A = -jnp.exp(p["A_log"]).astype(jnp.float32)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    y, h_final = ssd_chunked(xs.astype(jnp.float32), dt, A,
                             Bmat.astype(jnp.float32), Cmat.astype(jnp.float32),
                             cfg.ssm_chunk)
    y = y + xs.astype(jnp.float32) * p["D"][:, None]
    y = y.reshape(b, s, d_in).astype(x.dtype)
    y = rms_norm(p["norm"], y * jax.nn.silu(z))
    out = dense(p["out_proj"], y)
    if return_state:
        conv_cache = xbc_raw[:, -(cfg.conv_width - 1):, :]   # (B, W-1, conv_dim)
        return out, (conv_cache, h_final)
    return out


def mamba_chunk_forward(p: Params, cfg: ModelConfig, x: jnp.ndarray,
                        conv_carry: jnp.ndarray, h0: jnp.ndarray,
                        seg_len: jnp.ndarray):
    """One serve-time prefill segment, resumable: `mamba_forward` over a
    fixed-width window `x` (B, C, d) of which only the first `seg_len`
    rows are real prompt, continuing from `conv_carry` (B, W-1, conv_dim)
    and SSM state `h0` (B, nh, hd, n).

    Bitwise contract (pinned by tests): feeding a prompt through this in
    `C`-token segments — zero carries on the first segment, each segment's
    returned carries into the next — reproduces `mamba_forward`'s outputs
    and final state EXACTLY, provided `C` is a multiple of `cfg.ssm_chunk`.
    Three mechanisms make that exact rather than approximate:

      * padding rows beyond `seg_len` get dt forced to 0.0 AFTER the
        softplus, which makes them exact identities in the SSD recurrence
        (`exp(0) = 1` state decay, `+0.0` state update) — no masking of x
        or B/C is needed;
      * the conv continues via `_causal_conv_carry`, whose accumulation
        order matches `_causal_conv` term for term;
      * the inter-chunk scan is seeded with `h0` through `ssd_chunked`'s
        initial-state parameter — the per-chunk step function is the one
        the full pass runs.

    Returns (y (B, C, d), new_conv_carry (B, W-1, conv_dim) f32,
    h_final (B, nh, hd, n) f32).  The new conv carry is read at offset
    `seg_len` of the carry-extended raw conv input, i.e. the last W-1 REAL
    rows even when the segment underfills the window."""
    d_in, nh, conv_dim = _dims(cfg)
    n, hd = cfg.ssm_state, cfg.ssm_head_dim
    b, s, _ = x.shape

    with scope("in_proj"):
        zxbcdt = dense(p["in_proj"], x, role="in_proj")
        z, xbc_raw, dt = _split_proj(cfg, zxbcdt)
    seg_len = jnp.asarray(seg_len, jnp.int32)
    with scope("conv"):
        xbc = _causal_conv_carry(p["conv_w"], p["conv_b"], xbc_raw,
                                 conv_carry)
        xs = xbc[..., :d_in].reshape(b, s, nh, hd)
        Bmat = xbc[..., d_in : d_in + n]
        Cmat = xbc[..., d_in + n :]
        xs = constrain(xs, ("batch", None, "ssm_heads", None))
        ext = jnp.concatenate([conv_carry.astype(xbc_raw.dtype), xbc_raw],
                              axis=1)
        new_carry = jax.lax.dynamic_slice(
            ext, (jnp.int32(0), seg_len, jnp.int32(0)),
            (b, cfg.conv_width - 1, conv_dim)).astype(jnp.float32)

    with scope("ssd"):
        A = -jnp.exp(p["A_log"]).astype(jnp.float32)
        dtv = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
        row = jnp.arange(s)[None, :, None]
        dtv = jnp.where(row < seg_len, dtv, 0.0)
        y, h_final = ssd_chunked(xs.astype(jnp.float32), dtv, A,
                                 Bmat.astype(jnp.float32),
                                 Cmat.astype(jnp.float32),
                                 cfg.ssm_chunk, h0=h0.astype(jnp.float32))
        y = y + xs.astype(jnp.float32) * p["D"][:, None]
        y = y.reshape(b, s, d_in).astype(x.dtype)
    # the gated norm feeds the out projection: one scope
    with scope("out_proj"):
        y = rms_norm(p["norm"], y * jax.nn.silu(z))
        out = dense(p["out_proj"], y, role="out_proj")
    return out, new_carry, h_final


def mamba_init_state(cfg: ModelConfig, batch: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    d_in, nh, conv_dim = _dims(cfg)
    conv = jnp.zeros((batch, cfg.conv_width - 1, conv_dim), jnp.float32)
    h = jnp.zeros((batch, nh, cfg.ssm_head_dim, cfg.ssm_state), jnp.float32)
    return conv, h


def mamba_decode(p: Params, cfg: ModelConfig, x: jnp.ndarray,
                 conv_cache: jnp.ndarray, h: jnp.ndarray):
    """x: (B, 1, d); conv_cache: (B, W-1, conv_dim); h: (B, nh, hd, n)."""
    d_in, nh, conv_dim = _dims(cfg)
    n, hd = cfg.ssm_state, cfg.ssm_head_dim
    b = x.shape[0]

    with scope("in_proj"):
        zxbcdt = dense(p["in_proj"], x, role="in_proj")
        z, xbc_raw, dt = _split_proj(cfg, zxbcdt)
    with scope("conv"):
        window = jnp.concatenate([conv_cache.astype(xbc_raw.dtype), xbc_raw],
                                 axis=1)
        xbc = jax.nn.silu(
            jnp.einsum("bwc,wc->bc", window, p["conv_w"].astype(window.dtype))
            + p["conv_b"].astype(window.dtype))[:, None, :]
        new_conv = window[:, 1:, :].astype(jnp.float32)

    with scope("ssd"):
        xs = xbc[..., :d_in].reshape(b, nh, hd).astype(jnp.float32)
        Bm = xbc[:, 0, d_in : d_in + n].astype(jnp.float32)      # (B, n)
        Cm = xbc[:, 0, d_in + n :].astype(jnp.float32)
        A = -jnp.exp(p["A_log"]).astype(jnp.float32)
        dtv = jax.nn.softplus(dt[:, 0].astype(jnp.float32)
                              + p["dt_bias"])                    # (B, nh)

        dA = jnp.exp(dtv * A)                                    # (B, nh)
        h = h * dA[..., None, None] + jnp.einsum(
            "bhp,bn,bh->bhpn", xs, Bm, dtv)
        y = jnp.einsum("bhpn,bn->bhp", h, Cm) + xs * p["D"][:, None]
        y = y.reshape(b, 1, d_in).astype(x.dtype)
    with scope("out_proj"):
        y = rms_norm(p["norm"], y * jax.nn.silu(z))
        return dense(p["out_proj"], y, role="out_proj"), new_conv, h
