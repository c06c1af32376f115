"""Operations and bytes a serving step needs, from the configuration's
published sizes (HF key names) and the step's actual work — the same
whatever lane implements it.

A step is a decode lane (one token per decoding request, each attending to
its context) plus, in the unified program, a prefill lane (prompt segments
`(start, n)`: rows start .. start+n-1 of a prompt, row p attending to p+1
keys).  The least bytes a step moves are the layer weights and the output
head once, the embedding rows it looks up, the K/V of every key attended
(read once per request and step) and the K/V rows it writes.  Activations
are left out: at these widths they are a few MB against GB of weights and
cache.
"""

from __future__ import annotations

from typing import Dict, Iterable, Tuple

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def dims(cfg: Dict) -> Dict[str, int]:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or d // h
    return dict(d=d, h=h, hkv=cfg["num_key_value_heads"], hd=hd,
                ff=cfg["intermediate_size"], L=cfg["num_hidden_layers"],
                V=cfg["vocab_size"])


def layer_params(cfg: Dict) -> int:
    """Matmul weights of one decoder layer (q, k, v, o; gate, up, down)."""
    m = dims(cfg)
    attn = m["d"] * (m["h"] + 2 * m["hkv"]) * m["hd"] + m["h"] * m["hd"] * m["d"]
    return attn + 3 * m["d"] * m["ff"]


def norm_params(cfg: Dict) -> int:
    m = dims(cfg)
    per_layer = 2 * m["d"] + (2 * m["hd"] if cfg["model_type"] == "qwen3" else 0)
    return m["L"] * per_layer + m["d"]


def head_params(cfg: Dict) -> int:
    m = dims(cfg)
    return m["d"] * m["V"]


def kv_bytes_per_token(cfg: Dict) -> int:
    """K and V of one token over every layer."""
    m = dims(cfg)
    return m["L"] * 2 * m["hkv"] * m["hd"] * BYTES[cfg["torch_dtype"]]


def attention_flops(cfg: Dict, keys: int) -> int:
    """QK^T and PV of one query row against `keys` keys, every layer."""
    m = dims(cfg)
    return m["L"] * 4 * keys * m["h"] * m["hd"]


def token_flops(cfg: Dict, keys: int, head: bool) -> int:
    """Model operations of one token: 2 per layer weight, attention at its
    actual context, and the output head where its logits are needed."""
    f = 2 * cfg["num_hidden_layers"] * layer_params(cfg)
    f += attention_flops(cfg, keys)
    if head:
        f += 2 * head_params(cfg)
    return f


def step_work(cfg: Dict, decode_keys: Iterable[int],
              segments: Iterable[Tuple[int, int, bool]]) -> Tuple[int, int]:
    """(operations, bytes) of one step.

    decode_keys: per decoding request, the keys its new token attends to
    (its context including the token itself).  segments: per prompt
    segment, (start, n, completes) — `completes` when the segment ends the
    prompt, so its last row's logits give the first token."""
    m = dims(cfg)
    item = BYTES[cfg["torch_dtype"]]
    kv_tok = kv_bytes_per_token(cfg)
    flops = 0
    kv_read = 0
    rows = 0
    for keys in decode_keys:
        flops += token_flops(cfg, keys, head=True)
        kv_read += keys * kv_tok
        rows += 1
    for start, n, completes in segments:
        # row p attends to p + 1 keys: sum over the segment's rows
        flops += n * token_flops(cfg, 0, head=False)
        flops += attention_flops(cfg, n * start + n * (n + 1) // 2)
        if completes:
            flops += 2 * head_params(cfg)
        kv_read += (start + n) * kv_tok
        rows += n
    weights = (m["L"] * layer_params(cfg) + head_params(cfg)
               + norm_params(cfg)) * item
    moved = weights + rows * m["d"] * item + kv_read + rows * kv_tok
    return flops, moved


def least_time_s(flops: float, nbytes: float, peaks: Dict,
                 chips: int) -> Tuple[float, str]:
    """The least time the chips could take, and which bound sets it."""
    t_flops = flops / (chips * peaks["bf16_flops_per_s"])
    t_bytes = nbytes / (chips * peaks["hbm_bytes_per_s"])
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
