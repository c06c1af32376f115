"""`correct`: the control fails the limit, and a run with its timed path
broken underneath comes out not correct.

Each test drives `run.run_cell` on the CPU at a small size — the harness's
look for a chip is the only part left out — with a cell's own
configuration cut to 2 layers x 128 and its mix cut to short requests:
the qwen3 chat cell on one device, the granite backlog cell on four host
devices.  The limit is this size's own, set the way the cells' are: above
the program's reading (0.009-0.014 logits on the seeds tried) and below
the control's (0.10-0.23).
"""

import math

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run
from repro import hw

SEED = 2**31 + 12345
LIMIT = 0.05
CELL = {"name": "qwen3-1.7b.chat", "config": "qwen3-1.7b", "traffic": "chat",
        "chips": 1}


BACKLOG = {"name": "granite-3-8b-tp4.chat-backlog",
           "config": "granite-3-8b-tp4", "traffic": "chat-backlog",
           "chips": 4}


def small_config(name: str = "qwen3-1.7b"):
    """`name`'s configuration cut to 2 layers x 128; the vocabulary keeps
    its remainder mod 4 (granite's does not divide over four chips)."""
    cfg = run.load_config(name)
    cfg.update(num_hidden_layers=2, hidden_size=128, num_attention_heads=4,
               num_key_value_heads=max(2, cfg["mesh"]["model"]),
               head_dim=32, intermediate_size=256,
               vocab_size=512 + cfg["vocab_size"] % 4)
    if "attention_multiplier" in cfg:
        cfg["attention_multiplier"] = 1 / math.sqrt(32)
    cfg["runtime"] = {"max_slots": 4, "block_size": 16, "max_context": 256,
                      "num_blocks": None, "chunk_tokens": 64,
                      "chunk_segments": 4}
    cfg["check"] = dict(cfg["check"], max_logit_gap=LIMIT)
    return cfg


def small_mix():
    mix = run.traffic_mod.load("chat")
    mix["prompt"]["median"] = 40
    mix["output"]["median"] = 24
    mix["arrival"]["rate_per_s"] = 30.0  # keeps all four slots decoding
    mix["warmup_s"] = 1
    mix["tail_s"] = 20  # room to drain when the CPU is shared
    return mix


def serve(control=False):
    return run.run_cell(CELL, SEED, 3.0, False, config=small_config(),
                        mix=small_mix(), chip=hw.TPU_V5E, control=control)


def serve_backlog(control=False):
    """The backlog cell at the small size; needs four devices."""
    mix = run.traffic_mod.load("chat-backlog")
    mix["prompt"]["median"] = 40
    mix["output"]["median"] = 24
    mix["arrival"]["requests"] = 40
    mix["warmup_s"] = 1
    return run.run_cell(BACKLOG, SEED, 3.0, False,
                        config=small_config("granite-3-8b-tp4"), mix=mix,
                        chip=hw.TPU_V5E, control=control)


def test_sound_run_is_correct_and_control_is_not():
    res = serve(control=True)
    gap = res["checks"]["max_logit_gap"]["value"]
    ctrl = res["control_checks"]["max_logit_gap"]["value"]
    assert res["correct"], res["checks"]
    assert not res["control_correct"], res["control_checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert gap <= LIMIT < ctrl
    assert set(res["metrics"]) == {"ttft_p90_s", "tpot_p90_ms", "itl_p95_ms",
                                   "setup_s"}


def test_state_left_unchanged(monkeypatch):
    """The step returns the K/V pools it was given: nothing the prompt or
    the decode wrote stays in the cache."""
    from repro.models import attention as A

    def keep(fn):
        def wrapped(p, cfg, x, k_pool, v_pool, *a, **k):
            y, _, _ = fn(p, cfg, x, k_pool, v_pool, *a, **k)
            return y, k_pool, v_pool
        return wrapped

    monkeypatch.setattr(A, "attn_prefill_packed", keep(A.attn_prefill_packed))
    monkeypatch.setattr(A, "attn_decode_paged", keep(A.attn_decode_paged))
    assert not serve()["correct"]


def test_half_the_batch_left_out(monkeypatch):
    """The decode lane computes the first half of its slots and hands the
    rest the mean of those rows' logits."""
    from repro.models.zoo import DecoderLM

    orig = DecoderLM.decode_step_paged

    def half(self, *a, **k):
        logits, ks, vs = orig(self, *a, **k)
        h = logits.shape[0] // 2
        return logits.at[h:].set(logits[:h].mean(0)), ks, vs

    monkeypatch.setattr(DecoderLM, "decode_step_paged", half)
    assert not serve()["correct"]


def test_token_altered_where_produced(monkeypatch):
    """Every step's first sampled row comes out one id higher."""
    from repro.launch import steps

    orig = steps.sample_tokens

    def bump(logits, sampling, keys):
        tok = orig(logits, sampling, keys)
        return tok.at[0].set((tok[0] + 1) % logits.shape[-1])

    monkeypatch.setattr(steps, "sample_tokens", bump)
    assert not serve()["correct"]


EXCHANGE_LEFT_OUT = r"""
import json, sys
sys.path.insert(0, sys.argv[1])
import conftest  # noqa: F401  (puts bench/ and src/ on the path)
import jax
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import tp_mesh
from repro.models import mlp
import test_correct as tc

fault = sys.argv[2] == "fault"
orig = mlp.dense


def dense(p, x, role=None, activation=None):
    # mlp_down is row-parallel over the model axis: each chip's partial
    # product is all-reduced.  The fault keeps each chip's own partial.
    if role != "mlp_down" or not fault:
        return orig(p, x, role=role, activation=activation)
    mesh = tp_mesh(4)
    w = p["w"].astype(x.dtype)
    return jax.shard_map(lambda a, b: a @ b, mesh=mesh,
                         in_specs=(P(None, None, "model"), P("model", None)),
                         out_specs=P(), check_vma=False)(x, w)


mlp.dense = dense
res = tc.serve_backlog(control=not fault)
print(json.dumps({k: res.get(k) for k in ("correct", "checks", "metrics",
                                          "control_correct")}))
"""


@pytest.mark.parametrize("fault", ["sound", "fault"])
def test_exchange_between_chips_left_out(fault, tmp_path):
    """The backlog cell, tensor-parallel on four host devices: sound, the
    run is correct and its control is not; with mlp_down's all-reduce left
    out, the run is not correct."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    script = tmp_path / "exchange.py"
    script.write_text(EXCHANGE_LEFT_OUT)
    here = Path(__file__).resolve().parent
    out = subprocess.run([sys.executable, str(script), str(here), fault],
                         env=env, capture_output=True, text=True,
                         timeout=600, cwd=here.parents[1])
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["correct"] == (fault == "sound"), res["checks"]
    if fault == "sound":
        assert res["control_correct"] is False
        assert set(res["metrics"]) == {"tokens_per_s", "setup_s"}


def test_no_chip_no_result():
    """Without a TPU the command exits non-zero and prints no result."""
    root = Path(run.__file__).resolve().parents[1]
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen3-1.7b.chat",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300, cwd=root)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
