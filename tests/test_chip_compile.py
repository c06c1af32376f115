"""Compile-only rehearsals of the serving path's Pallas kernels for a TPU v5e.

No chip is attached: the TPU compiler JAX ships compiles for a topology
described by name and refuses what the chip's compiler would refuse, such as
a tile that is not aligned or a kernel that needs more VMEM than its
`vmem_limit_bytes`.  Shapes are qwen3-1.7b's published widths.  The kernels
decide `interpret` and their VMEM budget in `repro.kernels.tpu`; each test
steers that module to the described chip.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import hw
from repro.configs import get_config
from repro.core.schedules import (AttentionTemplate, MatmulTemplate, OpDesc,
                                  _vmem_matmul_bytes)
from repro.kernels import tpu
from repro.kernels.attention import flash_decode_paged, flash_prefill_paged
from repro.kernels.matmul import matmul_padded

CFG = get_config("qwen3-1.7b")
GROUP = CFG.n_heads // CFG.n_kv_heads
# the smoke configuration's pool: 8 slots x 64 blocks of 16 rows + the sink
SLOTS, BLOCK, TABLE, CHUNK, SEGMENTS = 8, 16, 64, 256, 4
POOL = SLOTS * TABLE + 1


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            yield topologies.get_topology_desc(platform="tpu",
                                               topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler can describe it here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def v5e(topo, monkeypatch):
    """Kernels compile natively for the described chip, cache off: a
    compile for a chip that is not attached cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    chip = hw.chip_for_kind(topo.devices[0].device_kind)
    monkeypatch.setattr(tpu, "interpret", lambda: False)
    monkeypatch.setattr(tpu, "target_chip", lambda: chip)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield chip
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _largest(template, op, chip):
    need = (lambda c: _vmem_matmul_bytes(c["bm"], c["bn"], c["bk"], op.dtype))
    return max(template.enumerate_configs(op, chip), key=need)


# (rows, K, N): each stage matmul of a 256-row prefill chunk, and mlp_up of
# a 2048-token prompt — where 512x1024x2048 blocks used to exceed the
# compiler's default VMEM limit
MATMULS = {
    "chunk-qkv_proj": (CHUNK, CFG.d_model, CFG.n_heads * CFG.hd),
    "chunk-mlp_up": (CHUNK, CFG.d_model, CFG.d_ff),
    "chunk-mlp_down": (CHUNK, CFG.d_ff, CFG.d_model),
    "chunk-lm_head": (CHUNK, CFG.d_model, CFG.vocab),
    "prompt2048-mlp_up": (2048, CFG.d_model, CFG.d_ff),
}


@pytest.mark.parametrize("name", sorted(MATMULS))
def test_matmul_largest_admitted_config_compiles(name, v5e, one_chip):
    m, k, n = MATMULS[name]
    op = OpDesc.matmul(m, n, k)
    cfg = _largest(MatmulTemplate(), op, v5e)
    bm, bn, bk = cfg["bm"], cfg["bn"], cfg["bk"]
    pad = lambda x, b: -(-x // b) * b  # noqa: E731
    x = jax.ShapeDtypeStruct((pad(m, bm), pad(k, bk)), jnp.bfloat16,
                             sharding=one_chip)
    w = jax.ShapeDtypeStruct((pad(k, bk), pad(n, bn)), jnp.bfloat16,
                             sharding=one_chip)
    text = _compiled_text(
        lambda x, w: matmul_padded(x, w, None, bm=bm, bn=bn, bk=bk,
                                   order=cfg["order"]), x, w)
    assert "tpu_custom_call" in text


def test_flash_decode_paged_compiles(v5e, one_chip):
    s = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    text = _compiled_text(
        flash_decode_paged, s((SLOTS, GROUP, CFG.hd)),
        s((POOL, BLOCK, CFG.hd)), s((POOL, BLOCK, CFG.hd)),
        s((SLOTS,), jnp.int32), s((SLOTS, TABLE), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("block_q", [128, "largest"])
def test_flash_prefill_paged_compiles(block_q, v5e, one_chip):
    if block_q == "largest":
        op = OpDesc.attention(1, CHUNK, TABLE * BLOCK, CFG.n_heads, CFG.hd,
                              label="prefill_chunk.attention",
                              kv_heads=CFG.n_kv_heads)
        block_q = max(c["block_q"] for c in
                      AttentionTemplate().enumerate_configs(op, v5e))
    s = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    text = _compiled_text(
        lambda q, k, v, t, i: flash_prefill_paged(q, k, v, t, i,
                                                  block_q=block_q),
        s((CHUNK, GROUP, CFG.hd)), s((POOL, BLOCK, CFG.hd)),
        s((POOL, BLOCK, CFG.hd)), s((SEGMENTS, TABLE), jnp.int32),
        s((SEGMENTS, 3), jnp.int32))
    assert "tpu_custom_call" in text
