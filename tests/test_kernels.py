"""Pallas-kernel correctness: shape/dtype sweeps against the pure-jnp
oracles in repro.kernels.ref (the Pallas interpreter on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import kernels
from repro.kernels import ref
from repro.kernels.matmul import matmul_padded

TOLS = {np.float32: dict(rtol=2e-4, atol=2e-4),
        jnp.bfloat16: dict(rtol=5e-2, atol=5e-2)}


@pytest.mark.parametrize("m,k,n", [(128, 128, 128), (256, 384, 128),
                                   (64, 512, 256), (70, 200, 130), (8, 128, 128)])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_matmul_shapes_dtypes(m, k, n, dtype):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.dtype(dtype))
    w = jnp.asarray(rng.standard_normal((k, n)) * 0.1, jnp.dtype(dtype))
    out = kernels.matmul(x, w)
    want = ref.matmul_ref(x, w)
    tol = TOLS[jnp.bfloat16] if dtype == "bfloat16" else TOLS[np.float32]
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **tol)


@pytest.mark.parametrize("order", ["mn", "nm"])
@pytest.mark.parametrize("act", [None, "relu", "gelu", "silu"])
def test_matmul_fused_epilogue(order, act):
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((128, 256)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((256, 128)) * 0.1, jnp.float32)
    b = jnp.asarray(rng.standard_normal((1, 128)), jnp.float32)
    out = matmul_padded(x, w, b, bm=64, bn=128, bk=128, order=order,
                        activation=act)
    want = ref.matmul_ref(x, w, b[0], activation=act)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("k,stride,cin,cout", [
    (1, 1, 8, 16), (3, 1, 16, 32), (3, 2, 16, 32), (5, 2, 4, 8), (7, 2, 3, 16)])
def test_conv2d_kernel_sweep(k, stride, cin, cout):
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((2, 16, 16, cin)) * 0.3, jnp.float32)
    w = jnp.asarray(rng.standard_normal((k, k, cin, cout)) * 0.2, jnp.float32)
    b = jnp.asarray(rng.standard_normal((cout,)) * 0.1, jnp.float32)
    out = kernels.conv2d(x, w, b, stride=stride, padding="SAME", layout="NHWC",
                         activation="relu")
    want = ref.conv2d_ref(x, w, b, stride=stride, padding="SAME", activation="relu")
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_conv2d_nchw_layout_matches():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((1, 8, 12, 12)) * 0.3, jnp.float32)
    w = jnp.asarray(rng.standard_normal((16, 8, 3, 3)) * 0.2, jnp.float32)
    out = kernels.conv2d(x, w, None, stride=1, padding="SAME", layout="NCHW")
    dn = jax.lax.conv_dimension_numbers(x.shape, w.shape, ("NCHW", "OIHW", "NCHW"))
    want = jax.lax.conv_general_dilated(x, w, (1, 1), "SAME", dimension_numbers=dn)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("sq,skv,h,hkv", [(256, 256, 4, 4), (256, 256, 4, 2),
                                          (200, 200, 4, 1), (128, 384, 2, 2)])
@pytest.mark.parametrize("causal", [True, False])
def test_attention_sweep(sq, skv, h, hkv, causal):
    if causal and sq != skv:
        pytest.skip("causal requires aligned histories here")
    rng = np.random.default_rng(4)
    q = jnp.asarray(rng.standard_normal((2, sq, h, 64)) * 0.3, jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, skv, hkv, 64)) * 0.3, jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, skv, hkv, 64)) * 0.3, jnp.float32)
    out = kernels.attention(q, k, v, causal=causal,
                            config={"block_q": 128, "block_kv": 128})
    want = ref.attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("lengths", [[64, 199], [1, 256], [256, 256]])
def test_attention_decode_lengths(lengths):
    rng = np.random.default_rng(5)
    B, S, H, HKV, D = 2, 256, 4, 2, 64
    q = jnp.asarray(rng.standard_normal((B, H, D)) * 0.3, jnp.float32)
    kc = jnp.asarray(rng.standard_normal((B, S, HKV, D)) * 0.3, jnp.float32)
    vc = jnp.asarray(rng.standard_normal((B, S, HKV, D)) * 0.3, jnp.float32)
    L = jnp.asarray(np.array(lengths, np.int32))
    out = kernels.attention_decode(q, kc, vc, L, config={"block_kv": 128})
    want = ref.attention_decode_ref(q, kc, vc, L)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=3e-4, atol=3e-4)


def test_fused_elementwise_chain():
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.standard_normal((3, 50, 33)), jnp.float32)
    e = jnp.asarray(rng.standard_normal((3, 50, 33)), jnp.float32)
    chain = [{"op": "add"}, {"op": "gelu"}, {"op": "mul"}, {"op": "tanh"}]
    out = kernels.fused_elementwise(x, chain, [e, e])
    want = ref.fused_elementwise_ref(x, chain, [e, e])
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_tuned_config_from_search_is_numerically_sound():
    """End-to-end: a genetic-search winning config must run correctly."""
    from repro.core import SearchTask, TEMPLATES, genetic_search
    from repro.core.schedules import OpDesc
    op = OpDesc.matmul(256, 256, 384, dtype="float32")
    res = genetic_search(SearchTask(op, TEMPLATES["pallas_matmul"], seed=0))
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((256, 384)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((384, 256)) * 0.1, jnp.float32)
    out = kernels.matmul(x, w, config=res.config)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref.matmul_ref(x, w)),
                               rtol=2e-4, atol=2e-4)


def test_kernels_interpret_exactly_off_tpu():
    """The one backend decision: interpreted on the CPU, no compiler
    params to hand a chip that is not there."""
    from repro.kernels import tpu

    assert jax.default_backend() == "cpu"
    assert tpu.interpret() is True
    assert tpu.pallas_call_args(("parallel",)) == {"interpret": True}


def test_device_kind_lookup_names_the_chip_and_refuses_unknown_kinds():
    from repro import hw

    assert hw.chip_for_kind("TPU v5 lite") is hw.TPU_V5E
    assert hw.chip_for_kind("TPU v5") is hw.TPU_V5P
    for kind in ("cpu", "TPU v9", ""):
        with pytest.raises(ValueError, match="no hardware model"):
            hw.chip_for_kind(kind)
    for chip, _source in hw.DEVICE_KINDS.values():
        assert 0 < chip.vmem_budget_bytes < chip.vmem_bytes
