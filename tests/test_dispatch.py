"""Plan-driven matmul dispatch: lane registry semantics, tuned-Pallas vs
XLA lane equivalence at serve shapes, and the continuous engine routing its
stage matmuls through a tuned plan without recompiling on admission."""

import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.plan import InferencePlan, OpChoice
from repro.distributed.sharding import DEFAULT_RULES
from repro.kernels import dispatch
from repro.launch.mesh import single_device_mesh
from repro.models import build_model
from repro.models.common import dense
from repro.serve.router import PlanRouter
from repro.serve.runtime import ContinuousEngine, RuntimeConfig

MM_CFG = {"bm": 8, "bn": 128, "bk": 128, "order": "mn", "k_unroll": 1}


def _forced_pallas_plan() -> InferencePlan:
    """A serve plan whose every stage matmul picks the tuned Pallas lane."""
    plan = InferencePlan("serve", "tpu_v5e")
    for stage in ("prefill", "decode", "prefill_chunk"):
        for op in dispatch.MATMUL_ROLES:
            plan.choices[f"{stage}.{op}"] = OpChoice(
                "pallas_matmul", dict(MM_CFG), 1e-4)
    return plan


# ------------------------------------------------------------------ registry
def test_lane_registry_has_both_lanes():
    lanes = dispatch.lanes()
    assert "xla" in lanes and "pallas_matmul" in lanes


def test_unknown_backend_raises_inside_context():
    x = jnp.ones((2, 8))
    w = jnp.ones((8, 8))
    with dispatch.matmul_dispatch({"qkv_proj": ("no_such_lane", {})}):
        with pytest.raises(KeyError, match="no_such_lane"):
            dispatch.dispatch_dense("qkv_proj", x, w)


def test_dense_outside_context_is_plain_matmul():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((3, 5, 16)), jnp.float32)
    p = {"w": jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)}
    assert dispatch.active_table() is None
    np.testing.assert_array_equal(np.asarray(dense(p, x, role="qkv_proj")),
                                  np.asarray(x @ p["w"]))


def test_unnamed_role_falls_back_to_xla_inside_context():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((4, 16)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
    table = {"mlp_up": ("pallas_matmul", dict(MM_CFG))}
    with dispatch.matmul_dispatch(table):
        assert dispatch.active_table() == table
        out = dispatch.dispatch_dense("qkv_proj", x, w)   # role not in table
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x @ w))


# -------------------------------------------------- lane equivalence (shapes)
# Serve-shaped stage matmuls of a small DecoderLM: d=64, h=4/hkv=2, hd=16,
# d_ff=128, vocab=97.  Decode: B=slots, L=1.  Prefill: B=1, long L.
_D, _QKV, _FF, _V = 64, (4 + 2 * 2) * 16, 128, 97
SERVE_MATMULS = [
    ("decode.qkv_proj", (4, 1, _D), _QKV, None),
    ("decode.mlp_up", (4, 1, _D), _FF, "silu"),
    ("decode.mlp_down", (4, 1, _FF), _D, None),
    ("decode.lm_head", (4, 1, _D), _V, None),
    ("prefill.qkv_proj", (1, 48, _D), _QKV, None),
    ("prefill.mlp_up", (1, 48, _D), _FF, "silu"),
    ("prefill.mlp_down", (1, 48, _FF), _D, None),
    ("prefill.lm_head", (1, 48, _D), _V, None),
]


@pytest.mark.parametrize("name,xshape,n,act", SERVE_MATMULS,
                         ids=[m[0] for m in SERVE_MATMULS])
def test_tuned_lane_matches_xla_lane_at_serve_shapes(name, xshape, n, act):
    """The paper's race is only sound if every lane computes the same
    function: tuned Pallas vs XLA within f32 tolerance at serve shapes."""
    rng = np.random.default_rng(zlib.crc32(name.encode()))
    x = jnp.asarray(rng.standard_normal(xshape), jnp.float32)
    w = jnp.asarray(rng.standard_normal((xshape[-1], n)), jnp.float32)
    ref = dispatch.xla_lane(x, w, activation=act)
    out = dispatch.pallas_matmul_lane(x, w, config=dict(MM_CFG),
                                      activation=act)
    assert out.shape == ref.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_fused_activation_matches_unfused():
    """activation= in the tuned kernel's epilogue == act(x @ w)."""
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((4, 1, _D)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((_D, _FF)), jnp.float32)
    fused = dispatch.pallas_matmul_lane(x, w, config=dict(MM_CFG),
                                        activation="silu")
    unfused = jax.nn.silu(x @ w)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(unfused),
                               rtol=2e-5, atol=2e-5)


# ------------------------------------------------------------- engine routing
@pytest.fixture(scope="module")
def tiny_f32_lm():
    # float32 so greedy argmax cannot flip on bf16-resolution near-ties
    # between the (equivalent) lanes.
    cfg = get_config("qwen3-1.7b").reduced(n_layers=2, d_model=64, d_ff=128,
                                           vocab=97, dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _drive(model, params, router, prompts):
    eng = ContinuousEngine(
        model, params, single_device_mesh(), DEFAULT_RULES,
        RuntimeConfig(max_slots=2, block_size=8, max_blocks_per_seq=6,
                      max_new_tokens=6),
        router=router)
    with eng.mesh:
        eng.submit(prompts[0])
        eng.step()
        eng.step()
        n_compiles = eng._unified._cache_size()
        eng.submit(prompts[1])              # mid-flight admission
        while eng.scheduler.has_work:
            eng.step()
    # plan-dispatched matmuls active or not, admission compiles nothing new
    assert eng._unified._cache_size() == n_compiles == 1
    eng.cache.alloc.check_invariants()
    return {r.rid: r.output for r in eng._done}


def test_engine_routes_plan_matmuls_both_stages_no_recompile(tiny_f32_lm):
    """With a serve plan whose stage matmul choices all pick pallas_matmul,
    the unified step's chunk lane AND decode lane run the tuned matmuls —
    greedy outputs must match the XLA-lane engine exactly (f32) and the
    unified program must still never recompile across admissions."""
    cfg, model, params = tiny_f32_lm
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, size=s).astype(np.int32)
               for s in (11, 17)]

    router = PlanRouter(_forced_pallas_plan())
    table = router.matmul_table("decode")
    assert all(b == "pallas_matmul" for b, _ in table.values())

    out_xla = _drive(model, params, PlanRouter(None), prompts)
    out_tuned = _drive(model, params, router, prompts)
    assert out_tuned == out_xla


def test_router_matmul_table_covers_all_roles():
    router = PlanRouter(_forced_pallas_plan())
    for stage in ("prefill", "decode", "prefill_chunk"):
        table = router.matmul_table(stage)
        assert set(table) == set(dispatch.MATMUL_ROLES)
    # planless router: every role on the XLA lane
    bare = PlanRouter(None).matmul_table("decode")
    assert all(choice == ("xla", {}) for choice in bare.values())
