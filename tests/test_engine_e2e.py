"""WPK end-to-end: graph -> optimize -> search/selection -> engine, verified
against the unoptimized reference — including the paper's ResNet-18."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import (
    Engine,
    Graph,
    InferencePlan,
    Tuner,
    default_registry,
    optimize_graph,
    select,
)
from repro.core.selection import op_desc_of
from repro.models.resnet import conv_groups, resnet18_graph


@pytest.fixture(scope="module")
def mini_convnet():
    rng = np.random.default_rng(0)
    g = Graph("mini")
    x = g.add_input("x", (2, 3, 16, 16))
    w1 = g.add_constant("w1", rng.standard_normal((8, 3, 3, 3)).astype(np.float32) * 0.2)
    c1 = g.add_node("conv2d", [x, w1], (2, 8, 16, 16), {"stride": 1, "padding": "SAME"})
    sc = g.add_constant("sc", (rng.random(8) + 0.5).astype(np.float32))
    sh = g.add_constant("sh", rng.standard_normal(8).astype(np.float32) * 0.1)
    b1 = g.add_node("batch_norm", [c1, sc, sh], (2, 8, 16, 16))
    r1 = g.add_node("relu", [b1], (2, 8, 16, 16))
    gp = g.add_node("global_avg_pool", [r1], (2, 8))
    wf = g.add_constant("wf", rng.standard_normal((8, 10)).astype(np.float32) * 0.3)
    mm = g.add_node("matmul", [gp, wf], (2, 10))
    g.set_outputs([mm])
    return g


def test_full_wpk_pipeline_equivalence(mini_convnet):
    g = mini_convnet
    gopt = optimize_graph(g)
    plan = select(gopt, tuner=Tuner(methods=("genetic",)), dtype="float32")
    eng = Engine(gopt, plan, default_registry())
    x = jnp.asarray(np.random.default_rng(1).standard_normal((2, 3, 16, 16)).astype(np.float32))
    err = eng.verify_against_reference(x)
    ref = Engine(g, None, None)(x)[0]
    np.testing.assert_allclose(np.asarray(eng(x)[0]), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)
    assert err < 1e-2


def test_plan_has_all_tunable_ops_and_candidates(mini_convnet):
    gopt = optimize_graph(mini_convnet)
    plan = select(gopt, tuner=Tuner(methods=("genetic",)))
    tunable = [n for n in gopt.nodes
               if n.op in ("fused_conv2d", "conv2d", "matmul", "fused_matmul")]
    assert len(plan.choices) == len(tunable)
    for choice in plan.choices.values():
        assert "xla" in choice.candidates          # vendor lane always raced
        assert choice.modeled_time_s == min(choice.candidates.values())


def test_plan_serialisation_roundtrip(mini_convnet, tmp_path):
    gopt = optimize_graph(mini_convnet)
    plan = select(gopt, tuner=Tuner(methods=("genetic",)))
    p = tmp_path / "plan.json"
    plan.save(str(p))
    plan2 = InferencePlan.load(str(p))
    assert plan2.backend_histogram() == plan.backend_histogram()
    assert abs(plan2.total_modeled_time_s() - plan.total_modeled_time_s()) < 1e-12


def test_third_party_ablation_never_faster():
    """Paper §3.4: excluding third-party (vendor) ops costs a little; the
    full system-level plan is by construction <= the WPK-only plan."""
    g = resnet18_graph(batch=1, image=32)
    gopt = optimize_graph(g)
    cache_tuner = Tuner(methods=("genetic",))
    full = select(gopt, tuner=cache_tuner, third_party=True)
    wpk_only = select(gopt, tuner=cache_tuner, third_party=False)
    assert full.total_modeled_time_s() <= wpk_only.total_modeled_time_s() + 1e-12


def test_resnet18_optimized_graph_equivalence():
    g = resnet18_graph(batch=1, image=32)
    gopt = optimize_graph(g)
    x = jnp.asarray(np.random.default_rng(2).standard_normal((1, 3, 32, 32)).astype(np.float32))
    ref = Engine(g, None, None)(x)[0]
    got = Engine(gopt, None, None)(x)[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=2e-3, atol=2e-3)
    # fusion must actually collapse conv+bn(+relu): no bare batch_norm left
    assert "batch_norm" not in gopt.op_histogram()


def test_resnet18_conv_groups_match_paper_criterion():
    groups = conv_groups()
    sigs = [op.signature() for _, op in groups]
    assert len(sigs) == len(set(sigs))           # deduplicated
    assert 10 <= len(groups) <= 13               # ResNet-18 has ~11 groups
    # the stem conv is the first group
    assert dict(groups[0][1].dims)["cin"] == 3


def test_op_desc_of_handles_all_tunables(mini_convnet):
    gopt = optimize_graph(mini_convnet)
    kinds = set()
    for node in gopt.nodes:
        d = op_desc_of(gopt, node)
        if d is not None:
            kinds.add(d.kind)
    assert "conv2d" in kinds and "matmul" in kinds
