"""Automated-search tests: genetic (§2.3), RL (§2.4), random baseline,
cache (§3.3), constraint validity (hypothesis property)."""

import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis (requirements-dev.txt)")
from hypothesis import given, settings, strategies as st

from repro import hw
from repro.core import (
    GeneticSearch,
    ModelFitness,
    SearchCache,
    SearchTask,
    TEMPLATES,
    Tuner,
    genetic_search,
    random_search,
    rl_search,
)
from repro.core.costmodel import pallas_time, roofline_bound
from repro.core.schedules import OpDesc

CONV = OpDesc.conv2d(1, 56, 56, 64, 128, 3, 3, stride=2)
MM = OpDesc.matmul(512, 1024, 768)


def test_genetic_beats_or_matches_random_at_equal_budget():
    t1 = SearchTask(CONV, TEMPLATES["pallas_conv2d"], seed=0)
    g = genetic_search(t1)
    t2 = SearchTask(CONV, TEMPLATES["pallas_conv2d"], seed=123)
    r = random_search(t2, budget=g.evals)
    assert g.runtime_s <= r.runtime_s * 1.05


def test_genetic_deterministic_given_seed():
    a = genetic_search(SearchTask(MM, TEMPLATES["pallas_matmul"], seed=7))
    b = genetic_search(SearchTask(MM, TEMPLATES["pallas_matmul"], seed=7))
    assert a.config == b.config and a.runtime_s == b.runtime_s


@pytest.mark.slow
def test_genetic_convergence_and_history_monotone():
    res = genetic_search(SearchTask(MM, TEMPLATES["pallas_matmul"], seed=1))
    hist = res.history
    assert all(b <= a + 1e-12 for a, b in zip(hist, hist[1:]))
    assert res.runtime_s >= roofline_bound(MM) * 0.5  # sane lower bound


def test_population_schedule_varies_size():
    gs = GeneticSearch(population=12, population_schedule=[12, 16, 8],
                       max_generations=3)
    res = gs.run(SearchTask(MM, TEMPLATES["pallas_matmul"], seed=2))
    assert res.runtime_s < float("inf")


@pytest.mark.slow
def test_best_config_beats_median_of_space():
    task = SearchTask(CONV, TEMPLATES["pallas_conv2d"], seed=0)
    res = genetic_search(task)
    rng = np.random.default_rng(0)
    tmpl = TEMPLATES["pallas_conv2d"]
    samples = [pallas_time(CONV, tmpl.random_config(CONV, rng)) for _ in range(50)]
    assert res.runtime_s <= np.median(samples)


@pytest.mark.slow
def test_rl_search_runs_and_improves_over_worst():
    task = SearchTask(CONV, TEMPLATES["pallas_conv2d"], seed=0)
    res = rl_search(task, episodes=2, steps_per_episode=8)
    assert np.isfinite(res.runtime_s)
    assert TEMPLATES["pallas_conv2d"].validate(CONV, res.config)
    assert res.evals > 8


def test_cache_hit_returns_without_evals(tmp_path):
    cache = SearchCache(str(tmp_path / "cache.json"))
    tuner = Tuner(methods=("genetic",), cache=cache)
    r1 = tuner.tune(MM)
    assert cache.misses >= 1
    r2 = tuner.tune(MM)
    assert r2.evals == 0 and "cache" in r2.method
    assert r2.config == r1.config
    cache.save()
    cache2 = SearchCache(str(tmp_path / "cache.json"))
    assert len(cache2) == len(cache)


def test_cache_respects_computational_identity():
    """Paper §3.1: same shapes/filter/stride/padding == identical op."""
    cache = SearchCache()
    op_a = OpDesc.conv2d(1, 28, 28, 128, 128, 3, 3, stride=1)
    op_b = OpDesc.conv2d(1, 28, 28, 128, 128, 3, 3, stride=1)
    op_c = OpDesc.conv2d(1, 28, 28, 128, 128, 3, 3, stride=2)
    cache.put("tpu_v5e", op_a, "pallas_conv2d", {"bm": 8}, 1.0, "genetic")
    assert cache.get("tpu_v5e", op_b, "pallas_conv2d") is not None
    assert cache.get("tpu_v5e", op_c, "pallas_conv2d") is None


def test_retarget_changes_best_config_or_runtime():
    """Hardware-awareness: v5e and v5p must not produce identical tuning."""
    r_e = Tuner(chip=hw.TPU_V5E, methods=("genetic",)).tune(MM)
    r_p = Tuner(chip=hw.TPU_V5P, methods=("genetic",)).tune(MM)
    assert r_e.runtime_s != r_p.runtime_s


# ---------------------------------------------------------------- property
@given(st.integers(0, 2**16),
       st.sampled_from(["pallas_matmul", "pallas_conv2d", "pallas_attention"]))
@settings(max_examples=30, deadline=None)
def test_random_configs_always_valid(seed, tmpl_name):
    """§2.3 Step1: every proposed configuration satisfies the hardware
    constraints (the CUDA <=1024-threads analogue is the VMEM-fit rule)."""
    tmpl = TEMPLATES[tmpl_name]
    op = {"pallas_matmul": MM, "pallas_conv2d": CONV,
          "pallas_attention": OpDesc.attention(2, 1024, 1024, 8, 128)}[tmpl_name]
    rng = np.random.default_rng(seed)
    cfg = tmpl.random_config(op, rng)
    assert tmpl.validate(op, cfg)
    # encode/decode roundtrip
    assert tmpl.decode(op, tmpl.encode(op, cfg)) == cfg


@given(st.integers(0, 2**16))
@settings(max_examples=20, deadline=None)
def test_modeled_time_above_roofline(seed):
    rng = np.random.default_rng(seed)
    tmpl = TEMPLATES["pallas_matmul"]
    cfg = tmpl.random_config(MM, rng)
    assert pallas_time(MM, cfg) >= roofline_bound(MM) * 0.9


def test_vmem_model_counts_the_output_block_and_scratch():
    """The matmul kernel's pipeline double-buffers A, B and the output
    block and keeps an f32 accumulator: the model counts all of them."""
    from repro.core.schedules import _vmem_matmul_bytes

    bm, bn, bk, item = 512, 1024, 2048, 2
    inputs = 2 * (bm * bk + bk * bn) * item
    assert (_vmem_matmul_bytes(bm, bn, bk, "bfloat16")
            >= inputs + 2 * bm * bn * item + bm * bn * 4)


@pytest.mark.parametrize("tmpl_name,op,cfg", [
    ("pallas_matmul", OpDesc.matmul(2048, 6144, 2048),
     {"bm": 512, "bn": 1024, "bk": 2048, "order": "mn", "k_unroll": 1}),
    ("pallas_attention", OpDesc.attention(1, 256, 1024, 16, 128,
                                          kv_heads=8),
     {"block_q": 256, "block_kv": 512}),
])
def test_validate_admits_exactly_the_chip_vmem_budget(tmpl_name, op, cfg):
    """What the search admits is what every pallas_call asks of the
    compiler: the chip's `vmem_budget_bytes`, to the byte."""
    import dataclasses

    tmpl = TEMPLATES[tmpl_name]
    lo, hi = 0, 1 << 40
    while lo < hi:  # smallest budget the template admits this config at
        mid = (lo + hi) // 2
        chip = dataclasses.replace(hw.TPU_V5E, vmem_budget_bytes=mid)
        lo, hi = (lo, mid) if tmpl.validate(op, cfg, chip) else (mid + 1, hi)
    need = lo
    assert 0 < need <= hw.TPU_V5E.vmem_budget_bytes
    assert not tmpl.validate(
        op, cfg, dataclasses.replace(hw.TPU_V5E, vmem_budget_bytes=need - 1))
    # the physical size plays no part: only the budget the compiler is told
    assert tmpl.validate(op, cfg, dataclasses.replace(
        hw.TPU_V5E, vmem_budget_bytes=need, vmem_bytes=need))
