"""Tensor-parallel continuous serving: mesh invariance + the layout race.

Three layers of pinning:

  * `serve_rules` is a pure function (mesh enters only through
    `mesh.shape`), so its three tiers — single-device identity,
    divisibility guards, verdict demotion — are tested with stub meshes
    and fabricated plans, no devices involved;
  * the layout axis of the plan race (`select(model_parallel=...)`) is
    pinned structurally: every serving-stage matmul whose shard dim
    divides must carry BOTH layout candidates, and the verdict must
    round-trip through plan save/load;
  * the engine-level contract — token streams byte-identical across mesh
    widths 1/2/4 (greedy AND keyed sampling, under pool-pressure
    preemption and prefix sharing), exactly two step executables per
    family at every width, admission compiles nothing — runs in a
    subprocess that forces 4 virtual host devices before importing jax
    (the pattern of tests/test_distributed.py).
"""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.configs import get_config
from repro.core.plan import InferencePlan, OpChoice
from repro.core.search.tuner import Tuner
from repro.distributed.sharding import DEFAULT_RULES
from repro.kernels.dispatch import MATMUL_ROLES
from repro.serve.router import PlanRouter, build_serve_plan


class FakeMesh:
    def __init__(self, model: int, data: int = 1):
        self.shape = {"data": data, "model": model}


def _raced_choice(layout: str, raced: bool = True) -> OpChoice:
    cands = ({"replicated": 1e-6, "model_parallel": 2e-6} if raced else {})
    return OpChoice("xla", {}, 1e-6, layout=layout, layout_candidates=cands)


def _plan_with(verdicts) -> InferencePlan:
    plan = InferencePlan("serve", "tpu_v5e")
    for name, choice in verdicts.items():
        plan.choices[name] = choice
    return plan


def _decoder_cfg(vocab: int = 97):
    # n_heads=4, n_kv_heads=2, head_dim=32 (reduced defaults)
    return get_config("qwen3-1.7b").reduced(n_layers=2, d_model=64,
                                            d_ff=128, vocab=vocab)


# ------------------------------------------------------------- serve_rules
def test_serve_rules_single_device_is_identity():
    router = PlanRouter(_plan_with({"decode.mlp_up":
                                    _raced_choice("replicated")}))
    out = router.serve_rules(DEFAULT_RULES, FakeMesh(model=1), _decoder_cfg())
    assert out is DEFAULT_RULES   # tier 1: the pre-mesh engine, untouched


def test_serve_rules_no_plan_applies_divisibility_guards():
    cfg = _decoder_cfg(vocab=97)          # prime: vocab can never shard
    r = PlanRouter(None).serve_rules(DEFAULT_RULES, FakeMesh(model=2), cfg)
    assert r.lookup("heads") == "model"       # 4 % 2 == 0
    assert r.lookup("kv_heads") == "model"    # 2 % 2 == 0
    assert r.lookup("ffn") == "model"         # 128 % 2 == 0
    assert r.lookup("vocab") is None          # 97 % 2 != 0
    assert r.lookup("embed_vec") == "model"   # d_model fallback, 64 % 2 == 0

    r4 = PlanRouter(None).serve_rules(DEFAULT_RULES, FakeMesh(model=4), cfg)
    assert r4.lookup("kv_heads") is None      # 2 % 4 != 0
    assert r4.lookup("heads") == "model"      # 4 % 4 == 0


def test_serve_rules_demotes_only_on_explicit_replicated_verdict():
    cfg = _decoder_cfg(vocab=128)             # everything divides 2
    mesh = FakeMesh(model=2)

    # an explicit replicated verdict on the mlp pair demotes 'ffn' — and
    # ONLY 'ffn' (the head axes keep their guard-passed layout)
    router = PlanRouter(_plan_with(
        {"decode.mlp_up": _raced_choice("replicated")}))
    r = router.serve_rules(DEFAULT_RULES, mesh, cfg)
    assert r.lookup("ffn") is None
    assert r.lookup("heads") == "model"
    assert r.lookup("vocab") == "model"

    # qkv/attention verdicts demote the coupled head axes together
    router = PlanRouter(_plan_with(
        {"prefill_chunk.attention": _raced_choice("replicated")}))
    r = router.serve_rules(DEFAULT_RULES, mesh, cfg)
    assert r.lookup("heads") is None and r.lookup("kv_heads") is None
    assert r.lookup("ffn") == "model"

    # lm_head demotes vocab AND the embed_vec fallback
    router = PlanRouter(_plan_with(
        {"decode.lm_head": _raced_choice("replicated")}))
    r = router.serve_rules(DEFAULT_RULES, mesh, cfg)
    assert r.lookup("vocab") is None and r.lookup("embed_vec") is None


def test_serve_rules_old_plans_and_nonserve_stages_never_demote():
    cfg = _decoder_cfg(vocab=128)
    mesh = FakeMesh(model=2)

    # a pre-layout plan (no layout_candidates) carries no verdict: the
    # guards alone govern, exactly as with no plan at all
    router = PlanRouter(_plan_with(
        {"decode.mlp_up": _raced_choice("replicated", raced=False)}))
    r = router.serve_rules(DEFAULT_RULES, mesh, cfg)
    assert r.lookup("ffn") == "model"

    # a prefill-only plan serves decode through the `_lookup` fallback, so
    # its replicated verdict governs...
    router = PlanRouter(_plan_with(
        {"prefill.mlp_up": _raced_choice("replicated")}))
    r = router.serve_rules(DEFAULT_RULES, mesh, cfg)
    assert r.lookup("ffn") is None
    # ...but stage-specific serving choices take precedence over the
    # fallback: with explicit model_parallel verdicts on the serve stages,
    # the stale prefill verdict no longer demotes
    router = PlanRouter(_plan_with({
        "prefill.mlp_up": _raced_choice("replicated"),
        "decode.mlp_up": _raced_choice("model_parallel"),
        "prefill_chunk.mlp_up": _raced_choice("model_parallel"),
        "decode.mlp_down": _raced_choice("model_parallel"),
        "prefill_chunk.mlp_down": _raced_choice("model_parallel"),
    }))
    r = router.serve_rules(DEFAULT_RULES, mesh, cfg)
    assert r.lookup("ffn") == "model"


def test_serve_rules_ssm_guards_and_demotion():
    cfg = get_config("mamba2-2.7b").reduced(n_layers=2)
    d_in = cfg.ssm_expand * cfg.d_model
    nh = d_in // cfg.ssm_head_dim
    conv_dim = d_in + 2 * cfg.ssm_state

    m = 2
    r = PlanRouter(None).serve_rules(DEFAULT_RULES, FakeMesh(model=m), cfg,
                                     family="ssm")
    assert r.lookup("ssm_heads") == ("model" if nh % m == 0 else None)
    assert r.lookup("conv_dim") == ("model" if conv_dim % m == 0 else None)

    router = PlanRouter(_plan_with(
        {"ssm_decode.in_proj": _raced_choice("replicated")}))
    r = router.serve_rules(DEFAULT_RULES, FakeMesh(model=m), cfg,
                           family="ssm")
    assert r.lookup("ssm_heads") is None and r.lookup("conv_dim") is None


# ------------------------------------------------------------ layout race
def test_plan_race_covers_both_layouts_per_matmul_stage():
    """Acceptance pin: with a model axis, `select` races >= 2 layout
    choices (replicated + model_parallel) for every serving-stage matmul
    whose shard dim divides, and records the verdict on the choice."""
    cfg = _decoder_cfg(vocab=128)
    plan = build_serve_plan(cfg, prefill_len=32, slots=4, max_seq=64,
                            tuner=Tuner(methods=("random",),
                                        random_budget=4),
                            model_parallel=2)
    for stage in ("decode", "prefill_chunk"):
        for role in MATMUL_ROLES + ("attention",):
            c = plan.choice(f"{stage}.{role}")
            assert c is not None, f"{stage}.{role} missing from plan"
            assert set(c.layout_candidates) == {
                "replicated", "model_parallel"}, (stage, role)
            assert c.layout in ("replicated", "model_parallel")
            # the verdict must agree with the recorded race times
            lc = c.layout_candidates
            fastest = min(lc, key=lc.get)
            assert c.layout == fastest or lc["replicated"] == lc[fastest]

    # single-device plans never open the layout axis
    flat = build_serve_plan(cfg, prefill_len=32, slots=4, max_seq=64,
                            tuner=Tuner(methods=("random",),
                                        random_budget=4))
    assert all(not c.layout_candidates for c in flat.choices.values())


def test_indivisible_dims_are_never_raced():
    cfg = _decoder_cfg(vocab=97)          # prime vocab: lm_head can't shard
    plan = build_serve_plan(cfg, prefill_len=32, slots=4, max_seq=64,
                            tuner=Tuner(methods=("random",),
                                        random_budget=4),
                            model_parallel=8)
    # vocab 97 % 8 != 0 and n_heads 4 % 8 != 0: no illegal layout
    # candidate appears on those roles, while divisible dims still race
    for stage in ("decode", "prefill_chunk"):
        assert not plan.choice(f"{stage}.lm_head").layout_candidates
        assert not plan.choice(f"{stage}.attention").layout_candidates
        # ffn 128 % 8 == 0 and qkv n-dim 256 % 8 == 0 still race
        assert plan.choice(f"{stage}.mlp_up").layout_candidates
        assert plan.choice(f"{stage}.qkv_proj").layout_candidates


def test_layout_verdict_roundtrips_through_plan_save(tmp_path):
    plan = _plan_with({
        "decode.mlp_up": _raced_choice("model_parallel"),
        "decode.lm_head": _raced_choice("replicated"),
        "decode.qkv_proj": OpChoice("xla", {}, 1e-6),   # pre-layout choice
    })
    path = str(tmp_path / "plan.json")
    plan.save(path)
    loaded = InferencePlan.load(path)
    assert loaded.choice("decode.mlp_up").layout == "model_parallel"
    assert loaded.choice("decode.mlp_up").layout_candidates == {
        "replicated": 1e-6, "model_parallel": 2e-6}
    assert loaded.choice("decode.lm_head").layout == "replicated"
    assert loaded.choice("decode.qkv_proj").layout == "replicated"
    assert loaded.choice("decode.qkv_proj").layout_candidates == {}

    router = PlanRouter(loaded)
    assert router.layout_table("decode")["mlp_up"] == "model_parallel"
    assert router.layout_table("decode")["lm_head"] == "replicated"


# --------------------------------------------- cross-mesh differential pins
# One subprocess, 4 virtual host devices: serve the same preemption +
# prefix-sharing + mixed-sampling workload at mesh widths 1/2/4 and pin
# byte-identical streams, the two-executable compile property at every
# width, compile-free admission, and the tuned layout table reaching the
# step builders (through engine.rules).
_CROSS_MESH = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import json
    import numpy as np
    import jax
    from repro.configs import get_config
    from repro.core.search.tuner import Tuner
    from repro.data import DataConfig, SyntheticLMData
    from repro.distributed.sharding import DEFAULT_RULES
    from repro.launch.mesh import single_device_mesh, tp_mesh
    from repro.launch.steps import TrainConfig, jit_train_step
    from repro.models import build_model
    from repro.optim import AdamWConfig, adamw_init
    from repro.serve.router import PlanRouter, build_serve_plan
    from repro.serve.runtime import ContinuousEngine, RuntimeConfig
    from repro.serve.sampling import SamplingParams

    SEEDS = %(seeds)s
    # A briefly-trained model, not random init: the learned affine task
    # gives every position a macroscopic argmax margin.  K-sharded layers
    # (mlp_down, out_proj) reassociate their reduction under the mesh, so
    # bf16 hidden states legitimately differ by ~1 ulp across layouts —
    # a random-init model's near-uniform logits flip on exactly that ulp,
    # while trained margins dominate it by orders of magnitude.  Byte
    # identity across meshes is a decision-level invariant, and this is
    # the regime (a model with actual structure) where it is exact.
    cfg = get_config("qwen3-1.7b").reduced(n_layers=2, d_model=128,
                                           d_ff=256, vocab=192)
    model = build_model(cfg)
    mesh1 = single_device_mesh()
    data = SyntheticLMData(DataConfig(vocab=cfg.vocab, seq_len=32,
                                      global_batch=8))
    STEPS = 60
    with mesh1:
        params = model.init(jax.random.PRNGKey(0))
        opt = adamw_init(params)
        b0 = data.batch(0)
        specs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                 for k, v in b0.items()}
        step = jit_train_step(
            model, mesh1, DEFAULT_RULES,
            TrainConfig(opt=AdamWConfig(lr=3e-3, warmup_steps=5,
                                        total_steps=STEPS)), specs)
        for i in range(STEPS):
            b = {k: jax.numpy.asarray(v) for k, v in data.batch(i).items()}
            params, opt, _ = step(params, opt, b)

    # num_blocks=8 (7 usable) forces decode-growth preemption with 3
    # slots; prompts 0/2/4 share a start so prefix sharing hits too
    rcfg = RuntimeConfig(max_slots=3, max_new_tokens=12, chunk_tokens=16,
                         num_blocks=8, prefix_sharing=True)

    def affine(start, n):
        return ((start + 17 * np.arange(n)) %% cfg.vocab).astype(np.int32)

    def workload(seed):
        rng = np.random.RandomState(seed)
        s_hot = int(rng.randint(0, cfg.vocab))
        prompts, samp = [], []
        for i, n in enumerate((48, 23, 64, 12, 48, 3)):
            start = s_hot if i %% 2 == 0 else int(rng.randint(0, cfg.vocab))
            prompts.append(affine(start, n))
            samp.append([None,
                         SamplingParams(temperature=0.7, top_k=8,
                                        seed=11 + seed),
                         None,
                         SamplingParams(temperature=0.5, top_p=0.9,
                                        seed=5 + seed)][i %% 4])
        return prompts, samp

    def serve(tp, seed, router=None, rules=DEFAULT_RULES):
        eng = ContinuousEngine(model, params, tp_mesh(tp), rules, rcfg,
                               router=router or PlanRouter(None))
        prompts, samp = workload(seed)
        for p, s in zip(prompts, samp):
            eng.submit(p, sampling=s)
        pre = (eng._unified._cache_size() + eng._decode_only._cache_size())
        done = eng.run()
        s = eng.metrics.summary()
        return ({r.rid: [int(t) for t in r.output] for r in done},
                {"admission_compiles": pre,
                 "unified": eng._unified._cache_size(),
                 "decode_only": eng._decode_only._cache_size(),
                 "preemptions": int(s["preemptions"]),
                 "prefix_hits": int(s.get("prefix_hit_tokens", 0)),
                 "rules": {a: eng.rules.lookup(a) for a in
                           ("heads", "kv_heads", "ffn", "vocab",
                            "embed_vec")}})

    out = {"ndev": len(jax.devices()), "runs": []}
    tuned4 = PlanRouter(build_serve_plan(
        cfg, prefill_len=64, slots=rcfg.max_slots, max_seq=rcfg.max_seq,
        chunk_tokens=rcfg.chunk_width,
        tuner=Tuner(methods=("random",), random_budget=4),
        model_parallel=4))
    for seed in SEEDS:
        base, info1 = serve(1, seed)
        run = {"seed": seed, "tp1": info1}
        for tp in (2, 4):
            got, info = serve(tp, seed)
            run[f"tp{tp}"] = info
            run[f"identical_tp{tp}"] = got == base
        got, info = serve(4, seed, router=tuned4)
        run["tp4_tuned"] = info
        run["identical_tp4_tuned"] = got == base
        out["runs"].append(run)
    print(json.dumps(out))
""")


def _run_cross_mesh(seeds, timeout=600):
    script = _CROSS_MESH % {"seeds": repr(list(seeds))}
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, timeout=timeout,
                         env={**__import__("os").environ,
                              "PYTHONPATH": "src"},
                         cwd="/root/repo")
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def _check_run(run):
    for tp in (2, 4):
        assert run[f"identical_tp{tp}"], (
            f"seed {run['seed']}: tp={tp} stream diverged from tp=1")
    assert run["identical_tp4_tuned"], (
        f"seed {run['seed']}: tuned-layout tp=4 stream diverged")
    for leg in ("tp1", "tp2", "tp4", "tp4_tuned"):
        info = run[leg]
        # admission compiles nothing; exactly 2 step executables after
        assert info["admission_compiles"] == 0, (leg, info)
        assert info["unified"] == 1, (leg, info)
        assert info["decode_only"] == 1, (leg, info)
    # the workload must actually exercise the hard paths
    assert run["tp1"]["preemptions"] > 0, run
    assert run["tp1"]["prefix_hits"] > 0, run
    # guards reach the step builders through engine.rules: at tp=4 the
    # indivisible axis (kv_heads=2) demotes, the rest shard
    r4 = run["tp4"]["rules"]
    assert r4["heads"] == "model" and r4["kv_heads"] is None, r4
    assert r4["ffn"] == "model" and r4["vocab"] == "model", r4
    r1 = run["tp1"]["rules"]
    assert r1["heads"] == "model" and r1["vocab"] == "model", r1


def test_cross_mesh_streams_byte_identical_fast():
    """Greedy + keyed-sampled token streams on 1x2 and 1x4 host meshes are
    byte-identical to single-device, under preemption and prefix sharing,
    with the two-executable and compile-free-admission pins at every
    width — the fast differential (one seed)."""
    payload = _run_cross_mesh([7])
    assert payload["ndev"] == 4
    _check_run(payload["runs"][0])


@pytest.mark.slow
def test_cross_mesh_streams_byte_identical_fuzz():
    """The seeded fuzz: several workloads (different prompt mixes, keys
    and preemption patterns) through the same cross-mesh differential."""
    payload = _run_cross_mesh([0, 1, 2], timeout=900)
    assert payload["ndev"] == 4
    for run in payload["runs"]:
        _check_run(run)


# --------------------------------------- packed-prefill XLA lane at tp=4
# The lane on a 1x4 host mesh, pools and queries sharded on their head axes
# as the serving step lays them out: it must match tp=1, and the compiled
# (partitioned) program must gather each chip's segment context locally:
# no collective at all, so no all-gather or all-to-all of a pool or of the
# gathered context.
_PACKED_LANE_TP = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import json, re
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import tp_mesh
    from repro.models.attention import attention_prefill_packed_xla

    rng = np.random.default_rng(3)
    h, hkv, d, bs, nbt, nb = 8, 4, 16, 8, 6, 32
    seg_lens, kv_starts = (6, 1, 8, 3), (2, 0, 9, 0)
    c, ns = sum(seg_lens) + 2, len(seg_lens)
    q = rng.standard_normal((1, c, h, d)).astype(np.float32)
    kp = rng.standard_normal((nb, bs, hkv, d)).astype(np.float32)
    vp = rng.standard_normal((nb, bs, hkv, d)).astype(np.float32)
    tables = rng.permutation(np.arange(1, nb))[:ns * nbt].reshape(
        ns, nbt).astype(np.int32)
    q0s = np.concatenate([[0], np.cumsum(seg_lens)[:-1]])
    info = np.stack([q0s, seg_lens, kv_starts], axis=1).astype(np.int32)

    def run(tp):
        mesh = tp_mesh(tp)
        heads = NamedSharding(mesh, P(None, None, "model", None))
        rep = NamedSharding(mesh, P())
        fn = jax.jit(attention_prefill_packed_xla,
                     in_shardings=(heads, heads, heads, rep, rep),
                     out_shardings=heads)
        args = [jax.device_put(a, s) for a, s in
                zip((q, kp, vp, tables, info),
                    (heads, heads, heads, rep, rep))]
        text = fn.lower(*args).compile().as_text()
        coll = [ln.strip() for ln in text.splitlines()
                if re.search(r"\\s(all-gather|all-to-all|all-reduce|"
                             r"reduce-scatter|collective-permute)"
                             r"(-start)?\\(", ln)]
        return np.asarray(fn(*args)), coll

    out1, _ = run(1)
    out4, coll4 = run(4)
    print(json.dumps({"ndev": len(jax.devices()),
                      "max_abs": float(np.abs(out4 - out1).max()),
                      "collectives": coll4}))
""")


def test_xla_packed_prefill_lane_stays_local_at_tp4():
    """At tp=4 (4 KV heads, one per chip) the lane gathers each segment's
    context from the chip's own pool shard: output equal to tp=1 within
    float tolerance, and no collective in the partitioned program."""
    out = subprocess.run([sys.executable, "-c", _PACKED_LANE_TP],
                         capture_output=True, text=True, timeout=300,
                         env={**os.environ, "PYTHONPATH": "src"},
                         cwd=Path(__file__).resolve().parents[1])
    assert out.returncode == 0, out.stderr[-3000:]
    payload = json.loads(out.stdout.strip().splitlines()[-1])
    assert payload["ndev"] == 4
    assert payload["max_abs"] < 1e-5, payload
    assert payload["collectives"] == [], payload["collectives"]
