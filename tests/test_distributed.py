"""Sharding-rule logic + an in-subprocess 8-device mini dry-run (the only
place outside launch/dryrun.py that forces host devices)."""

import dataclasses
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import SHAPES, get_config
from repro.distributed.sharding import DEFAULT_RULES, ShardingRules, prune_for_mesh
from repro.launch.mesh import make_mesh, single_device_mesh, tp_mesh
from repro.launch.steps import rules_for_shape, zero1_axes


def test_rules_lookup_and_replace():
    r = DEFAULT_RULES
    assert r.lookup("ffn") == "model"
    r2 = r.replace(ffn=None)
    assert r2.lookup("ffn") is None
    assert r.lookup("ffn") == "model"  # original untouched
    with pytest.raises(KeyError):
        r.lookup("nope")


def test_prune_for_mesh_drops_missing_axes():
    mesh = single_device_mesh()  # data, model only
    r = prune_for_mesh(DEFAULT_RULES, mesh)
    assert r.lookup("batch") == "data"  # ('pod','data') -> 'data'


def test_prune_for_mesh_tuple_axes():
    """Tuple-valued rules prune element-wise: a surviving pair stays a
    tuple, a single survivor collapses to a bare axis, none -> None."""
    class PodDataMesh:
        shape = {"pod": 2, "data": 4}

    class ModelOnlyMesh:
        shape = {"model": 2}

    r = prune_for_mesh(DEFAULT_RULES, PodDataMesh())
    assert r.lookup("batch") == ("pod", "data")   # both present: unchanged
    assert r.lookup("heads") is None              # 'model' absent

    r = prune_for_mesh(DEFAULT_RULES, ModelOnlyMesh())
    assert r.lookup("batch") is None              # neither tuple member
    assert r.lookup("heads") == "model"
    assert r.lookup("zero") is None               # 'data' absent
    assert r.lookup("seq") is None                # None stays None

    wide = DEFAULT_RULES.replace(batch=("pod", "data", "model"))
    r = prune_for_mesh(wide, PodDataMesh())
    assert r.lookup("batch") == ("pod", "data")   # multi-survivor tuple


def test_replace_round_trips_and_preserves_table():
    r = DEFAULT_RULES.replace(ffn=None, vocab=None, batch="data")
    back = r.replace(ffn="model", vocab="model", batch=("pod", "data"))
    assert back == DEFAULT_RULES          # frozen dataclass value equality
    assert dict(back.rules) == dict(DEFAULT_RULES.rules)
    # replace never reorders or drops axes — the table stays congruent
    assert [k for k, _ in r.rules] == [k for k, _ in DEFAULT_RULES.rules]
    with pytest.raises(dataclasses.FrozenInstanceError):
        r.rules = ()                      # frozen: no in-place mutation


def test_logical_to_spec_unknown_axis_raises():
    from jax.sharding import PartitionSpec as P
    from repro.distributed.sharding import logical_to_spec

    assert logical_to_spec(DEFAULT_RULES, ("batch", None, "heads")) == \
        P(("pod", "data"), None, "model")
    with pytest.raises(KeyError, match="made_up_axis"):
        logical_to_spec(DEFAULT_RULES, ("batch", "made_up_axis"))
    # None entries are legal and map to replicated dims, even trailing
    assert logical_to_spec(DEFAULT_RULES, (None, None)) == P(None, None)


def test_rules_for_shape_divisibility_fallbacks():
    mesh = make_mesh((1, 1), ("data", "model"))

    class FakeMesh:
        shape = {"data": 16, "model": 16}

    # kv=8 indivisible by 16 -> replicated KV
    cfg = get_config("internlm2-20b")
    r = rules_for_shape(cfg, SHAPES["train_4k"], FakeMesh())
    assert r.lookup("kv_heads") is None
    assert r.lookup("heads") == "model"      # 48 % 16 == 0

    # whisper: odd vocab -> embed_vec fallback
    cfg = get_config("whisper-base")
    r = rules_for_shape(cfg, SHAPES["train_4k"], FakeMesh())
    assert r.lookup("vocab") is None
    assert r.lookup("embed_vec") == "model"

    # qwen2-moe: 60 experts indivisible -> TP inside experts
    cfg = get_config("qwen2-moe-a2.7b")
    r = rules_for_shape(cfg, SHAPES["train_4k"], FakeMesh())
    assert r.lookup("experts") is None
    assert r.lookup("expert_ffn") == "model"

    # qwen3-moe keeps EP
    cfg = get_config("qwen3-moe-235b-a22b")
    r = rules_for_shape(cfg, SHAPES["train_4k"], FakeMesh())
    assert r.lookup("experts") == "model"

    # long_500k batch=1 -> SP
    cfg = get_config("mamba2-2.7b")
    r = rules_for_shape(cfg, SHAPES["long_500k"], FakeMesh())
    assert r.lookup("batch") is None
    assert r.lookup("ssm_state") == "data"


def test_zero1_rewrites_first_divisible_dim():
    class FakeMesh:
        shape = {"data": 4, "model": 2}

    logical = {"w": (None, None), "v": ("ffn", None), "s": (None,)}
    shapes = {"w": jax.ShapeDtypeStruct((8, 6), np.float32),
              "v": jax.ShapeDtypeStruct((4, 8), np.float32),
              "s": jax.ShapeDtypeStruct((7,), np.float32)}
    out = zero1_axes(logical, shapes, FakeMesh(), DEFAULT_RULES)
    assert out["w"] == ("zero", None)      # dim0 divisible by 4
    assert out["v"] == ("ffn", "zero")     # first None dim that divides
    assert out["s"] == (None,)             # 7 % 4 != 0 -> untouched


def test_param_shardings_cover_every_leaf():
    cfg = get_config("qwen3-1.7b").reduced()
    from repro.models import build_model
    from repro.launch.steps import make_state_shardings, TrainConfig
    model = build_model(cfg)
    mesh = single_device_mesh()
    p_shard, opt_shard = make_state_shardings(
        model, mesh, prune_for_mesh(DEFAULT_RULES, mesh), TrainConfig())
    params_shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert len(jax.tree.leaves(p_shard)) == len(jax.tree.leaves(params_shapes))
    assert len(jax.tree.leaves(opt_shard["m"])) == len(jax.tree.leaves(params_shapes))


_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, numpy as np
    from repro.configs import get_config, input_specs, SHAPES
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import TrainConfig, jit_train_step, rules_for_shape
    from repro.models import build_model
    from repro.optim import adamw_init
    import dataclasses

    cfg = get_config("qwen3-1.7b").reduced()
    shape = dataclasses.replace(SHAPES["train_4k"], seq_len=32, global_batch=8)
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    model = build_model(cfg)
    rules = rules_for_shape(cfg, shape, mesh)
    with mesh:
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        opt = jax.eval_shape(adamw_init, params)
        batch = input_specs(cfg, shape)
        fn = jit_train_step(model, mesh, rules, TrainConfig(microbatches=2), batch)
        compiled = fn.lower(params, opt, batch).compile()
        from repro.launch.steps import cost_dict
        cost = cost_dict(compiled.cost_analysis())
        print(json.dumps({"flops": float(cost.get("flops", 0)),
                          "ndev": len(jax.devices())}))
""")


@pytest.mark.slow
def test_multidevice_dryrun_subprocess():
    """An 8-device (2x2x2 pod/data/model) train-step lower+compile must
    succeed — the miniature version of the 512-device production dry-run."""
    out = subprocess.run([sys.executable, "-c", _SUBPROC], capture_output=True,
                         text=True, timeout=600,
                         env={**__import__("os").environ, "PYTHONPATH": "src"},
                         cwd="/root/repo")
    assert out.returncode == 0, out.stderr[-2000:]
    payload = json.loads(out.stdout.strip().splitlines()[-1])
    assert payload["ndev"] == 8
    assert payload["flops"] > 0


def test_compressed_allgather_mean_roundtrip():
    """int8-compressed gradient reduction under shard_map (1-device axis):
    value error stays within quantisation tolerance."""
    from functools import partial
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P
    from repro.optim import compressed_allgather_mean

    mesh = make_mesh((1,), ("pod",))
    x = np.linspace(-1, 1, 64).astype(np.float32)
    fn = shard_map(partial(compressed_allgather_mean, axis_name="pod"),
                   mesh=mesh, in_specs=P(), out_specs=P(), check_rep=False)
    got = np.asarray(fn(x))
    np.testing.assert_allclose(got, x, atol=2.0 / 127)


@pytest.mark.parametrize("build", [
    lambda: make_mesh((1, 1)),
    lambda: make_mesh((1,), ("pod",)),
    lambda: single_device_mesh(),
    lambda: tp_mesh(1),
], ids=["make_mesh", "make_mesh-1d", "single_device_mesh", "tp_mesh"])
def test_every_mesh_has_auto_axes(build):
    """launch/mesh.py decides axis types for the whole program: Auto on
    every axis (jax.make_mesh's own default is Explicit).
    make_production_mesh is make_mesh at (16, 16) / (2, 16, 16)."""
    from jax.sharding import AxisType

    mesh = build()
    assert mesh.axis_types == (AxisType.Auto,) * len(mesh.axis_names)


def test_compile_cache_dir_is_the_env_or_the_checkout(monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache as cc

    from repro import platform

    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(platform.CACHE_ENV, "/somewhere/else")
        assert platform.configure_compile_cache() == "/somewhere/else"
        assert jax.config.jax_compilation_cache_dir == prev
        monkeypatch.delenv(platform.CACHE_ENV)
        path = platform.configure_compile_cache()
        assert path == str(platform.CHECKOUT_CACHE)
        assert platform.CHECKOUT_CACHE.parent == (
            Path(__file__).resolve().parents[1])
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
        cc.reset_cache()
