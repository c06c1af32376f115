"""What a configuration file names decides what the harness runs: its
program fields, its reference (and so `correct`) and its work counts (and
so every roofline and MFU reading)."""

import dataclasses
import json
import types
from pathlib import Path

import pytest

import modules
import run
import stats
import test_correct as tc
import work
from repro import hw
from repro.configs import get_config

DATA = Path(__file__).resolve().parent / "data"
CONFIGS = sorted(p.stem for p in (run.BENCH / "configs").glob("*.json"))
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# decode rows at their contexts, prompt segments (start, n, completes)
STEPS = [([], [(0, 256, False)]), ([700, 1500, 4096], [(256, 256, True)]),
         ([1, 2, 3, 4000], []),
         ([2048] * 24, [(0, 64, True), (64, 192, False)])]


@pytest.mark.parametrize("name", CONFIGS)
def test_config_names_todays_modules(name):
    """Both cells' configurations run the preset, the dense reference of
    bench/reference.py and the counts of bench/work.py."""
    cfg = run.load_config(name)
    assert run.program_config(cfg) == get_config(cfg["program"]["arch"])
    ref = modules.of_config(cfg, "reference")
    assert Path(ref.__file__) == run.BENCH / "reference.py"
    named = modules.of_config(cfg, "work")
    assert Path(named.__file__) == run.BENCH / "work.py"
    for keys, segs in STEPS:
        assert named.step_work(cfg, keys, segs) == work.step_work(cfg, keys,
                                                                  segs)


def test_defaults_where_a_file_names_none():
    cfg = {k: v for k, v in run.load_config("qwen3-1.7b").items()
           if k not in ("reference", "work")}
    assert modules.of_config(cfg, "reference") is modules.load("reference")
    assert modules.of_config(cfg, "work") is modules.load("work")


def test_program_fields_come_from_the_preset_and_the_program_section():
    """q/k norm and every field the HF keys do not carry are the preset's,
    whatever `model_type` says; the `program` section's further fields
    are applied last, over the published sizes."""
    cfg = run.load_config("qwen3-1.7b")
    preset = get_config("qwen3-1.7b")
    assert run.program_config(dict(cfg, model_type="llama")).qk_norm
    cfg["program"] = {"arch": "qwen3-1.7b", "d_ff": 1536, "remat": "dots"}
    assert run.program_config(cfg) == dataclasses.replace(
        preset, d_ff=1536, remat="dots")
    cfg = dict(cfg, num_hidden_layers=4, program={"arch": "qwen3-1.7b"})
    assert run.program_config(cfg) == dataclasses.replace(preset, n_layers=4)


def _traced_run(cfg):
    steps = [run.Step(i, 0.25 * i, 0.25 * (i + 1),
                      "unified" if segs else "decode_only", keys, segs,
                      len(keys))
             for i, (keys, segs) in enumerate(STEPS)]
    device = {s.idx: (run.MODULES[s.kind], 0.2) for s in steps}
    return types.SimpleNamespace(config=cfg, traced_steps=steps,
                                 step_device=device, peaks=PEAKS, chips=1)


def test_rooflines_and_mfu_read_the_named_work_module():
    cfg = run.load_config("qwen3-1.7b")
    fixed = _traced_run(dict(cfg, work="tests/data/work_fixed"))
    # each step: 10^12 operations over 197 TFLOP/s, 0.2 s on the device,
    # 0.25 s of host wall time
    assert stats.roofline_pct(fixed, None) == pytest.approx(
        100 * 1e12 / 197e12 / 0.2)
    assert stats.roofline_pct(fixed, "jit_decode_only_step") == \
        pytest.approx(100 * 1e12 / 197e12 / 0.2)
    assert stats.mfu_pct(fixed) == pytest.approx(100 * 1e12 / 197e12 / 0.25)
    dense = _traced_run(cfg)
    flops = sum(work.step_work(cfg, k, s)[0] for k, s in STEPS)
    assert stats.mfu_pct(dense) == pytest.approx(
        100 * flops / (len(STEPS) * 0.25 * 197e12))
    assert stats.mfu_pct(dense) != pytest.approx(stats.mfu_pct(fixed))


def test_named_reference_decides_correct():
    """A configuration whose reference leaves out the q/k norm the program
    keeps reads not correct, at test_correct's small size."""
    with open(DATA / "qwen3-small-departing-reference.json") as f:
        cfg = json.load(f)
    same = lambda c: {k: v for k, v in c.items()  # noqa: E731
                      if k not in ("name", "reference", "assumed")}
    assert same(cfg) == same(tc.small_config())
    res = run.run_cell(tc.CELL, tc.SEED, 3.0, False, config=cfg,
                       mix=tc.small_mix(), chip=hw.TPU_V5E)
    assert res["attempted"] > 0 and res["failed"] == 0
    assert not res["correct"], res["checks"]
    assert res["checks"]["max_logit_gap"]["value"] > tc.LIMIT
