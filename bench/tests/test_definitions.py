"""BENCHMARK.json and the files it names agree: every cell's configuration
and traffic mix exist, every metric has its reader, and each
configuration file describes its program: the preset, with the fields its
`program` section replaces."""

import dataclasses
import json

import pytest

import run
from repro.configs import get_config

BENCH = run.benchmark()


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_cell_files_exist(cell):
    cfg = run.load_config(cell["config"])
    assert cfg["name"] == cell["config"]
    assert run.traffic_mod.load(cell["traffic"])["name"] == cell["traffic"]
    conf = {c["name"]: c for c in BENCH["configs"]}[cell["config"]]
    assert sorted(conf["reduced"]) == sorted(cfg["reduced"])
    assert conf["source"] == cfg["source"]
    assert cell["chips"] == cfg["mesh"]["model"]


@pytest.mark.parametrize("kind", ["e2e", "layers"])
def test_every_metric_has_a_reader(kind):
    """Every metric BENCHMARK.json names has a reader, and every reader
    file there is named by a metric."""
    section = {"e2e": "end_to_end", "layers": "per_layer"}[kind]
    names = {m["name"] for m in BENCH[section]}
    assert names == {p.stem for p in (run.BENCH / kind).glob("*.py")}
    for name in names:
        assert callable(run.reader(kind, name)), name


def test_every_cell_reports_its_metrics():
    for cell in BENCH["workloads"]:
        e2e = {m["name"] for m in run.metrics_for(BENCH, cell["name"],
                                                   "end_to_end")}
        layers = run.metrics_for(BENCH, cell["name"], "per_layer")
        assert "setup_s" in e2e and len(e2e) >= 2
        assert layers and all(m["moves"] in e2e for m in layers)


@pytest.mark.parametrize("name", sorted(
    p.stem for p in (run.BENCH / "configs").glob("*.json")))
def test_config_is_the_program_preset(name):
    """The program is the preset with exactly the fields of the file's
    `program` section beside `arch` replaced, each of them explained in
    the file (`reduced`, `why_reduced` or `assumed`, as the field or as
    `program.<field>`)."""
    cfg = run.load_config(name)
    assert cfg["name"] == name
    fields = {k: v for k, v in cfg["program"].items() if k != "arch"}
    preset = get_config(cfg["program"]["arch"])
    assert run.program_config(cfg) == dataclasses.replace(preset, **fields)
    explained = (set(cfg["reduced"]) | set(cfg.get("why_reduced", {}))
                 | set(cfg.get("assumed", {})))
    for f in fields:
        assert {f, f"program.{f}"} & explained, f


def test_peaks_have_a_source():
    with open(run.BENCH / "peaks.json") as f:
        peaks = json.load(f)
    assert peaks["source"]
    assert run.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(SystemExit):
        run.load_peaks("TPU v9")
