"""Spans and named scopes of the serving runtime, on the profiler's clock.

Pinned here:

  * the span and scope names are closed lists: an unknown name raises;
  * every engine step opens a `serve.step` span whose `step` stat is the
    recorder's `step_begin` index, with `serve.inputs`, `serve.launch` and
    `serve.sync` nested inside it, in a `jax.profiler` trace read back by
    the benchmark's own reader (`bench/trace_reduce.py`);
  * every step program (decoder and SSM, one device and tp=4 on four host
    devices) names the model part of at least 95% of its fusion, dot,
    custom-call, sort, gather and scatter instructions in its recorded
    scope table, and extracting the table compiles nothing.
"""

import contextlib
import json
import os
import re
import subprocess
import sys
import tempfile
import textwrap
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.distributed.sharding import DEFAULT_RULES
from repro.launch.mesh import single_device_mesh
from repro.models import build_model
from repro.serve import trace
from repro.serve.runtime import ContinuousEngine, RuntimeConfig
from repro.serve.trace import (SCOPE_NAMES, SPAN_NAMES, TraceRecorder,
                               scope, scope_path, scope_table, span)

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "bench"))

import trace_reduce  # noqa: E402

HEAVY = {"fusion", "dot", "custom-call", "sort", "gather", "scatter"}
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_COMPILES = []


def compiles() -> int:
    """Backend compilations so far in this process (one listener)."""
    if not _COMPILES:
        _COMPILES.append(0)

        def count(name, secs, **_):
            if name == BACKEND_COMPILE:
                _COMPILES[0] += 1

        jax.monitoring.register_event_duration_secs_listener(count)
    return _COMPILES[0]


@contextlib.contextmanager
def record_programs():
    """Record, per step program compiled meanwhile, its optimized HLO text,
    its scope table and the backend compilations its table extraction
    added (those after the `serve.launch` span closed)."""
    out, mark = {}, {}
    real_span, real_table = trace.span, trace.scope_table

    @contextlib.contextmanager
    def span_(name, **stats):
        with real_span(name, **stats) as s:
            yield s
        mark["after_call"] = compiles()

    def table_(text):
        module, table = real_table(text)
        out[module] = {"text": text, "table": table,
                       "extract_compiles": compiles() - mark["after_call"]}
        return module, table

    compiles()
    trace.span, trace.scope_table = span_, table_
    try:
        yield out
    finally:
        trace.span, trace.scope_table = real_span, real_table


_COMP = re.compile(r"^(ENTRY\s+)?%?([^\s(]+)\s*\(.*\{\s*$")
_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%?([^\s=]+)\s*=\s*.*?\s([a-z][a-z0-9\-]*)\(")


def device_instructions(text):
    """(name, opcode, line) of the instructions that run as device ops:
    those of the entry computation and of the loop bodies, conditions,
    calls and branches reachable from it (not fused or reducer ones)."""
    comps, cur, entry = {}, None, None
    for line in text.splitlines():
        m = _COMP.match(line)
        if m:
            cur = m.group(2)
            comps[cur] = []
            entry = cur if m.group(1) else entry
        elif cur is not None:
            m = _INSTR.match(line)
            if m:
                comps[cur].append((m.group(1), m.group(2), line))
    seen, todo, out = set(), [entry], []
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        for name, op, line in comps[c]:
            out.append((name, op, line))
            if op in ("while", "call", "conditional"):
                todo += re.findall(r"(?:body|condition|to_apply|"
                                   r"computation)=\{?%([\w.\-]+)", line)
                todo += re.findall(r"branch_computations=\{([^}]*)\}", line)
    return out


def check_coverage(programs, modules):
    """Extraction compiled nothing, and at least 95% of each program's
    heavy device instructions map to a scope under a lane or `sample`."""
    assert set(modules) <= set(programs), sorted(programs)
    roots = ("prefill_lane", "decode_lane", "sample")
    for module in modules:
        rec = programs[module]
        assert rec["extract_compiles"] == 0, module
        heavy = [n for n, op, _ in device_instructions(rec["text"])
                 if op in HEAVY]
        assert len(heavy) >= 10, module
        scoped = [n for n in heavy
                  if rec["table"].get(n, "").split("/")[0] in roots]
        assert len(scoped) >= 0.95 * len(heavy), (
            module, sorted(set(heavy) - set(scoped)))


# ---------------------------------------------------------------- names
@pytest.mark.parametrize("make,name", [(span, "serve.steps"),
                                       (span, "bench_step"),
                                       (scope, "atention"),
                                       (scope, "serve.step")])
def test_unknown_span_or_scope_name_is_refused(make, name):
    with pytest.raises(ValueError, match="closed"):
        make(name)


def test_known_names_open():
    for name in SPAN_NAMES:
        with span(name, step=0):
            pass
    for name in SCOPE_NAMES:
        with scope(name):
            pass


@pytest.mark.parametrize("op_name,path", [
    ("jit(f)/decode_lane/layers/while/body/closed_call/attention/dot_general",
     "decode_lane/layers/attention"),
    ("jit(f)/decode_lane/layers/while/body/dynamic_slice",
     "decode_lane/layers"),
    ("jit(f)/decode_lane/layers/mlp_up/mlp_up/dot_general",
     "decode_lane/layers/mlp_up"),
    ("jit(f)/sample/jit(argsort)/sort", "sample"),
    ("reduce_window_sum", ""),
])
def test_scope_path(op_name, path):
    assert scope_path(op_name) == path


HLO = textwrap.dedent("""\
    HloModule jit_step, entry_computation_layout={()->f32[4]}

    %body (p: (s32[], f32[4])) -> (s32[], f32[4]) {
      %p = (s32[], f32[4]) parameter(0)
      %x = f32[4] get-tuple-element(%p), index=1
      %copy-start = (f32[4], f32[4], u32[]) copy-start(%x)
      %copy-done = f32[4] copy-done(%copy-start)
      %dot = f32[4] dot(%copy-done, %x), metadata={op_name="jit(step)/decode_lane/layers/while/body/attention/dot_general"}
      ROOT %t = (s32[], f32[4]) tuple(%i, %dot)
    }

    ENTRY %main (a: f32[4], b: f32[4]) -> f32[4] {
      %a = f32[4] parameter(0)
      %b = f32[4] parameter(1)
      %c = f32[] constant(0), metadata={op_name="jit(step)/decode_lane/embed/mul"}
      %emb = f32[4] multiply(%a, %a), metadata={op_name="jit(step)/decode_lane/embed/mul"}
      %zeros = f32[4] broadcast(%c), dimensions={}
      %init = (f32[4], f32[4]) tuple(%emb, %zeros)
      %while = (s32[], f32[4]) while(%init), condition=%cond, body=%body, metadata={op_name="jit(step)/decode_lane/layers/while"}
      %out = f32[4] get-tuple-element(%while), index=1
      %copy = f32[4] copy(%out)
      %iota = s32[4] iota(), iota_dimension=0
      ROOT %sort = f32[4] sort(%copy, %iota), to_apply=%cmp, metadata={op_name="jit(step)/sample/sort"}
    }
""")


def test_scope_table_charges_unnamed_instructions():
    """Metadata names an instruction; one without (a copy, a buffer the
    compiler inserts) takes its operand's scope, else its loop's, else
    its user's — never a shared constant's, and a tuple only its user's."""
    module, table = scope_table(HLO)
    assert module == "jit_step"
    assert table["dot"] == "decode_lane/layers/attention"
    assert table["while"] == "decode_lane/layers"
    assert table["copy-done"] == "decode_lane/layers"      # loop body
    assert table["copy"] == "decode_lane/layers"           # operand: while
    assert table["zeros"] == "decode_lane/layers"          # user: the loop
    assert table["init"] == "decode_lane/layers"           # not %emb's
    assert table["iota"] == "sample"                       # user: the sort
    assert table["a"] == "decode_lane/embed"               # user: %emb
    assert "b" not in table                                # nothing to take


# ------------------------------------------------------------- engines
def _engine(model, params, rec=None, mesh=None, max_new=3):
    return ContinuousEngine(
        model, params, mesh or single_device_mesh(), DEFAULT_RULES,
        RuntimeConfig(max_slots=3, block_size=8, max_blocks_per_seq=6,
                      chunk_tokens=8, max_new_tokens=max_new), trace=rec)


@pytest.fixture(scope="module")
def decoder():
    """A reduced decoder engine with a recorder, whose two step programs
    compiled (one request) while their scope tables were recorded."""
    cfg = get_config("qwen3-1.7b").reduced(n_layers=2, d_model=64, d_ff=128,
                                           vocab=97)
    model = build_model(cfg)
    rec = TraceRecorder()
    eng = _engine(model, model.init(jax.random.PRNGKey(0)), rec)
    with record_programs() as programs:
        eng.submit(np.arange(1, 12, dtype=np.int32))
        eng.run()
    rec.clear()
    return eng, rec, programs


def test_step_spans_on_the_profiler_clock(decoder):
    eng, rec, _ = decoder
    for p in (np.arange(1, 12, dtype=np.int32),
              np.arange(3, 8, dtype=np.int32)):
        eng.submit(p)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            eng.run()
        finally:
            jax.profiler.stop_trace()
        tr = trace_reduce.load(sorted(Path(d).rglob("*.xplane.pb"))[-1])
    host = [h for h in tr.host if h[0] in SPAN_NAMES]
    steps = [h for h in host if h[0] == "serve.step"
             and h[3].get("kind") != "none"]
    begins = [e.fields for e in rec.events if e.name == "step_begin"]
    assert len(steps) == len(begins) >= 3
    for (_, s, e, stats), b in zip(steps, begins):
        assert stats["step"] == b["step"]
        assert stats["kind"] == b["kind"]
        assert stats["decode_rows"] == b["decode_rows"]
        assert stats["chunk_tokens"] == b["chunk_tokens"]
        inside = {h[0] for h in host if s <= h[1] and h[2] <= e}
        assert {"serve.admit", "serve.schedule", "serve.inputs",
                "serve.launch", "serve.sync", "serve.commit"} <= inside
        launch = [h for h in host if h[0] == "serve.launch" and s <= h[1]
                  and h[2] <= e]
        assert launch[0][3]["program"] == (
            "jit_unified_step" if b["kind"] == "unified"
            else "jit_decode_only_step")


def test_decoder_programs_are_scoped_and_extraction_compiles_nothing(
        decoder):
    _, _, programs = decoder
    check_coverage(programs, ["jit_unified_step", "jit_decode_only_step"])
    assert trace.program_scopes()["jit_unified_step"] == \
        programs["jit_unified_step"]["table"]
    paths = set(programs["jit_decode_only_step"]["table"].values())
    assert {"decode_lane/layers", "decode_lane/layers/attention",
            "decode_lane/layers/kv_write", "decode_lane/layers/qkv_proj",
            "decode_lane/layers/o_proj", "decode_lane/layers/mlp_up",
            "decode_lane/layers/mlp_down", "decode_lane/lm_head",
            "sample"} <= paths


def test_ssm_programs_are_scoped_and_extraction_compiles_nothing():
    cfg = get_config("mamba2-2.7b").reduced(n_layers=2)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompt = np.arange(1, 20, dtype=np.int32) % cfg.vocab
    eng = _engine(model, params, max_new=2)
    with record_programs() as programs:
        eng.submit(prompt)
        eng.run()
    check_coverage(programs, ["jit_ssm_unified_step",
                              "jit_ssm_decode_only_step"])
    paths = set(programs["jit_ssm_unified_step"]["table"].values())
    assert {"prefill_lane/layers/conv", "prefill_lane/layers/ssd",
            "prefill_lane/layers/in_proj", "decode_lane/layers/ssd",
            "sample"} <= paths


_TP4 = textwrap.dedent("""
    import os, sys, json
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, %(tests)r)
    import jax, numpy as np
    from repro.configs import get_config
    from repro.distributed.sharding import DEFAULT_RULES
    from repro.launch.mesh import tp_mesh
    from repro.models import build_model
    from repro.serve.runtime import ContinuousEngine, RuntimeConfig
    from test_serve_spans import record_programs

    cfg = get_config("qwen3-1.7b").reduced(n_layers=2, d_model=64,
                                           d_ff=128, vocab=96)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ContinuousEngine(model, params, tp_mesh(4), DEFAULT_RULES,
                           RuntimeConfig(max_slots=4, block_size=8,
                                         max_blocks_per_seq=6, chunk_tokens=8,
                                         max_new_tokens=2))
    with record_programs() as programs:
        eng.submit(np.arange(1, 12, dtype=np.int32))
        eng.run()
    print(json.dumps({"ndev": len(jax.devices()), "programs": programs}))
""")


def test_tp4_programs_are_scoped_and_extraction_compiles_nothing():
    script = _TP4 % {"tests": str(ROOT / "tests")}
    env = {**os.environ, "PYTHONPATH": str(ROOT / "src")}
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, timeout=300, env=env, cwd=str(ROOT))
    assert out.returncode == 0, out.stderr[-3000:]
    payload = json.loads(out.stdout.strip().splitlines()[-1])
    assert payload["ndev"] == 4
    check_coverage(payload["programs"],
                   ["jit_unified_step", "jit_decode_only_step"])
    texts = "".join(p["text"] for p in payload["programs"].values())
    assert "all-reduce" in texts or "all-gather" in texts


def test_compile_cache_key_covers_metadata(monkeypatch):
    """A step program must not load the executable of a program that
    differs from it only in metadata: its scope table is read from it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    from repro import platform

    prev_dir = jax.config.jax_compilation_cache_dir
    prev_key = jax.config.jax_compilation_cache_include_metadata_in_key
    monkeypatch.setenv(platform.CACHE_ENV, "/somewhere/else")
    try:
        platform.configure_compile_cache()
        assert jax.config.jax_compilation_cache_include_metadata_in_key
    finally:
        jax.config.update("jax_compilation_cache_dir", prev_dir)
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          prev_key)
        cc.reset_cache()
