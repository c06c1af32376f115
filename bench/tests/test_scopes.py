"""The readers of the program's own spans and scopes (bench/scopes.py and
the per-layer metrics built on it), on a hand-made trace and table."""

import types

import pytest

import run
import scopes
import trace_reduce as tr
import work

# device 0: two decode-only executions around one unified one; a `while`
# holds the first execution's layer ops.  Window [50, 700).
MODULES = {0: [("jit_decode_only_step(1)", 100, 200),
               ("jit_unified_step(2)", 300, 500),
               ("jit_decode_only_step(1)", 600, 650),
               ("jit_decode_only_step(1)", 720, 760)]}    # after the window
OPS = {0: [("%while.1 = (s32[]) while(%t)", 100, 190),
           ("%fusion.1 = bf16[8] fusion(%p)", 100, 130),
           ("%fusion.2 = bf16[8] fusion(%p)", 130, 160),
           ("%fusion.3 = bf16[8] fusion(%p)", 160, 190),
           ("%sort.1 = f32[8] sort(%x)", 190, 200),
           ("%fusion.1 = bf16[8] fusion(%q)", 300, 400),
           ("%copy.5 = bf16[8] copy(%q)", 400, 450),
           ("%sort.2 = f32[8] sort(%y)", 450, 500),
           ("%fusion.1 = bf16[8] fusion(%p)", 600, 620),
           ("%fusion.2 = bf16[8] fusion(%p)", 620, 640),
           ("%sort.1 = f32[8] sort(%x)", 640, 650),
           ("%fusion.1 = bf16[8] fusion(%p)", 720, 760)]}
HOST = [("bench_window", 50, 700, {}),
        ("serve.step", 90, 210, {"step": 0, "kind": "decode_only"}),
        ("serve.sync", 150, 205, {}),
        ("serve.step", 290, 520, {"step": 1, "kind": "unified"}),
        ("serve.launch", 295, 300, {"program": "jit_unified_step"}),
        ("serve.sync", 320, 510, {}),
        ("serve.step", 560, 570, {"kind": "none"}),
        ("serve.step", 590, 660, {"step": 2, "kind": "decode_only"}),
        ("serve.sync", 595, 655, {}),
        ("serve.step", 710, 770, {"step": 3, "kind": "decode_only"}),
        ("serve.sync", 715, 765, {})]
TABLES = {"jit_decode_only_step": {"while.1": "decode_lane/layers",
                                   "fusion.1": "decode_lane/layers/attention",
                                   "fusion.2": "decode_lane/layers",
                                   "fusion.3": "decode_lane/layers/mlp_up",
                                   "sort.1": "sample"},
          "jit_unified_step": {"fusion.1": "prefill_lane/layers/attention",
                               "sort.2": "sample"}}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def fake_run(host=HOST):
    steps = [run.Step(0, 0.0, 1.0, "decode_only", [100, 200], [], 2),
             run.Step(2, 2.0, 3.0, "decode_only", [101, 201], [], 2)]
    return types.SimpleNamespace(
        trace=tr.Trace(MODULES, OPS, host), config=run.load_config(
            "qwen3-1.7b"), peaks=PEAKS, chips=1, traced_steps=steps)


@pytest.fixture
def tables(monkeypatch):
    monkeypatch.setattr(scopes, "program_scopes", lambda: TABLES)


def read(name, r):
    return run.reader("layers", name)(r)


def test_leaf_ops_leave_out_containers():
    leaves = scopes.leaf_ops(OPS[0])
    assert ("%while.1 = (s32[]) while(%t)", 100, 190) not in leaves
    assert len(leaves) == len(OPS[0]) - 1


def test_step_spans_pair_each_step_with_its_sync():
    spans = scopes.step_spans(fake_run().trace)
    assert [(st[1], sy and sy[1]) for st, sy in spans] == [
        (90, 150), (290, 320), (560, None), (590, 595)]


@pytest.mark.parametrize("name,value", [
    # steps that ran a program, less their sync: 65, 40, 10 ns
    ("engine_host_ms.chat", 115 / 3 / 1e6),
    ("engine_host_ms.backlog", 115 / 3 / 1e6),
    # idle [50,100) [200,300) [500,600) [650,700) = 300 ns, of which in a
    # step outside its sync: 10 + 5 + 10 + 10 + 10 + 5 + 5 = 55 ns
    ("idle_engine_share.chat", 100 * 55 / 300),
    # decode-only executions in the window: attention 30 and 20 ns
    ("decode_attention_ms.chat", 25 / 1e6),
    # exactly decode_lane/layers: 30 and 20 ns (the while left out)
    ("scan_io_ms.chat", 25 / 1e6),
    # sample: 10, 50, 10 ns over three executions of both programs
    ("sample_ms.chat", 70 / 3 / 1e6),
    ("prefill_attention_ms.backlog", 100 / 1e6),
])
def test_readers_on_a_hand_made_trace(tables, name, value):
    assert read(name, fake_run()) == pytest.approx(value)


def test_decode_attention_roofline(tables):
    cfg = fake_run().config
    need = [work.least_time_s(
        sum(work.attention_flops(cfg, k) for k in keys),
        sum(k for k in keys) * work.kv_bytes_per_token(cfg), PEAKS, 1)[0]
        for keys in ([100, 200], [101, 201])]
    assert read("decode_attention_roofline.chat", fake_run()) == \
        pytest.approx(100 * (sum(need) / 2) / 25e-9)


def test_by_scope_sums_every_op_of_a_program(tables):
    got = scopes.by_scope_ms(fake_run(), "jit_unified_step")
    assert got == pytest.approx({"prefill_lane/layers/attention": 100e-6,
                                 "": 50e-6, "sample": 50e-6})


NAMES = ["engine_host_ms.chat", "engine_host_ms.backlog",
         "idle_engine_share.chat", "decode_attention_ms.chat",
         "decode_attention_roofline.chat", "scan_io_ms.chat",
         "sample_ms.chat", "prefill_attention_ms.backlog"]


@pytest.mark.parametrize("name", NAMES)
def test_no_spans_read_nothing(tables, name):
    bare = [h for h in HOST if not h[0].startswith("serve.")]
    assert read(name, fake_run(bare)) is None


@pytest.mark.parametrize("name", NAMES)
def test_no_table_reads_nothing_from_the_device(monkeypatch, name):
    """The span readers need no table; the device-op readers read none."""
    monkeypatch.setattr(scopes, "program_scopes", lambda: {})
    spans_only = name.startswith(("engine_host_ms", "idle_engine_share"))
    assert (read(name, fake_run()) is None) == (not spans_only)
