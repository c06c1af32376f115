"""The reduction from a profiler trace to the per-layer metrics."""

from pathlib import Path

import pytest

import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"


def synthetic():
    """Two devices, a 100 ns window, two annotated steps.  Device 0 runs
    ops [10, 30) and [20, 40) (overlapping) and [60, 70); device 1 runs
    [10, 50).  Step 0 spans [5, 45), step 1 [55, 95)."""
    modules = {0: [("jit_unified_step(7)", 10, 40),
                   ("jit_decode_only_step(9)", 60, 70)],
               1: [("jit_unified_step(7)", 10, 50),
                   ("jit_decode_only_step(9)", 60, 64)]}
    ops = {0: [("%fusion.1 = bf16[8] fusion(bf16[8] %p)", 10, 30),
               ("%all-reduce-start.2 = bf16[8] all-reduce-start(%fusion.1)",
                20, 40),
               ("%fusion.3 = bf16[8] fusion(bf16[8] %all-reduce-done.2)",
                60, 70)],
           1: [("%all-reduce.5 = bf16[8] all-reduce(bf16[8] %x)", 10, 50),
               ("%while.4 = (s32[]) while(%t)", 10, 50)]}
    host = [("bench_window", 0, 100, {}),
            ("bench_step", 5, 45, {"idx": 0}),
            ("PjitFunction(unified_step)", 6, 12, {}),
            ("bench_step", 55, 95, {"idx": 1}),
            ("np.asarray", 71, 90, {})]
    return tr.Trace(modules, ops, host)


def test_busy_union_and_window():
    t = synthetic()
    assert tr.window(t) == (0, 100)
    assert tr.window_s(t) == pytest.approx(100e-9)
    # device 0: [10, 40) + [60, 70) = 40 ns; device 1: 40 ns
    assert tr.busy_s(t) == pytest.approx(40e-9)


def test_union_clips_to_window():
    spans = [("a", -10, 5), ("b", 3, 8), ("c", 95, 120)]
    assert tr.union_ns(spans, 0, 100) == 8 + 5


def test_steps_matched_by_module_name():
    dev = tr.step_device_s(synthetic(), {0: "jit_unified_step",
                                         1: "jit_decode_only_step"})
    assert dev[0][0] == "jit_unified_step"
    assert dev[0][1] == pytest.approx((30 + 40) / 2 * 1e-9)
    assert dev[1] == ("jit_decode_only_step", pytest.approx(7e-9))


def test_collective_sum():
    # device 0: all-reduce-start 20 ns; device 1: all-reduce 40 ns
    assert tr.collective_s(synthetic()) == pytest.approx(30e-9)
    assert tr.is_collective("all-gather-done.3")
    assert not tr.is_collective("fusion.12")
    # a fusion that reads a collective's result is not a collective
    assert not tr.is_collective("%fusion.3 = bf16[8] fusion(%all-reduce.2)")


def test_breakdown():
    b = tr.breakdown(synthetic())
    ops = dict(b["device_ops"])
    # summed by kind over both devices, halved; the loop is left out
    assert ops["all-reduce"] == pytest.approx(20e-9)
    assert ops["all-reduce-start"] == pytest.approx(10e-9)
    assert ops["fusion"] == pytest.approx(15e-9)
    assert "while" not in ops
    gaps = dict(b["idle_gaps"])
    # device 0 idles [0,10) (middle 5: inside step 0), [40,60) (middle
    # 50: between steps, host idle), [70,100) (middle 85: np.asarray)
    assert gaps["bench_step"] == pytest.approx(10e-9)
    assert gaps["host idle"] == pytest.approx(20e-9)
    assert gaps["np.asarray"] == pytest.approx(30e-9)


def test_breakdown_reads_only_the_window():
    """The profiler runs on before and after the window: operations
    outside it add no idle time."""
    t = synthetic()
    t.ops[0] = [("%fusion.0 = bf16[8] fusion()", -30, -20)] + t.ops[0] + [
        ("%fusion.9 = bf16[8] fusion()", 150, 160)]
    gaps = dict(tr.breakdown(t)["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(60e-9)
    assert gaps["np.asarray"] == pytest.approx(30e-9)


def test_recorded_one_chip_trace():
    """A trace recorded on one TPU v5e: two small programs in three
    annotated steps."""
    t = tr.load(DATA / "one_chip.xplane.pb")
    assert t.devices == [0]
    lo, hi = tr.window(t)
    assert hi > lo
    # steps 0 and 2 ran one program, step 1 a smaller one (both named
    # after a lambda)
    steps = tr.step_device_s(t, {i: "jit__lambda" for i in range(3)})
    assert sorted(steps) == [0, 1, 2]
    assert min(steps[0][1], steps[2][1]) > 2 * steps[1][1] > 0
    # the device clock of this trace runs about 1 ms behind the host's,
    # so the first program falls before the window: busy holds the others
    # (the union of their operations, a little under their module spans)
    busy = tr.busy_s(t)
    assert steps[1][1] + steps[2][1] == pytest.approx(busy, rel=0.01)
    assert busy < tr.window_s(t)
    assert tr.collective_s(t) == 0.0
    b = tr.breakdown(t)
    assert {k for k, _ in b["device_ops"]} >= {"fusion", "copy-done"}
