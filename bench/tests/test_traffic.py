"""The traffic generator: every run replays the mix's one schedule, the
seed draws only the prompts' token ids, and the lengths and gaps follow
the file's distributions."""

import numpy as np
import pytest

import traffic

SEED = 2**31 + 12345


@pytest.fixture(params=sorted(p.stem
                              for p in traffic.TRAFFIC_DIR.glob("*.json")))
def mix(request):
    return traffic.load(request.param)


def test_one_schedule_for_every_seed(mix):
    a = traffic.schedule(mix, 45, 4096)
    assert a == traffic.schedule(mix, 45, 4096)
    pa = traffic.prompt_tokens(a, 151936, SEED)
    pb = traffic.prompt_tokens(a, 151936, SEED)
    pc = traffic.prompt_tokens(a, 151936, SEED + 1)
    assert all(np.array_equal(x, y) for x, y in zip(pa, pb))
    assert not all(np.array_equal(x, y) for x, y in zip(pa, pc))
    assert [len(p) for p in pc] == [r.prompt_len for r in a]


def test_schedule_seed_orders_the_sizes(mix):
    other = dict(mix, schedule_seed=mix["schedule_seed"] + 1)
    a = traffic.schedule(mix, 45, 4096)
    b = traffic.schedule(other, 45, 4096)
    assert [r.prompt_len for r in a] != [r.prompt_len for r in b]
    assert sorted(r.output_len for r in a) == sorted(r.output_len for r in b)


def test_lengths_follow_the_file(mix):
    reqs = traffic.schedule(mix, 3000, 1 << 20)   # no context cut
    prm = np.array([r.prompt_len for r in reqs])
    out = np.array([r.output_len for r in reqs])
    assert np.median(prm) == pytest.approx(mix["prompt"]["median"], rel=0.02)
    assert np.median(out) == pytest.approx(mix["output"]["median"], rel=0.02)
    # lognormal: the log-lengths' spread is sigma
    for x, key in ((prm, "prompt"), (out, "output")):
        q1, q3 = np.percentile(np.log(x), [25, 75])
        assert (q3 - q1) / 1.349 == pytest.approx(mix[key]["sigma"], rel=0.05)


def test_window_holds_rate_times_seconds():
    mix = traffic.load("chat")
    t0, t1 = mix["warmup_s"], mix["warmup_s"] + 45
    win = [r for r in traffic.schedule(mix, 45, 4096) if t0 <= r.due_s < t1]
    assert len(win) == round(mix["arrival"]["rate_per_s"] * 45)
    # the gaps of the window are the exponential's quantiles, scaled to it
    gaps = np.diff([r.due_s for r in win] + [t1])
    want = traffic.exponential_quantiles(1.0, len(win))
    assert sorted(gaps) == pytest.approx(sorted(45 * want / want.sum()))


def test_context_cut_only_where_needed():
    mix = traffic.load("chat")
    reqs = traffic.schedule(mix, 3000, 4096)
    assert all(r.prompt_len + r.output_len <= 4096 for r in reqs)
    assert all(r.prompt_len >= 1 and r.output_len >= 1 for r in reqs)
    uncut = traffic.schedule(mix, 3000, 1 << 20)
    cut = sum(a.prompt_len != b.prompt_len for a, b in zip(reqs, uncut))
    assert 0 < cut < 0.15 * len(reqs)


def test_arrivals():
    mix = traffic.load("chat")
    reqs = traffic.schedule(mix, 45, 4096)
    due = np.array([r.due_s for r in reqs])
    rate = mix["arrival"]["rate_per_s"]
    assert due[0] == 0.0 and np.all(np.diff(due) > 0)
    assert len(reqs) == traffic.request_count(mix, 45)
    # the run covers warm-up, window and tail at the file's rate
    span = mix["warmup_s"] + 45 + mix["tail_s"]
    assert due[-1] < span
    assert len(reqs) == pytest.approx(rate * span, abs=3)
    back = traffic.schedule(traffic.load("chat-backlog"), 45, 4096)
    assert all(r.due_s == 0.0 for r in back)
