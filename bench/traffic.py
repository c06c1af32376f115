"""Traffic generator: one general reader of the mix files in `traffic/`.

A mix file fixes the arrival process and the length distributions.  The
schedule it yields is a list of `Request(due_s, prompt_len, output_len)`:

  arrival.process "poisson"  open loop at `rate_per_s`, in three spans:
                             the warm-up (`warmup_s`), the measured window
                             (--seconds) and a tail (`tail_s`) that keeps
                             the load on while the window's last requests
                             finish
  arrival.process "backlog"  `requests` requests, all due at t=0

Lengths are lognormal (`median`, `sigma`), clipped only so that prompt and
output fit the context the cell holds.  Within each span the gaps and the
lengths are the distributions' quantiles at (i + 0.5) / n, n = rate x
span, in an order drawn from the mix's own `schedule_seed`: every run
replays the same schedule of sizes and due times, so runs with different
seeds do the same work.  The run's seed draws the prompts' token ids
(`prompt_tokens`) and the weights.
"""

from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from statistics import NormalDist
from typing import Dict, List

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


@dataclasses.dataclass(frozen=True)
class Request:
    due_s: float
    prompt_len: int
    output_len: int


def load(name: str) -> Dict:
    with open(TRAFFIC_DIR / f"{name}.json") as f:
        return json.load(f)


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_quantiles(median: float, sigma: float, n: int) -> np.ndarray:
    z = np.array([NormalDist().inv_cdf(u) for u in _quantiles(n)])
    return median * np.exp(sigma * z)


def exponential_quantiles(rate: float, n: int) -> np.ndarray:
    return -np.log1p(-_quantiles(n)) / rate


def spans(mix: Dict, seconds: float) -> List[tuple]:
    """(start_s, length_s, n_requests) of each span of the schedule."""
    arr = mix["arrival"]
    if arr["process"] == "backlog":
        return [(0.0, 0.0, int(arr["requests"]))]
    if arr["process"] != "poisson":
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    out, t = [], 0.0
    for length in (mix["warmup_s"], seconds, mix["tail_s"]):
        out.append((t, length, max(1, round(arr["rate_per_s"] * length))))
        t += length
    return out


def request_count(mix: Dict, seconds: float) -> int:
    return sum(n for _, _, n in spans(mix, seconds))


def lengths(mix: Dict, n: int, max_context: int, rng) -> tuple:
    """(prompt_lens, output_lens), int arrays of n, in seed order.  An
    output is at least 1 token and at most half the context; where prompt
    plus output would pass the context, the prompt is cut to fit."""
    out = lognormal_quantiles(mix["output"]["median"], mix["output"]["sigma"],
                              n)
    out = np.clip(np.rint(out), 1, max_context // 2).astype(np.int64)
    prm = lognormal_quantiles(mix["prompt"]["median"], mix["prompt"]["sigma"],
                              n)
    prm = np.rint(prm).astype(np.int64)
    out = out[rng.permutation(n)]
    prm = prm[rng.permutation(n)]
    prm = np.clip(prm, 1, max_context - out)
    return prm, out


def schedule(mix: Dict, seconds: float, max_context: int) -> List[Request]:
    """The cell's requests in due order: the same for every run."""
    rng = np.random.default_rng(mix["schedule_seed"])
    reqs: List[Request] = []
    for start, length, n in spans(mix, seconds):
        if length > 0:
            gaps = exponential_quantiles(1.0, n)[rng.permutation(n)]
            due = start + length * (np.cumsum(gaps) - gaps) / gaps.sum()
        else:
            due = np.full(n, start)
        prm, out = lengths(mix, n, max_context, rng)
        reqs += [Request(float(d), int(p), int(o))
                 for d, p, o in zip(due, prm, out)]
    return reqs


def prompt_tokens(reqs: List[Request], vocab: int, seed: int) -> List[np.ndarray]:
    """Token ids of every prompt, uniform over the vocabulary, from the
    seed (a stream apart from the one that orders the sizes)."""
    rng = np.random.default_rng([seed, 1])
    return [rng.integers(0, vocab, size=r.prompt_len, dtype=np.int32)
            for r in reqs]
