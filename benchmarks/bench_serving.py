"""Serving benchmark: continuous batching + paged KV-cache vs fixed batches.

Both engines serve the SAME workload — Poisson arrivals, mixed prompt
lengths, mixed per-request generation budgets — against the same model and
the same wall clock:

  fixed      — the original `FixedBatchEngine` drain loop driven
               arrival-aware: a batch forms from whatever has arrived,
               prompts pad to the provisioned maximum, and every batch
               decodes the full worst-case token budget (a static-batch
               server cannot stop per-request);
  continuous — `ContinuousEngine`: one unified token-budget step — each
               engine step carries up to `chunk_tokens` of prompt work
               alongside a decode token for EVERY in-flight request, KV
               lives in pages, and each request retires at exactly its
               own budget.

Reported per engine: useful tokens/s (only the tokens each request asked
for count), latency p50/p95 (completion - arrival), and for the continuous
engine TTFT and occupancy.  The paper's §3.4 claim shape (e2e serving
speedup at matched latency) reproduces here as the tokens/s ratio at the
reported p95s.

A third section (`--pressure`) is the pool-pressure sweep: the same Poisson
workload replayed with the KV block pool shrunk to 1.0x / 0.5x / 0.25x of
the worst-case demand (slots x max table width).  Worst-case reservation
simply could not run below 1.0x; on-demand growth + preemption completes
the full workload at every size — the sweep reports tokens/s, p95,
preemption count, swap traffic and stall time per pool size, making the
reservation-vs-preemption trade measurable.

A fourth section (`--interference`) is the PREFILL-INTERFERENCE sweep: a
Poisson mix of long and short prompts replayed through a chunked engine
(`chunk_tokens` budget slices every long prompt across steps) and an
unchunked one (whole prompt in one chunk) under a deterministic virtual
clock whose per-step cost is linear in the tokens the step carries
(c0 + c_tok x (decode rows + chunk tokens)).  The headline number is
decode TIME-BETWEEN-TOKENS p95: every in-flight decoder samples each of
its steps' cost as one inter-token gap, so a prompt monopolizing a step
is a gap spike suffered by the whole decode batch.  Chunking must hold
decode TBT p95 at or below the unchunked engine's while trading a bounded
amount of long-prompt TTFT (their prefill now spans several steps).

A fifth section (`--packing`) is the SEGMENT-PACKING sweep: a short-prompt-
heavy Poisson workload replayed through a packed engine (one step's chunk
carries prompt segments from up to `chunk_segments` requests) and a
single-segment one (each step's chunk carries one request's slice, PR 4
behaviour) under the same virtual clock.  The compiled chunk lane executes
at its full width whenever it runs, so the cost model charges chunk-
carrying steps the LANE WIDTH (not the tokens committed) and decode-only
steps nothing for the lane — which is exactly the game: packing fills the
width with useful prompt tokens (chunk fill fraction -> 1), and the
compiled decode-only fast path skips the lane when there is no prompt
work at all.  Reported per engine: useful tokens/s, TTFT p95, chunk fill
fraction, packed segments and decode-only step counts.

A sixth section (`--prefix`) is the PREFIX-SHARING sweep: a Poisson
workload where most requests begin with one hot system prompt, replayed
with prefix sharing on vs off under the same virtual clock.  Sharing-on
admissions adopt the system prompt's KV blocks from the allocator's
prefix index (refcounted, copy-on-write on divergence) and start prefill
at the first unshared token, so the hot prefix is prefilled once, ever —
reported as chunk tokens committed, prefix-hit tokens, CoW copies,
tokens/s and TTFT p95 per setting (the streams themselves are pinned
byte-identical by the test suite).

A second section (`--lanes`) reports the PER-LANE breakdown of the plan's
stage matmul dispatch: the same Poisson workload replayed through an
xla-only plan, the tuned serve plan (`build_serve_plan` — each stage
matmul raced per the paper's system-level exploration), and a forced
all-Pallas plan, with each run's `PlanRouter.describe()` lane table.  On
this CPU container the Pallas lanes execute in interpret mode, so their
tokens/s is NOT a TPU performance statement — the section demonstrates
observable plan-driven dispatch and measures the xla-vs-tuned delta.

`--family ssm` swaps the model family: the SAME continuous scheduler
serves Mamba2 through the `SSMFamilyAdapter` (fixed-size slot-pooled
conv+SSM state rows instead of paged KV blocks — repro.serve.statecache),
with the state pool provisioned one row short of the slot count so the
replay exercises slot preemption + host swap, vs the arrival-aware
`FixedBatchEngine` drain on the same Poisson workload.  Reported: useful
tokens/s both engines, TTFT p95, preemption count, and a zero-errors
guard (every submitted request must complete).

A TENSOR-PARALLEL sweep (skip with `--no-tp`; needs `--devices 4`)
replays a workload prefix at serving-mesh widths 1x1 / 1x2 / 1x4 —
device-subset meshes over virtual host devices (repro.platform sets
--xla_force_host_platform_device_count before jax imports) — each width
under its own tuned plan (`build_serve_plan(model_parallel=tp)` races
replicated vs model-parallel per stage matmul, pricing the implied
collectives), plus tuned-vs-forced-replicated at the widest mesh.  Token
streams are byte-identical across widths (pinned by
tests/test_tp_serving.py); with fewer than 4 host devices the sweep's
CSV rows emit 0.0 with a "skipped" note so the schema never moves.

`--sampling mixed` gives every headline request per-request
SamplingParams from a fixed cycle (greedy / temperature / temperature+
top-k / temperature+top-p, unique seed each) instead of all-greedy — the
knobs and keys are traced data, so the run still uses the same two
compiled step programs.  A SAMPLED-DIFFERENTIAL section (skip with
`--no-sampled`) always replays a mixed-sampling prefix of the workload
through a fresh continuous engine AND the B=1 fixed drain with aligned
rids: because every token's draw is keyed by (seed, rid, token_index),
the two engines must produce byte-identical sampled streams; any
mismatch is a non-zero exit.

`--trace out.json` additionally records the headline continuous run's
structured event trace (repro.serve.trace): the file is Chrome-trace JSON
(drop it on ui.perfetto.dev for one timeline track per request plus
scheduler/pool tracks), the raw events ride along under the "reproServe"
key, the run's ServeMetrics are cross-validated against the events by
repro.serve.traceview (non-zero exit on any violation), and the report
gains a per-request time-attribution table (queued / prefill / stall /
decode fractions of each request's life).

Run:  PYTHONPATH=src python benchmarks/bench_serving.py [--requests 32]
"""

from __future__ import annotations

import argparse
import time
from collections import deque
from typing import List

# must run before anything imports jax: --devices N asks the CPU backend
# for N virtual host devices, and the backend latches XLA_FLAGS at the
# first jax import (see repro.platform) — the TP mesh sweep needs 4
from repro import platform

platform.configure_from_argv()

import jax
import numpy as np

from repro.configs import get_config
from repro.core.plan import InferencePlan, OpChoice
from repro.core.search.tuner import Tuner
from repro.distributed.sharding import DEFAULT_RULES
from repro.kernels.dispatch import MATMUL_ROLES
from repro.launch.mesh import single_device_mesh, tp_mesh
from repro.models import build_model
from repro.serve import (
    ContinuousEngine,
    FixedBatchEngine,
    PlanRouter,
    RuntimeConfig,
    SamplingParams,
    ServeConfig,
    TraceRecorder,
    build_serve_plan,
    percentile,
    write_trace,
)
from repro.serve import traceview


def mixed_sampling(i: int) -> SamplingParams:
    """Per-request sampling cycle for `--sampling mixed` and the sampled
    differential: greedy / pure temperature / temperature+top-k /
    temperature+top-p, each sampled request with its own seed."""
    r = i % 4
    if r == 0:
        return SamplingParams()
    if r == 1:
        return SamplingParams(temperature=0.8, seed=1000 + i)
    if r == 2:
        return SamplingParams(temperature=1.0, top_k=8, seed=1000 + i)
    return SamplingParams(temperature=0.9, top_p=0.85, seed=1000 + i)


def make_workload(rng: np.random.Generator, n: int, vocab: int, rate_hz: float,
                  prompt_lo: int = 8, prompt_hi: int = 48,
                  new_lo: int = 2, new_hi: int = 32):
    """Poisson arrivals with mixed prompt lengths and generation budgets."""
    gaps = rng.exponential(1.0 / rate_hz, size=n)
    arrivals = np.cumsum(gaps)
    out = []
    for i in range(n):
        plen = int(rng.integers(prompt_lo, prompt_hi + 1))
        out.append({
            "prompt": rng.integers(0, vocab, size=plen).astype(np.int32),
            "max_new": int(rng.integers(new_lo, new_hi + 1)),
            "arrival": float(arrivals[i]),
        })
    return out


# ----------------------------------------------------------------- continuous
def drive_continuous(engine: ContinuousEngine, workload) -> dict:
    t0 = time.perf_counter()
    engine.metrics.start_time = t0
    for w in workload:
        engine.submit(w["prompt"], max_new_tokens=w["max_new"],
                      arrival_time=t0 + w["arrival"],
                      sampling=w.get("sampling"))
    done = engine.run()
    s = engine.metrics.summary()
    return {
        "tokens_per_s": s["tokens_per_s"],
        "latency_p50_s": s["latency_p50_s"],
        "latency_p95_s": s["latency_p95_s"],
        "ttft_p50_s": s["ttft_p50_s"],
        "slot_occupancy": s["slot_occupancy_mean"],
        "cache_occupancy": s["cache_occupancy_mean"],
        "chunk_fill_frac": s["chunk_fill_frac"],
        "packed_segments": int(s["packed_segments"]),
        "decode_only_steps": int(s["decode_only_steps"]),
        "tokens": int(s["tokens_out"]),
        "done": len(done),
    }


# ---------------------------------------------------------------- fixed batch
def drive_fixed(model, params, mesh, cfg: ServeConfig, prompt_pad: int,
                workload) -> dict:
    """Arrival-aware driver around the static drain loop: batches form from
    arrived requests only; prompts pad to the provisioned max; every batch
    decodes the full worst-case budget."""
    eng = FixedBatchEngine(model, params, mesh, DEFAULT_RULES, cfg)

    def pad(p):
        out = np.zeros((prompt_pad,), np.int32)
        out[prompt_pad - len(p):] = p          # static server left-pads
        return out

    # warm the two compiled programs outside the timed region
    eng.submit(pad(workload[0]["prompt"]))
    eng.run()
    eng.stats = {k: 0 if isinstance(v, int) else 0.0
                 for k, v in eng.stats.items()}

    pending = deque(workload)
    latencies: List[float] = []
    useful_tokens = 0
    t0 = time.perf_counter()
    t_last = t0
    while pending:
        now = time.perf_counter() - t0
        batch = []
        while (pending and pending[0]["arrival"] <= now
               and len(batch) < cfg.batch_size):
            batch.append(pending.popleft())
        if not batch:
            time.sleep(min(1e-3, pending[0]["arrival"] - now))
            continue
        for w in batch:
            eng.submit(pad(w["prompt"]), sampling=w.get("sampling"))
        eng.run()
        t_done = time.perf_counter()
        t_last = t_done
        for w in batch:
            latencies.append((t_done - t0) - w["arrival"])
            useful_tokens += w["max_new"]
    wall = max(1e-9, t_last - t0)
    return {
        "tokens_per_s": useful_tokens / wall,
        "latency_p50_s": percentile(latencies, 50),
        "latency_p95_s": percentile(latencies, 95),
        "tokens": useful_tokens,
        "done": len(latencies),
    }


# -------------------------------------------------- per-lane plan breakdown
def _lane_histogram(router: PlanRouter) -> dict:
    hist: dict = {}
    for _, backend in router.describe().items():
        hist[backend] = hist.get(backend, 0) + 1
    return hist


def _forced_pallas_plan(tuned: InferencePlan) -> InferencePlan:
    """The tuned plan with every stage matmul overridden onto the Pallas
    lane (tuned config where the race produced one; {} otherwise, which
    `ops.matmul` fills with the kernel's own aligned defaults)."""
    forced = InferencePlan(tuned.graph_name, tuned.chip)
    for name, c in tuned.choices.items():
        op = name.split(".", 1)[1]
        if op in MATMUL_ROLES:
            cfg = dict(c.config) if c.backend == "pallas_matmul" else {}
            forced.choices[name] = OpChoice("pallas_matmul", cfg,
                                            c.modeled_time_s, dict(c.candidates))
        else:
            forced.choices[name] = c
    return forced


def lane_breakdown(model, params, mesh, cfg, rcfg: RuntimeConfig,
                   workload, verbose: bool = True) -> dict:
    """Replay the same Poisson workload through xla-only / tuned / forced
    Pallas matmul plans — the observable proof that the serve forward pass
    dispatches the plan's stage matmul choices."""
    prompt_hi = max(len(w["prompt"]) for w in workload)
    tuned = build_serve_plan(cfg, prefill_len=prompt_hi, slots=rcfg.max_slots,
                             max_seq=rcfg.max_seq,
                             tuner=Tuner(methods=("random",), random_budget=16))
    plans = {
        "xla-only": None,
        "tuned plan": tuned,
        "forced pallas": _forced_pallas_plan(tuned),
    }
    results = {}
    for label, plan in plans.items():
        router = PlanRouter(plan)
        engine = ContinuousEngine(model, params, mesh, DEFAULT_RULES, rcfg,
                                  router=router)
        warm_engine(engine, cfg.vocab, prompt_hi)
        r = drive_continuous(engine, workload)
        r["lanes"] = _lane_histogram(router)
        results[label] = r
        if verbose:
            matmuls = {k: v for k, v in router.describe().items()
                       if k.split(".", 1)[1] in MATMUL_ROLES}
            lanes = (", ".join(f"{k}={v}" for k, v in sorted(r["lanes"].items()))
                     or "xla (no plan)")
            print(f"{label:14s}: {r['tokens_per_s']:8.1f} tok/s | "
                  f"p95 {r['latency_p95_s']:6.2f}s | lanes: {lanes}")
            if matmuls and label != "xla-only":
                for name, backend in sorted(matmuls.items()):
                    print(f"                 {name:18s} -> {backend}")
    return results


def warm_engine(engine: ContinuousEngine, vocab: int, prompt_hi: int) -> None:
    """Compile THE unified step program outside a timed replay.  One short
    request suffices: chunk geometry is data, so every prompt length —
    longer than any seen here included — reuses the same program."""
    rng = np.random.default_rng(0)
    engine.submit(rng.integers(0, vocab, size=min(8, prompt_hi))
                  .astype(np.int32), max_new_tokens=2)
    engine.run()
    engine.reset_metrics()


# --------------------------------------------------- tensor-parallel sweep
TP_WIDTHS = (1, 2, 4)

# the replicated baseline: DEFAULT_RULES with every model-axis rule the
# serving path shards knocked out, so the engine serves a WIDE mesh with
# fully replicated params and pools (serve_rules only ever narrows, so
# this stays replicated whatever the plan's layout verdicts say)
REPLICATED_RULES = DEFAULT_RULES.replace(
    heads=None, kv_heads=None, ffn=None, experts=None, vocab=None,
    embed_vec=None, ssm_heads=None, conv_dim=None)


def _layout_summary(router: PlanRouter, stage: str = "decode") -> str:
    """Compressed per-stage layout table for CSV derived columns and trace
    metadata: 'attention:mp,lm_head:rep,...'."""
    table = router.layout_table(stage)
    return ",".join(f"{k}:{'mp' if v == 'model_parallel' else 'rep'}"
                    for k, v in sorted(table.items()))


def tp_sweep(model, params, cfg, rcfg: RuntimeConfig, workload,
             verbose: bool = True) -> dict:
    """Tensor-parallel mesh sweep: the same Poisson workload replayed at
    mesh widths 1/2/4 — device-SUBSET meshes (`tp_mesh`), so one
    --xla_force_host_platform_device_count=4 process races all three —
    each width under its own tuned plan (`build_serve_plan(model_parallel=
    tp)`: the layout race prices the implied collectives next to the
    matmul lanes, and the winning per-stage layouts reach the step
    builders through `serve_rules`).  A second leg compares the widest
    mesh's TUNED layouts against a forced-replicated baseline with the
    same backend lanes.  Token streams are byte-identical across widths
    (pinned by tests/test_tp_serving.py); on this CPU container the
    tokens/s deltas measure dispatch/collective overhead on virtual
    devices, not TPU interconnect behaviour."""
    n_dev = jax.local_device_count()
    results: dict = {"devices": n_dev, "skipped": n_dev < max(TP_WIDTHS)}
    if results["skipped"]:
        if verbose:
            print(f"tp sweep skipped: {n_dev} host device(s) < "
                  f"{max(TP_WIDTHS)} (relaunch with --devices "
                  f"{max(TP_WIDTHS)})")
        return results
    prompt_hi = max(len(w["prompt"]) for w in workload)
    widest_router = None
    for tp in TP_WIDTHS:
        plan = build_serve_plan(
            cfg, prefill_len=prompt_hi, slots=rcfg.max_slots,
            max_seq=rcfg.max_seq, chunk_tokens=rcfg.chunk_width,
            tuner=Tuner(methods=("random",), random_budget=16),
            model_parallel=tp)
        router = PlanRouter(plan)
        engine = ContinuousEngine(model, params, tp_mesh(tp), DEFAULT_RULES,
                                  rcfg, router=router)
        warm_engine(engine, cfg.vocab, prompt_hi)
        r = drive_continuous(engine, workload)
        s = engine.metrics.summary()
        r.update(ttft_p95_s=s["ttft_p95_s"], mesh=engine.mesh_tag,
                 layouts=_layout_summary(router))
        results[tp] = r
        if tp == max(TP_WIDTHS):
            widest_router = router
        if verbose:
            print(f"mesh {engine.mesh_tag}: {r['tokens_per_s']:8.1f} tok/s "
                  f"| ttft p95 {r['ttft_p95_s']:6.2f}s | "
                  f"layouts {r['layouts']}")
    # tuned-vs-replicated at the widest mesh: same plan (same backend
    # lanes), base rules knocked down to replicated — isolates the layout
    # dimension the tuner races
    engine = ContinuousEngine(model, params, tp_mesh(max(TP_WIDTHS)),
                              REPLICATED_RULES, rcfg, router=widest_router)
    warm_engine(engine, cfg.vocab, prompt_hi)
    r = drive_continuous(engine, workload)
    s = engine.metrics.summary()
    r.update(ttft_p95_s=s["ttft_p95_s"], mesh=engine.mesh_tag,
             layouts="forced replicated")
    results["replicated"] = r
    results["tuned"] = results[max(TP_WIDTHS)]
    if verbose:
        t, p = results["tuned"], results["replicated"]
        print(f"mesh {t['mesh']} tuned layouts vs replicated: "
              f"{t['tokens_per_s']:8.1f} vs {p['tokens_per_s']:8.1f} tok/s "
              f"| ttft p95 {t['ttft_p95_s']:.2f}s vs {p['ttft_p95_s']:.2f}s")
    return results


# ------------------------------------------------------- pool-pressure sweep
def pressure_sweep(model, params, mesh, cfg, rcfg: RuntimeConfig, workload,
                   factors=(1.0, 0.5, 0.25), verbose: bool = True) -> dict:
    """Replay the same Poisson workload with the block pool shrunk to
    `factor` x worst-case demand (max_slots x max_blocks_per_seq).  The
    old worst-case-reservation admission would serialize or starve below
    1.0x; on-demand growth + preemption must complete every request at
    every factor, trading throughput/p95 for memory."""
    import dataclasses as _dc

    worst = rcfg.max_slots * rcfg.max_blocks_per_seq
    prompt_hi = max(len(w["prompt"]) for w in workload)
    results = {}
    for f in factors:
        usable = max(rcfg.max_blocks_per_seq, int(round(worst * f)))
        sized = _dc.replace(rcfg, num_blocks=usable + 1)
        engine = ContinuousEngine(model, params, mesh, DEFAULT_RULES, sized)
        warm_engine(engine, cfg.vocab, prompt_hi)
        r = drive_continuous(engine, workload)
        s = engine.metrics.summary()
        errors = len(workload) - r["done"]
        r.update(pool_blocks=usable, factor=f, errors=errors,
                 preemptions=int(s["preemptions"]),
                 swap_mb=(s["swap_out_bytes"] + s["swap_in_bytes"]) / 2**20,
                 stall_s=s["stall_s"], swap_in_time_s=s["swap_in_time_s"])
        results[f] = r
        if verbose:
            print(f"pool {f:4.2f}x ({usable:3d} blocks): "
                  f"{r['tokens_per_s']:8.1f} tok/s | "
                  f"p95 {r['latency_p95_s']:6.2f}s | "
                  f"preemptions {r['preemptions']:3d} | "
                  f"swap {r['swap_mb']:6.2f} MiB | "
                  f"swap-in {r['swap_in_time_s']:5.2f}s | "
                  f"stall {r['stall_s']:5.2f}s | errors {errors}")
    full = results[min(factors)]
    if verbose:
        ok = full["errors"] == 0 and full["preemptions"] >= 1
        print(f"pool-pressure check (smallest pool completes full workload "
              f"via preemption): {'PASS' if ok else 'MISS'}")
    return results


# ------------------------------------------------ prefill-interference sweep
def interference_workload(rng: np.random.Generator, n: int, vocab: int,
                          rate_hz: float, short_hi: int = 12,
                          long_len: int = 64, long_frac: float = 0.5,
                          new_lo: int = 8, new_hi: int = 16):
    """Poisson mix of short (decode-dominated) and long (prefill-heavy)
    prompts — the workload where a monopolizing prefill shows up as
    decode-side head-of-line latency.  Built on `make_workload` (same
    arrival process); a `long_frac` share of requests get their prompt
    replaced by a `long_len`-token one and tagged `long`."""
    out = make_workload(rng, n, vocab, rate_hz, prompt_lo=4,
                        prompt_hi=short_hi, new_lo=new_lo, new_hi=new_hi)
    for w in out:
        w["long"] = bool(rng.random() < long_frac)
        if w["long"]:
            w["prompt"] = rng.integers(0, vocab, size=long_len).astype(np.int32)
    return out


def _replay_virtual(model, params, mesh, rcfg: RuntimeConfig, workload,
                    chunk_tokens, chunk_segments: int = None,
                    prefix_sharing: bool = None,
                    c0: float = 0.25, c_tok: float = 0.125):
    """Replay the workload under a deterministic virtual clock: a step
    that carries prompt work costs c0 + c_tok x (decode rows + the chunk
    lane's COMPILED width), a decode-only step costs c0 + c_tok x decode
    rows.  The lane-width charge is the honest price of the unified step —
    the compiled chunk lane executes at full width however little of it is
    filled — so the model makes both wins measurable: segment packing
    raises the useful tokens bought per lane charge (fill fraction), and
    the decode-only fast path drops the charge entirely on chunk-less
    steps.  Same cost model for every engine, so comparisons isolate
    SCHEDULING — how prompt work is sliced and packed — from kernel speed.

    The headline interference metric is the DECODE TIME-BETWEEN-TOKENS
    distribution: every (in-flight decoder, step) pair contributes that
    step's cost as one inter-token gap sample.  A prompt monopolizing a
    step shows up as a gap spike suffered by every concurrent decoder —
    exactly the head-of-line stall chunking exists to remove."""
    import dataclasses as _dc

    clock = {"t": 0.0}
    sized = _dc.replace(rcfg, chunk_tokens=chunk_tokens)
    if chunk_segments is not None:
        sized = _dc.replace(sized, chunk_segments=chunk_segments)
    if prefix_sharing is not None:
        sized = _dc.replace(sized, prefix_sharing=prefix_sharing)
    eng = ContinuousEngine(model, params, mesh, DEFAULT_RULES, sized,
                           now_fn=lambda: clock["t"])
    by_rid = {}
    for w in workload:
        rid = eng.submit(w["prompt"], max_new_tokens=w["max_new"],
                         arrival_time=w["arrival"])
        by_rid[rid] = w
    eng.metrics.start_time = 0.0
    tbt_gaps: List[float] = []
    with eng.mesh:
        while eng.scheduler.has_work:
            n_occ = len(eng.metrics.slot_occupancy)
            n_chunk_steps = eng.metrics.chunk_steps
            if eng.step():
                dec_rows = 0
                if len(eng.metrics.slot_occupancy) > n_occ:
                    dec_rows = round(eng.metrics.slot_occupancy[-1]
                                     * eng.cfg.max_slots)
                lane = (eng.cfg.chunk_width
                        if eng.metrics.chunk_steps > n_chunk_steps else 0)
                cost = c0 + c_tok * (dec_rows + lane)
                clock["t"] += cost
                tbt_gaps.extend([cost] * dec_rows)
            else:
                clock["t"] += c0 / 4          # idle tick (future arrivals)
    eng.metrics.end_time = clock["t"]
    done = eng._done
    short = [r.latency_s for r in done if not by_rid[r.rid].get("long")]
    long_ttft = [r.ttft_s for r in done if by_rid[r.rid].get("long")]
    s = eng.metrics.summary()
    return {
        "decode_tbt_p50_s": percentile(tbt_gaps, 50),
        "decode_tbt_p95_s": percentile(tbt_gaps, 95),
        "decode_tbt_max_s": max(tbt_gaps, default=0.0),
        "short_latency_p95_s": percentile(short, 95),
        "long_ttft_p95_s": percentile(long_ttft, 95),
        "ttft_p95_s": percentile([r.ttft_s for r in done], 95),
        "tokens_per_s": s["tokens_per_s"],
        "chunks": int(s["prefill_chunks"]),
        "chunk_steps": int(s["chunk_steps"]),
        "chunk_fill_frac": s["chunk_fill_frac"],
        "packed_segments": int(s["packed_segments"]),
        "decode_only_steps": int(s["decode_only_steps"]),
        "preemptions": int(s["preemptions"]),
        "chunk_tokens_committed": int(s["chunk_tokens_committed"]),
        "prefix_hit_tokens": int(s["prefix_hit_tokens"]),
        "cow_copies": int(s["cow_copies"]),
        "done": len(done),
    }


def interference_sweep(model, params, mesh, cfg, rcfg: RuntimeConfig,
                       requests: int = 24, seed: int = 0,
                       chunk_tokens: int = 16, rate_hz: float = 0.25,
                       verbose: bool = True) -> dict:
    """Decode p95 with vs without chunked prefill on a long/short Poisson
    mix (virtual clock — deterministic).  The unchunked engine carries a
    whole long prompt in ONE step, so every in-flight decoder's inter-token
    gap spikes by the full prompt's cost; the chunked engine bounds each
    step's prompt work at `chunk_tokens`, holding decode TBT p95 down at a
    bounded TTFT cost to the long prompts themselves (their prefill now
    spans several steps) — the reservation-free version of the trade the
    ROADMAP's "chunked prefill" open item asked for."""
    rng = np.random.default_rng(seed)
    long_len = min(64, rcfg.max_seq - 17)
    workload = interference_workload(rng, requests, cfg.vocab, rate_hz,
                                     long_len=long_len)
    results = {}
    for label, ct in (("chunked", chunk_tokens), ("unchunked", None)):
        r = _replay_virtual(model, params, mesh, rcfg, workload, ct)
        results[label] = r
        if verbose:
            print(f"{label:10s}: decode tbt p50 {r['decode_tbt_p50_s']:5.2f}  "
                  f"p95 {r['decode_tbt_p95_s']:5.2f}  "
                  f"max {r['decode_tbt_max_s']:5.2f} | "
                  f"long ttft p95 {r['long_ttft_p95_s']:6.2f} | "
                  f"short lat p95 {r['short_latency_p95_s']:6.2f} | "
                  f"chunks {r['chunks']:3d} | {r['done']} reqs (virtual s)")
    if verbose:
        ok = (results["chunked"]["decode_tbt_p95_s"]
              <= results["unchunked"]["decode_tbt_p95_s"])
        print("prefill-interference check (chunked decode TBT p95 <= "
              f"unchunked): {'PASS' if ok else 'MISS'}")
    return results


# ------------------------------------------------- sampled differential
def sampled_differential(model, params, mesh, cfg, rcfg: RuntimeConfig,
                         workload, n: int = 12,
                         verbose: bool = True) -> dict:
    """Mixed-sampling replay pinned against the B=1 fixed drain.

    A prefix of the Poisson workload gets per-request SamplingParams from
    the mixed cycle and runs through a FRESH continuous engine (chunked
    prefill, packing, the usual schedule) and through a fresh
    `FixedBatchEngine` at batch_size=1 with UNPADDED prompts (left-padding
    changes the logits; B=1 needs none).  Every token's draw is keyed by
    (seed, rid, token_index) — pure request identity and progress — so the
    rid sequences are aligned (fresh engines, same submission order) and
    the continuous streams must equal the drain's byte for byte (prefix
    compare: the static drain decodes the batch-wide worst-case budget).
    Any mismatch fails the bench."""
    sub = [dict(w) for w in workload[:n]]
    for i, w in enumerate(sub):
        w["sampling"] = mixed_sampling(i)
    prompt_hi = max(len(w["prompt"]) for w in sub)

    engine = ContinuousEngine(model, params, mesh, DEFAULT_RULES, rcfg)
    warm_engine(engine, cfg.vocab, prompt_hi)
    engine._rid = 0       # keys are rid-keyed: drop the warm-up rid so the
    #                       replay's rids align with the fresh baseline's
    t0 = time.perf_counter()
    engine.metrics.start_time = t0
    for w in sub:
        engine.submit(w["prompt"], max_new_tokens=w["max_new"],
                      arrival_time=t0 + w["arrival"], sampling=w["sampling"])
    finished = engine.run()
    done = {q.rid: q.output for q in finished}
    s = engine.metrics.summary()
    r = {"tokens_per_s": s["tokens_per_s"], "done": len(finished)}

    fixed = FixedBatchEngine(
        model, params, mesh, DEFAULT_RULES,
        ServeConfig(batch_size=1, max_seq=rcfg.max_seq,
                    max_new_tokens=max(w["max_new"] for w in sub)))
    for w in sub:
        fixed.submit(w["prompt"], sampling=w["sampling"])
    ref = {q.rid: q.output for q in fixed.run()}

    mismatches = sum(1 for rid, out in done.items()
                     if out != ref[rid][: len(out)])
    sampled_n = sum(1 for w in sub if not w["sampling"].greedy)
    out = {"tokens_per_s": r["tokens_per_s"], "mismatches": mismatches,
           "requests": len(sub), "sampled_requests": sampled_n,
           "done": r["done"]}
    if verbose:
        ok = mismatches == 0 and r["done"] == len(sub)
        print(f"sampled    : {r['tokens_per_s']:8.1f} tok/s | "
              f"{sampled_n}/{len(sub)} sampled | "
              f"mismatches vs B=1 drain: {mismatches} "
              f"({'PASS' if ok else 'FAIL'}: keyed streams replay "
              "byte-identically across schedules)")
    return out


# --------------------------------------------------- segment-packing sweep
def packing_sweep(model, params, mesh, cfg, rcfg: RuntimeConfig,
                  requests: int = 24, seed: int = 0, chunk_tokens: int = 32,
                  rate_hz: float = 1.5, verbose: bool = True) -> dict:
    """Useful tokens/s with vs without segment packing on a short-prompt-
    heavy Poisson workload (virtual clock — deterministic).  Every prompt
    is far smaller than the chunk budget, so the single-segment engine
    (PR 4 behaviour: one request's slice per step) pays the full compiled
    lane width for a mostly idle chunk each prefill step; the packed
    engine carries several prompts' segments per step, buying more useful
    prompt tokens for the same lane charge — higher chunk fill fraction,
    fewer chunk steps, better tokens/s AND better TTFT p95 (short prompts
    stop queueing behind one-per-step chunk scheduling)."""
    rng = np.random.default_rng(seed)
    workload = make_workload(rng, requests, cfg.vocab, rate_hz,
                             prompt_lo=4, prompt_hi=12, new_lo=4, new_hi=12)
    results = {}
    for label, segs in (("packed", max(2, rcfg.chunk_segments)),
                        ("single-seg", 1)):
        r = _replay_virtual(model, params, mesh, rcfg, workload,
                            chunk_tokens, chunk_segments=segs)
        results[label] = r
        if verbose:
            print(f"{label:10s}: {r['tokens_per_s']:7.2f} tok/s | "
                  f"ttft p95 {r['ttft_p95_s']:6.2f} | "
                  f"chunk fill {r['chunk_fill_frac']:4.0%} "
                  f"({r['chunks']:3d} chunks / {r['chunk_steps']:3d} steps) | "
                  f"packed segs {r['packed_segments']:3d} | "
                  f"decode-only {r['decode_only_steps']:3d} | "
                  f"{r['done']} reqs (virtual s)")
    if verbose:
        ok = (results["packed"]["tokens_per_s"]
              > results["single-seg"]["tokens_per_s"]
              and results["packed"]["packed_segments"] > 0)
        print("segment-packing check (packed tokens/s > single-segment, "
              f"packing observed): {'PASS' if ok else 'MISS'}")
    return results


# --------------------------------------------------- prefix-sharing sweep
def prefix_workload(rng: np.random.Generator, n: int, vocab: int,
                    rate_hz: float, system_len: int = 32,
                    share_frac: float = 0.75, tail_lo: int = 2,
                    tail_hi: int = 16, new_lo: int = 4, new_hi: int = 12):
    """Poisson arrivals where a `share_frac` share of requests begin with
    ONE hot `system_len`-token system prompt (an exact multiple of the
    headline block size, so its blocks are index-eligible) followed by a
    short per-request tail; the rest carry unrelated prompts.  The shape
    every multi-tenant chat serving deployment exhibits — and the one
    prefix sharing exists for: the system prompt's KV should be prefilled
    once, ever."""
    system = rng.integers(0, vocab, size=system_len).astype(np.int32)
    out = make_workload(rng, n, vocab, rate_hz, prompt_lo=4,
                        prompt_hi=system_len // 2, new_lo=new_lo,
                        new_hi=new_hi)
    for w in out:
        w["shared"] = bool(rng.random() < share_frac)
        if w["shared"]:
            tail = rng.integers(0, vocab,
                                size=int(rng.integers(tail_lo, tail_hi + 1)))
            w["prompt"] = np.concatenate([system, tail.astype(np.int32)])
    return out


def prefix_sweep(model, params, mesh, cfg, rcfg: RuntimeConfig,
                 requests: int = 24, seed: int = 0, chunk_tokens: int = 32,
                 rate_hz: float = 1.0, verbose: bool = True) -> dict:
    """Useful tokens/s with vs without prefix sharing on a shared-system-
    prompt Poisson workload (virtual clock — deterministic).  The sharing
    engine admits each hot-prefix request with its system prompt's blocks
    ADOPTED from the prefix index (refcounted, copy-on-write on divergence)
    and starts prefill at the first unshared token, so the chunk lane
    commits only the tails — fewer chunk-carrying steps, each a full
    lane-width charge saved, which the cost model converts into tokens/s
    and TTFT wins.  The sharing-off engine prefills every copy of the
    system prompt from scratch.  Streams are byte-identical either way
    (pinned by tests/test_prefix_sharing.py); this sweep measures the
    work, not the answers."""
    rng = np.random.default_rng(seed)
    workload = prefix_workload(rng, requests, cfg.vocab, rate_hz)
    results = {}
    for label, share in (("on", True), ("off", False)):
        r = _replay_virtual(model, params, mesh, rcfg, workload,
                            chunk_tokens, prefix_sharing=share)
        results[label] = r
        if verbose:
            print(f"sharing-{label:3s}: {r['tokens_per_s']:7.2f} tok/s | "
                  f"ttft p95 {r['ttft_p95_s']:6.2f} | "
                  f"chunk tokens {r['chunk_tokens_committed']:4d} "
                  f"({r['chunk_steps']:3d} steps) | "
                  f"prefix hits {r['prefix_hit_tokens']:4d} | "
                  f"cow {r['cow_copies']:2d} | "
                  f"{r['done']} reqs (virtual s)")
    if verbose:
        on, off = results["on"], results["off"]
        ok = (on["prefix_hit_tokens"] > 0
              and on["chunk_tokens_committed"]
              <= 0.6 * off["chunk_tokens_committed"]
              and on["tokens_per_s"] > off["tokens_per_s"])
        print("prefix-sharing check (>=40% fewer chunk tokens, tokens/s "
              f"improves, hits observed): {'PASS' if ok else 'MISS'}")
    return results


# -------------------------------------------------------------------- harness
def bench(requests: int = 32, slots: int = 4, seed: int = 0,
          rate_hz: float = 0.0, verbose: bool = True,
          lanes: bool = True, lane_requests: int = 12,
          pressure: bool = True, interference: bool = True,
          interference_requests: int = 24, packing: bool = True,
          packing_requests: int = 24, prefix: bool = True,
          prefix_requests: int = 24, sampling: str = "greedy",
          sampled: bool = True, sampled_requests: int = 12,
          tp: bool = True, tp_requests: int = 12,
          trace_path: str = None) -> dict:
    cfg = get_config("qwen3-1.7b").reduced(n_layers=2, d_model=128, d_ff=256,
                                           vocab=211)
    model = build_model(cfg)
    mesh = single_device_mesh()
    with mesh:
        params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(seed)

    prompt_hi, new_hi = 48, 32
    rcfg = RuntimeConfig(max_slots=slots, block_size=16,
                         max_blocks_per_seq=-(-(prompt_hi + new_hi) // 16),
                         max_new_tokens=new_hi)
    recorder = TraceRecorder() if trace_path else None
    engine = ContinuousEngine(model, params, mesh, DEFAULT_RULES, rcfg,
                              trace=recorder)

    # Warm-up: compile THE unified step program (mixed lengths only warm
    # the host paths — chunk geometry is data, nothing else ever compiles).
    warm = [rng.integers(0, cfg.vocab, size=s).astype(np.int32)
            for s in (8, prompt_hi // 2, prompt_hi)] * 2
    for p in warm:
        engine.submit(p, max_new_tokens=4)
    engine.run()
    # Measure sustained (post-compile) capacity with a saturated burst.
    t0 = time.perf_counter()
    burst = 3 * slots
    for _ in range(burst):
        engine.submit(rng.integers(0, cfg.vocab, size=prompt_hi // 2)
                      .astype(np.int32), max_new_tokens=16)
    engine.run()
    cap_tok_s = (burst * 16) / (time.perf_counter() - t0)
    engine.reset_metrics()

    avg_new = (2 + new_hi) / 2
    if rate_hz <= 0:
        # offer ~1.3x sustained capacity: both engines run saturated, so
        # tokens/s measures ENGINE capacity rather than the arrival rate.
        rate_hz = max(0.1, 1.3 * cap_tok_s / avg_new)
    if verbose:
        print(f"sustained decode capacity ~{cap_tok_s:,.0f} tok/s -> "
              f"Poisson rate {rate_hz:.2f} req/s")

    workload = make_workload(rng, requests, cfg.vocab, rate_hz,
                             prompt_hi=prompt_hi, new_hi=new_hi)
    if sampling == "mixed":
        # per-request knobs on the HEADLINE workload too: same two step
        # programs, the knob/key arrays are just traced data
        for i, w in enumerate(workload):
            w["sampling"] = mixed_sampling(i)

    if recorder is not None:
        recorder.clear()      # drop warm-up/capacity events: the trace (and
        #                       its audit) covers exactly the headline replay
    cont = drive_continuous(engine, workload)
    fixed = drive_fixed(
        model, params, mesh,
        ServeConfig(batch_size=slots, max_seq=prompt_hi + new_hi,
                    max_new_tokens=new_hi),
        prompt_pad=prompt_hi, workload=workload)

    speedup = cont["tokens_per_s"] / max(1e-9, fixed["tokens_per_s"])
    if verbose:
        print(f"fixed      : {fixed['tokens_per_s']:8.1f} tok/s | "
              f"p50 {fixed['latency_p50_s']:6.2f}s  p95 {fixed['latency_p95_s']:6.2f}s | "
              f"{fixed['done']} reqs")
        print(f"continuous : {cont['tokens_per_s']:8.1f} tok/s | "
              f"p50 {cont['latency_p50_s']:6.2f}s  p95 {cont['latency_p95_s']:6.2f}s | "
              f"ttft p50 {cont['ttft_p50_s']:.2f}s | slot occ "
              f"{cont['slot_occupancy']:.0%} | cache occ {cont['cache_occupancy']:.0%}")
        print(f"chunk lane : fill {cont['chunk_fill_frac']:.0%} | packed segs "
              f"{cont['packed_segments']} | decode-only steps "
              f"{cont['decode_only_steps']}")
        print(f"continuous-batching speedup: {speedup:.2f}x tokens/s "
              f"(target >= 1.3x at equal-or-better p95: "
              f"{'PASS' if speedup >= 1.3 and cont['latency_p95_s'] <= fixed['latency_p95_s'] else 'MISS'})")
    out = {"fixed": fixed, "continuous": cont, "speedup": speedup}
    if recorder is not None:
        metadata = {
            "usable_blocks": engine.kv_cfg.num_blocks - 1,
            "block_size": engine.kv_cfg.block_size,
            "max_slots": rcfg.max_slots,
            "chunk_width": engine._chunk_width,
            "chunk_segments": engine._chunk_segments,
            "mesh": engine.mesh_tag,
            "layouts": _layout_summary(engine.router),
            "requests": requests, "seed": seed,
        }
        write_trace(trace_path, recorder.events, metrics=engine.metrics,
                    metadata=metadata)
        report = traceview.audit(recorder.events, metrics=engine.metrics,
                                 metadata=metadata)
        out["trace_audit_ok"] = report.ok
        if verbose:
            print(f"--- trace: {len(recorder.events)} events -> {trace_path} "
                  "(Chrome trace-event JSON; open in ui.perfetto.dev) ---")
            print("per-request time attribution (from trace events):")
            print(traceview.format_attribution(report.lifecycles))
            print(report.summary())
    if sampled:
        if verbose:
            print("--- sampled differential (mixed-sampling prefix vs the "
                  "B=1 fixed drain; keyed streams must match bytewise) ---")
        out["sampled"] = sampled_differential(
            model, params, mesh, cfg, rcfg,
            workload, n=min(sampled_requests, requests), verbose=verbose)
    if packing:
        if verbose:
            print("--- segment-packing sweep (short-prompt-heavy Poisson "
                  "mix; packed vs single-segment chunking; virtual clock) ---")
        out["packing"] = packing_sweep(model, params, mesh, cfg, rcfg,
                                       requests=packing_requests, seed=seed,
                                       verbose=verbose)
    if prefix:
        if verbose:
            print("--- prefix-sharing sweep (hot shared system prompt; "
                  "sharing on vs off; virtual clock) ---")
        out["prefix"] = prefix_sweep(model, params, mesh, cfg, rcfg,
                                     requests=prefix_requests, seed=seed,
                                     verbose=verbose)
    if interference:
        if verbose:
            print("--- prefill-interference sweep (long/short Poisson mix; "
                  "chunked vs unchunked prefill; virtual clock) ---")
        out["interference"] = interference_sweep(
            model, params, mesh, cfg, rcfg,
            requests=interference_requests, seed=seed, verbose=verbose)
    if pressure:
        if verbose:
            print("--- pool-pressure sweep (same Poisson workload; pool "
                  "shrunk vs worst-case demand; preemption + swap) ---")
        out["pressure"] = pressure_sweep(model, params, mesh, cfg, rcfg,
                                         workload, verbose=verbose)
    if lanes:
        if verbose:
            print("--- stage-matmul lane breakdown (same Poisson workload; "
                  "Pallas lanes run in interpret mode on CPU) ---")
        out["lanes"] = lane_breakdown(model, params, mesh, cfg, rcfg,
                                      workload[:lane_requests], verbose=verbose)
    if tp:
        if verbose:
            print("--- tensor-parallel mesh sweep (same workload at mesh "
                  "1x1/1x2/1x4; tuned layouts vs replicated) ---")
        out["tp"] = tp_sweep(model, params, cfg, rcfg,
                             workload[:tp_requests], verbose=verbose)
    return out


# ------------------------------------------------------ ssm family scenario
def bench_ssm(requests: int = 16, slots: int = 3, seed: int = 0,
              rate_hz: float = 0.0, verbose: bool = True,
              sampling: str = "greedy", trace_path: str = None) -> dict:
    """Mamba2 through the SAME continuous scheduler (`--family ssm`).

    The `SSMFamilyAdapter` swaps the paged KV pool for the fixed-size
    `SlotStateCache` (one conv+SSM state row per in-flight request) while
    the orchestration loop, scheduler, metrics and trace taxonomy stay
    exactly the decoder's.  The state pool is provisioned one row SHORT
    of the slot count (`state_slots = slots`, usable = slots - 1), so the
    replay exercises slot preemption + host swap on state rows the way
    the decoder's pool-pressure sweep does on KV blocks.  Reference: the
    same Poisson workload drained arrival-aware through the
    `FixedBatchEngine` (whole-prompt prefill, full worst-case budget)."""
    cfg = get_config("mamba2-2.7b").reduced(n_layers=2)
    model = build_model(cfg)
    mesh = single_device_mesh()
    with mesh:
        params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(seed)

    q = cfg.ssm_chunk
    prompt_pad, new_hi = 3 * q, 12
    rcfg = RuntimeConfig(max_slots=slots, chunk_tokens=q,
                         max_new_tokens=new_hi, state_slots=slots)
    recorder = TraceRecorder() if trace_path else None
    engine = ContinuousEngine(model, params, mesh, DEFAULT_RULES, rcfg,
                              trace=recorder)
    assert engine.family == "ssm", engine.family
    warm_engine(engine, cfg.vocab, q)

    # Sustained (post-compile) capacity -> arrival rate, as the decoder does.
    t0 = time.perf_counter()
    burst = 3 * slots
    for _ in range(burst):
        engine.submit(rng.integers(0, cfg.vocab, size=q).astype(np.int32),
                      max_new_tokens=8)
    engine.run()
    cap_tok_s = (burst * 8) / (time.perf_counter() - t0)
    engine.reset_metrics()
    if rate_hz <= 0:
        rate_hz = max(0.1, 1.3 * cap_tok_s / ((2 + new_hi) / 2))
    if verbose:
        print(f"[ssm] sustained decode capacity ~{cap_tok_s:,.0f} tok/s -> "
              f"Poisson rate {rate_hz:.2f} req/s")

    # Arbitrary prompt lengths: the chunk lane pads ragged tails with
    # zero-dt rows; the fixed drain left-pads to `prompt_pad` (a multiple
    # of the SSD scan chunk, which whole-prompt prefill requires).
    workload = make_workload(rng, requests, cfg.vocab, rate_hz,
                             prompt_lo=4, prompt_hi=prompt_pad,
                             new_lo=2, new_hi=new_hi)
    if sampling == "mixed":
        for i, w in enumerate(workload):
            w["sampling"] = mixed_sampling(i)
    if recorder is not None:
        recorder.clear()      # the trace covers exactly the headline replay
    cont = drive_continuous(engine, workload)
    s = engine.metrics.summary()
    fixed = drive_fixed(
        model, params, mesh,
        ServeConfig(batch_size=slots, max_seq=prompt_pad + new_hi,
                    max_new_tokens=new_hi),
        prompt_pad=prompt_pad, workload=workload)
    speedup = cont["tokens_per_s"] / max(1e-9, fixed["tokens_per_s"])
    errors = requests - cont["done"]
    out = {"fixed": fixed, "continuous": cont, "speedup": speedup,
           "preemptions": int(s["preemptions"]),
           "ttft_p95_s": s["ttft_p95_s"], "errors": errors}
    if verbose:
        print(f"[ssm] fixed      : {fixed['tokens_per_s']:8.1f} tok/s | "
              f"p95 {fixed['latency_p95_s']:6.2f}s | {fixed['done']} reqs")
        print(f"[ssm] continuous : {cont['tokens_per_s']:8.1f} tok/s | "
              f"p95 {cont['latency_p95_s']:6.2f}s | "
              f"ttft p95 {s['ttft_p95_s']:.2f}s | "
              f"preemptions {out['preemptions']} | slot occ "
              f"{cont['slot_occupancy']:.0%} | state occ "
              f"{cont['cache_occupancy']:.0%}")
        print(f"[ssm] continuous-batching speedup: {speedup:.2f}x tokens/s | "
              f"errors {errors} "
              f"({'PASS' if errors == 0 else 'FAIL'}: continuous completes "
              "the full workload)")
    if recorder is not None:
        metadata = {
            # one state row per request: the pool audit replays slot
            # alloc/free as 1-block events against the usable row count
            "usable_blocks": engine.cache.cfg.usable,
            "block_size": 1,
            "max_slots": rcfg.max_slots,
            "chunk_width": engine._chunk_width,
            "chunk_segments": engine._chunk_segments,
            "family": "ssm",
            "mesh": engine.mesh_tag,
            "requests": requests, "seed": seed,
        }
        write_trace(trace_path, recorder.events, metrics=engine.metrics,
                    metadata=metadata)
        report = traceview.audit(recorder.events, metrics=engine.metrics,
                                 metadata=metadata)
        out["trace_audit_ok"] = report.ok
        if verbose:
            print(f"--- trace: {len(recorder.events)} events -> {trace_path} "
                  "(Chrome trace-event JSON; open in ui.perfetto.dev) ---")
            print("per-request time attribution (from trace events):")
            print(traceview.format_attribution(report.lifecycles))
            print(report.summary())
    return out


# -------------------------------------------------------------- CSV schema
# The harness CSV contract (benchmarks/run.py prints `name,us_per_call,
# derived`).  Rows used to be ad-hoc tuples appended in run(); the schema —
# column count/types AND the exact row names run() emits — is now pinned so
# dashboard/trajectory parsers can't silently break when rows are added.
# Extending the bench means extending `expected_csv_names()` AND its
# snapshot test (tests/test_trace.py) in the same change.
CSV_COLUMNS = ("name", "value", "derived")

PACKING_LABELS = ("packed", "single-seg")
PREFIX_LABELS = ("on", "off")
INTERFERENCE_LABELS = ("chunked", "unchunked")
PRESSURE_FACTORS = (1.0, 0.5, 0.25)
LANE_LABELS = ("xla-only", "tuned plan", "forced pallas")


def csv_row(name: str, value, derived: str = "") -> tuple:
    """Build one schema-conforming CSV row: (str name, float value,
    str derived).  Loud on drift — a non-numeric value or empty name is a
    bug in the bench, not a formatting detail for the parser to absorb."""
    if not isinstance(name, str) or not name:
        raise ValueError(f"CSV row name must be a non-empty str: {name!r}")
    return (name, float(value), str(derived))


def expected_csv_names(sampled: bool = True, packing: bool = True,
                       prefix: bool = True, interference: bool = True,
                       pressure: bool = True, lanes: bool = True,
                       ssm: bool = True, tp: bool = True) -> list:
    """The exact, ordered row names run() appends — the pinned schema."""
    names = ["serve_fixed_tok_s", "serve_continuous_tok_s",
             "serve_speedup_x", "serve_chunk_fill_frac"]
    if sampled:
        names += ["serve_sampled_tok_s", "serve_sampled_mismatches"]
    if packing:
        names += [f"serve_packing_{l.replace('-', '_')}_tok_s"
                  for l in PACKING_LABELS]
    if prefix:
        names += [f"serve_prefix_{l}_tok_s" for l in PREFIX_LABELS]
    if interference:
        names += [f"serve_interference_{l}_decode_tbt_p95_s"
                  for l in INTERFERENCE_LABELS]
    if pressure:
        names += [f"serve_pool_{f:.2f}x_tok_s" for f in PRESSURE_FACTORS]
    if lanes:
        names += [f"serve_lane_{l.replace(' ', '_')}_tok_s"
                  for l in LANE_LABELS]
    if ssm:
        names += ["serve_ssm_fixed_tok_s", "serve_ssm_continuous_tok_s",
                  "serve_ssm_speedup_x", "serve_ssm_preemptions"]
    if tp:
        names += [f"serve_tp_mesh{w}_tok_s" for w in TP_WIDTHS]
        names += ["serve_tp_tuned_tok_s", "serve_tp_replicated_tok_s"]
    return names


def run(csv_rows):
    """benchmarks.run harness entry."""
    r = bench(requests=24, slots=4, verbose=False, lane_requests=8)
    start = len(csv_rows)
    csv_rows.append(csv_row("serve_fixed_tok_s",
                            r["fixed"]["tokens_per_s"]))
    csv_rows.append(csv_row("serve_continuous_tok_s",
                            r["continuous"]["tokens_per_s"],
                            f"p95={r['continuous']['latency_p95_s']:.2f}s"))
    csv_rows.append(csv_row("serve_speedup_x", r["speedup"],
                            "continuous vs fixed, same Poisson workload"))
    csv_rows.append(csv_row("serve_chunk_fill_frac",
                            r["continuous"]["chunk_fill_frac"],
                            f"packed_segments="
                            f"{r['continuous']['packed_segments']} "
                            f"decode_only_steps="
                            f"{r['continuous']['decode_only_steps']}"))
    sd = r.get("sampled", {})
    csv_rows.append(csv_row("serve_sampled_tok_s", sd["tokens_per_s"],
                            f"sampled={sd['sampled_requests']}/"
                            f"{sd['requests']} mixed cycle"))
    csv_rows.append(csv_row("serve_sampled_mismatches", sd["mismatches"],
                            "keyed streams vs B=1 drain (must be 0)"))
    for label, pr in r.get("packing", {}).items():
        csv_rows.append(csv_row(
            f"serve_packing_{label.replace('-', '_')}_tok_s",
            pr["tokens_per_s"],
            f"ttft_p95={pr['ttft_p95_s']:.2f} "
            f"fill={pr['chunk_fill_frac']:.2f} "
            f"packed_segments={pr['packed_segments']} "
            f"decode_only={pr['decode_only_steps']} virtual-clock"))
    for label, xr in r.get("prefix", {}).items():
        csv_rows.append(csv_row(
            f"serve_prefix_{label}_tok_s", xr["tokens_per_s"],
            f"ttft_p95={xr['ttft_p95_s']:.2f} "
            f"chunk_tokens={xr['chunk_tokens_committed']} "
            f"prefix_hits={xr['prefix_hit_tokens']} "
            f"cow={xr['cow_copies']} virtual-clock"))
    for label, ir in r.get("interference", {}).items():
        csv_rows.append(csv_row(
            f"serve_interference_{label}_decode_tbt_p95_s",
            ir["decode_tbt_p95_s"],
            f"tbt_max={ir['decode_tbt_max_s']:.2f} "
            f"long_ttft_p95={ir['long_ttft_p95_s']:.2f} "
            f"chunks={ir['chunks']} virtual-clock"))
    for f, pr in r.get("pressure", {}).items():
        csv_rows.append(csv_row(
            f"serve_pool_{f:.2f}x_tok_s", pr["tokens_per_s"],
            f"preemptions={pr['preemptions']} swap_mb={pr['swap_mb']:.2f} "
            f"swap_in_s={pr['swap_in_time_s']:.3f} errors={pr['errors']}"))
    for label, lr in r.get("lanes", {}).items():
        lanes = ",".join(f"{k}:{v}" for k, v in sorted(lr["lanes"].items()))
        csv_rows.append(csv_row(
            f"serve_lane_{label.replace(' ', '_')}_tok_s",
            lr["tokens_per_s"], lanes or "no plan (all xla)"))
    sr = bench_ssm(requests=8, slots=3, verbose=False)
    csv_rows.append(csv_row("serve_ssm_fixed_tok_s",
                            sr["fixed"]["tokens_per_s"]))
    csv_rows.append(csv_row("serve_ssm_continuous_tok_s",
                            sr["continuous"]["tokens_per_s"],
                            f"ttft_p95={sr['ttft_p95_s']:.2f}s "
                            f"errors={sr['errors']}"))
    csv_rows.append(csv_row("serve_ssm_speedup_x", sr["speedup"],
                            "mamba2 continuous vs fixed, same Poisson "
                            "workload"))
    csv_rows.append(csv_row("serve_ssm_preemptions", sr["preemptions"],
                            "state pool one row short of slots"))
    # TP sweep rows: a fixed schema whatever the host's device count — a
    # single-device harness emits 0.0 with a "skipped" derived note, the
    # CI mesh-smoke job (4 virtual devices) emits real numbers
    tpr = r.get("tp", {})
    skipped = (f"skipped: {tpr.get('devices', 1)} host device(s)"
               if tpr.get("skipped", True) else "")
    for w in TP_WIDTHS:
        tr = tpr.get(w)
        csv_rows.append(csv_row(
            f"serve_tp_mesh{w}_tok_s",
            0.0 if tr is None else tr["tokens_per_s"],
            skipped if tr is None else
            f"ttft_p95={tr['ttft_p95_s']:.2f} layouts={tr['layouts']}"))
    for leg in ("tuned", "replicated"):
        tr = tpr.get(leg)
        csv_rows.append(csv_row(
            f"serve_tp_{leg}_tok_s",
            0.0 if tr is None else tr["tokens_per_s"],
            skipped if tr is None else
            f"mesh={tr['mesh']} ttft_p95={tr['ttft_p95_s']:.2f} "
            f"layouts={tr['layouts']}"))
    got = [row[0] for row in csv_rows[start:]]
    if got != expected_csv_names():
        raise AssertionError(
            "bench_serving CSV schema drifted from expected_csv_names(): "
            f"{got}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--family", choices=("decoder", "ssm"), default="decoder",
                    help="model family behind the continuous scheduler: "
                         "decoder (paged KV blocks) or ssm (Mamba2, "
                         "slot-pooled state rows; implies a zero-errors "
                         "guard and skips the decoder-only sweeps)")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rate", type=float, default=0.0,
                    help="Poisson arrival rate (req/s); 0 = auto from capacity")
    ap.add_argument("--no-lanes", action="store_true",
                    help="skip the stage-matmul per-lane plan breakdown")
    ap.add_argument("--lane-requests", type=int, default=12,
                    help="workload prefix replayed per lane in the breakdown")
    ap.add_argument("--no-pressure", action="store_true",
                    help="skip the pool-pressure (preemption) sweep")
    ap.add_argument("--no-interference", action="store_true",
                    help="skip the prefill-interference (chunking) sweep")
    ap.add_argument("--interference-requests", type=int, default=24,
                    help="requests in the long/short interference mix")
    ap.add_argument("--no-packing", action="store_true",
                    help="skip the segment-packing sweep")
    ap.add_argument("--packing-requests", type=int, default=24,
                    help="requests in the short-prompt packing mix")
    ap.add_argument("--no-prefix", action="store_true",
                    help="skip the prefix-sharing sweep")
    ap.add_argument("--prefix-requests", type=int, default=24,
                    help="requests in the shared-system-prompt mix")
    ap.add_argument("--require-prefix-hits", action="store_true",
                    help="exit non-zero unless the sharing-on replay "
                         "adopted prompt tokens from the prefix index "
                         "(CI guard)")
    ap.add_argument("--sampling", choices=("greedy", "mixed"),
                    default="greedy",
                    help="per-request sampling on the headline workload: "
                         "greedy (default, temperature 0 everywhere) or "
                         "mixed (a fixed cycle of greedy / temperature / "
                         "top-k / top-p with per-request seeds; same two "
                         "compiled step programs — knobs are traced data)")
    ap.add_argument("--no-sampled", action="store_true",
                    help="skip the sampled differential (mixed-sampling "
                         "replay pinned byte-identical against the B=1 "
                         "fixed drain; mismatches exit non-zero)")
    ap.add_argument("--sampled-requests", type=int, default=12,
                    help="workload prefix replayed in the sampled "
                         "differential")
    ap.add_argument("--devices", type=int, default=None,
                    help="virtual host devices for the CPU backend "
                         "(applied by repro.platform BEFORE the jax import "
                         "at the top of this file; the TP sweep needs 4)")
    ap.add_argument("--no-tp", action="store_true",
                    help="skip the tensor-parallel mesh sweep")
    ap.add_argument("--tp-requests", type=int, default=12,
                    help="workload prefix replayed per mesh width in the "
                         "TP sweep")
    ap.add_argument("--require-decode-only", action="store_true",
                    help="exit non-zero unless the headline continuous run "
                         "dispatched the decode-only fast path (CI guard)")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="record the headline continuous run's event trace "
                         "to PATH (Chrome-trace JSON, opens in "
                         "ui.perfetto.dev; audited against ServeMetrics)")
    args = ap.parse_args()
    platform.configure_compile_cache()
    if args.family == "ssm":
        result = bench_ssm(args.requests, args.slots, args.seed, args.rate,
                           sampling=args.sampling, trace_path=args.trace)
        if args.trace and not result.get("trace_audit_ok", False):
            print("trace audit: FAIL — event trace disagrees with "
                  "ServeMetrics")
            raise SystemExit(1)
        if result["errors"]:
            print(f"zero-errors guard: FAIL — {result['errors']} requests "
                  "never completed")
            raise SystemExit(1)
        raise SystemExit(0)
    result = bench(args.requests, args.slots, args.seed, args.rate,
                   lanes=not args.no_lanes, lane_requests=args.lane_requests,
                   pressure=not args.no_pressure,
                   interference=not args.no_interference,
                   interference_requests=args.interference_requests,
                   packing=not args.no_packing,
                   packing_requests=args.packing_requests,
                   prefix=not args.no_prefix,
                   prefix_requests=args.prefix_requests,
                   sampling=args.sampling, sampled=not args.no_sampled,
                   sampled_requests=args.sampled_requests,
                   tp=not args.no_tp, tp_requests=args.tp_requests,
                   trace_path=args.trace)
    if args.trace and not result.get("trace_audit_ok", False):
        print("trace audit: FAIL — event trace disagrees with ServeMetrics")
        raise SystemExit(1)
    sd = result.get("sampled")
    if sd is not None and (sd["mismatches"] or sd["done"] < sd["requests"]):
        print(f"sampled differential: FAIL — {sd['mismatches']} stream "
              f"mismatches, {sd['done']}/{sd['requests']} completed")
        raise SystemExit(1)
    if args.require_prefix_hits:
        px = result.get("prefix", {}).get("on", {})
        hits = px.get("prefix_hit_tokens", 0)
        if hits == 0:
            print("prefix-sharing guard: FAIL — the sharing-on replay "
                  "never adopted a prompt token from the prefix index")
            raise SystemExit(1)
        print(f"prefix-sharing guard: PASS ({hits} prefix-hit tokens, "
              f"{px.get('cow_copies', 0)} CoW copies)")
    if args.require_decode_only:
        n = result["continuous"]["decode_only_steps"]
        if n == 0:
            print("decode-only guard: FAIL — the headline continuous run "
                  "never dispatched the decode-only fast path")
            raise SystemExit(1)
        print(f"decode-only guard: PASS ({n} decode-only steps)")
