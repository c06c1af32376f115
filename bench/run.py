"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (`workloads` of BENCHMARK.json) names a model configuration
(`bench/configs/<config>.json`) and a traffic mix
(`bench/traffic/<traffic>.json`).  One run:

  1. fails at once, printing no result, where JAX finds no TPU or fewer
     chips than the cell asks for;
  2. keeps JAX's compilation cache in `.jax_cache/` of the checkout
     (`repro.platform.configure_compile_cache`);
  3. makes the bfloat16 weights on the device from --seed, in one jitted
     call, in the layout the engine serves them in;
  4. selects the serve plan the system selects for the cell's shapes
     (`build_serve_plan`, the tuner's seed and budget fixed in the
     configuration file);
  5. builds `ContinuousEngine` on the cell's mesh and warms its two step
     programs with one short request;
  6. serves the traffic — submitting each request once it is due, with
     `arrival_time` its due time, and stepping the engine itself — through
     a warm-up into the measured window of --seconds;
  7. checks what the window served against the plain float32 reference
     the configuration names (`bench/modules.py`; by default
     `bench/reference.py`);
  8. prints the cell's metrics as the last line of standard output: its
     end-to-end metrics with --trace 0, its per-layer metrics, read from a
     profiler trace of a slice of the window, with --trace 1 (the
     profiler is started once in set-up, so that its slow first start
     falls outside the window).

Everything that belongs to one configuration, traffic mix or metric lives
in a file of its own, found by name: configs/, traffic/, e2e/ and layers/.
A configuration file names its program preset and fields (`program`),
and may name its reference and its work counts (`bench/modules.py`).
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional, Tuple  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import modules  # noqa: E402
import traffic as traffic_mod  # noqa: E402

# the module each kind of engine step runs
MODULES = {"unified": "jit_unified_step",
           "decode_only": "jit_decode_only_step"}
TRACE_SECONDS = 3.0     # the traced slice: the window's last seconds


# ------------------------------------------------------------ definitions
def benchmark() -> Dict:
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def cell_of(bench: Dict, name: str) -> Dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"run.py: no workload {name!r} in BENCHMARK.json")


def load_config(name: str) -> Dict:
    with open(BENCH / "configs" / f"{name}.json") as f:
        return json.load(f)


def load_peaks(kind: str) -> Dict:
    with open(BENCH / "peaks.json") as f:
        peaks = json.load(f)
    if kind not in peaks or kind == "source":
        raise SystemExit(f"run.py: no peaks for device kind {kind!r} in "
                         "bench/peaks.json")
    return peaks[kind]


def reader(kind: str, name: str):
    """The `read(run)` function of bench/<kind>/<name>.py."""
    return modules.load(f"{kind}/{name}").read


def metrics_for(bench: Dict, cell: str, section: str) -> List[Dict]:
    """The metrics of `section` the cell reports: those listing it, and
    those with no list whose end-to-end metric the cell reports."""
    e2e = {m["name"] for m in bench["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]}
    out = []
    for m in bench[section]:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif section == "end_to_end" or m["moves"] in e2e:
            out.append(m)
    return out


# ------------------------------------------------------------ run records
@dataclasses.dataclass
class Req:
    due: float                      # host clock
    prompt_len: int
    output_len: int
    rid: Optional[int] = None
    admitted: Optional[float] = None
    token_times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    finished: Optional[float] = None
    submitted: Optional[float] = None
    failed: bool = False


@dataclasses.dataclass
class Step:
    idx: int
    t_begin: float
    t_end: float
    kind: str                       # "unified" | "decode_only"
    decode_keys: List[int]          # keys each decoded token attended to
    segments: List[Tuple[int, int, bool]]   # (start, n, completes)
    new_tokens: int                 # output tokens emitted
    kv_blocks: int = 0              # pool blocks the live requests fill


@dataclasses.dataclass
class Run:
    """What the metric readers read."""
    cell: Dict
    config: Dict
    mix: Dict
    chips: int
    peaks: Dict
    seconds: float
    t0: float                       # window bounds, host clock
    t1: float
    setup_s: float
    requests: List[Req]
    steps: List[Step]
    trace: Optional[object] = None  # trace_reduce.Trace of the slice
    step_device: Optional[Dict[int, Tuple[str, float]]] = None

    @property
    def window_steps(self) -> List[Step]:
        return [s for s in self.steps if self.t0 < s.t_end <= self.t1]

    @property
    def traced_steps(self) -> List[Step]:
        if not self.step_device:
            return []
        return [s for s in self.steps if s.idx in self.step_device]


# ---------------------------------------------------------------- set-up
# the program's ModelConfig field for each published size (HF key)
PROGRAM_SIZES = {"num_hidden_layers": ("n_layers", int),
                 "hidden_size": ("d_model", int),
                 "num_attention_heads": ("n_heads", int),
                 "num_key_value_heads": ("n_kv_heads", int),
                 "head_dim": ("head_dim", int),
                 "intermediate_size": ("d_ff", int),
                 "vocab_size": ("vocab", int),
                 "rope_theta": ("rope_theta", float),
                 "torch_dtype": ("dtype", str)}


def program_config(cfg: Dict):
    """The program's ModelConfig for a configuration file: the registry
    preset `program.arch`, with the published sizes the file gives
    (`PROGRAM_SIZES`), then every further field of its `program` section.
    Whatever else the block is (q/k norm, experts, ...) is the preset's."""
    from repro.configs import get_config

    fields = dict(cfg["program"])
    base = get_config(fields.pop("arch"))
    sizes = {field: cast(cfg[key])
             for key, (field, cast) in PROGRAM_SIZES.items() if key in cfg}
    return dataclasses.replace(base, **{**sizes, **fields})


def seed_key(seed: int):
    import jax

    key = jax.random.key(0)
    key = jax.random.fold_in(key, np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32((seed >> 32) & 0xFFFFFFFF))


def make_weights(model, shardings, seed: int, dtype: str):
    """Random weights from the seed, made on the device in one jitted call
    in the serving dtype and layout: norm scales 1, embeddings N(0, 1),
    every matrix N(0, 1/fan_in).  Only the tree's names and shapes come
    from the program."""
    import jax
    import jax.numpy as jnp

    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    dt = jnp.dtype(dtype)

    def init(key):
        out = []
        for i, (path, leaf) in enumerate(flat):
            name = jax.tree_util.keystr(path)
            k = jax.random.fold_in(key, i)
            if "norm" in name:
                out.append(jnp.ones(leaf.shape, dt))
            elif name.endswith("['emb']"):
                out.append(jax.random.normal(k, leaf.shape, dt))
            else:
                fan_in = leaf.shape[-2]
                out.append((jax.random.normal(k, leaf.shape, jnp.float32)
                            / math.sqrt(fan_in)).astype(dt))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(init, out_shardings=shardings)(seed_key(seed))


def runtime_config(cfg: Dict):
    from repro.serve import RuntimeConfig

    rt = cfg["runtime"]
    return RuntimeConfig(
        max_slots=rt["max_slots"], block_size=rt["block_size"],
        max_blocks_per_seq=rt["max_context"] // rt["block_size"],
        num_blocks=rt.get("num_blocks"), chunk_tokens=rt["chunk_tokens"],
        chunk_segments=rt["chunk_segments"], eos_id=-1,
        prefix_sharing=False)


def serve_plan(cfg: Dict, mcfg, rcfg, chip):
    """The plan the system selects for the cell's shapes; None (the XLA
    lanes) where the configuration sets no plan."""
    from repro.core.search.tuner import Tuner
    from repro.serve import build_serve_plan

    p = cfg["plan"]
    if p is None:
        return None
    return build_serve_plan(
        mcfg, prefill_len=rcfg.chunk_width, slots=rcfg.max_slots,
        max_seq=rcfg.max_seq, chunk_tokens=rcfg.chunk_width, chip=chip,
        tuner=Tuner(chip=chip, methods=(p["method"],),
                    random_budget=p["budget"], seed=p["seed"]),
        model_parallel=cfg["mesh"]["model"])


def make_mesh(cfg: Dict):
    from repro.launch.mesh import single_device_mesh, tp_mesh

    m = cfg["mesh"]["model"]
    return single_device_mesh() if m == 1 else tp_mesh(m)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------- serving
class Server:
    """Drives the engine: submits due requests, steps it, and turns the
    engine's trace events into per-request token times and step records."""

    def __init__(self, engine, recorder, mesh, reqs: List[Req],
                 prompts: List[np.ndarray], block_size: int):
        self.engine, self.rec, self.mesh = engine, recorder, mesh
        self.reqs, self.prompts = reqs, prompts
        self.block_size = block_size
        self.by_rid: Dict[int, Req] = {}
        self.prefilled: Dict[int, int] = {}     # prompt rows committed
        self.next = 0
        self.steps: List[Step] = []
        self.trace_dir = None
        self.window_ann = None

    def submit_due(self, now: float) -> None:
        while self.next < len(self.reqs) and self.reqs[self.next].due <= now:
            r = self.reqs[self.next]
            r.submitted = now
            try:
                r.rid = self.engine.submit(
                    self.prompts[self.next], max_new_tokens=r.output_len,
                    arrival_time=r.due)
                self.by_rid[r.rid] = r
            except ValueError as e:
                log(f"request {self.next} refused: {e}")
                r.failed = True
            self.next += 1

    def step(self, clock) -> bool:
        import jax

        idx = len(self.steps)
        t_begin = clock()
        with jax.profiler.TraceAnnotation("bench_step", idx=idx):
            ran = self.engine.step()
        t_end = clock()
        kind, segs, keys, new = None, [], [], 0
        for ev in self.rec.events:
            r = self.by_rid.get(ev.rid)
            if ev.name == "admit" and r is not None:
                r.admitted = ev.t
            elif ev.name == "chunk_scheduled":
                n = ev.fields["n"]
                start = ev.fields["start"]
                segs.append((start, n, start + n == r.prompt_len))
                self.prefilled[ev.rid] = start + n
            elif ev.name == "step_begin":
                kind = ev.fields["kind"]
            elif ev.name == "first_token":
                r.tokens.append(ev.fields["token"])
                r.token_times.append(t_end)
                new += 1
            elif ev.name == "decode_token":
                keys.append(r.prompt_len + len(r.tokens))
                r.tokens.append(ev.fields["token"])
                r.token_times.append(t_end)
                new += 1
            elif ev.name == "finish":
                r.finished = t_end
                self.prefilled.pop(ev.rid, None)
        self.rec.clear()
        if kind is not None:
            bs = self.block_size
            blocks = sum(-(-(n + len(self.by_rid[rid].tokens)) // bs)
                         for rid, n in self.prefilled.items())
            self.steps.append(Step(idx, t_begin, t_end, kind, keys, segs,
                                   new, blocks))
        return ran

    def start_trace(self) -> None:
        import jax

        self.trace_dir = tempfile.TemporaryDirectory(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.trace_dir.name, profiler_options=opts)
        self.window_ann = jax.profiler.TraceAnnotation("bench_window")
        self.window_ann.__enter__()

    def close_slice(self) -> None:
        if self.window_ann is not None:
            self.window_ann.__exit__(None, None, None)
            self.window_ann = None

    def stop_trace(self) -> None:
        """Stop the profiler; it pauses the host for seconds, so this runs
        only once the window has closed."""
        import jax

        self.close_slice()
        jax.profiler.stop_trace()

    def read_trace(self):
        """The stopped trace, reduced; read once the window has closed."""
        import trace_reduce

        paths = sorted(Path(self.trace_dir.name).rglob("*.xplane.pb"))
        if not paths:
            raise RuntimeError("the profiler wrote no .xplane.pb")
        tr = trace_reduce.load(paths[-1])
        self.trace_dir.cleanup()
        return tr


def warm_profiler() -> None:
    """Start and stop the profiler once in set-up: its first start takes
    seconds, which would otherwise stall the window."""
    import jax

    with tempfile.TemporaryDirectory(prefix="bench_trace_") as d:
        jax.profiler.start_trace(d)
        jax.profiler.stop_trace()


def serve(server: Server, mix: Dict, seconds: float, origin: float,
          trace: bool, clock) -> Tuple[float, float, bool]:
    """Serve until the window has closed; returns (t0, t1, traced)."""
    backlog = mix["arrival"]["process"] == "backlog"
    warm_end = origin + mix["warmup_s"]
    t0 = None if backlog else warm_end
    t1 = None if backlog else warm_end + seconds
    traced = False
    with server.mesh:
        while True:
            now = clock()
            server.submit_due(now)
            if backlog and t0 is None and now >= warm_end:
                t0 = now
                t1 = t0 + seconds
            if t0 is not None and trace and not traced \
                    and now >= t1 - TRACE_SECONDS:
                t = clock()
                server.start_trace()
                traced = True
                log(f"profiler start: {clock() - t:.2f} s")
            if t1 is not None and now >= t1:
                server.close_slice()
                if backlog or all(r.tokens or r.failed for r in server.reqs
                                  if t0 <= r.due < t1):
                    break
                if now >= t1 + mix["tail_s"]:
                    break
            if not server.step(clock):
                nxt = (server.reqs[server.next].due
                       if server.next < len(server.reqs) else now + 1e-3)
                time.sleep(min(max(nxt - clock(), 0.0), 1e-3))
    if backlog:
        # the window closes with the step that crossed t1
        t1 = max(s.t_end for s in server.steps if s.t_begin < t1)
    if traced:
        t = clock()
        server.stop_trace()
        log(f"profiler stop, after the window: {clock() - t:.2f} s")
    return t0, t1, traced


# ------------------------------------------------------------ correctness
def check(cfg: Dict, params, run: Run, prompts: Dict[int, np.ndarray],
          seed: int, control: bool = False) -> Tuple[Dict, Optional[Dict]]:
    """Compare the served tokens of a sample of finished requests with the
    reference the configuration names (`modules.of_config`): the widest
    gap by which a served token's reference logit lies below the
    reference's best at its position.  Where the finished requests hold
    fewer than the sample's tokens (a backlog finishes few in a window),
    every request that was served a token is drawn from, with the tokens
    it was served.

    With `control`, also the same checks with the control in the program's
    place: at each position of the same prompts and served tokens, the
    token the reference computed in float8 puts first, read under the
    float32 reference."""
    shared = modules.load("reference")
    ref_mod = modules.of_config(cfg, "reference")
    chk = cfg["check"]
    reqs = run.requests
    pool = [i for i, r in enumerate(reqs) if r.finished is not None]
    if sum(len(reqs[i].tokens) for i in pool) < chk["sample_tokens"]:
        pool = [i for i, r in enumerate(reqs) if r.tokens]
    if not pool:
        return {"empty_sample": {"value": 1, "limit": 0}}, None
    rng = np.random.default_rng([seed, 2])
    longest = max(pool, key=lambda i: reqs[i].prompt_len
                  + len(reqs[i].tokens))
    pick, served = [longest], len(reqs[longest].tokens)
    for i in rng.permutation([i for i in pool if i != longest]):
        if served >= chk["sample_tokens"] or len(pick) >= chk["max_requests"]:
            break
        pick.append(int(i))
        served += len(reqs[int(i)].tokens)
    seqs, positions, toks = [], [], []
    bad_len = 0
    vocab = cfg["vocab_size"]
    for i in pick:
        r = reqs[i]
        out = np.asarray(r.tokens, np.int64)
        full = len(out) == r.output_len or r.finished is None
        if not full or len(out) > r.output_len or out.min() < 0 \
                or out.max() >= vocab:
            bad_len += 1
        out = np.clip(out, 0, vocab - 1)
        seqs.append(np.concatenate([prompts[i], out[:-1]]).astype(np.int32))
        positions.append(np.arange(r.prompt_len - 1,
                                   r.prompt_len - 1 + len(out)))
        toks.append(out)
    width = -(-max(len(s) for s in seqs) // shared.ROW_BLOCK) \
        * shared.ROW_BLOCK
    t = time.perf_counter()
    ref = ref_mod.logits_at(cfg, params, seqs, positions,
                            batch=chk["max_requests"], width=width)
    gaps = np.concatenate([shared.served_gaps(lg, tk)
                           for lg, tk in zip(ref, toks)])
    log(f"reference over {len(pick)} requests, {len(gaps)} served tokens, "
        f"width {width}: {time.perf_counter() - t:.1f} s")
    # a configuration with no calibrated limit reads not correct
    limit = chk["max_logit_gap"] or 0.0
    out = {"bad_streams": {"value": bad_len, "limit": 0},
           "max_logit_gap": {"value": float(gaps.max()), "limit": limit}}
    if not control:
        return out, None
    low = ref_mod.logits_at(cfg, params, seqs, positions,
                            batch=chk["max_requests"], width=width,
                            precision="fp8")
    cgaps = np.concatenate([shared.served_gaps(r_, lw.argmax(axis=1))
                            for r_, lw in zip(ref, low)])
    return out, dict(out, max_logit_gap={"value": float(cgaps.max()),
                                         "limit": limit})


def is_correct(checks: Dict, failed: int) -> bool:
    """No request failed and every compared number is within its limit."""
    return failed == 0 and all(v["value"] <= v["limit"]
                               for v in checks.values())


# ------------------------------------------------------------------- run
_COMPILES: List[float] = []


def compile_times() -> List[float]:
    """Host times at which a backend compilation ended, from one listener
    registered for the process."""
    import jax

    if not _COMPILES:
        _COMPILES.append(float("-inf"))
        jax.monitoring.register_event_duration_secs_listener(
            lambda name, secs, **_: _COMPILES.append(time.perf_counter())
            if name == "/jax/core/compile/backend_compile_duration" else None)
    return _COMPILES

def run_cell(cell: Dict, seed: int, seconds: float, trace: bool, *,
             bench: Optional[Dict] = None, config: Optional[Dict] = None,
             mix: Optional[Dict] = None, chip=None, control: bool = False,
             start: Optional[float] = None, keep_run: bool = False):
    """One run of `cell`; returns the result object (and the `Run` record
    with `keep_run`).  `config`, `mix` and `chip` replace the files and the
    chip found by device kind (tests run a small configuration on the
    CPU); `control` adds `control_checks` and `control_correct`, the same
    comparison with the control's tokens in the program's place."""
    import jax

    from repro import hw, platform
    from repro.distributed.sharding import (DEFAULT_RULES, params_shardings,
                                            prune_for_mesh)
    from repro.models import build_model
    from repro.serve import ContinuousEngine, PlanRouter
    from repro.serve.family import resolve_family_adapter
    from repro.serve.trace import TraceRecorder

    start = PROCESS_START if start is None else start
    clock = time.perf_counter
    bench = benchmark() if bench is None else bench
    cfg = load_config(cell["config"]) if config is None else config
    mix = traffic_mod.load(cell["traffic"]) if mix is None else mix
    devices = jax.devices()
    kind = devices[0].device_kind
    chip = hw.chip_for_kind(kind) if chip is None else chip
    peaks = load_peaks(kind) if jax.default_backend() == "tpu" else None

    platform.configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    mcfg = program_config(cfg)
    model = build_model(mcfg)
    rcfg = runtime_config(cfg)
    mesh = make_mesh(cfg)
    router = PlanRouter(serve_plan(cfg, mcfg, rcfg, chip))
    log(f"plan: {json.dumps(router.describe())}")
    rules = router.serve_rules(DEFAULT_RULES, mesh, mcfg,
                               resolve_family_adapter(model).family)
    shardings = params_shardings(mesh, prune_for_mesh(rules, mesh),
                                 model.logical_axes())
    t = clock()
    params = make_weights(model, shardings, seed, cfg["torch_dtype"])
    jax.block_until_ready(params)
    log(f"weights: {clock() - t:.2f} s")

    recorder = TraceRecorder()
    t = clock()
    engine = ContinuousEngine(model, params, mesh, DEFAULT_RULES, rcfg,
                              router=router, trace=recorder)
    # compile (or load) both step programs: one short request takes a
    # unified step for its prompt, then decode-only steps
    engine.submit(np.arange(1, 9, dtype=np.int32), max_new_tokens=3)
    engine.run()
    engine.reset_metrics()
    recorder.clear()
    log(f"engine and step programs: {clock() - t:.2f} s")

    compiles = compile_times()

    max_ctx = cfg["runtime"]["max_context"]
    sched = traffic_mod.schedule(mix, seconds, max_ctx)
    prompts = traffic_mod.prompt_tokens(sched, cfg["vocab_size"], seed)
    if trace:
        t = clock()
        warm_profiler()
        log(f"profiler warm-up: {clock() - t:.2f} s")
    origin = clock()
    reqs = [Req(origin + r.due_s, r.prompt_len, r.output_len) for r in sched]
    server = Server(engine, recorder, mesh, reqs, prompts,
                    rcfg.block_size)
    t0, t1, traced = serve(server, mix, seconds, origin, trace, clock)
    in_window = sum(t0 <= c <= t1 for c in compiles)
    ends = [s.t_end for s in server.steps if t0 <= s.t_end <= t1]
    if len(ends) > 1:
        gap, at = max((b - a, a) for a, b in zip(ends, ends[1:]))
        log(f"longest time between two step ends in the window: {gap:.3f} s, "
            f"{at - t0:.2f} s into it")
    log(f"window {t1 - t0:.3f} s from {t0 - start:.2f} s after start; "
        f"{len(server.steps)} steps; compilations in the window: "
        f"{in_window}; generator late by at most "
        f"{max(r.submitted - r.due for r in reqs if r.submitted):.4f} s")

    tr = server.read_trace() if traced else None
    run = Run(cell, cfg, mix, len(devices), peaks, seconds, t0, t1,
              t0 - start, reqs, server.steps)
    pool = rcfg.kv_config().num_blocks
    filled = [s.kv_blocks / pool for s in run.window_steps]
    if filled:
        log(f"pool filled in the window: mean {100 * np.mean(filled):.1f}%, "
            f"peak {100 * max(filled):.1f}% of {pool} blocks")
    if tr is not None:
        import trace_reduce

        run.trace = tr
        run.step_device = trace_reduce.step_device_s(
            tr, {s.idx: MODULES[s.kind] for s in server.steps})

    used = devices[:cfg["mesh"]["model"]]
    peak_mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in used)
    del engine, server
    gc.collect()

    checks, control_checks = check(cfg, params, run, dict(enumerate(prompts)),
                                   seed, control=control)
    backlog = mix["arrival"]["process"] == "backlog"
    if backlog:
        counted = [r for r in reqs if r.token_times
                   and r.token_times[0] <= t1]
    else:
        counted = [r for r in reqs if t0 <= r.due < t1]
    failed = sum(1 for r in counted if r.failed or not r.tokens)

    section = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in metrics_for(bench, cell["name"], section):
        value = reader("layers" if trace else "e2e", m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": int(peak_mem)}
    result = {"correct": is_correct(checks, failed),
              "attempted": len(counted), "failed": failed,
              "metrics": metrics, "device": device}
    if tr is not None:
        import trace_reduce

        device["busy_s"] = trace_reduce.busy_s(tr)
        device["window_s"] = trace_reduce.window_s(tr)
        result["breakdown"] = trace_reduce.breakdown(tr)
    if control_checks is not None:
        result["control_checks"] = control_checks
        result["control_correct"] = is_correct(control_checks, failed)
    result["checks"] = checks
    return (result, run) if keep_run else result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = benchmark()
    cell = cell_of(bench, args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"run.py: needs a TPU, but JAX's first device is "
                     f"{devices[0].platform!r}; there is no fallback")
    if len(devices) < cell["chips"]:
        raise SystemExit(f"run.py: {args.workload} needs {cell['chips']} chips; "
                     f"JAX sees {len(devices)}")
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      bench=bench)
    log(f"correct: {result['correct']}")
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
