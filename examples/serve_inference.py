"""End-to-end serving driver (the paper is an inference system).

Boots a small qwen3-family LM, briefly trains it on the synthetic pipeline
so decode produces the learnable next-token structure, then serves a queue
of requests through the continuous-batching runtime: WPK inference plan ->
plan-aware router -> slot scheduler -> paged KV-cache -> ONE jitted
unified step (token-budget chunked-prefill lane + the decode batch) that
requests join and leave in flight.

Run:  PYTHONPATH=src python examples/serve_inference.py [--requests 12]
"""

import argparse
import time

# must run before anything imports jax: --devices N asks the CPU backend
# for N virtual host devices, and the backend latches XLA_FLAGS at the
# first jax import (see repro.platform)
from repro import platform

platform.configure_from_argv()

import jax
import numpy as np

from repro.configs import get_config
from repro.core.search.tuner import Tuner
from repro.data import DataConfig, SyntheticLMData
from repro.distributed.sharding import DEFAULT_RULES
from repro.launch.mesh import single_device_mesh, tp_mesh
from repro.launch.steps import TrainConfig, jit_train_step
from repro.models import build_model
from repro.optim import AdamWConfig, adamw_init
from repro.serve import (
    ContinuousEngine,
    PlanRouter,
    RuntimeConfig,
    build_serve_plan,
)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--train-steps", type=int, default=30)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--no-plan", action="store_true",
                    help="skip WPK plan tuning (pure XLA dispatch)")
    ap.add_argument("--devices", type=int, default=None,
                    help="virtual host devices (applied by repro.platform "
                         "before the jax import above)")
    ap.add_argument("--tp", type=int, default=1,
                    help="model-parallel mesh width for SERVING (<= "
                         "--devices); token streams are byte-identical "
                         "across widths")
    args = ap.parse_args()
    platform.configure_compile_cache()

    cfg = get_config("qwen3-1.7b").reduced(n_layers=2, d_model=128, d_ff=256,
                                           vocab=211)
    model = build_model(cfg)
    # warm-up training stays single-device; serving gets its own
    # (1, tp) mesh — the engine's serve_rules guard every indivisible
    # axis, and the token streams are byte-identical across widths
    mesh = single_device_mesh()
    serve_mesh = tp_mesh(args.tp) if args.tp > 1 else mesh
    data = SyntheticLMData(DataConfig(vocab=cfg.vocab, seq_len=32, global_batch=8))

    with mesh:
        params = model.init(jax.random.PRNGKey(0))
        opt = adamw_init(params)
        batch0 = data.batch(0)
        specs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in batch0.items()}
        step = jit_train_step(
            model, mesh, DEFAULT_RULES,
            TrainConfig(opt=AdamWConfig(lr=3e-3, warmup_steps=5,
                                        total_steps=args.train_steps)), specs)
        for i in range(args.train_steps):
            b = {k: jax.numpy.asarray(v) for k, v in data.batch(i).items()}
            params, opt, m = step(params, opt, b)
        print(f"warm-up train: final loss {float(m['loss']):.3f} "
              f"({args.train_steps} steps)")

    rcfg = RuntimeConfig(max_slots=4, block_size=16, max_blocks_per_seq=4,
                         max_new_tokens=args.new_tokens)
    router = PlanRouter(None)
    if not args.no_plan:
        t0 = time.perf_counter()
        plan = build_serve_plan(
            cfg, prefill_len=32, slots=rcfg.max_slots, max_seq=rcfg.max_seq,
            chunk_tokens=rcfg.chunk_width,
            tuner=Tuner(methods=("random",), random_budget=16),
            model_parallel=args.tp)
        router = PlanRouter(plan)
        print(f"serve plan tuned in {time.perf_counter() - t0:.1f}s: "
              f"{router.describe()}")

    engine = ContinuousEngine(model, params, serve_mesh, DEFAULT_RULES, rcfg,
                              router=router)
    if args.tp > 1:
        print(f"serving mesh {engine.mesh_tag} | decode layouts "
              f"{router.layout_table('decode')}")
    rng = np.random.default_rng(0)
    correct = 0
    prompts = {}
    for _ in range(args.requests):
        start = int(rng.integers(0, cfg.vocab))
        prompt = (start + 17 * np.arange(16)) % cfg.vocab  # pipeline's rule
        rid = engine.submit(prompt)
        prompts[rid] = prompt

    t0 = time.perf_counter()
    done = engine.run()
    wall = time.perf_counter() - t0

    for req in done:
        prompt = prompts[req.rid]
        want = (prompt[-1] + 17 * (1 + np.arange(args.new_tokens))) % cfg.vocab
        correct += int(np.array_equal(req.output, want))
    s = engine.metrics.summary()
    print(f"served {len(done)} requests in {wall:.2f}s | "
          f"{s['tokens_per_s']:,.0f} tok/s | "
          f"latency p50 {s['latency_p50_s']:.2f}s p95 {s['latency_p95_s']:.2f}s | "
          f"ttft p50 {s['ttft_p50_s']:.2f}s | "
          f"slot occ {s['slot_occupancy_mean']:.0%} | "
          f"cache occ mean {s['cache_occupancy_mean']:.0%} "
          f"max {s['cache_occupancy_max']:.0%}")
    print(f"{correct}/{len(done)} requests continued the learned sequence exactly")


if __name__ == "__main__":
    main()
