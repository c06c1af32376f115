"""Smoke run of continuous serving on a TPU: qwen3-1.7b at its published widths.

The model is `configs/qwen3_1_7b.py` unchanged (28 layers x 2048, GQA 16/8,
head_dim 128, d_ff 6144, vocab 151,936) with random bf16 weights drawn from
`--seed`.  One process drives every phase through the entry points a user
calls (`ContinuousEngine` over `resolve_family_adapter` and the step
programs):

  kernels  each Pallas kernel of the serving path, once at these widths,
           against its reference in `repro.kernels.ref`;
  phase A  the requests served with no plan, so the XLA lanes run;
  phase B  the same requests under a plan that sends decode and prefill
           attention to `flash_decode_paged` / `flash_prefill_paged` and
           every stage matmul to `pallas_matmul`.

Each request's first token is checked against a dense forward of the same
weights (`DecoderLM.hidden`, no paging): its reference logit must be within
`FIRST_TOKEN_MARGIN` of the reference maximum.

With `--chips 4` only the tensor-parallel path runs: the same requests on
`tp_mesh(4)` and on `tp_mesh(1)`, first tokens compared.

Run from the root of a checkout, on a machine with a TPU:

    python chip_smoke.py [--seed 0] [--chips 4]

Without a TPU it exits non-zero at once and prints no result.  JAX's
compilation cache is kept in `JAX_COMPILATION_CACHE_DIR` where that is set,
else in `.jax_cache/` of the checkout.  The last line of the output is
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}`.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ARCH = "qwen3-1.7b"
N_REQUESTS = 12
PROMPT_LENS = (64, 512)          # prompt lengths, uniform, both ends included
NEW_TOKENS = 32
# Sized from `memory_analysis()` of both step programs compiled for a v5e:
# 8 slots x 64 blocks x 16 rows hold every request (512 + 32 <= 1024 rows)
# in a 513-block pool of ~0.9 GB next to ~4 GB of bf16 weights.
RUNTIME = dict(max_slots=8, block_size=16, max_blocks_per_seq=64,
               chunk_tokens=256, chunk_segments=4, max_new_tokens=NEW_TOKENS)
# Kernel vs reference: bf16 inputs, f32 accumulation on both sides.  The
# matmul rounds the same f32 sum to bf16 once on each side (one bf16 step,
# 2^-8 relative, apart); attention also rounds its probabilities to bf16
# before the PV product at different points (normalised in the reference,
# max-shifted in the kernel), a few bf16 steps on O(1) outputs.
MATMUL_TOL = 1 / 128
ATTENTION_TOL = 2e-2
# Logit units; the reference logits have a standard deviation near 1.  The
# engine's bf16 paged paths and the dense reference round at different
# points through 28 layers, which moves near-tied logits by a few
# hundredths; a token that is wrong (not a near-tie) sits several units
# below the maximum of 151,936 logits.
FIRST_TOKEN_MARGIN = 0.25


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, summed from its
    monitoring events; a persistent-cache hit skips the backend compile."""

    def __init__(self):
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, **_):
        if name.startswith("/jax/core/compile/"):
            self.total += secs


def fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAIL: {msg}")


def log(msg: str) -> None:
    print(msg, flush=True)


def make_requests(vocab: int, seed: int):
    rng = np.random.default_rng(seed)
    lens = rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1, size=N_REQUESTS)
    return [rng.integers(0, vocab, size=int(n)).astype(np.int32)
            for n in lens]


def bf16_params(model, seed: int):
    """Random weights made on the device, in the serving dtype."""
    init = jax.jit(lambda key: jax.tree.map(
        lambda a: a.astype(jnp.bfloat16), model.init(key)))
    return init(jax.random.PRNGKey(seed))


def reference_logits(model, params, prompts) -> np.ndarray:
    """Last-position logits of each prompt from the dense forward (f32).
    Prompts are right-padded to one length: attention is causal, so the
    padding never reaches a real row."""
    from repro.models.common import lm_head_logits

    width = max(len(p) for p in prompts)
    toks = np.zeros((len(prompts), width), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    last = jnp.asarray([len(p) - 1 for p in prompts])

    @jax.jit
    def fwd(params, toks, last):
        h = model.hidden(params, {"tokens": toks})
        h = h[jnp.arange(h.shape[0]), last][:, None]
        return lm_head_logits(params["lm_head"], h)[:, 0].astype(jnp.float32)

    return np.asarray(fwd(params, jnp.asarray(toks), last))


def check_first_tokens(label: str, done, ref: np.ndarray) -> None:
    firsts = np.asarray([r.output[0] for r in done])
    gaps = ref.max(axis=1) - ref[np.arange(len(done)), firsts]
    exact = int(np.sum(firsts == ref.argmax(axis=1)))
    log(f"{label}: first tokens vs dense reference: {exact}/{len(done)} "
        f"argmax, largest gap {gaps.max():.4f} logits "
        f"(margin {FIRST_TOKEN_MARGIN})")
    if gaps.max() > FIRST_TOKEN_MARGIN:
        fail(f"{label}: first token {gaps.argmax()} is {gaps.max():.4f} "
             f"logits below the reference maximum")


def serve(label, model, params, mesh, rcfg, router, prompts, vocab, clock,
          ref):
    """Serve every prompt to completion twice on one engine: the first
    pass compiles, the second is timed.  Returns the finished requests of
    the first pass, in submission order."""
    from repro.distributed.sharding import DEFAULT_RULES
    from repro.serve import ContinuousEngine

    engine = ContinuousEngine(model, params, mesh, DEFAULT_RULES, rcfg,
                              router=router)
    passes = []
    for _ in range(2):
        c0, t0 = clock.total, time.perf_counter()
        engine.reset_metrics()
        rids = [engine.submit(p, max_new_tokens=NEW_TOKENS) for p in prompts]
        by_rid = {r.rid: r for r in engine.run()}
        wall = time.perf_counter() - t0
        done = [by_rid.get(rid) for rid in rids]
        if any(r is None for r in done):
            fail(f"{label}: {sum(r is None for r in done)} requests "
                 "did not finish")
        for r in done:
            out = np.asarray(r.output)
            if len(out) != NEW_TOKENS:
                fail(f"{label}: request {r.rid} produced {len(out)} of "
                     f"{NEW_TOKENS} tokens")
            if out.min() < 0 or out.max() >= vocab:
                fail(f"{label}: request {r.rid} has token ids outside "
                     f"[0, {vocab})")
        s = engine.metrics.summary()
        passes.append(done)
        log(f"{label} pass {len(passes)} on {jax.devices()[0].device_kind}: "
            f"compile {clock.total - c0:.2f} s | "
            f"steps {int(s['chunk_steps'] + s['decode_only_steps'])} "
            f"(decode-only {int(s['decode_only_steps'])}) | "
            f"wall {wall:.3f} s | {s['tokens_per_s']:.1f} tokens/s | "
            f"{len(done)}/{len(prompts)} requests x {NEW_TOKENS} tokens")
    first, second = passes
    if [r.output for r in first] != [r.output for r in second]:
        fail(f"{label}: the second pass changed greedy token streams")
    check_first_tokens(label, first, ref)
    return engine, first


def pallas_plan(cfg, rcfg, chip, seed: int):
    """A serve plan whose decode and prefill_chunk stages run every matmul
    on `pallas_matmul` and attention on the paged Pallas kernels.  Each
    takes the cost-model race's tuned config where the race picked that
    lane (the search proposes only configs the chip's VMEM model admits)
    and the kernels' smallest defaults otherwise."""
    from repro.core.plan import InferencePlan, OpChoice
    from repro.core.search.tuner import Tuner
    from repro.kernels.dispatch import MATMUL_ROLES
    from repro.serve import build_serve_plan

    tuned = build_serve_plan(
        cfg, prefill_len=rcfg.chunk_width, slots=rcfg.max_slots,
        max_seq=rcfg.max_seq, chunk_tokens=rcfg.chunk_width, chip=chip,
        tuner=Tuner(chip=chip, methods=("random",), random_budget=16,
                    seed=seed))
    plan = InferencePlan(tuned.graph_name, tuned.chip)
    for stage in ("decode", "prefill_chunk"):
        for op, lane in [(r, "pallas_matmul") for r in MATMUL_ROLES] + [
                ("attention", "pallas_attention")]:
            c = tuned.choices[f"{stage}.{op}"]
            conf = dict(c.config) if c.backend == lane else {}
            if op == "attention":
                conf["max_segments"] = rcfg.chunk_segments
            plan.choices[f"{stage}.{op}"] = OpChoice(lane, conf,
                                                     c.modeled_time_s)
    return plan


def step_programs_text(engine) -> list:
    """Compiled text of the engine's unified and decode-only programs, at
    the shapes the engine dispatches (from the persistent cache when the
    run already compiled them)."""
    from repro.serve.sampling import (segment_sampling_arrays,
                                      slot_sampling_arrays)

    ad, cfg = engine.adapter, engine.cfg
    slots, nbt = cfg.max_slots, cfg.max_blocks_per_seq
    z = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731
    dsp, dks = map(jnp.asarray, slot_sampling_arrays([None] * slots))
    ssp, sks = map(jnp.asarray, segment_sampling_arrays([], ad.chunk_segments))
    k, v = ad.cache.k, ad.cache.v
    dec = (engine.params, k, v, z(slots, nbt), z(slots), z(slots, 1))
    with engine.mesh:
        unified = ad._unified.lower(
            *dec, z(1, ad.chunk_width), z(ad.chunk_segments, nbt),
            z(ad.chunk_segments, 3), dsp, dks, ssp, sks)
        decode_only = ad._decode_only.lower(*dec, dsp, dks)
        return [unified.compile().as_text(), decode_only.compile().as_text()]


def check_kernels(cfg, chip, seed: int) -> None:
    """Each Pallas kernel of the serving path at qwen3-1.7b's widths
    against its plain reference."""
    from repro.core.schedules import MatmulTemplate, OpDesc, _vmem_matmul_bytes
    from repro.kernels import ops, ref

    rng = np.random.default_rng(seed)
    bf = lambda a: jnp.asarray(a, jnp.bfloat16)  # noqa: E731
    f32 = lambda a: np.asarray(a, np.float32)  # noqa: E731

    def report(name, got, want, tol):
        got, want = f32(got), f32(want)
        err = np.abs(got - want)
        bound = tol + tol * np.abs(want)
        log(f"kernel {name}: max abs err {err.max():.3e}, worst err/bound "
            f"{(err / bound).max():.3f} (tol {tol:g} abs + rel)")
        if not np.all(np.isfinite(got)) or np.any(err > bound):
            fail(f"kernel {name} disagrees with its reference")

    # matmul: mlp_up for a 2048-token prompt, under the largest config the
    # VMEM model admits for that shape
    m, k, n = 2048, cfg.d_model, cfg.d_ff
    mm_op = OpDesc.matmul(m, n, k)
    big = max(MatmulTemplate().enumerate_configs(mm_op, chip),
              key=lambda c: _vmem_matmul_bytes(c["bm"], c["bn"], c["bk"],
                                               mm_op.dtype))
    x = bf(rng.standard_normal((m, k)))
    w = bf(rng.standard_normal((k, n)) / np.sqrt(k))
    report(f"pallas_matmul {m}x{k}@{k}x{n} {big}",
           jax.jit(lambda x, w: ops.matmul(x, w, config=big))(x, w),
           ref.matmul_ref(x, w), MATMUL_TOL)

    # paged attention over a pool of random blocks, shuffled tables
    h, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    bs, nbt = RUNTIME["block_size"], RUNTIME["max_blocks_per_seq"]
    slots, segs = RUNTIME["max_slots"], RUNTIME["chunk_segments"]
    nb = slots * nbt + 1
    kp = bf(rng.standard_normal((nb, bs, hkv, d)))
    vp = bf(rng.standard_normal((nb, bs, hkv, d)))
    tables = rng.permutation(np.arange(1, nb))[:slots * nbt].reshape(
        slots, nbt).astype(np.int32)

    def dense_kv(table):
        return kp[table].reshape(1, -1, hkv, d), vp[table].reshape(1, -1, hkv, d)

    q = bf(rng.standard_normal((slots, h, d)))
    lengths = rng.integers(1, nbt * bs + 1, size=slots).astype(np.int32)
    got = jax.jit(ops.attention_decode_paged)(q, kp, vp, jnp.asarray(lengths),
                                              jnp.asarray(tables))
    want = []
    for i in range(slots):
        kd, vd = dense_kv(tables[i])
        want.append(ref.attention_decode_ref(
            q[i:i + 1].astype(jnp.float32), kd.astype(jnp.float32),
            vd.astype(jnp.float32), jnp.asarray(lengths[i:i + 1]))[0])
    report(f"flash_decode_paged B={slots} H={h}/{hkv} D={d} "
           f"table={nbt}x{bs}", got, jnp.stack(want), ATTENTION_TOL)

    c = RUNTIME["chunk_tokens"]
    seg_len = np.array([100, 80, 50, 26], np.int32)[:segs]
    kv_start = np.array([0, 300, 17, 600], np.int32)[:segs]
    offs = np.concatenate([[0], np.cumsum(seg_len)[:-1]]).astype(np.int32)
    info = np.stack([offs, seg_len, kv_start], 1).astype(np.int32)
    qc = bf(rng.standard_normal((1, c, h, d)))
    got = jax.jit(ops.attention_prefill_packed)(
        qc, kp, vp, jnp.asarray(tables[:segs]), jnp.asarray(info))
    for s in range(segs):
        o, n_s, kv0 = int(offs[s]), int(seg_len[s]), int(kv_start[s])
        kd, vd = dense_kv(tables[s])
        want = ref.attention_ref(
            qc[:, o:o + n_s].astype(jnp.float32),
            kd[:, :kv0 + n_s].astype(jnp.float32),
            vd[:, :kv0 + n_s].astype(jnp.float32), causal=True)
        report(f"flash_prefill_paged C={c} segment {s} rows {n_s} "
               f"kv_start {kv0}", got[:, o:o + n_s], want, ATTENTION_TOL)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights, prompts and kernel "
                         "inputs")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the tensor-parallel path on 4 chips")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"needs a TPU, but JAX's first device is {dev.platform!r} "
             f"({dev.device_kind}); there is no fallback")
    count = len(jax.devices())
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={count}")
    if count < args.chips:
        fail(f"--chips {args.chips} needs {args.chips} TPU devices; JAX "
             f"sees {count}")

    from repro import hw, platform
    from repro.configs import get_config
    from repro.launch.mesh import single_device_mesh, tp_mesh
    from repro.models import build_model
    from repro.serve import PlanRouter, RuntimeConfig

    chip = hw.chip_for_kind(dev.device_kind)
    log(f"compilation cache: {platform.configure_compile_cache()}")
    clock = CompileClock()
    cfg = get_config(ARCH)
    model = build_model(cfg)
    rcfg = RuntimeConfig(**RUNTIME)
    t0 = time.perf_counter()
    params = bf16_params(model, args.seed)
    jax.block_until_ready(params)
    prompts = make_requests(cfg.vocab, args.seed)
    log(f"{ARCH}: {cfg.n_layers}x{cfg.d_model}, heads {cfg.n_heads}/"
        f"{cfg.n_kv_heads} x {cfg.hd}, d_ff {cfg.d_ff}, vocab {cfg.vocab}; "
        f"weights made in {time.perf_counter() - t0:.1f} s; "
        f"{len(prompts)} prompts of {min(map(len, prompts))}-"
        f"{max(map(len, prompts))} tokens, {NEW_TOKENS} new tokens each")
    ref = reference_logits(model, params, prompts)
    if not np.all(np.isfinite(ref)):
        fail("dense reference logits are not finite")

    if args.chips == 4:
        streams = {}
        for tp in (4, 1):
            engine, done = serve(f"tp={tp}", model, params, tp_mesh(tp),
                                 rcfg, PlanRouter(None), prompts, cfg.vocab,
                                 clock, ref)
            streams[tp] = [r.output for r in done]
            del engine
        first = sum(a[0] == b[0] for a, b in zip(streams[4], streams[1]))
        tokens = sum(int(np.sum(np.asarray(a) == np.asarray(b)))
                     for a, b in zip(streams[4], streams[1]))
        log(f"tp=4 vs tp=1: first tokens agree {first}/{len(prompts)}, "
            f"all tokens {tokens}/{len(prompts) * NEW_TOKENS}")
    else:
        check_kernels(cfg, chip, args.seed)
        mesh = single_device_mesh()
        engine, done_a = serve("phase A (xla lanes)", model, params, mesh,
                               rcfg, PlanRouter(None), prompts, cfg.vocab,
                               clock, ref)
        del engine
        router = PlanRouter(pallas_plan(cfg, rcfg, chip, args.seed))
        log(f"phase B plan: {router.describe()}")
        engine, done_b = serve("phase B (pallas lanes)", model, params, mesh,
                               rcfg, router, prompts, cfg.vocab, clock, ref)
        for name, text in zip(("unified", "decode-only"),
                              step_programs_text(engine)):
            if "tpu_custom_call" not in text:
                fail(f"phase B {name} program holds no Pallas kernel")
        same = sum(int(np.sum(np.asarray(a.output) == np.asarray(b.output)))
                   for a, b in zip(done_a, done_b))
        log(f"phase B vs phase A: both programs hold tpu_custom_call; "
            f"tokens agree {same}/{len(prompts) * NEW_TOKENS}")

    stats = dev.memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        log(f"device memory on {dev.device_kind} 0: peak "
            f"{stats['peak_bytes_in_use'] / 2**30:.2f} GiB of "
            f"{stats.get('bytes_limit', 0) / 2**30:.2f} GiB")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
