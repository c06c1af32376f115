"""Segment-packed prefill + the compiled decode-only fast path.

The load-bearing contracts pinned here:

  * the segment-packed prefill Pallas kernel and the XLA lane agree with
    a from-scratch per-segment gather reference at segment boundaries —
    2-4 segments, ragged lengths, GQA groupings, block_q tiles that
    straddle segments — and the XLA lane's rows are bitwise the same
    packed beside other segments as computed alone;
  * packing is invisible to the tokens: a `chunk_segments`-packed engine
    emits byte-identical greedy streams to a single-segment one (PR 4
    behaviour) and to an unlimited one, fast small case + slow multi-seed
    Poisson fuzz including runs under pool pressure (preemption layered on
    packing);
  * the runtime owns EXACTLY TWO step executables — the unified packed
    step and the decode-only fast path — and admission (packed admission
    of several prompts at once included) compiles zero new programs;
  * chunk-less steps dispatch the decode-only program (the chunk-wide idle
    forward is skipped, not masked);
  * satellites: `next_chunks` greedy-fill/ordering semantics, the
    `max_segments` tunable existing only in the prefill_chunk stage's
    template space, `PlanRouter.chunk_segments` falling back to
    single-segment on plans tuned before the segmented kernel, and the
    chunk-lane utilization metrics (`chunk_fill_frac`, `packed_segments`,
    `decode_only_steps`).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.plan import InferencePlan, OpChoice
from repro.core.schedules import AttentionTemplate, OpDesc
from repro.distributed.sharding import DEFAULT_RULES
from repro.launch.mesh import single_device_mesh
from repro.models import build_model
from repro.serve.kvcache import BlockAllocator, KVCacheConfig
from repro.serve.metrics import ServeMetrics
from repro.serve.router import DEFAULT_CHUNK_TOKENS, PlanRouter
from repro.serve.runtime import ContinuousEngine, RuntimeConfig
from repro.serve.scheduler import ContinuousScheduler, ServeRequest


# ------------------------------------------------------------------ kernel
def _packed_reference(q, k_pool, v_pool, seg_tables, seg_info):
    """Per-segment gather + per-row causally-masked softmax, GQA-grouped.
    Rows outside every segment are left as zeros (callers discard them)."""
    c, h, d = q.shape
    bs = k_pool.shape[1]
    hkv = k_pool.shape[2]
    nbt = seg_tables.shape[1]
    out = np.zeros((c, h, d), np.float32)
    for s, (q0, qn, kv0) in enumerate(np.asarray(seg_info)):
        if qn == 0:
            continue
        table = np.asarray(seg_tables)[s]
        k_ctx = np.asarray(k_pool)[table].reshape(nbt * bs, hkv, d)
        v_ctx = np.asarray(v_pool)[table].reshape(nbt * bs, hkv, d)
        qs = np.asarray(q)[q0:q0 + qn].reshape(qn, hkv, h // hkv, d)
        sc = np.einsum("qhgd,khd->hgqk", qs, k_ctx) / np.sqrt(d)
        qpos = kv0 + np.arange(qn)[None, None, :, None]
        kpos = np.arange(nbt * bs)[None, None, None, :]
        sc = np.where(kpos <= qpos, sc, -1e30)
        p = np.exp(sc - sc.max(-1, keepdims=True))
        p = p / p.sum(-1, keepdims=True)
        o = np.einsum("hgqk,khd->qhgd", p, v_ctx)
        out[q0:q0 + qn] = o.reshape(qn, h, d)
    return out


_SEGMENT_LAYOUTS = [
    ((5, 9), (7, 0), None),          # 2 segments, one resuming mid-prompt
    ((3, 4, 2), (0, 11, 5), 4),      # 3 ragged segments, tiles straddle
    ((6, 1, 8, 3), (2, 0, 9, 0), 8),  # 4 segments incl. a 1-token one
    ((11,), (13,), 4),               # single segment (PR 4 shape)
]


def _packed_inputs(seg_lens, kv_starts, dtype=jnp.float32):
    """q (1, C, 4, 16), K/V pools of 32 blocks of 8 with 2 KV heads,
    disjoint 6-block tables per segment (distinct requests own distinct
    blocks), and the segments packed from row 0 with two trailing padding
    rows."""
    rng = np.random.default_rng(11)
    h, hkv, d, bs, nbt, nb = 4, 2, 16, 8, 6, 32
    c = sum(seg_lens) + 2
    ns = len(seg_lens)
    q = jnp.asarray(rng.standard_normal((1, c, h, d)), dtype)
    kp = jnp.asarray(rng.standard_normal((nb, bs, hkv, d)), dtype)
    vp = jnp.asarray(rng.standard_normal((nb, bs, hkv, d)), dtype)
    perm = rng.permutation(np.arange(1, nb))
    seg_tables = np.asarray(perm[:ns * nbt]).reshape(ns, nbt).astype(np.int32)
    q0s = np.concatenate([[0], np.cumsum(seg_lens)[:-1]])
    seg_info = np.stack([q0s, seg_lens, kv_starts], axis=1).astype(np.int32)
    return q, kp, vp, seg_tables, seg_info


@pytest.mark.parametrize("seg_lens,kv_starts,block_q", _SEGMENT_LAYOUTS)
def test_packed_prefill_kernel_matches_gather_reference(seg_lens, kv_starts,
                                                        block_q):
    """`flash_prefill_paged` (via the packed ops wrapper) must agree with a
    per-segment gather reference at segment boundaries: every row attends
    to its OWN request's committed rows only, causally, whatever block_q
    tiling cuts across the segment layout."""
    from repro.kernels import ops as K
    q, kp, vp, seg_tables, seg_info = _packed_inputs(seg_lens, kv_starts)
    cfg = {"block_q": block_q} if block_q else None
    out = K.attention_prefill_packed(q, kp, vp, jnp.asarray(seg_tables),
                                     jnp.asarray(seg_info), config=cfg)
    ref = _packed_reference(q[0], kp, vp, seg_tables, seg_info)
    got = np.asarray(out[0])
    for q0, qn, _ in seg_info:                 # compare real rows only
        np.testing.assert_allclose(got[q0:q0 + qn], ref[q0:q0 + qn],
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("seg_lens,kv_starts",
                         [(lens, starts) for lens, starts, _ in
                          _SEGMENT_LAYOUTS])
def test_packed_prefill_xla_lane_matches_gather_reference(seg_lens,
                                                          kv_starts):
    """The XLA lane (each segment's table gathered once, each row scored
    against its own segment's view) against the same reference, on the
    kernel's segment layouts."""
    from repro.models.attention import attention_prefill_packed_xla
    q, kp, vp, seg_tables, seg_info = _packed_inputs(seg_lens, kv_starts)
    out = jax.jit(attention_prefill_packed_xla)(
        q, kp, vp, jnp.asarray(seg_tables), jnp.asarray(seg_info))
    ref = _packed_reference(q[0], kp, vp, seg_tables, seg_info)
    got = np.asarray(out[0])
    for q0, qn, _ in seg_info:                 # compare real rows only
        np.testing.assert_allclose(got[q0:q0 + qn], ref[q0:q0 + qn],
                                   rtol=2e-5, atol=2e-5)


def test_xla_lane_rows_are_packing_invariant():
    """Bit for bit, in bf16: each segment's rows computed packed, at their
    row offset beside the other segments, equal the same rows computed
    alone at row 0 with the other descriptors idle.  The byte-identical
    stream tests (packed vs single-segment vs unlimited) rest on this."""
    from repro.models.attention import attention_prefill_packed_xla
    q, kp, vp, seg_tables, seg_info = _packed_inputs(
        (6, 1, 8, 3), (2, 0, 9, 0), jnp.bfloat16)
    lane = jax.jit(attention_prefill_packed_xla)
    packed = np.asarray(lane(q, kp, vp, seg_tables, seg_info)[0]
                        .astype(jnp.float32))
    ns = len(seg_info)
    for s, (q0, qn, kv0) in enumerate(seg_info):
        alone_q = jnp.zeros_like(q).at[0, :qn].set(q[0, q0:q0 + qn])
        alone_tables = np.zeros_like(seg_tables)   # idle rows: null block
        alone_tables[0] = seg_tables[s]
        alone_info = np.asarray([[0, qn, kv0]] + [[qn, 0, 0]] * (ns - 1),
                                np.int32)
        alone = lane(alone_q, kp, vp, alone_tables, alone_info)
        np.testing.assert_array_equal(
            np.asarray(alone[0, :qn].astype(jnp.float32)),
            packed[q0:q0 + qn], err_msg=f"segment {s}")


def test_packed_row_map_assigns_rows_to_their_segments():
    from repro.models.attention import packed_row_map
    seg_info = np.asarray([[0, 3, 7], [3, 2, 0], [5, 0, 0], [5, 0, 0]],
                          np.int32)
    sid, pos, valid = jax.jit(lambda i: packed_row_map(i, 8))(seg_info)
    assert list(np.asarray(sid)[:5]) == [0, 0, 0, 1, 1]
    assert list(np.asarray(pos)) == [7, 8, 9, 0, 1, 0, 0, 0]
    assert list(np.asarray(valid)) == [True] * 5 + [False] * 3


# --------------------------------------------------------------- scheduler
def _scheduler(max_slots=3):
    kv_cfg = KVCacheConfig(num_blocks=64, block_size=4, max_blocks_per_seq=8)
    return ContinuousScheduler(max_slots, kv_cfg, BlockAllocator(kv_cfg))


def _req(rid, plen, max_new=4):
    return ServeRequest(rid=rid, prompt=np.arange(plen, dtype=np.int32),
                        max_new_tokens=max_new, arrival_time=0.0)


def test_next_chunks_greedy_fill_oldest_first():
    """Budget is packed oldest-admission-first: the head request may split
    mid-prompt, later requests ride in whatever budget remains (the tail
    segment splitting too), and `max_segments` caps the packing."""
    sched = _scheduler()
    for rid, plen in ((1, 10), (2, 3), (3, 5)):
        sched.submit(_req(rid, plen))
    sched.admit(now=0.0)

    chunks = sched.next_chunks(12, max_segments=4)
    assert [(c[0].rid, c[1], c[2]) for c in chunks] == [(1, 0, 10), (2, 0, 2)]
    for req, start, n in chunks:
        req.prefilled = start + n

    # head finished, the split request resumes at its split point
    chunks = sched.next_chunks(12, max_segments=4)
    assert [(c[0].rid, c[1], c[2]) for c in chunks] == [(2, 2, 1), (3, 0, 5)]

    # max_segments=1 restores the PR 4 single-chunk pick
    assert [(c[0].rid, c[1], c[2]) for c in sched.next_chunks(12, 1)] \
        == [(2, 2, 1)]
    # and no pending prompt work -> empty
    for req, start, n in sched.next_chunks(12, 4):
        req.prefilled = start + n
    assert sched.next_chunks(12, 4) == []


# -------------------------------------------------------------- engine e2e
@pytest.fixture(scope="module")
def tiny_lm():
    cfg = get_config("qwen3-1.7b").reduced(n_layers=2, d_model=64, d_ff=128,
                                           vocab=97)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _engine(model, params, *, chunk_tokens, chunk_segments=4, num_blocks=None,
            max_slots=4, now_fn=None, router=None, max_new=10):
    return ContinuousEngine(
        model, params, single_device_mesh(), DEFAULT_RULES,
        RuntimeConfig(max_slots=max_slots, block_size=8, max_blocks_per_seq=6,
                      num_blocks=num_blocks, max_new_tokens=max_new,
                      chunk_tokens=chunk_tokens,
                      chunk_segments=chunk_segments),
        router=router, now_fn=now_fn)


def test_packed_vs_single_segment_identity_and_two_step_programs(tiny_lm):
    """Fast differential: a chunk_segments=4 engine, a single-segment one
    and an unlimited-budget one must emit byte-identical greedy streams;
    every engine owns EXACTLY two compiled step programs (unified +
    decode-only) with zero admission-time compiles; and the packed engine
    demonstrably packed (packed_segments > 0) while dispatching the
    decode-only fast path on chunk-less steps."""
    cfg, model, params = tiny_lm
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, size=int(rng.integers(3, 14)))
               .astype(np.int32) for _ in range(7)]
    budgets = [int(rng.integers(2, 10)) for _ in prompts]

    outs, engines = {}, {}
    for label, ct, segs in (("packed", 8, 4), ("single", 8, 1),
                            ("unlimited", None, 4)):
        eng = _engine(model, params, chunk_tokens=ct, chunk_segments=segs)
        with eng.mesh:
            for p, b in zip(prompts, budgets):
                eng.submit(p, max_new_tokens=b, arrival_time=0.0)
            eng.step()                           # warm: the unified program
            n_uni = eng._unified._cache_size()
            while eng.scheduler.has_work:
                eng.step()
        # exactly two step executables, each compiled exactly once, and
        # admission mid-run compiled nothing new
        assert eng._unified._cache_size() == n_uni == 1, label
        assert eng._decode_only._cache_size() == 1, label
        assert eng.metrics.decode_only_steps > 0, label
        outs[label] = {r.rid: r.output for r in eng._done}
        engines[label] = eng
        eng.cache.alloc.check_invariants()
        assert eng.cache.alloc.num_used == 0

    assert outs["packed"] == outs["single"] == outs["unlimited"]
    # the packed engine really packed: several requests' segments shared a
    # step, the single-segment engine never did, and packing bought strictly
    # fewer chunk-carrying steps for the same committed tokens
    mp, ms = engines["packed"].metrics, engines["single"].metrics
    assert mp.packed_segments > 0 and ms.packed_segments == 0
    assert mp.chunk_tokens_committed == ms.chunk_tokens_committed \
        == sum(len(p) for p in prompts)
    assert mp.chunk_steps < ms.chunk_steps
    assert mp.chunk_fill_frac() > ms.chunk_fill_frac()


def test_decode_only_fast_path_dispatches_on_chunkless_steps(tiny_lm):
    """Once a prompt is fully committed the remaining steps carry no chunk
    work and must run the decode-only program — counted by the metric the
    CI bench guard watches."""
    cfg, model, params = tiny_lm
    eng = _engine(model, params, chunk_tokens=8, max_new=6)
    rng = np.random.default_rng(4)
    eng.submit(rng.integers(0, cfg.vocab, size=5).astype(np.int32),
               arrival_time=0.0)
    with eng.mesh:
        eng.step()
        assert eng.metrics.chunk_steps == 1          # prompt fit one chunk
        assert eng.metrics.decode_only_steps == 0    # chunk lane ran
        while eng.scheduler.has_work:
            eng.step()
    # budget 6: 1 token from the completing chunk + 5 decode-only steps
    assert eng.metrics.decode_only_steps == 5
    assert eng._decode_only._cache_size() == 1
    assert len(eng._done) == 1 and len(eng._done[0].output) == 6


# ------------------------------------------------------- router / template
def test_max_segments_is_tuned_only_for_the_chunk_stage():
    """The attention template races `max_segments` (the segmented kernel's
    packing-grid axis) only for prefill_chunk-stage ops — decode/prefill
    spaces are unchanged."""
    t = AttentionTemplate()
    chunk_op = OpDesc.attention(1, 32, 96, 4, 64,
                                label="prefill_chunk.attention")
    assert "max_segments" in t.space(chunk_op)
    assert all(m <= 32 for m in t.space(chunk_op)["max_segments"])
    for label in ("decode.attention", "prefill.attention"):
        assert "max_segments" not in t.space(
            OpDesc.attention(1, 32, 96, 4, 64, label=label))
    # configs with the extra axis still validate (descriptors are scalars)
    cfg = {"block_q": 128, "block_kv": 128, "max_segments": 4}
    assert t.validate(chunk_op, cfg)


def test_chunk_segments_router_fallback():
    """No plan -> the engine's default packs; a plan whose prefill_chunk
    attention choice raced `max_segments` -> the tuned width; a PALLAS
    config tuned BEFORE the segmented kernel existed -> single-segment;
    an XLA choice (packing-invariant lane, nothing tuned to protect) ->
    the engine's default, whatever the plan's age."""
    assert PlanRouter(None).chunk_segments(default=8) == 8

    new_plan = InferencePlan("serve", "tpu_v5e")
    new_plan.choices["prefill_chunk.attention"] = OpChoice(
        "pallas_attention", {"block_q": 16, "block_kv": 32,
                             "max_segments": 2}, 1e-4)
    assert PlanRouter(new_plan).chunk_segments(default=8) == 2

    old_plan = InferencePlan("serve", "tpu_v5e")
    old_plan.choices["prefill_chunk.attention"] = OpChoice(
        "pallas_attention", {"block_q": 16, "block_kv": 32}, 1e-4)
    assert PlanRouter(old_plan).chunk_segments(default=8) == 1
    # prefill-only PALLAS plans (pre-chunk-stage) are old a fortiori
    older = InferencePlan("serve", "tpu_v5e")
    older.choices["prefill.attention"] = OpChoice(
        "pallas_attention", {"block_q": 16, "block_kv": 32}, 1e-4)
    assert PlanRouter(older).chunk_segments(default=8) == 1
    # an xla winner never caps packing — the gather lane is per-row
    # identical at every packing width
    xla_plan = InferencePlan("serve", "tpu_v5e")
    xla_plan.choices["prefill_chunk.attention"] = OpChoice("xla", {}, 1e-4)
    assert PlanRouter(xla_plan).chunk_segments(default=8) == 8


def test_old_pallas_plan_caps_engine_packing_to_single_segment(tiny_lm):
    cfg, model, params = tiny_lm
    old_plan = InferencePlan("serve", "tpu_v5e")
    old_plan.choices["prefill_chunk.attention"] = OpChoice(
        "pallas_attention", {"block_q": 8, "block_kv": 32}, 1e-4)
    eng = _engine(model, params, chunk_tokens=8,
                  router=PlanRouter(old_plan))
    assert eng._chunk_segments == 1   # cap sizes the compiled grid itself
    eng2 = _engine(model, params, chunk_tokens=8)
    assert eng2._chunk_segments == eng2.cfg.chunk_segments == 4


def test_max_segments_race_is_measurable_in_the_cost_model():
    """The tunable must not be decided by search-order tie-break: packing
    amortizes the launch overhead across the segments one invocation can
    commit, while the segment grid axis multiplies grid-step issue cost —
    a real, deterministic optimum interior to the space."""
    from repro.core.costmodel import pallas_time
    op = OpDesc.attention(1, 32, 96, 4, 64, label="prefill_chunk.attention")
    base = {"block_q": 128, "block_kv": 128}
    t = {ns: pallas_time(op, dict(base, max_segments=ns))
         for ns in (1, 4, 64)}
    assert t[4] < t[1]          # launch amortization wins at chunk shapes
    assert t[64] > t[1]         # runaway packing drowns in grid steps
    # configs without the key (decode/prefill stages) price as width 1
    assert pallas_time(op, base) == t[1]


def test_chunk_tokens_default_is_the_shared_constant():
    """Satellite: RuntimeConfig's default budget and the serve graph's
    fallback width come from one constant — they can't drift."""
    from repro.serve.router import build_serve_graph
    assert RuntimeConfig().chunk_tokens == DEFAULT_CHUNK_TOKENS
    g = build_serve_graph(get_config("qwen3-1.7b").reduced(
        n_layers=2, d_model=64, d_ff=128, vocab=97),
        prefill_len=48, slots=4, max_seq=96)
    assert g.tensors["x_chunk"].shape[1] == DEFAULT_CHUNK_TOKENS


# ----------------------------------------------------------------- metrics
def test_chunk_lane_utilization_metrics():
    m = ServeMetrics()
    m.record_chunk_step([4, 3], 16)       # packed step: 2 segments, 7/16
    m.record_chunk_step([16], 16)         # full single-segment step
    m.record_decode_only_step()
    assert m.chunk_steps == 2
    assert m.prefill_chunks == 3
    assert m.chunk_tokens_committed == 23
    assert m.packed_segments == 2         # only the shared step's segments
    assert m.decode_only_steps == 1
    assert m.chunk_fill_frac() == pytest.approx(23 / 32)
    s = m.summary()
    assert s["chunk_fill_frac"] == pytest.approx(23 / 32)
    assert s["packed_segments"] == 2.0
    assert s["decode_only_steps"] == 1.0
    assert s["chunk_steps"] == 2.0
    assert ServeMetrics().chunk_fill_frac() == 0.0


# ------------------------------------------------------------- slow fuzz
@pytest.mark.slow
def test_differential_fuzz_packed_poisson_traces(tiny_lm):
    """Slow differential fuzz on the Poisson harness: random arrival traces
    replayed through a packed engine, a single-segment engine and an
    unlimited one under the same virtual clock — every per-request greedy
    stream must match across seeds, with exactly two step executables and
    zero admission compiles, including runs where a shrunken pool layers
    preemption on top of packing."""
    cfg, model, params = tiny_lm
    for seed in range(3):
        rng = np.random.default_rng(seed)
        n = 10
        arrivals = np.cumsum(rng.exponential(0.2, size=n))
        prompts = [rng.integers(0, cfg.vocab,
                                size=int(rng.integers(3, 28))).astype(np.int32)
                   for _ in range(n)]
        budgets = [int(rng.integers(2, 14)) for _ in range(n)]

        def replay(chunk_tokens, chunk_segments, num_blocks=None):
            clock = {"t": 0.0}
            eng = _engine(model, params, chunk_tokens=chunk_tokens,
                          chunk_segments=chunk_segments,
                          num_blocks=num_blocks, max_slots=3,
                          now_fn=lambda: clock["t"])
            for a, p, b in zip(arrivals, prompts, budgets):
                eng.submit(p, max_new_tokens=b, arrival_time=float(a))
            with eng.mesh:
                while eng.scheduler.has_work:
                    ran = eng.step()
                    clock["t"] += 0.2 if ran else 0.05
            assert eng._unified._cache_size() == 1
            assert eng._decode_only._cache_size() <= 1
            eng.cache.alloc.check_invariants()
            assert eng.cache.alloc.num_used == 0
            return eng, {r.rid: r.output for r in eng._done}

        _, out_unl = replay(chunk_tokens=None, chunk_segments=4)
        packed, out_p = replay(chunk_tokens=6, chunk_segments=4)
        single, out_s = replay(chunk_tokens=6, chunk_segments=1)
        assert out_p == out_unl, f"packed stream diverged (seed {seed})"
        assert out_s == out_unl, f"single-seg stream diverged (seed {seed})"
        # both engines commit every prompt token; packing (greedy fill) can
        # only reduce the number of chunk-carrying steps
        total = sum(len(p) for p in prompts)
        assert packed.metrics.chunk_tokens_committed == total
        assert single.metrics.chunk_tokens_committed == total
        assert packed.metrics.chunk_steps <= single.metrics.chunk_steps
        small, out_small = replay(chunk_tokens=6, chunk_segments=4,
                                  num_blocks=8)
        assert out_small == out_unl, \
            f"packed+preempted stream diverged (seed {seed})"
        assert small.metrics.preemptions >= 1, f"no preemption (seed {seed})"
