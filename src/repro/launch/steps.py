"""Step builders: train_step / prefill_step / serve_step with shardings.

These are what `dryrun.py` lowers for every (arch × shape × mesh) cell and
what the trainer/server run for real.  Everything sharding-related funnels
through `ShardingRules`, so a hillclimb iteration = new rules + re-lower.

Distributed-optimization features:
  * microbatched gradient accumulation (`microbatches > 1`) — emits the
    per-microbatch grad pattern XLA's latency-hiding scheduler can overlap
    with the next microbatch's compute;
  * ZeRO-1 — optimizer moments sharded along the 'zero' (data) axis on the
    first divisible dim of each leaf;
  * donated params/opt-state/cache buffers;
  * optional int8-compressed pod-axis gradient reduction (see
    repro.optim.compress) for the DCN hop.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeSpec
from repro.kernels.dispatch import matmul_dispatch
from repro.kernels.sampling import sample_tokens
from repro.distributed.sharding import (
    DEFAULT_RULES,
    ShardingRules,
    activation_rules,
    params_shardings,
    prune_for_mesh,
)
from repro.optim import AdamWConfig, adamw_init, adamw_update


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    microbatches: int = 1
    zero1: bool = True
    compress_pod_grads: bool = False
    opt: AdamWConfig = AdamWConfig()


def cost_dict(cost) -> Dict[str, float]:
    """Normalise `Compiled.cost_analysis()` across JAX versions: newer
    releases return one properties dict, older ones a one-element list."""
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    return dict(cost)


# ---------------------------------------------------------------- shardings
def batch_logical_axes(batch_specs: Dict[str, Any]) -> Dict[str, Any]:
    out = {}
    for k, v in batch_specs.items():
        out[k] = ("batch",) + (None,) * (len(v.shape) - 1)
    return out


def rules_for_shape(cfg: ModelConfig, shape: ShapeSpec,
                    mesh: Mesh) -> ShardingRules:
    """Shape- and config-aware rule overrides (the baseline sharding scheme;
    the §Perf hillclimb iterates by overriding the result).

    Divisibility fallbacks (each dim must divide its mesh axis):
      * kv_heads/heads indivisible by |model|  -> replicate (note: GQA archs
        with few KV heads keep K/V projections replicated — a known baseline
        cost, see EXPERIMENTS.md);
      * vocab indivisible                       -> shard the embed-table
        d_model dim instead ('embed_vec' -> model);
      * n_experts indivisible                   -> TP inside experts
        ('expert_ffn' -> model) instead of EP.
    """
    rules = DEFAULT_RULES
    m = mesh.shape.get("model", 1)
    dp = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)

    if cfg.n_heads and cfg.n_heads % m:
        rules = rules.replace(heads=None)
    if cfg.n_kv_heads and cfg.n_kv_heads % m:
        rules = rules.replace(kv_heads=None)
    if cfg.d_ff and cfg.d_ff % m:
        rules = rules.replace(ffn=None)
    if cfg.vocab % m:
        rules = rules.replace(vocab=None)
        if cfg.d_model % m == 0:
            rules = rules.replace(embed_vec="model")
    if cfg.n_experts:
        if cfg.n_experts % m:
            rules = rules.replace(experts=None)
            if cfg.d_expert % m == 0:
                rules = rules.replace(expert_ffn="model")
    if cfg.ssm_state:
        d_in = cfg.ssm_expand * cfg.d_model
        nh = d_in // cfg.ssm_head_dim
        conv_dim = d_in + 2 * cfg.ssm_state
        if nh % m:
            rules = rules.replace(ssm_heads=None)
        if conv_dim % m:
            rules = rules.replace(conv_dim=None)

    if (shape.kind == "decode" and cfg.n_kv_heads and cfg.n_kv_heads % m
            and shape.seq_len % m == 0):
        # §Perf cell (b): GQA KV heads indivisible by |model| would replicate
        # the KV cache over the model axis — shard the cache sequence dim
        # there instead (11.6x on the dominant memory term for granite
        # decode_32k; see EXPERIMENTS.md).
        rules = rules.replace(kv_seq="model")

    if shape.global_batch % dp != 0 or shape.global_batch < dp:
        # batch unshardable (long_500k B=1): replicate batch, shard the
        # sequence/state dims instead (SP).
        d = mesh.shape.get("data", 1)
        rules = rules.replace(
            batch=None,
            kv_seq="data" if shape.seq_len % d == 0 else None,
            ssm_state="data" if (cfg.ssm_state and cfg.ssm_state % d == 0) else None,
            seq="data" if shape.seq_len % d == 0 else None,
        )
    if shape.kind == "train" and shape.seq_len >= 16_384:
        rules = rules.replace(seq="data")  # SP for long-sequence training
    return rules


def zero1_axes(logical_tree, shapes_tree, mesh: Mesh, rules: ShardingRules):
    """Rewrite the first shardable None axis of each optimizer-moment leaf to
    'zero' (the data axis) when the dim divides evenly — ZeRO-1."""
    zset = rules.lookup("zero")
    zsize = mesh.shape.get(zset, 1) if isinstance(zset, str) else 1

    def rewrite(axes, shaped):
        if zsize <= 1:
            return axes
        used = {a for a in axes if a is not None}
        out = list(axes)
        for i, (a, dim) in enumerate(zip(axes, shaped.shape)):
            if a is None and dim % zsize == 0 and "zero" not in used:
                out[i] = "zero"
                break
        return tuple(out)

    return jax.tree.map(
        rewrite, logical_tree, shapes_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            a is None or isinstance(a, str) for a in x))


def make_state_shardings(model, mesh: Mesh, rules: ShardingRules,
                         train_cfg: Optional[TrainConfig] = None):
    """NamedShardings for (params, opt_state) trees."""
    p_logical = model.logical_axes()
    p_shard = params_shardings(mesh, rules, p_logical)
    if train_cfg is None:
        return p_shard, None
    params_shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    mv_logical = p_logical
    if train_cfg.zero1:
        mv_logical = zero1_axes(p_logical, params_shapes, mesh, rules)
    mv_shard = params_shardings(mesh, rules, mv_logical)
    opt_shard = {"m": mv_shard, "v": mv_shard,
                 "step": NamedSharding(mesh, P())}
    return p_shard, opt_shard


def make_batch_shardings(mesh: Mesh, rules: ShardingRules, batch_specs):
    return {
        k: NamedSharding(
            mesh, rules.spec(("batch",) + (None,) * (len(v.shape) - 1)))
        for k, v in batch_specs.items()
    }


# ---------------------------------------------------------------- train step
def make_train_step(model, train_cfg: TrainConfig, rules: ShardingRules):
    """(params, opt_state, batch) -> (params, opt_state, metrics)."""

    def loss_fn(params, batch):
        l, metrics = model.loss(params, batch)
        return l, metrics

    def train_step(params, opt_state, batch):
        with activation_rules(rules):
            k = train_cfg.microbatches
            if k == 1:
                (loss, metrics), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, batch)
            else:
                micro = jax.tree.map(
                    lambda x: x.reshape((k, x.shape[0] // k) + x.shape[1:]),
                    batch)

                def acc_body(carry, mb):
                    g_acc, l_acc = carry
                    (l, _), g = jax.value_and_grad(loss_fn, has_aux=True)(
                        params, mb)
                    g_acc = jax.tree.map(jnp.add, g_acc, g)
                    return (g_acc, l_acc + l), None

                zeros = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params)
                (grads, loss), _ = jax.lax.scan(
                    acc_body, (zeros, jnp.zeros((), jnp.float32)), micro)
                grads = jax.tree.map(lambda g: g / k, grads)
                loss = loss / k
                metrics = {"loss": loss}
            params, opt_state, om = adamw_update(
                params, grads, opt_state, train_cfg.opt)
            metrics = dict(metrics, **om)
        return params, opt_state, metrics

    return train_step


def jit_train_step(model, mesh: Mesh, rules: ShardingRules,
                   train_cfg: TrainConfig, batch_specs):
    rules = prune_for_mesh(rules, mesh)
    p_shard, opt_shard = make_state_shardings(model, mesh, rules, train_cfg)
    b_shard = make_batch_shardings(mesh, rules, batch_specs)
    step = make_train_step(model, train_cfg, rules)
    return jax.jit(
        step,
        in_shardings=(p_shard, opt_shard, b_shard),
        out_shardings=(p_shard, opt_shard, None),
        donate_argnums=(0, 1),
    )


# ---------------------------------------------------------------- serve steps
def make_prefill_step(model, rules: ShardingRules, max_seq: int):
    def prefill_step(params, batch):
        with activation_rules(rules):
            return model.prefill(params, batch, max_seq)

    return prefill_step


def make_serve_step(model, rules: ShardingRules):
    def serve_step(params, cache, tokens):
        with activation_rules(rules):
            return model.decode_step(params, cache, tokens)

    return serve_step


def cache_shardings(model, mesh: Mesh, rules: ShardingRules, batch: int,
                    max_seq: int):
    logical = model.cache_logical_axes()
    cache_shapes = jax.eval_shape(lambda: model.init_cache(batch, max_seq))

    def spec_of(axes, shaped):
        axes = tuple(axes) + (None,) * (len(shaped.shape) - len(axes))
        return NamedSharding(mesh, rules.spec(axes))

    return jax.tree.map(
        spec_of, logical, cache_shapes,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            a is None or isinstance(a, str) for a in x))


def jit_prefill_step(model, mesh: Mesh, rules: ShardingRules, batch_specs,
                     max_seq: int, batch: int):
    rules = prune_for_mesh(rules, mesh)
    p_shard, _ = make_state_shardings(model, mesh, rules, None)
    b_shard = make_batch_shardings(mesh, rules, batch_specs)
    c_shard = cache_shardings(model, mesh, rules, batch, max_seq)
    step = make_prefill_step(model, rules, max_seq)
    return jax.jit(step, in_shardings=(p_shard, b_shard),
                   out_shardings=(None, c_shard))


def jit_serve_step(model, mesh: Mesh, rules: ShardingRules, batch: int,
                   max_seq: int):
    rules = prune_for_mesh(rules, mesh)
    p_shard, _ = make_state_shardings(model, mesh, rules, None)
    c_shard = cache_shardings(model, mesh, rules, batch, max_seq)
    tok_shard = NamedSharding(mesh, rules.spec(("batch", None)))
    step = make_serve_step(model, rules)
    return jax.jit(step, in_shardings=(p_shard, c_shard, tok_shard),
                   out_shardings=(None, c_shard), donate_argnums=(1,))


# ------------------------------------------------- paged (continuous) serving
def paged_pool_sharding(model, mesh: Mesh, rules: ShardingRules):
    """NamedSharding of the paged KV pool: blocks replicated, kv_heads
    sharded along 'model' exactly like the monolithic cache's head axis."""
    axes = model.paged_cache_logical_axes()["k"]
    return NamedSharding(mesh, rules.spec(axes))


def jit_unified_step(model, mesh: Mesh, rules: ShardingRules,
                     decode_attn_backend: str = "xla",
                     chunk_attn_backend: str = "xla", chunk_attn_config=None,
                     decode_matmul_table=None, chunk_matmul_table=None):
    """(params, k_pool, v_pool,
        dec_tables, dec_lengths, dec_tokens,   # decode lane: every slot
        ch_tokens, seg_tables, seg_info,       # prefill lane: packed chunk
        dec_sampling, dec_keys,                # per-slot sampling (traced)
        seg_sampling, seg_keys)                # per-segment sampling (traced)
        -> (dec_next (slots,), seg_next (S,), k_pool, v_pool)

    THE serving step program for steps that carry prompt work: each
    invocation carries up to `chunk_tokens` of pending prompt work —
    ch_tokens is a fixed-width (1, C) buffer PACKED with contiguous prompt
    segments from up to S requests, described by the traced (S, 3)
    descriptor array `seg_info` ([row_offset, seg_len, kv_start] per
    segment) and the (S, nbt) per-segment block tables — alongside a
    decode token for every in-flight slot.  All lanes share the paged
    pool: every chunk row scatters its K/V into its OWN segment's blocks
    (committed incrementally, chunk by chunk) and the decode lane appends
    one row per active slot, all inside a single compiled program.

    Every argument shape is static in (slots, pool blocks, table width,
    chunk budget, segment slots), so admission, chunk progress, packing,
    retirement, preemption and resume are pure data updates — this program
    compiles exactly ONCE.  Idle segment slots are masked by data (seg_len
    0 with an all-null table; padding rows divert to the sink block), and
    slots that are empty or still prefilling carry all-null decode tables
    with length 0.  Masking hides results, not FLOPs — the chunk lane
    executes at its compiled width whenever THIS program runs, which is
    exactly why chunk-less steps dispatch `jit_decode_only_step` instead
    (the second and last step executable; see ContinuousEngine.step).

    seg_next holds each segment's next-token sample, valid only for
    segments that complete their prompt this step (the host consumes
    exactly those).  Sampling is FUSED per lane via
    `repro.kernels.sampling.sample_tokens`: the (rows, 3) float32
    [temperature, top_k, top_p] and (rows, 3) int32 [seed, rid,
    token_index] arrays are traced data, so per-request knobs never
    retrace — greedy rows (temperature 0) reduce bitwise to the argmax
    path this program always had.  The attention backends and the
    per-stage matmul tables (the plan's `decode` and `prefill_chunk`
    stage choices) are closed over — static at trace time, zero per-step
    dispatch cost."""
    # imported here: repro.serve imports this module
    from repro.serve.trace import scope

    rules = prune_for_mesh(rules, mesh)
    p_shard, _ = make_state_shardings(model, mesh, rules, None)
    pool_shard = paged_pool_sharding(model, mesh, rules)
    slot_shard = NamedSharding(mesh, rules.spec(("batch",)))
    row_shard = NamedSharding(mesh, rules.spec(("batch", None)))

    def unified_step(params, k_pool, v_pool, dec_tables, dec_lengths,
                     dec_tokens, ch_tokens, seg_tables, seg_info,
                     dec_sampling, dec_keys, seg_sampling, seg_keys):
        with activation_rules(rules):
            # prefill lane: a packed chunk of prompt segments, K/V committed
            # to each segment's blocks in-program (no separate commit)
            with matmul_dispatch(chunk_matmul_table), scope("prefill_lane"):
                ch_logits, k_pool, v_pool = model.prefill_packed_paged(
                    params, k_pool, v_pool, seg_tables, ch_tokens,
                    seg_info, attn_backend=chunk_attn_backend,
                    attn_config=chunk_attn_config)
            # decode lane: one token for every slot (the lanes touch
            # disjoint blocks — a request never prefills and decodes in the
            # same step — so XLA is free to schedule them together)
            with matmul_dispatch(decode_matmul_table), scope("decode_lane"):
                logits, k_pool, v_pool = model.decode_step_paged(
                    params, k_pool, v_pool, dec_tables, dec_lengths,
                    dec_tokens, attn_backend=decode_attn_backend)
        # keyed sampling fused for all lanes: seg_next[s] is the first
        # token of segment s's request, valid only when that segment
        # completes its prompt (the host consumes it exactly then)
        with scope("sample"):
            nxt = sample_tokens(logits[:, -1], dec_sampling, dec_keys)
            seg_next = sample_tokens(ch_logits[0], seg_sampling, seg_keys)
        return nxt, seg_next, k_pool, v_pool

    return jax.jit(
        unified_step,
        in_shardings=(p_shard, pool_shard, pool_shard, row_shard, slot_shard,
                      row_shard, None, None, None, None, None, None, None),
        out_shardings=(None, None, pool_shard, pool_shard),
        donate_argnums=(1, 2),
    )


def jit_decode_only_step(model, mesh: Mesh, rules: ShardingRules,
                         decode_attn_backend: str = "xla",
                         decode_matmul_table=None):
    """(params, k_pool, v_pool, dec_tables, dec_lengths, dec_tokens,
        dec_sampling, dec_keys)
        -> (dec_next (slots,), k_pool, v_pool)

    The decode-only fast path: the unified step's decode lane compiled
    WITHOUT the chunk lane.  `jit_unified_step` executes its prefill lane
    at the full compiled chunk width even when every descriptor row is
    idle — the budget would price every step — so the engine dispatches
    this program instead whenever no prompt work is pending.  Pool/table
    shapes and shardings match the unified program exactly (the donated
    pools ping-pong between the two executables without a layout shift),
    and the decode lane's float program is identical — an active slot's
    attention never reads the sink block the idle chunk lane would have
    scribbled on, so switching programs step to step is invisible to the
    token streams.  With it the serving runtime owns exactly TWO step
    executables, chosen per step by whether prompt work exists; admission
    still compiles nothing."""
    # imported here: repro.serve imports this module
    from repro.serve.trace import scope

    rules = prune_for_mesh(rules, mesh)
    p_shard, _ = make_state_shardings(model, mesh, rules, None)
    pool_shard = paged_pool_sharding(model, mesh, rules)
    slot_shard = NamedSharding(mesh, rules.spec(("batch",)))
    row_shard = NamedSharding(mesh, rules.spec(("batch", None)))

    def decode_only_step(params, k_pool, v_pool, dec_tables, dec_lengths,
                         dec_tokens, dec_sampling, dec_keys):
        with activation_rules(rules):
            with matmul_dispatch(decode_matmul_table), scope("decode_lane"):
                logits, k_pool, v_pool = model.decode_step_paged(
                    params, k_pool, v_pool, dec_tables, dec_lengths,
                    dec_tokens, attn_backend=decode_attn_backend)
        with scope("sample"):
            nxt = sample_tokens(logits[:, -1], dec_sampling, dec_keys)
        return nxt, k_pool, v_pool

    return jax.jit(
        decode_only_step,
        in_shardings=(p_shard, pool_shard, pool_shard, row_shard, slot_shard,
                      row_shard, None, None),
        out_shardings=(None, pool_shard, pool_shard),
        donate_argnums=(1, 2),
    )


def jit_commit_prefill(model, mesh: Mesh, rules: ShardingRules):
    """(k_pool, v_pool, ks, vs, block_ids) -> (k_pool, v_pool)

    Scatter up to S resuming requests' per-layer K/V
    (L, S, S_pad, Hkv, hd) into the physical pool at `block_ids`
    ((S, S_pad/block_size) entries; padding entries — short tables and
    empty segment rows alike — point at the null sink block, whose payload
    rows are zeros and which is never read).  Donates the pools.

    Since the unified step commits prefill KV in-program (chunk by chunk),
    this is now only the *resume* path: preempted requests' swapped-out
    KV, read back from the host buffers and scattered into their freshly
    allocated blocks (`ContinuousEngine._resume_group`).  Resume always
    pads to S segments of the full table width (max_blocks_per_seq
    blocks), so exactly one shape ever traces — no bucket ladder anywhere
    in the serving runtime — and a burst of K swap-ins lands in
    ceil(K / S) invocations instead of K."""
    rules = prune_for_mesh(rules, mesh)
    pool_shard = paged_pool_sharding(model, mesh, rules)

    def commit(k_pool, v_pool, ks, vs, block_ids):
        n_layers, block_size = k_pool.shape[0], k_pool.shape[2]
        n_seg, s_pad = ks.shape[1], ks.shape[2]
        nb = s_pad // block_size
        kb = ks.reshape(n_layers, n_seg * nb, block_size, *ks.shape[3:])
        vb = vs.reshape(n_layers, n_seg * nb, block_size, *vs.shape[3:])
        flat_ids = block_ids.reshape(-1)
        k_pool = k_pool.at[:, flat_ids].set(kb.astype(k_pool.dtype))
        v_pool = v_pool.at[:, flat_ids].set(vb.astype(v_pool.dtype))
        return k_pool, v_pool

    return jax.jit(commit, in_shardings=(pool_shard, pool_shard, None, None,
                                         None),
                   out_shardings=(pool_shard, pool_shard),
                   donate_argnums=(0, 1))


def jit_cow_block(model, mesh: Mesh, rules: ShardingRules):
    """(k_pool, v_pool, src, dst) -> (k_pool, v_pool)

    Device side of copy-on-write: duplicate physical block `src` into
    `dst` in both pools.  The host allocator has already repointed the
    writing request's block table at `dst`; co-owners keep reading `src`.
    Block ids are traced scalars, so every CoW shares ONE executable —
    lazily compiled at the first copy, never on admission.  Donates the
    pools (they ping-pong exactly like the step programs')."""
    rules = prune_for_mesh(rules, mesh)
    pool_shard = paged_pool_sharding(model, mesh, rules)

    def copy(k_pool, v_pool, src, dst):
        k_pool = k_pool.at[:, dst].set(k_pool[:, src])
        v_pool = v_pool.at[:, dst].set(v_pool[:, src])
        return k_pool, v_pool

    return jax.jit(copy,
                   in_shardings=(pool_shard, pool_shard, None, None),
                   out_shardings=(pool_shard, pool_shard),
                   donate_argnums=(0, 1))


# ------------------------------------------- slot-pooled (continuous) serving
# The state-cache families' counterparts of the paged builders above: the
# per-request state is fixed-size (conv window + SSM state), so the pool is
# a (layers, num_slots, ...) grid, the "block table" degenerates to ONE
# traced row index per request, and there is no growth and no in-decode
# extension — otherwise the program discipline is identical: every shape is
# static in (slots, pool rows, chunk width), exactly two step executables,
# admission compiles nothing.

def slot_state_shardings(model, mesh: Mesh, rules: ShardingRules):
    """(conv NamedSharding, ssm NamedSharding) of the slot state pools:
    pool rows replicated, feature axes sharded per the model's declared
    logical axes (`MambaLM.slot_state_logical_axes`)."""
    axes = model.slot_state_logical_axes()
    return (NamedSharding(mesh, rules.spec(axes["conv"])),
            NamedSharding(mesh, rules.spec(axes["ssm"])))


def jit_ssm_unified_step(model, mesh: Mesh, rules: ShardingRules,
                         decode_matmul_table=None, chunk_matmul_table=None):
    """(params, conv_pool, ssm_pool,
        dec_state_idx, dec_tokens,                # decode lane: every slot
        ch_tokens, ch_state_idx, ch_seg_len, ch_seg_start,  # prefill lane
        dec_sampling, dec_keys,                   # per-slot sampling (traced)
        ch_sampling, ch_keys)                     # (1, 3) chunk sampling
        -> (dec_next (slots,), ch_next (), conv_pool, ssm_pool)

    THE ssm serving step for steps that carry prompt work: one C-token
    prompt segment (C a multiple of `cfg.ssm_chunk`, rows past `ch_seg_len`
    dt-masked into exact identities) committed into the chunk request's
    state row, alongside a decode token for every in-flight slot
    (`dec_state_idx` maps slot -> pool row; idle/prefilling slots point at
    the null row).  The lanes touch disjoint pool rows — a request never
    prefills and decodes in the same step — so XLA may schedule them
    freely.  Every index is traced data: admission, chunk progress,
    retirement, preemption and resume never recompile, and `ch_seg_start
    == 0` selects zero carries in-program so a freshly claimed row needs no
    zeroing pass.  `ch_next` is the segment's next-token sample, consumed
    by the host only when the segment completes its prompt.  Sampling is
    fused exactly as in the paged steps (`repro.kernels.sampling`): the
    per-slot / per-chunk sampling and key arrays are traced data, greedy
    rows reduce bitwise to the argmax path."""
    # imported here: repro.serve imports this module
    from repro.serve.trace import scope

    rules = prune_for_mesh(rules, mesh)
    p_shard, _ = make_state_shardings(model, mesh, rules, None)
    conv_shard, ssm_shard = slot_state_shardings(model, mesh, rules)
    slot_shard = NamedSharding(mesh, rules.spec(("batch",)))
    row_shard = NamedSharding(mesh, rules.spec(("batch", None)))

    def ssm_unified_step(params, conv_pool, ssm_pool, dec_state_idx,
                         dec_tokens, ch_tokens, ch_state_idx, ch_seg_len,
                         ch_seg_start, dec_sampling, dec_keys, ch_sampling,
                         ch_keys):
        with activation_rules(rules):
            # prefill lane: one prompt segment, state committed in-program
            with matmul_dispatch(chunk_matmul_table), scope("prefill_lane"):
                ch_logits, conv_pool, ssm_pool = model.prefill_chunk_slots(
                    params, conv_pool, ssm_pool, ch_state_idx, ch_tokens,
                    ch_seg_len, ch_seg_start)
            # decode lane: one token for every slot
            with matmul_dispatch(decode_matmul_table), scope("decode_lane"):
                logits, conv_pool, ssm_pool = model.decode_step_slots(
                    params, conv_pool, ssm_pool, dec_state_idx, dec_tokens)
        with scope("sample"):
            nxt = sample_tokens(logits[:, -1], dec_sampling, dec_keys)
            ch_next = sample_tokens(ch_logits[:, -1], ch_sampling,
                                    ch_keys)[0]
        return nxt, ch_next, conv_pool, ssm_pool

    return jax.jit(
        ssm_unified_step,
        in_shardings=(p_shard, conv_shard, ssm_shard, slot_shard, row_shard,
                      None, None, None, None, None, None, None, None),
        out_shardings=(None, None, conv_shard, ssm_shard),
        donate_argnums=(1, 2),
    )


def jit_ssm_decode_only_step(model, mesh: Mesh, rules: ShardingRules,
                             decode_matmul_table=None):
    """(params, conv_pool, ssm_pool, dec_state_idx, dec_tokens,
        dec_sampling, dec_keys)
        -> (dec_next (slots,), conv_pool, ssm_pool)

    The ssm decode-only fast path: the unified step's decode lane compiled
    without the chunk lane, dispatched whenever no prompt work is pending.
    Pool shapes/shardings match the unified program exactly, so the donated
    pools ping-pong between the two executables without a layout shift, and
    the decode lane's float program is identical — switching programs is
    invisible to the token streams."""
    # imported here: repro.serve imports this module
    from repro.serve.trace import scope

    rules = prune_for_mesh(rules, mesh)
    p_shard, _ = make_state_shardings(model, mesh, rules, None)
    conv_shard, ssm_shard = slot_state_shardings(model, mesh, rules)
    slot_shard = NamedSharding(mesh, rules.spec(("batch",)))
    row_shard = NamedSharding(mesh, rules.spec(("batch", None)))

    def ssm_decode_only_step(params, conv_pool, ssm_pool, dec_state_idx,
                             dec_tokens, dec_sampling, dec_keys):
        with activation_rules(rules):
            with matmul_dispatch(decode_matmul_table), scope("decode_lane"):
                logits, conv_pool, ssm_pool = model.decode_step_slots(
                    params, conv_pool, ssm_pool, dec_state_idx, dec_tokens)
        with scope("sample"):
            nxt = sample_tokens(logits[:, -1], dec_sampling, dec_keys)
        return nxt, conv_pool, ssm_pool

    return jax.jit(
        ssm_decode_only_step,
        in_shardings=(p_shard, conv_shard, ssm_shard, slot_shard, row_shard,
                      None, None),
        out_shardings=(None, conv_shard, ssm_shard),
        donate_argnums=(1, 2),
    )


def jit_ssm_commit_state(model, mesh: Mesh, rules: ShardingRules):
    """(conv_pool, ssm_pool, conv, ssm, rows) -> (conv_pool, ssm_pool)

    Scatter up to S resuming requests' per-layer state (conv
    (L, S, W-1, conv_dim), ssm (L, S, nh, hd, n)) into pool rows `rows`
    ((S,) entries; padding entries point at the null row 0 with zero
    payloads — zeros over zeros, never read) — the ssm resume path:
    preempted requests' swapped-out state read back from the host buffers
    into their freshly claimed rows.  `rows` is traced data, so exactly
    one shape ever traces, and a burst of K swap-ins lands in ceil(K / S)
    invocations.  Donates the pools."""
    rules = prune_for_mesh(rules, mesh)
    conv_shard, ssm_shard = slot_state_shardings(model, mesh, rules)

    def commit(conv_pool, ssm_pool, conv, ssm, rows):
        conv_pool = conv_pool.at[:, rows].set(conv.astype(conv_pool.dtype))
        ssm_pool = ssm_pool.at[:, rows].set(ssm.astype(ssm_pool.dtype))
        return conv_pool, ssm_pool

    return jax.jit(commit,
                   in_shardings=(conv_shard, ssm_shard, None, None, None),
                   out_shardings=(conv_shard, ssm_shard),
                   donate_argnums=(0, 1))
