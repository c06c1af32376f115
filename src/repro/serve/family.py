"""The engine/model-family seam: per-family adapters for continuous batching.

`ContinuousEngine` (runtime.py) is family-AGNOSTIC orchestration — admit,
schedule, grow-or-preempt, dispatch, retire — over an abstract notion of
"the family's per-request device state".  Everything that knows WHAT that
state is lives here, behind one adapter object per model family:

  * `DecoderFamilyAdapter` — the paged-KV family (attention decoders).
    Per-request state is a growing set of KV blocks: the adapter owns the
    `PagedKVCache`, the block-table bookkeeping, and the paged step
    programs (`jit_unified_step` / `jit_decode_only_step` /
    `jit_commit_prefill`).  This is a verbatim relocation of the logic the
    engine used to inline — same programs, same shapes, same call order —
    so carving the seam is a provable no-op: byte-identical token streams
    and the same two step executables.

  * `SSMFamilyAdapter` — the state-cache family (`zoo.MambaLM`).
    Per-request state is FIXED-SIZE (one depthwise-conv window plus one
    SSM state per layer), so the paged machinery collapses: the pool is a
    `SlotStateCache` grid of state rows, the "block table" degenerates to
    one traced row index per slot, growth is a no-op, and the footprint is
    claimed lazily when the request's first prompt chunk dispatches
    (`claim_chunk`) — which is how a state pool smaller than the slot
    count drives the engine's ordinary preemption path.

The adapter protocol (duck-typed; both classes implement it):

    family              str tag stamped on metrics and trace events
    chunk_width         the prefill lane's resolved token width
    chunk_segments      segments one chunk may pack (ssm: always 1)
    resume_segments     swap-ins one commit invocation may pack
    cache               the family's device-state container (swap buffers)
    alloc               its allocator (trace binding, occupancy, invariants)
    capacity()          the scheduler's capacity-seam object
    victim_eligible     predicate narrowing preemption victims (or None)
    grow_for_decode(req, need_rows) -> bool   cover the next decode write
    claim_chunk(req, start, n) -> bool        cover a prompt chunk dispatch
                        (decoder: copy-on-write any shared block the
                        chunk's rows [start, start+n) would land in)
    register_prefix(req)                      index req's committed
                        full-block prompt prefixes for later sharing
                        (ssm: no-op — state rows are not shareable)
    swap_out(rid) -> nbytes                   device state -> host buffer
    resume_commit(group) -> [nbytes, ...]     host buffers -> device state:
                        ONE commit invocation for up to resume_segments
                        re-admitted requests
    dispatch(params, slots, lengths, last_tok, chunks)
                        -> (next_tokens (slots,), seg_next | None, compiled)

`dispatch` builds the decode lane's per-slot arrays from the scheduler's
slots (`decode_lane_host`: rids, sampling knobs and key triples) and the
chunk lane's per-segment arrays (token index 0 — a segment's sample is
the request's FIRST token), and threads both into its step programs as
traced data.  Its phases are the `serve.inputs` / `serve.launch` /
`serve.sync` spans (`repro.serve.trace`); `compiled` says the call built a
new executable, whose scope table `trace.launch` recorded.

The engine reads `_unified` / `_decode_only` / `_commit` off the adapter
for compile-count accounting (each is a jitted program whose
`_cache_size()` pins the exactly-two-executables property).
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.distributed.sharding import ShardingRules, prune_for_mesh
from repro.launch.steps import (
    jit_commit_prefill,
    jit_cow_block,
    jit_decode_only_step,
    jit_ssm_commit_state,
    jit_ssm_decode_only_step,
    jit_ssm_unified_step,
    jit_unified_step,
    paged_pool_sharding,
    slot_state_shardings,
)
from repro.serve.kvcache import NULL_BLOCK, PagedKVCache
from repro.serve.router import PlanRouter, serve_stages
from repro.serve.sampling import (segment_sampling_arrays,
                                  slot_sampling_arrays)
from repro.serve.scheduler import PagedCapacity, ServeRequest
from repro.serve.statecache import SlotStateCache, SlotCapacity
from repro.serve.trace import launch, span


def decode_lane_host(slots):
    """The decode lane's per-slot host arrays: each slot's rid where it
    decodes (None where empty or still prefilling — masked exactly like an
    empty slot), and its sampling knobs and PRNG key triple."""
    dec_rids = [r.rid if (r is not None and not r.prefilling) else None
                for r in slots]
    dec_sampling, dec_keys = slot_sampling_arrays(slots)
    return dec_rids, dec_sampling, dec_keys


def resolve_family_adapter(model):
    """The adapter class serving `model`, by capability probe: the ssm
    slot-pooled entry points first (`decode_step_slots`), then the paged
    decode path.  Raises TypeError for families with neither — they serve
    through the fixed-batch `ServeEngine`."""
    if getattr(model.cfg, "family", None) == "ssm" and hasattr(
            model, "decode_step_slots"):
        return SSMFamilyAdapter
    if hasattr(model, "decode_step_paged"):
        return DecoderFamilyAdapter
    raise TypeError(
        f"{type(model).__name__} has no paged decode path; use the "
        "fixed-batch ServeEngine for this family")


class DecoderFamilyAdapter:
    """Paged-KV family: block-table bookkeeping + the paged step programs."""

    family = "decoder"

    def __init__(self, model, mesh, rules: ShardingRules, cfg,
                 router: PlanRouter):
        mcfg = model.cfg
        self.kv_cfg = cfg.kv_config()
        # the pools are BORN in their serving sharding (blocks replicated,
        # kv_heads over the model axis): the unified program's donated pool
        # arguments then carry the same sharding on the very first step as
        # on every later one, so exactly one executable ever builds — a
        # replicated-first-call would compile a second, layout-shifted copy
        pool_shard = paged_pool_sharding(model, mesh,
                                         prune_for_mesh(rules, mesh))
        self.cache = PagedKVCache(self.kv_cfg, mcfg.n_layers, mcfg.n_kv_heads,
                                  mcfg.hd, jnp.dtype(mcfg.dtype),
                                  sharding=pool_shard)
        # fixed prefill-lane geometry: the step's prompt-token budget and
        # the packed-segment descriptor height, both compiled in.  The
        # height is the EFFECTIVE packing width — cfg.chunk_segments
        # narrowed by the plan's tuned `max_segments` (old Pallas plans,
        # tuned before the segmented kernel existed, narrow it to 1) — so
        # the segmented kernel's grid is exactly as tall as the packing
        # the scheduler will actually do: the tuned knob sizes the grid,
        # it doesn't just throttle host-side packing under a wider one.
        self.chunk_width = cfg.chunk_width
        self.chunk_segments = max(1, min(
            cfg.chunk_segments,
            router.chunk_segments(default=cfg.chunk_segments)))
        # THE two compiled step programs: the unified step carrying the
        # decode batch plus one packed prompt chunk, and the decode-only
        # fast path for steps with no prompt work (the unified program's
        # chunk lane executes at its compiled width even when idle, so
        # skipping it is a dispatch decision, not a mask).  Attention
        # backends and per-stage matmul lane tables come from the plan's
        # stage choices (decode + the prefill_chunk stage), closed over at
        # trace time — dispatch never recompiles mid-serve, and admission
        # compiles nothing at all.
        decode_backend, _ = router.attention_backend("decode")
        chunk_backend, chunk_config = router.attention_backend(
            "prefill_chunk")
        self._unified = jit_unified_step(
            model, mesh, rules,
            decode_attn_backend=decode_backend,
            chunk_attn_backend=chunk_backend,
            chunk_attn_config=chunk_config,
            decode_matmul_table=router.matmul_table("decode"),
            chunk_matmul_table=router.matmul_table("prefill_chunk"))
        self._decode_only = jit_decode_only_step(
            model, mesh, rules,
            decode_attn_backend=decode_backend,
            decode_matmul_table=router.matmul_table("decode"))
        # resume-only commit (swap-in scatter): single full-width shape
        # carrying up to `resume_segments` requests per invocation, padded
        # segments diverted to the null sink
        self.resume_segments = self.chunk_segments
        self._commit = jit_commit_prefill(model, mesh, rules)
        # copy-on-write block duplication (prefix sharing); jit is lazy, so
        # this compiles at the FIRST shared-block write, never on admission
        self._cow = jit_cow_block(model, mesh, rules)

    # ------------------------------------------------------------- capacity
    @property
    def alloc(self):
        return self.cache.alloc

    def capacity(self) -> PagedCapacity:
        return PagedCapacity(self.kv_cfg, self.cache.alloc)

    # every resident holds blocks from admission: any victim frees capacity
    victim_eligible = None

    def _cow_rows(self, req: ServeRequest, lo_row: int, hi_row: int) -> bool:
        """Copy-on-write every shared block whose rows intersect
        [lo_row, hi_row) before a write lands there: the allocator swaps a
        fresh private block into req's table and the jitted copy program
        duplicates the payload — co-owners keep reading the original.
        False when the pool runs dry mid-way (the engine preempts a victim
        and retries; copies already made stay consistent)."""
        if not self.kv_cfg.prefix_sharing or hi_row <= lo_row:
            return True
        bs = self.kv_cfg.block_size
        for bi in range(lo_row // bs, (hi_row - 1) // bs + 1):
            try:
                copied = self.cache.alloc.cow(req.rid, bi)
            except MemoryError:
                return False
            if copied is not None:
                src, dst = copied
                self.cache.k, self.cache.v = self._cow(
                    self.cache.k, self.cache.v, np.int32(src), np.int32(dst))
        return True

    def grow_for_decode(self, req: ServeRequest, need_rows: int) -> bool:
        """Extend req's block table to cover its next decode write; False
        when the pool is dry (the engine preempts a victim and retries).
        The decode write lands at row need_rows - 1 — privatize that block
        first if it is shared (full-prompt prefix adoption can leave the
        last prompt block shared into decode)."""
        if not self.cache.alloc.extend(req.rid, need_rows):
            return False
        return self._cow_rows(req, need_rows - 1, need_rows)

    def claim_chunk(self, req: ServeRequest, start: int, n: int) -> bool:
        """Admission already allocated the prompt's blocks; the only lazy
        work is copy-on-write when the chunk's KV rows [start, start+n)
        would land in a block adopted from the prefix index."""
        return self._cow_rows(req, start, start + n)

    def register_prefix(self, req: ServeRequest) -> None:
        """Index req's committed full-block prompt prefixes so later
        admissions can adopt them (first registration wins)."""
        if self.kv_cfg.prefix_sharing:
            self.cache.alloc.register_prefix(
                req.rid, req.prompt, min(req.prefilled, req.prompt_len))

    # ------------------------------------------------------------- swapping
    def is_swapped(self, rid: int) -> bool:
        return self.cache.is_swapped(rid)

    def swap_out(self, rid: int) -> int:
        return self.cache.swap_out(rid)

    def resume_commit(self, group: List[ServeRequest]) -> List[int]:
        """Swap up to `resume_segments` re-admitted requests' KV back in
        with ONE commit invocation: each host buffer scatters into its
        freshly allocated blocks, every segment padded to the FULL table
        width and the group padded to the full segment count (padding ids
        point at the null sink with zero payloads) so exactly one commit
        shape ever traces.  Returns the bytes moved per request."""
        assert 0 < len(group) <= self.resume_segments
        bs = self.kv_cfg.block_size
        nb_pad = self.kv_cfg.max_blocks_per_seq
        n_seg = self.resume_segments
        hosts = [self.cache.take_swapped(r.rid) for r in group]
        k0 = hosts[0][0]
        L = k0.shape[0]
        ks = np.zeros((L, n_seg, nb_pad * bs, *k0.shape[3:]), k0.dtype)
        vs = np.zeros_like(ks)
        ids = np.full((n_seg, nb_pad), NULL_BLOCK, np.int32)
        nbytes: List[int] = []
        for i, (req, (k_host, v_host)) in enumerate(zip(group, hosts)):
            nbytes.append(k_host.nbytes + v_host.nbytes)
            table = self.cache.alloc.tables[req.rid]
            nb = k_host.shape[1]
            assert nb == len(table)
            ks[:, i, :nb * bs] = k_host.reshape(L, nb * bs, *k_host.shape[3:])
            vs[:, i, :nb * bs] = v_host.reshape(L, nb * bs, *v_host.shape[3:])
            ids[i, :nb] = table
        self.cache.k, self.cache.v = self._commit(
            self.cache.k, self.cache.v, jnp.asarray(ks), jnp.asarray(vs),
            jnp.asarray(ids))
        return nbytes

    # ------------------------------------------------------------- dispatch
    def _chunk_inputs(self, chunks: List[Tuple[ServeRequest, int, int]]):
        """Host-side prefill-lane arrays for a packed chunk: the segments'
        prompt slices concatenated from row 0 (fixed `chunk_width`,
        zero-padded), each segment's block table, and the (S, 3) descriptor
        array [row_offset, seg_len, kv_start].  Idle segment slots carry
        seg_len 0 with an all-null table (their row_offset sits at the fill
        level so offsets stay monotone; padding rows divert to the sink)."""
        c = self.chunk_width
        ns = self.chunk_segments
        toks = np.zeros((1, c), np.int32)
        tables = np.full((ns, self.kv_cfg.max_blocks_per_seq),
                         NULL_BLOCK, np.int32)
        info = np.zeros((ns, 3), np.int32)
        q0 = 0
        for i, (req, start, n) in enumerate(chunks):
            toks[0, q0:q0 + n] = req.prompt[start:start + n]
            held = self.cache.alloc.tables[req.rid]
            tables[i, :len(held)] = held
            info[i] = (q0, n, start)
            q0 += n
        info[len(chunks):, 0] = q0            # idle slots: empty span at fill
        return toks, tables, info

    def dispatch(self, params, slots: List[Optional[ServeRequest]],
                 lengths: np.ndarray, last_tok: np.ndarray,
                 chunks: List[Tuple[ServeRequest, int, int]]):
        """Run ONE step program invocation: the unified step when `chunks`
        carries prompt work, else the decode-only fast path.  Returns the
        decode lane's next tokens (host, (slots,)), the chunk segments'
        next-token samples ((segments,) or None) and whether the call
        compiled."""
        with span("serve.inputs"):
            dec_rids, dec_sampling, dec_keys = decode_lane_host(slots)
            dec = (jnp.asarray(self.cache.table_array(dec_rids)),
                   jnp.asarray(lengths), jnp.asarray(last_tok[:, None]))
            samp = (jnp.asarray(dec_sampling), jnp.asarray(dec_keys))
            if chunks:
                ch_toks, seg_tables, seg_info = self._chunk_inputs(chunks)
                seg_sp, seg_ks = segment_sampling_arrays(chunks,
                                                         self.chunk_segments)
                chunk = tuple(map(jnp.asarray, (ch_toks, seg_tables,
                                                seg_info)))
                seg_samp = (jnp.asarray(seg_sp), jnp.asarray(seg_ks))
        if chunks:
            (nxt_dev, seg_next_dev, self.cache.k, self.cache.v), compiled = \
                launch(self._unified, params, self.cache.k, self.cache.v,
                       *dec, *chunk, *samp, *seg_samp)
        else:
            # decode-only fast path: no prompt work pending, so the step
            # skips the chunk-wide forward instead of masking it
            (nxt_dev, self.cache.k, self.cache.v), compiled = launch(
                self._decode_only, params, self.cache.k, self.cache.v,
                *dec, *samp)
        with span("serve.sync"):
            nxt = np.asarray(nxt_dev, np.int32)
            seg_next = (np.asarray(seg_next_dev, np.int32) if chunks
                        else None)
        return nxt, seg_next, compiled

    def occupancy(self) -> float:
        return self.cache.alloc.occupancy()


class SSMFamilyAdapter:
    """State-cache family: slot-pooled conv/SSM state + the ssm programs.

    Chunk geometry: the lane width is `cfg.chunk_width` rounded UP to a
    multiple of the model's SSD scan block (`cfg.ssm_chunk`) so every
    non-final prompt chunk splits the scan exactly on a block boundary —
    the condition under which chunked prefill is bitwise identical to the
    fixed-batch whole-prompt prefill.  Packing is 1: the SSD recurrence
    threads ONE request's carry through the lane, so segments cannot
    share it the way disjoint paged block-tables can."""

    family = "ssm"

    def __init__(self, model, mesh, rules: ShardingRules, cfg,
                 router: PlanRouter):
        mcfg = model.cfg
        q = max(1, mcfg.ssm_chunk)
        self.chunk_width = -(-cfg.chunk_width // q) * q
        self.chunk_segments = 1
        # resume packing is independent of chunk packing: state rows are
        # disjoint scatter targets, so one commit can carry many swap-ins
        # even though the SSD recurrence holds the chunk lane at width 1
        self.resume_segments = max(1, cfg.chunk_segments)
        self.state_cfg = cfg.state_config()
        # state pools born in their serving sharding (rows replicated,
        # feature dims over the model axis) — same one-executable donation
        # argument as the paged pools above
        self.cache = SlotStateCache.for_model(
            self.state_cfg, mcfg,
            shardings=slot_state_shardings(model, mesh,
                                           prune_for_mesh(rules, mesh)))
        chunk_stage, decode_stage = "ssm_prefill_chunk", "ssm_decode"
        assert chunk_stage in serve_stages(self.family)
        self._unified = jit_ssm_unified_step(
            model, mesh, rules,
            decode_matmul_table=router.matmul_table(decode_stage),
            chunk_matmul_table=router.matmul_table(chunk_stage))
        self._decode_only = jit_ssm_decode_only_step(
            model, mesh, rules,
            decode_matmul_table=router.matmul_table(decode_stage))
        self._commit = jit_ssm_commit_state(model, mesh, rules)

    # ------------------------------------------------------------- capacity
    @property
    def alloc(self):
        return self.cache.alloc

    def capacity(self) -> SlotCapacity:
        return SlotCapacity(self.cache.alloc)

    @property
    def victim_eligible(self):
        # fresh admission reserves nothing, so a resident that has not yet
        # dispatched its first chunk owns no state row — evicting it frees
        # no capacity.  Narrow victims to actual row holders.
        return lambda r: self.cache.alloc.holds(r.rid)

    def grow_for_decode(self, req: ServeRequest, need_rows: int) -> bool:
        # fixed-size state: nothing grows during decode
        return True

    def claim_chunk(self, req: ServeRequest, start: int, n: int) -> bool:
        """Lazily claim req's state row at first-chunk dispatch; False when
        the pool is dry (the engine preempts a row holder and retries).
        The chunk geometry is irrelevant: state rows are fixed-size and
        never shared, so there is nothing to copy-on-write."""
        if self.cache.alloc.holds(req.rid):
            return True
        if not self.cache.alloc.can_allocate(1):
            return False
        self.cache.alloc.allocate(req.rid)
        return True

    def register_prefix(self, req: ServeRequest) -> None:
        # recurrent state is a lossy summary of the whole prefix — rows are
        # owned by exactly one request, so there is no prefix index to feed
        return None

    # ------------------------------------------------------------- swapping
    def is_swapped(self, rid: int) -> bool:
        return self.cache.is_swapped(rid)

    def swap_out(self, rid: int) -> int:
        return self.cache.swap_out(rid)

    def resume_commit(self, group: List[ServeRequest]) -> List[int]:
        """Scatter up to `resume_segments` re-admitted requests' host-side
        (conv, ssm) states into their freshly claimed pool rows with ONE
        commit invocation.  The row array is traced data, padded entries
        point at the null row with zero payloads — one shape ever traces.
        Returns the bytes moved per request."""
        assert 0 < len(group) <= self.resume_segments
        n_seg = self.resume_segments
        hosts = [self.cache.take_swapped(r.rid) for r in group]
        conv0, ssm0 = hosts[0]
        conv = np.zeros((conv0.shape[0], n_seg, *conv0.shape[1:]),
                        conv0.dtype)
        ssm = np.zeros((ssm0.shape[0], n_seg, *ssm0.shape[1:]), ssm0.dtype)
        rows = np.zeros((n_seg,), np.int32)   # padding -> null row sink
        nbytes: List[int] = []
        for i, (req, (conv_host, ssm_host)) in enumerate(zip(group, hosts)):
            nbytes.append(conv_host.nbytes + ssm_host.nbytes)
            conv[:, i] = conv_host
            ssm[:, i] = ssm_host
            rows[i] = self.cache.alloc.slot_of(req.rid)
        self.cache.conv, self.cache.ssm = self._commit(
            self.cache.conv, self.cache.ssm, jnp.asarray(conv),
            jnp.asarray(ssm), jnp.asarray(rows))
        return nbytes

    # ------------------------------------------------------------- dispatch
    def dispatch(self, params, slots: List[Optional[ServeRequest]],
                 lengths: np.ndarray, last_tok: np.ndarray,
                 chunks: List[Tuple[ServeRequest, int, int]]):
        """One ssm step program invocation.  The decode lane maps each slot
        to its state row (`index_array`; idle/prefilling slots hit the null
        row); the chunk lane carries at most ONE segment (packing width 1).
        Traced scalars go in as strongly-typed np.int32 so the weak-typed
        Python-int path can never trace a second executable."""
        with span("serve.inputs"):
            dec_rids, dec_sampling, dec_keys = decode_lane_host(slots)
            dec = (jnp.asarray(self.cache.index_array(dec_rids)),
                   jnp.asarray(last_tok[:, None]))
            samp = (jnp.asarray(dec_sampling), jnp.asarray(dec_keys))
            if chunks:
                req, start, n = chunks[0]
                ch_toks = np.zeros((1, self.chunk_width), np.int32)
                ch_toks[0, :n] = req.prompt[start:start + n]
                row = self.cache.alloc.slot_of(req.rid)
                ch_sp, ch_ks = segment_sampling_arrays(chunks, 1)
                chunk = (jnp.asarray(ch_toks), np.int32(row), np.int32(n),
                         np.int32(start))
                ch_samp = (jnp.asarray(ch_sp), jnp.asarray(ch_ks))
        if chunks:
            (nxt_dev, ch_next_dev, self.cache.conv, self.cache.ssm), \
                compiled = launch(self._unified, params, self.cache.conv,
                                  self.cache.ssm, *dec, *chunk, *samp,
                                  *ch_samp)
        else:
            (nxt_dev, self.cache.conv, self.cache.ssm), compiled = launch(
                self._decode_only, params, self.cache.conv, self.cache.ssm,
                *dec, *samp)
        with span("serve.sync"):
            nxt = np.asarray(nxt_dev, np.int32)
            ch_next = (np.asarray(ch_next_dev, np.int32).reshape(1)
                       if chunks else None)
        return nxt, ch_next, compiled

    def occupancy(self) -> float:
        return self.cache.alloc.occupancy()
