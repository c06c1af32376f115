"""Continuous-batching serving runtime (tentpole of the serving subsystem).

Request lifecycle under the UNIFIED TOKEN-BUDGET STEP:

    submit() -> waiting -> [scheduler admits into a free slot if the
                family's capacity model accepts the request — admission
                itself runs no program]
             -> chunked prefill: each engine step packs up to
                `chunk_tokens` of pending prompt work — prompt SEGMENTS
                from up to `chunk_segments` requests, oldest admission
                first, greedy fill — into the step's prefill lane,
                committing each segment's per-request state (paged KV
                blocks, or a slot-pooled conv/SSM state row) in-program,
                chunk by chunk, while the decode lane advances EVERY
                in-flight request in the same compiled program (a long
                prompt never stalls the decode batch, and short prompts no
                longer waste the budget's tail)
             -> the chunk that completes the prompt also samples the first
                token (TTFT spans all of the prompt's chunks)
             -> joins the decode batch the NEXT step; greedy decode, one
                token per engine step; per-request state grows on demand
                where the family's state grows at all; retiring on
                eos/max_new -> capacity + slot freed, metrics recorded.

THE ENGINE IS FAMILY-AGNOSTIC.  Everything that knows what a family's
per-request device state *is* lives behind a `FamilyAdapter`
(`repro.serve.family`): the paged KV-cache, block tables and paged step
programs for attention decoders (`DecoderFamilyAdapter`); the fixed-size
slot-pooled conv/SSM state and its step programs for `MambaLM`
(`SSMFamilyAdapter`).  The engine's `step()` is pure orchestration —

    admit -> schedule chunk -> grow-or-preempt -> dispatch -> retire

— and every family-specific question routes through the adapter:
`grow_for_decode` (cover the next decode write), `claim_chunk` (cover a
prompt chunk dispatch; the ssm family claims its state row lazily here),
`swap_out`/`resume_commit` (preemption transport), `dispatch` (the one
step-program invocation), `victim_eligible` (narrow preemption victims to
requests whose eviction frees capacity).  Likewise the scheduler consults
the adapter's capacity object (`scheduler.PagedCapacity` /
`statecache.SlotCapacity`) for all admission/footprint arithmetic.

One engine step = ONE invocation of one of exactly TWO jitted programs per
family: the unified step (packed prefill lane + decode lane) when prompt
work is pending, the decode-only fast path when none is — the unified
program's chunk lane executes at its compiled width even when idle, so
chunk-less steps skip it entirely instead of masking it.  Both programs'
shapes are static in (slots, pool size, table/index width, chunk budget,
segment slots): admission, chunk packing, retirement, preemption and
resume are all pure data updates.  Each program compiles exactly once.

Under pool pressure the grow path preempts: when a request cannot extend
(paged family) or claim its first-chunk state row (ssm family), the
scheduler's victim (LIFO by admission, preferring the most remaining
budget, narrowed to capacity holders) has its state swapped out to a host
buffer, its slot and capacity are released, and it joins the resume queue.
Mid-prefill requests preempt the same way — `ServeRequest.prefilled` rides
along, so a resumed request continues its prompt at the next uncommitted
token.  Resume re-admits ahead of new arrivals and scatters the saved
state back through the family's jitted commit program at one fixed shape.
No token is recomputed and the step programs never recompile (preemption
only edits index *data*).

Key properties the fixed-batch `ServeEngine` lacks:

  * requests are admitted into *running* decode batches, and long prompts
    are time-sliced: a 200-token prompt crosses the device as
    ceil(200/chunk_tokens) budgeted chunks, each sharing its step with the
    whole decode batch, instead of a dedicated B=1 prefill program that
    stalls everyone (head-of-line interference);
  * short prompts are PACKED: one step's chunk carries segments from up to
    `chunk_segments` requests (greedy fill, oldest admission first), so a
    burst of small prompts fills the budget the head request leaves idle
    instead of spending one step each (the ssm family's packing width is
    1: the SSD recurrence threads one request's carry through the lane);
  * no cross-request padding: per-slot lengths/indices mean a 12-token
    prompt next to a 200-token prompt costs 12 tokens of state;
  * exactly TWO compiled programs serve every step (static slot/pool/chunk
    shapes; the decode-only variant skips the idle chunk lane); admission
    compiles nothing, ever;
  * the tuned `InferencePlan` drives dispatch per family: stage-qualified
    choices (`decode` / `prefill_chunk` for decoders, `ssm_decode` /
    `ssm_prefill_chunk` for the state-cache family) pick each lane's
    attention backend and matmul tables separately (see
    `repro.serve.router`, `repro.kernels.dispatch`).

The engine clock is injectable (`now_fn`) so benchmarks can replay Poisson
arrival traces in wall time or virtual time with identical scheduling.

Passing a `repro.serve.trace.TraceRecorder` as `trace=` records every
scheduler / allocator / step decision as a typed event on the engine clock
(admission, chunk packing, preemption and swap, pool accounting, step
dispatch with lane fill and host time, program compiles), each stamped
with the serving family.  The recorder threads through the scheduler and
the family's allocator, exports to Chrome-trace-event JSON for
`ui.perfetto.dev`, and feeds the trace audit (`repro.serve.traceview`).
Disabled — the default — every emission site holds the no-op recorder, so
serving costs one attribute lookup per site and the per-token loops skip
even that via the `enabled` flag.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, List, Optional

import jax
import numpy as np

from repro.distributed.sharding import (ShardingRules, params_shardings,
                                        prune_for_mesh)
from repro.serve.family import resolve_family_adapter
from repro.serve.kvcache import KVCacheConfig
from repro.serve.metrics import ServeMetrics
from repro.serve.router import DEFAULT_CHUNK_TOKENS, PlanRouter
from repro.serve.sampling import SamplingParams, truncate_at_eos
from repro.serve.scheduler import ContinuousScheduler, ServeRequest
from repro.serve.statecache import StateCacheConfig
from repro.serve.trace import NULL_RECORDER, TraceRecorder, span


@dataclasses.dataclass
class RuntimeConfig:
    max_slots: int = 4                # decode batch width (compiled once)
    block_size: int = 16              # KV block granularity (token rows)
    max_blocks_per_seq: int = 8       # per-request table width
    num_blocks: Optional[int] = None  # pool size; default: slots*table + null
    max_new_tokens: int = 32          # default generation budget
    eos_id: int = -1                  # -1: never stop early
    # prompt tokens the unified step may carry per engine step (the prefill
    # lane's width).  None = max_seq: any admissible prompt prefills in one
    # chunk (the "unchunked" configuration — identical token streams, just
    # no slicing).  Smaller budgets slice long prompts across steps so the
    # decode batch keeps streaming.  The lane's width is baked into the
    # unified program, so every step that carries ANY prompt work executes
    # the full width — but chunk-less steps dispatch the compiled
    # decode-only program and skip the lane entirely, and segment packing
    # fills the width with several short prompts at once, so the budget is
    # only ever paid when (and as fully as) prompt work exists.  The
    # default is the shared `router.DEFAULT_CHUNK_TOKENS` so the engine
    # and an untuned serve plan can't drift onto different chunk shapes.
    # (The ssm family rounds the resolved width UP to a multiple of the
    # model's `ssm_chunk` so chunk boundaries split the SSD scan exactly
    # on block boundaries — see `family.SSMFamilyAdapter`.)
    chunk_tokens: Optional[int] = DEFAULT_CHUNK_TOKENS
    # prompt segments one step's chunk may pack.  Greedy fill means a step
    # carries min(chunk_segments, prefilling requests) segments; 1 restores
    # the single-request chunk lane.  A tuned plan may narrow this via its
    # prefill_chunk stage's `max_segments` choice (old Pallas plans, tuned
    # before the segmented kernel, narrow it to 1 — see
    # PlanRouter.chunk_segments); the narrowed value is the segmented
    # kernel's compiled descriptor height, so the tuned knob sizes the
    # block_q x max-segments grid itself.
    chunk_segments: int = 4
    # state-slot pool rows for the slot-pooled (ssm) family, INCLUDING the
    # reserved null row.  None = max_slots + 1 (every slot can hold state
    # simultaneously — no state-pool preemption).  Smaller pools force the
    # ordinary grow-or-preempt path at first-chunk claim time.  Ignored by
    # the paged family.
    state_slots: Optional[int] = None
    # prefix sharing (paged family only): admission may map a prompt's
    # full-block prefixes onto blocks other requests already committed
    # (refcounted, copy-on-write) and start prefill at the first unshared
    # token.  Off by default — token streams are byte-identical either
    # way; the differential suite toggles it explicitly.
    prefix_sharing: bool = False

    @property
    def max_seq(self) -> int:
        return self.block_size * self.max_blocks_per_seq

    @property
    def chunk_width(self) -> int:
        """The prefill lane's RESOLVED width: chunk_tokens clamped to
        [1, max_seq], with None meaning max_seq.  Pass THIS to
        `build_serve_plan(chunk_tokens=...)` so the plan's prefill_chunk
        stage is tuned at the width the engine actually runs."""
        return max(1, min(self.chunk_tokens or self.max_seq, self.max_seq))

    def kv_config(self) -> KVCacheConfig:
        nb = self.num_blocks
        if nb is None:
            nb = self.max_slots * self.max_blocks_per_seq + 1
        return KVCacheConfig(num_blocks=nb, block_size=self.block_size,
                             max_blocks_per_seq=self.max_blocks_per_seq,
                             prefix_sharing=self.prefix_sharing)

    def state_config(self) -> StateCacheConfig:
        ns = self.state_slots
        if ns is None:
            ns = self.max_slots + 1
        return StateCacheConfig(num_slots=ns)


class ContinuousEngine:
    """Slot-based continuous-batching engine over a family state cache."""

    # family-owned attributes tests and tools read off the engine; resolved
    # through the adapter so the seam stays invisible to existing callers
    _ADAPTER_ATTRS = ("_unified", "_decode_only", "_commit", "_cow", "cache",
                      "kv_cfg")

    def __init__(self, model, params, mesh, rules: ShardingRules,
                 cfg: RuntimeConfig, router: Optional[PlanRouter] = None,
                 now_fn: Optional[Callable[[], float]] = None,
                 trace: Optional[TraceRecorder] = None):
        self.model = model
        self.mesh = mesh
        self.cfg = cfg
        self.router = router or PlanRouter(None)
        self.now_fn = now_fn or time.perf_counter
        # the mesh tag stamped on step trace events and run metadata:
        # "<data>x<model>" ("1x1" on a single device) — the traceview audit
        # one-checks it the way it one-checks the family tag
        self.mesh_tag = "{}x{}".format(mesh.shape.get("data", 1),
                                       mesh.shape.get("model", 1))
        # structured event tracing (`repro.serve.trace`): the recorder is
        # threaded through the scheduler and the family's allocator so
        # every lifecycle / pool / step event lands in ONE stream on the
        # ENGINE clock.  Disabled (the default) it is the no-op recorder —
        # one attribute lookup per emission site, per-token hot loops guard
        # on `trace.enabled` and skip even that.
        self.trace = trace if trace is not None else NULL_RECORDER
        if self.trace.enabled and self.trace.now_fn is None:
            self.trace.now_fn = self.now_fn
        # the family seam: raises TypeError for families with neither a
        # paged nor a slot-pooled serving path.  Before the adapter builds
        # its step programs, the router folds the plan's per-stage layout
        # verdicts (and the mesh's divisibility guards) into the rules —
        # on a single-device mesh this returns `rules` untouched, so the
        # tuned layout table reaches the step builders exactly when a
        # model axis exists to shard over.
        adapter_cls = resolve_family_adapter(model)
        self.rules = self.router.serve_rules(rules, mesh, model.cfg,
                                             adapter_cls.family)
        # commit the params onto THIS mesh in the step programs' own layout
        # before any program runs: params trained (or loaded) on a
        # different mesh reshard once here, and the first step sees exactly
        # the in_shardings it compiled for — admission compiles nothing,
        # and no layout-shifted second executable can ever build
        self.params = jax.device_put(
            params, params_shardings(mesh, prune_for_mesh(self.rules, mesh),
                                     model.logical_axes()))
        self.adapter = adapter_cls(model, mesh, self.rules, cfg, self.router)
        self.family = self.adapter.family
        self.adapter.alloc.trace = self.trace
        self.scheduler = ContinuousScheduler(
            cfg.max_slots, trace=self.trace,
            capacity=self.adapter.capacity())
        self.scheduler.family = self.family
        self.metrics = ServeMetrics(family=self.family)
        self._rid = 0
        self._step_idx = 0
        self._done: List[ServeRequest] = []
        # the adapter's resolved prefill-lane geometry (see family.py)
        self._chunk_width = self.adapter.chunk_width
        self._chunk_segments = self.adapter.chunk_segments
        # per-slot host state (decode lane; prefilling slots stay zeroed so
        # their dummy decode row writes to the null sink)
        self._lengths = np.zeros((cfg.max_slots,), np.int32)
        self._last_tok = np.zeros((cfg.max_slots,), np.int32)

    def __getattr__(self, name):
        # family-owned state (compiled programs, cache, kv config) lives on
        # the adapter; keep the engine's historical attribute surface
        if name in type(self)._ADAPTER_ATTRS:
            return getattr(self.adapter, name)
        raise AttributeError(
            f"{type(self).__name__!r} object has no attribute {name!r}")

    # ------------------------------------------------------------ interface
    def submit(self, prompt: np.ndarray, max_new_tokens: Optional[int] = None,
               arrival_time: Optional[float] = None,
               sampling: Optional[SamplingParams] = None) -> int:
        self._rid += 1
        if max_new_tokens is None:
            max_new_tokens = self.cfg.max_new_tokens
        req = ServeRequest(
            rid=self._rid, prompt=np.asarray(prompt, np.int32),
            max_new_tokens=max_new_tokens,
            arrival_time=(arrival_time if arrival_time is not None
                          else self.now_fn()),
            sampling=sampling if sampling is not None else SamplingParams())
        self.scheduler.submit(req)
        return self._rid

    def run(self) -> List[ServeRequest]:
        """Step until every submitted request completes; returns them in
        completion order.  Idle steps (all slots empty, next arrival still
        in the future) back off briefly instead of spinning."""
        if self.metrics.start_time is None:
            self.metrics.start_time = self.now_fn()
        with self.mesh:
            while self.scheduler.has_work:
                if not self.step():
                    time.sleep(2e-4)
        self.metrics.end_time = self.now_fn()
        done, self._done = self._done, []
        return done

    def reset_metrics(self) -> None:
        """Fresh metrics (e.g. after a warm-up pass); compiled programs and
        cache state are kept."""
        self.metrics = ServeMetrics(family=self.family)

    # ------------------------------------------------- preemption / resume
    def _ensure_blocks(self, req: ServeRequest) -> None:
        """Grow req's state to cover its next decode write (position
        `lengths[slot]`), preempting victims while the pool is dry.  The
        submit-time guard (single-request worst case fits the pool) makes
        the loop terminate: once every other eligible request is evicted,
        req owns every allocated unit and growth cannot fail.  (Families
        with fixed-size state grow trivially — the adapter returns True.)"""
        need_rows = int(self._lengths[req.slot]) + 1
        while not self.adapter.grow_for_decode(req, need_rows):
            victim = self.scheduler.victim_for_preemption(
                exclude_rid=req.rid, eligible=self.adapter.victim_eligible)
            if victim is None:
                raise MemoryError(
                    f"request {req.rid} cannot grow to {need_rows} rows with "
                    "no victims left — submit() guard violated")
            self._preempt(victim)

    def _claim_chunk(self, req: ServeRequest, start: int, n: int) -> bool:
        """Cover a prompt chunk's dispatch footprint (the ssm family claims
        its state row lazily here; the paged family copy-on-writes any
        shared block the chunk's rows would land in), preempting capacity
        holders while the pool is dry.  False only when no eligible victim
        remains — the chunk then waits for a later step."""
        while not self.adapter.claim_chunk(req, start, n):
            victim = self.scheduler.victim_for_preemption(
                exclude_rid=req.rid, eligible=self.adapter.victim_eligible)
            if victim is None:
                return False
            self._preempt(victim)
        return True

    def _preempt(self, victim: ServeRequest) -> None:
        """Swap the victim's state out to host, free its capacity + slot,
        queue it for resume.  Works mid-prefill too: the committed chunks
        travel with the swap and `prefilled` marks where the prompt
        resumes."""
        slot = victim.slot
        nbytes = self.adapter.swap_out(victim.rid)
        self.scheduler.preempt(victim, self.now_fn())
        self._reset_slot(slot)
        self.metrics.record_preemption(nbytes)

    def _resume_all(self, reqs: List[ServeRequest]) -> None:
        """Swap re-admitted requests back in, segment-packed: up to
        `resume_segments` requests share ONE commit invocation, so a burst
        of K swap-ins costs ceil(K / resume_segments) program dispatches
        instead of K — the resume-path counterpart of chunk packing."""
        width = self.adapter.resume_segments
        for i in range(0, len(reqs), width):
            self._resume_group(reqs[i:i + width])

    def _resume_group(self, group: List[ServeRequest]) -> None:
        """One packed commit: scatter the group's host-side state back into
        their freshly claimed capacity (one fixed shape — see the adapters'
        `resume_commit`), then restore each slot's host state.  No forward
        pass — no token is recomputed; a mid-prefill request continues
        chunking from `prefilled`.  The batch's wall time is split evenly
        across the group for per-request swap-in accounting."""
        t0 = time.perf_counter()
        if self.trace.enabled:
            n_commit = self._commit._cache_size()
        nbytes = self.adapter.resume_commit(group)
        batch_s = time.perf_counter() - t0
        swap_in_s = batch_s / len(group)
        self.metrics.record_resume_commit(len(group))
        if self.trace.enabled and self._commit._cache_size() > n_commit:
            self.trace.emit("compile", program="commit", host_s=batch_s)
        for req, nb in zip(group, nbytes):
            if self.trace.enabled:
                self.trace.emit("swap_in", rid=req.rid, nbytes=nb)
                self.trace.emit("resume", rid=req.rid,
                                stall_s=req.last_stall_s,
                                swap_in_s=swap_in_s)
            self.metrics.record_resume(nb, req.last_stall_s,
                                       swap_in_s=swap_in_s)
            slot = req.slot
            if req.prefilling:
                # not in the decode batch yet: stay masked (zeroed) until
                # the remaining chunks commit the rest of the prompt
                self._reset_slot(slot)
            else:
                self._lengths[slot] = req.prompt_len + len(req.output) - 1
                self._last_tok[slot] = req.output[-1]

    def _reset_slot(self, slot: int) -> None:
        # stale lengths on a freed slot would index past the (all-null)
        # block table; zeroed state keeps every inactive slot's writes
        # pinned to the sink row.
        self._lengths[slot] = 0
        self._last_tok[slot] = 0

    def _finished(self, req: ServeRequest) -> bool:
        if len(req.output) >= req.max_new_tokens:
            return True
        # stop-at-first-eos ANYWHERE in the stream, the same rule
        # `truncate_at_eos` applies at retirement — not just when eos is
        # the latest token, so the two definitions cannot diverge
        return self.cfg.eos_id >= 0 and self.cfg.eos_id in req.output

    def _retire(self, req: ServeRequest, now: float) -> None:
        """Retire a finished request: truncate its stream at the first eos
        (the shared `truncate_at_eos` rule — so the finish event's digest
        and n_output describe the stream callers actually receive), then
        release the slot and record completion."""
        slot = req.slot
        req.output = truncate_at_eos(req.output, self.cfg.eos_id)
        self.scheduler.retire(req, now)
        self._reset_slot(slot)
        self._complete(req)

    def _complete(self, req: ServeRequest) -> None:
        self.metrics.record_completion(req.latency_s, len(req.output))
        self._done.append(req)

    # ----------------------------------------------------------- unified step
    def step(self) -> bool:
        """One engine step = one invocation of one of the family's TWO
        compiled step programs: admit (resumes swap back in; fresh arrivals
        just take a slot), pack the step's prefill chunk (token-budget
        accounting, greedy fill over up to `chunk_segments` requests), grow
        every *decoding* request's state to cover its next token and claim
        every packed segment's chunk footprint (preempting victims if the
        pool is dry), then dispatch either the unified program (packed
        chunk lane + decode lane) or — when no prompt work is pending —
        the decode-only fast path, which skips the idle chunk lane
        entirely.  Returns False when nothing ran."""
        with span("serve.step") as step_span:
            return self._step(step_span)

    def _step(self, step_span) -> bool:
        """The body of `step`, inside its `serve.step` span; each phase
        opens a child span (see `repro.serve.trace.SPAN_NAMES`)."""
        now = self.now_fn()
        with span("serve.admit") as admit_span:
            admitted = self.scheduler.admit(now)
            resuming = [r for r in admitted if self.adapter.is_swapped(r.rid)]
            if self.cfg.prefix_sharing:
                # fresh admissions run nothing; a non-zero `prefilled` on
                # one means admission adopted that many prompt tokens' KV
                # from the prefix index — work the chunk lane will never do
                rs = {r.rid for r in resuming}
                for req in admitted:
                    if req.rid not in rs and req.prefilled > 0:
                        self.metrics.record_prefix_hit(req.prefilled)
            if resuming:
                self._resume_all(resuming)
            # fresh admissions run nothing here: their prompts stream
            # through the unified step's chunk lane, starting this step
            admit_span.set_metadata(admitted=len(admitted),
                                    resumed=len(resuming))

        preempted = self.metrics.preemptions
        with span("serve.schedule"):
            chunks = self.scheduler.next_chunks(self._chunk_width,
                                                self._chunk_segments)
            # on-demand growth for the decode batch: every decoding request
            # secures the unit its next write lands in.  A request
            # preempted as some later grower's victim drops out of this
            # step (slot is None by then) — including, possibly, any of the
            # packed segments' requests.
            for req in [r for r in self.scheduler.slots
                        if r is not None and not r.prefilling]:
                if req.slot is not None:
                    self._ensure_blocks(req)
            # chunk-claim: each packed segment's request must hold its
            # family footprint before dispatch (ssm: lazy state-row claim;
            # paged: copy-on-write any shared block under the chunk's rows
            # — the prompt's blocks themselves were allocated at admission)
            chunks = [ch for ch in chunks
                      if ch[0].slot is not None and self._claim_chunk(*ch)]
            chunks = [ch for ch in chunks if ch[0].slot is not None]
            decoding = [r for r in self.scheduler.slots
                        if r is not None and not r.prefilling]
        preempted = self.metrics.preemptions - preempted

        if not decoding and not chunks:
            step_span.set_metadata(kind="none", preempted=preempted)
            return bool(admitted)

        trace = self.trace
        kind = "unified" if chunks else "decode_only"
        step_idx = self._step_idx
        self._step_idx += 1
        chunk_tokens = sum(n for _, _, n in chunks)
        step_span.set_metadata(step=step_idx, kind=kind,
                               decode_rows=len(decoding),
                               chunk_tokens=chunk_tokens,
                               segments=len(chunks), preempted=preempted)
        if trace.enabled:
            for req, start, n in chunks:
                trace.emit("chunk_scheduled", t=now, rid=req.rid,
                           start=start, n=n)
            trace.emit("step_begin", t=now, step=step_idx, kind=kind,
                       family=self.family, mesh=self.mesh_tag,
                       lane_width=self._chunk_width if chunks else 0,
                       segments=len(chunks), chunk_tokens=chunk_tokens,
                       decode_rows=len(decoding))

        t0 = time.perf_counter()
        nxt, seg_next, compiled = self.adapter.dispatch(
            self.params, self.scheduler.slots, self._lengths,
            self._last_tok, chunks)
        step_s = time.perf_counter() - t0

        with span("serve.commit") as commit_span:
            n_done = len(self._done)
            if trace.enabled and compiled:
                trace.emit("compile", program=kind, host_s=step_s)
            # attribute chunk-only steps to prefill time, everything else
            # to decode time
            if decoding:
                self.metrics.decode_time_s += step_s
            else:
                self.metrics.prefill_time_s += step_s

            now = self.now_fn()
            if trace.enabled:
                trace.emit("step_end", t=now, step=step_idx, kind=kind,
                           family=self.family, mesh=self.mesh_tag,
                           lane_width=self._chunk_width if chunks else 0,
                           segments=len(chunks), chunk_tokens=chunk_tokens,
                           decode_rows=len(decoding), host_s=step_s)
            if chunks:
                self.metrics.record_chunk_step([n for _, _, n in chunks],
                                               self._chunk_width)
                for i, (req, start, n) in enumerate(chunks):
                    req.prefilled = start + n
                    # the chunk's KV is committed and final: index the
                    # prompt's covered full-block prefixes for later
                    # admissions to adopt
                    self.adapter.register_prefix(req)
                    if trace.enabled:
                        trace.emit("chunk_committed", t=now, rid=req.rid,
                                   start=start, n=n, prefilled=req.prefilled)
                    if not req.prefilling:    # this chunk ended the prompt
                        first = int(seg_next[i])
                        req.output.append(first)
                        req.first_token_time = now
                        trace.emit("first_token", t=now, rid=req.rid,
                                   token=first)
                        self.metrics.record_first_token(
                            now - req.arrival_time)
                        self.metrics.prefills += 1
                        slot = req.slot
                        self._lengths[slot] = req.prompt_len
                        self._last_tok[slot] = first
                        if self._finished(req):
                            self._retire(req, now)
            elif decoding:
                self.metrics.record_decode_only_step()

            if decoding:
                self.metrics.record_step(len(decoding), self.cfg.max_slots,
                                         self.adapter.occupancy())
                emit_tokens = trace.enabled
                for req in decoding:
                    slot = req.slot
                    req.output.append(int(nxt[slot]))
                    self._lengths[slot] += 1
                    self._last_tok[slot] = nxt[slot]
                    if emit_tokens:
                        trace.emit("decode_token", t=now, rid=req.rid,
                                   token=int(nxt[slot]))
                    if self._finished(req):
                        self._retire(req, now)
            # copy-on-write copies performed while growing/claiming this
            # step (the allocator counts them; state-row allocators have
            # none)
            drain = getattr(self.adapter.alloc, "drain_cow_copies", None)
            if drain is not None:
                copied = drain()
                if copied:
                    self.metrics.record_cow(copied)
            commit_span.set_metadata(retired=len(self._done) - n_done)
        return True
