"""Iteration-level continuous-batching scheduler.

EQuARX-style fleet thinking: the kernel keeps the MXU fed only if the
scheduler keeps the kernel fed. One scheduler iteration = one fused
prefill/decode step over a FIXED number of decode slots (S) x a FIXED
chunk width (C): prefilling slots contribute up to C prompt tokens,
decoding slots contribute their one in-flight token, idle lanes are
masked — shapes never change, so the whole serving lifetime is one
compiled executable.

Host-side state machine only (numpy, no jax — the one exception is
copy-on-write, where the scheduler asks the cache for a device block
copy before a shared block would be written): admission from a
FIFO-with-priority queue gated by block-pool watermark backpressure
(admitting a request reserves blocks for its whole prompt+output up
front, so a running request can never OOM the pool mid-flight),
retirement of EOS/length-finished lanes, per-request deadlines that
cancel and reclaim blocks, and client cancels. Time comes from an
injectable `clock` (seconds, monotonic) so the chaos/serving test tier
runs without sleeps.

ISSUE 10 grows two modes on the same iteration loop:

- **Prefix caching** (`prefix_cache=PrefixCacheIndex(...)`): admission
  looks the prompt's full chunks up in the hash-chain index, reserves
  only the UNSHARED suffix (+1 copy-on-write spare when the whole
  prompt matched), starts prefill past the shared positions, registers
  freshly-prefilled full chunks back into the index at commit, and
  retirement UNREFS blocks instead of freeing them. Under watermark
  pressure admission evicts idle cached blocks (LRU, leaf-first)
  before it backpressures.
- **Speculative decoding** (`spec_k=k`): decode lanes plan
  q = min(k+1, chunk, remaining) columns instead of 1; the engine
  fills columns 1..q-1 with draft-model proposals, the fused step
  verifies all q columns in one prefill-shaped call, and commit()
  accepts the longest matching draft prefix plus the target's own next
  token — 1..q tokens per lane per iteration, ids bitwise-identical to
  plain greedy decode (rejection-sampled acceptance sits behind
  `spec_mode="rejection"`).
"""

import heapq
import threading
import time
from concurrent.futures import InvalidStateError

import numpy as np

from .decode_strategies import (BeamHypothesis, GroupResult, beam_step,
                                finalize_beam, fold_key, host_sample)
from .kv_cache import NEG_INF

__all__ = ["ContinuousBatchingScheduler", "GenerationResult",
           "DeadlineExceeded", "RequestCancelled", "IterationPlan"]


class DeadlineExceeded(TimeoutError):
    """The request's deadline passed before generation finished; its
    slot and blocks were reclaimed."""


class RequestCancelled(RuntimeError):
    """The request was cancelled (client cancel or server shutdown)."""


class GenerationResult:
    """What a finished request's future resolves to."""

    __slots__ = ("request_id", "token_ids", "score", "finish_reason",
                 "prompt_len", "ttft_ms")

    def __init__(self, request_id, token_ids, score, finish_reason,
                 prompt_len, ttft_ms):
        self.request_id = request_id
        self.token_ids = token_ids          # np.int32 (n_generated,)
        self.score = score                  # sum of chosen-token logprobs
        self.finish_reason = finish_reason  # "eos" | "length"
        self.prompt_len = prompt_len
        self.ttft_ms = ttft_ms              # submit -> first token

    def __repr__(self):
        return (f"GenerationResult(id={self.request_id}, "
                f"n={len(self.token_ids)}, reason={self.finish_reason!r}, "
                f"score={self.score:.3f})")


class _Request:
    __slots__ = ("rid", "prompt", "max_new_tokens", "eos_id", "priority",
                 "deadline", "stream", "future", "submitted_at", "tenant",
                 "generated", "score", "first_token_at", "last_token_at",
                 "chain_keys", "group", "lane", "sampling", "guided",
                 "guided_state")

    def __init__(self, rid, prompt, max_new_tokens, eos_id, priority,
                 deadline, stream, future, submitted_at, tenant=None,
                 group=None, lane=0, sampling=None, guided=None):
        self.rid = rid
        self.prompt = prompt                # np.int32 (P,)
        self.max_new_tokens = max_new_tokens
        self.eos_id = eos_id
        self.priority = priority
        self.deadline = deadline            # absolute clock seconds or None
        self.stream = stream                # callable(rid, token) or None
        self.future = future
        self.submitted_at = submitted_at
        self.tenant = tenant                # cost-attribution identity
        self.generated = []
        self.score = 0.0
        self.first_token_at = None
        self.last_token_at = None
        self.chain_keys = None      # prefix chunk hashes, computed once
        self.group = group          # RequestGroup when forked (n>1/beam)
        self.lane = lane            # rank within the group (0 = leader)
        self.sampling = sampling    # SamplingParams or None
        self.guided = guided        # guided.Constraint or None
        self.guided_state = None    # current automaton state


class _Slot:
    __slots__ = ("req", "blocks", "table", "pos", "admit_seq", "shared",
                 "keys", "registered", "cow_spares", "cow_copies",
                 "tier", "hold", "ahead", "emits_ahead", "cols", "emit",
                 "done", "ending")

    def __init__(self, req, blocks, table, admit_seq, shared=(),
                 keys=(), registered=0, cow_spares=(), tier="device"):
        self.req = req
        self.blocks = blocks                # every block to release
        self.table = table                  # np.int32 (max_blocks,)
        self.pos = 0                        # next position, as committed
        self.admit_seq = admit_seq          # admission age (chaos targets)
        self.shared = list(shared)          # prefix-cache blocks in table
        self.keys = list(keys)              # chunk chain keys computed
        self.registered = registered        # prompt chunks in the index
        self.cow_spares = list(cow_spares)  # reserved copy-on-write blocks
        self.cow_copies = 0
        # "host" when this lane's KV crossed the host tier (admitted
        # over swapped-in spilled chains, or resumed from a preempt) —
        # the flight recorder's tier tag
        self.tier = tier
        # a held slot is a fork-group FOLLOWER waiting for its leader's
        # prefill: it owns its suffix reservation but plans no work
        # until the fork clears the hold (commit's _fork_group)
        self.hold = False
        # what plans not yet committed feed this lane: the columns, and
        # the tokens they emit (the engine dispatches the next step
        # before it commits the last, docs/serving.md "One step in
        # flight"); and of the newest plan alone, `cols` and `emit`
        self.ahead = 0
        self.emits_ahead = 0
        self.cols = 0
        self.emit = 0
        # the request ended while a step not yet committed fed the lane:
        # the slot is released at that step's commit
        self.done = False
        # (exception, count key) of a cancel or deadline that a plan
        # decided while a step fed the lane: applied at its commit
        self.ending = None

    @property
    def next_pos(self):
        """The next position to feed: what is committed, and what the
        plans not yet committed feed."""
        return self.pos + self.ahead

    @property
    def prefilling(self):
        return self.next_pos < len(self.req.prompt)

    @property
    def exhausted(self):
        """The plans not yet committed emit the request's last token."""
        return (not self.prefilling and len(self.req.generated)
                + self.emits_ahead >= self.req.max_new_tokens)

    @property
    def live(self):
        """The lane is one that an engine which commits each step
        before it plans the next would still hold after the newest
        plan: not ended, and fed by that plan or with tokens left."""
        return not (self.done or self.ending is not None
                    or (not self.cols and self.exhausted))

    @property
    def plannable(self):
        """A plan made now would feed this lane."""
        return not (self.hold or self.done or self.ending is not None
                    or self.exhausted)


class _Preempted:
    """A preempted request parked off-device: its KV sits in host-tier
    blocks (its reservation — the no-mid-flight-OOM invariant), its
    position/stream state rides the _Request untouched, and resume
    swap-ins rebuild a slot that continues bitwise where it stopped."""

    __slots__ = ("req", "pos", "host_blocks", "keys", "registered",
                 "not_before")

    def __init__(self, req, pos, host_blocks, keys, registered,
                 not_before):
        self.req = req
        self.pos = pos
        self.host_blocks = host_blocks
        self.keys = keys
        self.registered = registered
        self.not_before = not_before    # earliest resume iteration


def _lane_tuple(sid, slot):
    """One lane's flight-recorder tuple, in EXACTLY
    serving_telemetry.LANE_FIELDS order — the flight dump's
    _expand_lanes zips these against that schema, so every producer
    must go through this helper (plan()'s slot loop and
    lane_snapshot()). Position and tokens are those the newest plan
    started from, committed or not."""
    group = slot.req.group
    pos = slot.next_pos - slot.cols
    return (sid, slot.req.rid, int(pos), pos < len(slot.req.prompt),
            int(slot.admit_seq),
            len(slot.req.generated) + slot.emits_ahead - slot.emit,
            int(slot.blocks[0]) if slot.blocks else None,
            len(slot.shared), int(slot.cow_copies), slot.tier,
            group.gid if group is not None else None,
            int(slot.req.lane) if group is not None else None)


class IterationPlan:
    """One fused step's host-built inputs + the bookkeeping commit()
    needs. `emitting[s]` marks slots whose step output IS a generated
    token (decode slots, and prefill slots finishing their prompt this
    iteration). `decode_cols[s]` is the number of verify columns a
    DECODE lane plans (1 in plain mode; up to spec_k+1 in speculative
    mode, where the engine fills columns 1..q-1 with draft proposals
    before the fused step runs); 0 marks a prefill lane. `limits[s]` is
    the lane's reserved token horizon (prompt + max_new_tokens) — the
    draft step's rollout must never write a position past it.
    `from_prev[s]` marks a decode lane whose token is the previous
    step's output, not yet committed: the engine hands that step's ids
    to this one, which takes column 0 from them. `slots` holds the slot
    each of `slot_ids` names, for commit() to tell it from a slot
    admitted in its place. `iteration` is the scheduler's count that
    planned it."""

    __slots__ = ("tokens", "positions", "valid", "tables", "slot_ids",
                 "emitting", "prefill_tokens", "decode_cols", "limits",
                 "lanes_detail", "queue_depth", "sample_ctl",
                 "guided_lanes", "needs_rows", "valid_columns",
                 "from_prev", "slots", "iteration")

    def __init__(self, tokens, positions, valid, tables, slot_ids,
                 emitting, prefill_tokens, decode_cols=None,
                 limits=None, lanes_detail=None, queue_depth=None,
                 sample_ctl=None, guided_lanes=None, needs_rows=False,
                 valid_columns=0, from_prev=None, slots=None,
                 iteration=None):
        self.tokens = tokens                # (S, C) int32
        self.positions = positions          # (S, C) int32
        self.valid = valid                  # (S, C) bool
        self.tables = tables                # (S, M) int32
        self.slot_ids = slot_ids            # slots with work this iter
        self.emitting = emitting            # set of slot ids
        self.prefill_tokens = prefill_tokens
        self.decode_cols = decode_cols      # (S,) int32
        self.limits = limits                # (S,) int32
        # telemetry-only (None otherwise): pre-step lane occupancy in
        # serving_telemetry.LANE_FIELDS order + post-admit queue depth,
        # captured inside plan()'s slot loop so the engine's flight
        # entry needs no second lock round-trip over the slots
        self.lanes_detail = lanes_detail
        self.queue_depth = queue_depth
        # strategies-step controls (None when the engine's step has no
        # sampling path): (do_sample (S,) bool, temperature (S,) f32,
        # top_k (S,) i32 0=off, top_p (S,) f32 2.0=off, keys (S,2) u32)
        self.sample_ctl = sample_ctl
        # [(sid, req)] lanes whose emission needs a constraint mask
        self.guided_lanes = guided_lanes
        # True when commit() will read the full logp rows (a beam step
        # or a pending group fork) — the engine only materializes the
        # (S, [C,] V) rows output host-side when asked
        self.needs_rows = needs_rows
        # columns of the (S, C) grid that carry a token this iteration;
        # the rest of the grid is padding the fused step computes anyway
        self.valid_columns = valid_columns
        self.from_prev = from_prev          # (S,) bool
        self.slots = slots
        self.iteration = iteration

    @property
    def padded_columns(self):
        return self.tokens.size - self.valid_columns


class ContinuousBatchingScheduler:
    """Owns the request queue, the slot map, and the block accounting.
    Thread-safe: submits/cancels may come from any thread; plan() and
    commit() are called by the single engine loop."""

    def __init__(self, cache, num_slots=4, chunk=4, max_context=None,
                 clock=None, watermark_blocks=0, chaos=None,
                 telemetry=None, prefix_cache=None, spec_k=0,
                 spec_mode="greedy", spec_seed=0):
        self._cache = cache
        self._tel = telemetry       # ServingTelemetry or None (hooks
        #                             are cheap host bookkeeping, called
        #                             under self._lock)
        self.num_slots = int(num_slots)
        self.chunk = int(chunk)
        self._prefix = prefix_cache  # PrefixCacheIndex or None
        self.spec_k = int(spec_k)
        self.spec_mode = spec_mode
        if self.spec_k:
            if spec_mode not in ("greedy", "rejection"):
                raise ValueError(
                    f"spec_mode {spec_mode!r}: expected 'greedy' or "
                    f"'rejection'")
            if self.chunk < self.spec_k + 1:
                raise ValueError(
                    f"spec_k={self.spec_k} needs chunk >= spec_k+1 "
                    f"(the verify step feeds the committed token plus "
                    f"k drafts in one chunked call); got chunk="
                    f"{self.chunk}")
        self._spec_rng = np.random.default_rng(spec_seed)
        self.max_context = int(max_context or
                               cache.usable_blocks * cache.block_size)
        self.max_blocks = cache.blocks_for_tokens(self.max_context)
        self._clock = clock or time.monotonic
        self.watermark_blocks = int(watermark_blocks)
        self._chaos = chaos
        self._lock = threading.RLock()
        self._queue = []                # heap of (priority, seq, req)
        self._seq = 0
        self._slots = [None] * self.num_slots
        self._cancel_rids = set()
        self._admit_seq = 0
        self.iteration = 0
        # preempt-and-resume (host KV tier): FIFO of _Preempted
        # records + host-block pledges. A request admitted LAZILY
        # (blocks for prompt+1 instead of prompt+output) pledges its
        # full worst-case block count against the host tier — worst
        # case it parks there whole, which is what lets lazy admission
        # retire the full-reservation concurrency ceiling without
        # re-admitting mid-flight OOM. Plain attributes, not counts{}:
        # the counts dict auto-registers serving.<key> counters, and
        # these publish as the serving.kv.tier.* gauges instead.
        self._preempted = []
        self._host_pledged = 0
        self._pledges = {}          # rid -> pledged block count
        self.preempts = 0
        self.resumes = 0
        self.counts = {"admitted": 0, "retired": 0, "cancelled": 0,
                       "deadline_cancels": 0, "generated_tokens": 0,
                       "prefill_tokens": 0, "spec.proposed": 0,
                       "spec.accepted": 0, "group.requests": 0,
                       "group.lanes": 0, "group.forks": 0,
                       "group.cow_copies": 0, "beam.reorders": 0,
                       "guided.masked_steps": 0, "guided.violations": 0}
        from ..observability import _help
        from ..observability.metrics import global_registry
        reg = global_registry()
        self._mc = {k: reg.counter(f"serving.{k}", _help(f"serving.{k}"))
                    for k in self.counts}
        self._ttft = reg.histogram("serving.ttft_ms",
                                   _help("serving.ttft_ms"))
        self._itl = reg.histogram("serving.itl_ms",
                                  _help("serving.itl_ms"))
        self._g_accept = reg.gauge("serving.spec.accept_rate",
                                   _help("serving.spec.accept_rate"))

    def _count(self, key, n=1):
        self.counts[key] += n
        self._mc[key].inc(n)

    # -- client side -------------------------------------------------------
    def now(self):
        return self._clock()

    def enqueue(self, req):
        with self._lock:
            heapq.heappush(self._queue, (req.priority, self._seq, req))
            self._seq += 1

    def request_cancel(self, rid):
        with self._lock:
            self._cancel_rids.add(rid)

    @property
    def queue_depth(self):
        with self._lock:
            return len(self._queue)

    @property
    def active_count(self):
        with self._lock:
            return sum(s is not None for s in self._slots)

    def has_work(self):
        with self._lock:
            return bool(self._queue) or bool(self._preempted) or any(
                s is not None for s in self._slots)

    def load_snapshot(self):
        """(queue_depth, active_slots, free_blocks) under ONE lock hold
        — the fleet router's power-of-two-choices load probe
        (serving/router.py) reads all three per candidate per submit,
        and three separate property reads would take the lock three
        times AND could tear across an admission. Preempted requests
        count as queued load: they are admitted work waiting for
        blocks, invisible to the slot count."""
        with self._lock:
            return (len(self._queue) + len(self._preempted),
                    sum(s is not None for s in self._slots),
                    self._cache.num_free)

    # -- retirement --------------------------------------------------------
    def _unpledge(self, req):
        m = self._pledges.pop(req.rid, None)
        if m:
            self._host_pledged -= m

    def _finish(self, req, reason):
        self._unpledge(req)
        ttft = None
        if req.first_token_at is not None:
            ttft = (req.first_token_at - req.submitted_at) * 1e3
        res = GenerationResult(req.rid,
                               np.asarray(req.generated, np.int32),
                               req.score, reason, len(req.prompt), ttft)
        try:
            if not req.future.cancelled():
                req.future.set_result(res)
        except InvalidStateError:
            pass        # client cancelled between the check and the set
        self._count("retired")
        if ttft is not None:
            self._ttft.observe(ttft)
        if self._tel is not None:
            self._tel.on_finish(
                req.rid, self.iteration, "retire", reason=reason,
                e2e_ms=(self.now() - req.submitted_at) * 1e3,
                prompt_len=len(req.prompt), generated=len(req.generated))
        if req.group is not None:
            self._on_group_finish(req, res)
        return res

    def _fail(self, req, exc, count_key):
        self._unpledge(req)
        try:
            if not req.future.cancelled():
                req.future.set_exception(exc)
        except InvalidStateError:
            pass        # client cancelled between the check and the set
        self._count(count_key)
        if self._tel is not None:
            outcome = ("deadline" if count_key == "deadline_cancels"
                       else "cancel")
            if outcome == "deadline":
                self._tel.on_deadline_cancel(req.rid, self.iteration)
            self._tel.on_finish(req.rid, self.iteration, outcome,
                                reason=type(exc).__name__,
                                prompt_len=len(req.prompt),
                                generated=len(req.generated))
        if req.group is not None:
            self._on_group_fail(req, exc, count_key)

    # -- fork groups: finish/fail as a unit --------------------------------
    def _on_group_finish(self, req, res):
        group = req.group
        group.results[req.lane] = res
        group.lane_sids.pop(req.lane, None)
        if group.failed or len(group.results) < group.k:
            return
        if group.kind == "beam":
            # rank the finished beams exactly as the dense epilogue:
            # lane r's generated list IS hypothesis r (eos-padded —
            # done lanes keep committing eos at zero cost, mirroring
            # the dense scan's masked emissions)
            hist = np.stack([
                np.asarray(group.results[r].token_ids, np.int32)
                for r in range(group.k)])
            ids, norm, order = finalize_beam(
                hist, group.scores, group.eos_id,
                group.beam.length_penalty)
            hyps = [BeamHypothesis(ids[i],
                                   float(group.scores[int(order[i])]),
                                   float(norm[i]))
                    for i in range(group.k)]
            out = GroupResult(group.gid, "beam", hypotheses=hyps,
                              prompt_len=len(req.prompt))
        else:
            out = GroupResult(
                group.gid, "sample",
                lanes=[group.results[r] for r in range(group.k)],
                prompt_len=len(req.prompt))
        try:
            if not group.future.cancelled():
                group.future.set_result(out)
        except InvalidStateError:
            pass

    def _on_group_fail(self, req, exc, count_key):
        group = req.group
        group.lane_sids.pop(req.lane, None)
        if group.failed:
            return
        group.failed = True
        try:
            if not group.future.cancelled():
                group.future.set_exception(exc)
        except InvalidStateError:
            pass
        # the group fails as a unit: siblings still flying are marked
        # cancelled (their slots release through the normal sweep next
        # iteration); siblings that never reached a slot (the leader
        # died queued) fail here so no lane future dangles
        for lane in group.lanes:
            if lane is req or lane.future.done():
                continue
            if lane.lane in group.lane_sids:
                self._cancel_rids.add(lane.rid)
            else:
                self._fail(lane, RequestCancelled(
                    f"request {lane.rid} cancelled with its group"),
                    "cancelled")

    def _release_slot(self, sid):
        slot = self._slots[sid]
        self._slots[sid] = None
        group = slot.req.group
        if group is not None:
            # a forked lane's table mixes private suffix blocks with
            # blocks sibling lanes (and maybe the index) still hold —
            # release is unref-per-block, never the single-owner free
            if self._prefix is not None:
                self._prefix.release(slot.blocks)
            else:
                self._cache.unref_blocks(slot.blocks)
            group.lane_sids.pop(slot.req.lane, None)
            group.released += 1
            if group.released >= group.k and group.spares:
                # last lane out: the pooled COW reserve goes home
                self._cache.free(group.spares)
                group.spares = []
            return
        if self._prefix is not None:
            # retirement UNREFS instead of frees: a block this request
            # registered into (or matched from) the prefix index keeps
            # the index's ref and becomes an evictable cached block;
            # private blocks drop to refcount 0 and free normally
            self._prefix.release(slot.blocks)
        else:
            self._cache.free(slot.blocks)

    def _drop_queued(self, pred, exc_fn, count_key):
        kept = []
        for item in self._queue:
            req = item[2]
            if pred(req):
                self._fail(req, exc_fn(req), count_key)
            else:
                kept.append(item)
        if len(kept) != len(self._queue):
            self._queue = kept
            heapq.heapify(self._queue)

    def _drop_preempted(self, pred, exc_fn, count_key):
        """The _drop_queued sweep for parked requests: a cancel or
        deadline must reach a preempted request too (its future is as
        live as a queued one's), and its host-tier blocks — its
        reservation — go back to the host pool."""
        kept = []
        for rec in self._preempted:
            if pred(rec.req):
                self._cache.host.free(rec.host_blocks)
                self._fail(rec.req, exc_fn(rec.req), count_key)
            else:
                kept.append(rec)
        self._preempted = kept

    def drop_queued_request(self, rid, exc):
        """Remove ONE queued request and fail its future — submit()'s
        lost-the-race-with-close sweep: an enqueue that landed after
        cancel_all's queue sweep would otherwise sit forever with no
        worker to plan it. If the request was instead already admitted
        to a slot (close(drain=True) with a live worker), fall back to
        a normal cancel mark for the next iteration. Returns True if it
        was still queued."""
        with self._lock:
            before = len(self._queue)
            self._drop_queued(lambda r: r.rid == rid, lambda r: exc,
                              "cancelled")
            if len(self._queue) != before:
                return True
            self._cancel_rids.add(rid)
            return False

    def cancel_all(self, exc=None):
        """Server shutdown without drain: fail everything outstanding."""
        with self._lock:
            exc = exc or RequestCancelled("server closed")
            self._drop_queued(lambda r: True, lambda r: exc, "cancelled")
            self._drop_preempted(lambda r: True, lambda r: exc,
                                 "cancelled")
            for sid, slot in enumerate(self._slots):
                if slot is not None:
                    self._fail(slot.req, exc, "cancelled")
                    self._release_slot(sid)

    # -- one iteration -----------------------------------------------------
    def _apply_cancels_and_deadlines(self, now):
        # chaos-planned cancels resolve to the oldest active requests
        # (admission order, NOT slot order — freed slots get reused)
        if self._chaos is not None:
            for idx in self._chaos.serving_cancels_at(self.iteration):
                # a lane the step in flight finishes is gone once it is
                # committed: it is not active here
                active = [s.req.rid for s in sorted(
                    (s for s in self._slots if s is not None
                     and not s.done and not s.exhausted),
                    key=lambda s: s.admit_seq)]
                if idx < len(active):
                    self._cancel_rids.add(active[idx])
        if self._cancel_rids:
            rids = self._cancel_rids
            self._cancel_rids = set()
            self._drop_queued(lambda r: r.rid in rids,
                              lambda r: RequestCancelled(
                                  f"request {r.rid} cancelled"),
                              "cancelled")
            self._drop_preempted(lambda r: r.rid in rids,
                                 lambda r: RequestCancelled(
                                     f"request {r.rid} cancelled"),
                                 "cancelled")
            for sid, slot in enumerate(self._slots):
                if slot is not None and slot.req.rid in rids:
                    self._end_lane(sid, RequestCancelled(
                        f"request {slot.req.rid} cancelled"), "cancelled")
        self._drop_queued(
            lambda r: r.deadline is not None and now > r.deadline,
            lambda r: DeadlineExceeded(
                f"request {r.rid} deadline passed while queued"),
            "deadline_cancels")
        self._drop_preempted(
            lambda r: r.deadline is not None and now > r.deadline,
            lambda r: DeadlineExceeded(
                f"request {r.rid} deadline passed while preempted"),
            "deadline_cancels")
        for sid, slot in enumerate(self._slots):
            if slot is None:
                continue
            dl = slot.req.deadline
            if dl is not None and now > dl:
                self._end_lane(sid, DeadlineExceeded(
                    f"request {slot.req.rid} deadline passed after "
                    f"{len(slot.req.generated) + slot.emits_ahead} "
                    f"tokens"), "deadline_cancels")

    def _end_lane(self, sid, exc, count_key):
        """A cancel or deadline ends the request in slot `sid`. Where a
        step not yet committed feeds the lane, the request ends at that
        step's commit, after the tokens it emits, as if it had been
        committed before this plan: unless that step emits its last
        token, when the request finishes there and this is a no-op."""
        slot = self._slots[sid]
        if slot.done:
            return
        if slot.ahead:
            if not slot.exhausted and slot.ending is None:
                slot.ending = (exc, count_key)
            return
        self._fail(slot.req, exc, count_key)
        self._release_slot(sid)

    def _retire_lane(self, sid):
        """Free slot `sid` of a request that has ended, or, where a step
        not yet committed still writes its blocks (or its state or ring
        lane), mark it done: that step's commit frees it. No other lane
        is handed what the device has yet to write."""
        slot = self._slots[sid]
        if slot.ahead:
            slot.done = True
        else:
            self._release_slot(sid)

    def forget_uncommitted(self):
        """The steps planned and not committed will never be: every
        lane goes back to what is committed (a lane they fed is fed
        again), and a slot that waited for them is freed."""
        with self._lock:
            for sid, slot in enumerate(self._slots):
                if slot is None:
                    continue
                slot.ahead = slot.emits_ahead = slot.cols = slot.emit = 0
                if slot.done:
                    self._release_slot(sid)
                elif slot.ending is not None:
                    exc, count_key = slot.ending
                    slot.ending = None
                    self._fail(slot.req, exc, count_key)
                    self._release_slot(sid)

    def plans_ahead(self):
        """Whether to plan now, before the step in flight is committed:
        False where no lane would be fed (every lane is done, held,
        ending or fed its last token by that step), or where a request
        waits in the queue for a slot, none is free, and that step
        feeds some lane its last token. The engine then commits that
        step first and plans as an engine that commits each step before
        the next plan does, so that the waiting request is admitted
        into the slot that commit frees at the same iteration."""
        with self._lock:
            if self._queue and all(s is not None for s in self._slots) \
                    and any(s.exhausted and not s.done
                            for s in self._slots):
                return False
            return any(s is not None and s.plannable for s in self._slots)

    def _admit(self, now):
        self._try_resume(now)
        while self._queue:
            free_sid = next((i for i, s in enumerate(self._slots)
                             if s is None), None)
            if free_sid is None:
                return
            req = self._queue[0][2]
            if req.group is not None:
                if not self._admit_group(req, now):
                    return
                continue
            p_len = len(req.prompt)
            n_full = p_len // self._cache.block_size
            m_total = self._cache.blocks_for_tokens(
                p_len + req.max_new_tokens)
            # lazy admission (host tier on): reserve blocks for the
            # prompt + the first decode write only, and PLEDGE the full
            # worst-case count against the host pool instead — if this
            # request must ever give its device blocks back, preempt
            # parks it in its pledged host space. The pledge is
            # conservative (a parked request holds used <= m_total host
            # blocks yet still pledges m_total), but it is what keeps
            # the no-mid-flight-OOM invariant: lazy lanes can ALWAYS be
            # preempted, so a mid-flight allocation can always be
            # satisfied by preempting someone. A request whose worst
            # case exceeds the whole host tier falls back to full
            # reservation (it could never park, so it must never need
            # to).
            host = self._cache.host
            lazy = (host is not None and m_total <= host.num_blocks)
            if lazy:
                host_avail = host.num_free - self._host_pledged
                if self._prefix is not None:
                    host_avail += self._prefix.host_entry_count()
                if host_avail < m_total:
                    # pledge pool exhausted: fall back to full
                    # reservation (correct without host space — a
                    # fully-reserved lane never grows mid-flight)
                    lazy = False
            m_admit = (self._cache.blocks_for_tokens(p_len + 1)
                       if lazy else m_total)
            # prefix probe (pure — no refs, no recency, no metric
            # movement: a backpressured admission retries every
            # iteration and must not read as cache traffic): only the
            # unshared suffix is newly reserved. When the WHOLE prompt
            # matched, prefill restarts at the last prompt token (its
            # logits seed generation) — that token's write lands in the
            # last shared block, so one extra block is reserved up
            # front as the guaranteed copy-on-write target (the
            # no-mid-flight-OOM invariant must survive COW). The chain
            # is hashed ONCE per request, whatever the retry count.
            shared, keys, protect = [], (), frozenset()
            if self._prefix is not None:
                if req.chain_keys is None:
                    req.chain_keys = self._prefix.chain_keys(
                        req.prompt, n_full)
                keys = req.chain_keys
                shared = self._prefix.match(req.prompt, keys)
                protect = frozenset(keys[:len(shared)])
            shared_tokens = len(shared) * self._cache.block_size
            full_cover = shared_tokens == p_len and shared_tokens > 0
            # a None in the match is a SPILLED chain entry: it counts
            # toward the matched depth (no re-prefill!) but claim()
            # must swap it back in, which costs one fresh device block
            n_spilled = sum(1 for b in shared if b is None)
            need = (m_admit - len(shared)
                    + (1 if full_cover else 0))
            need_free = need + n_spilled
            # watermark backpressure: keep headroom unless the pool is
            # otherwise idle (an idle pool must admit or deadlock).
            # Evictable cached blocks count as available — eviction
            # runs BEFORE backpressure — but the entries THIS match
            # depends on are protected, so they neither count as
            # supply nor get evicted out from under the admission.
            floor = self.watermark_blocks if self.active_count else 0
            avail = self._cache.num_free
            if self._prefix is not None:
                protected_idle = sum(
                    1 for b in shared
                    if b is not None and self._cache.refcount(b) == 1)
                avail += (self._prefix.evictable_total()
                          - protected_idle)
            if avail - need_free < floor:
                return
            if self._prefix is not None \
                    and self._cache.num_free < need_free:
                self._prefix.evict_for(need_free, protect)
            if self._cache.num_free < need_free:
                return
            blocks = self._cache.allocate(need)
            if blocks is None:
                return
            if self._prefix is not None:
                # commit the match: refs + LRU touches + hit/miss
                # counters move exactly once per ADMISSION. Spilled
                # entries are materialized by swap-in here (the free
                # blocks were checked above), so the returned list is
                # fully device-resident.
                shared = self._prefix.claim(keys, shared, n_full)
            heapq.heappop(self._queue)
            cow_spares = [blocks.pop()] if full_cover else []
            table = self._cache.make_table(shared + blocks,
                                           self.max_blocks)
            slot = _Slot(req, shared + blocks + cow_spares, table,
                         self._admit_seq, shared=shared, keys=keys,
                         registered=len(shared), cow_spares=cow_spares,
                         tier="host" if n_spilled else "device")
            if lazy:
                self._host_pledged += m_total
                self._pledges[req.rid] = m_total
            # shared positions skip prefill entirely: their KV is
            # already in the pool, bitwise what this request would have
            # written (same tokens, same params, same executable)
            slot.pos = p_len - 1 if full_cover else shared_tokens
            self._slots[free_sid] = slot
            self._admit_seq += 1
            self._count("admitted")
            if self._tel is not None:
                self._tel.on_admit(
                    req.rid, free_sid, self.iteration,
                    (now - req.submitted_at) * 1e3,
                    blocks=len(slot.blocks))

    def _admit_group(self, leader, now):
        """Group-atomic admission: the leader's queue entry stands for
        all K lanes, and either every lane gets its slot and its whole
        block reservation in one shot, or nothing moves (all-or-nothing
        keeps the no-mid-flight-OOM invariant — a half-admitted group
        could never finish). The reservation is FULL (no lazy pledging:
        forked lanes are pinned, see _preempt_victim) and covers the
        worst case exactly:

            leader prompt+output blocks        (prefix-shared part free)
          + (K-1) per-lane suffix extras       (each lane's divergence)
          + K pooled COW spares                (one boundary-block copy
                                               per lane — lanes never
                                               write below the prompt's
                                               last block, so deeper
                                               prompt blocks stay
                                               single-copy)

        Followers are admitted HELD: they own their suffix blocks but
        plan no work until the leader's prefill completes and commit's
        _fork_group aliases the prompt table into them (refs taken at
        fork time, not here — an earlier ref would make the leader's
        own prefill writes look shared and trigger spurious COW)."""
        group = leader.group
        k = group.k
        free_sids = [i for i, s in enumerate(self._slots) if s is None]
        if len(free_sids) < k:
            return False
        bs = self._cache.block_size
        p_len = len(leader.prompt)
        n_full = p_len // bs
        m_prompt = self._cache.blocks_for_tokens(p_len)
        m_total = self._cache.blocks_for_tokens(
            p_len + leader.max_new_tokens)
        extra = m_total - m_prompt
        shared, keys, protect = [], (), frozenset()
        if self._prefix is not None:
            if leader.chain_keys is None:
                leader.chain_keys = self._prefix.chain_keys(
                    leader.prompt, n_full)
            keys = leader.chain_keys
            shared = self._prefix.match(leader.prompt, keys)
            protect = frozenset(keys[:len(shared)])
        shared_tokens = len(shared) * bs
        full_cover = shared_tokens == p_len and shared_tokens > 0
        n_spilled = sum(1 for b in shared if b is None)
        need = (m_total - len(shared)) + (k - 1) * extra + k
        need_free = need + n_spilled
        floor = self.watermark_blocks if self.active_count else 0
        avail = self._cache.num_free
        if self._prefix is not None:
            protected_idle = sum(
                1 for b in shared
                if b is not None and self._cache.refcount(b) == 1)
            avail += self._prefix.evictable_total() - protected_idle
        if avail - need_free < floor:
            return False
        if self._prefix is not None \
                and self._cache.num_free < need_free:
            self._prefix.evict_for(need_free, protect)
        if self._cache.num_free < need_free:
            return False
        blocks = self._cache.allocate(need)
        if blocks is None:
            return False
        if self._prefix is not None:
            shared = self._prefix.claim(keys, shared, n_full)
        heapq.heappop(self._queue)
        group.spares = [blocks.pop() for _ in range(k)]
        lane_extras = [[blocks.pop() for _ in range(extra)]
                       for _ in range(k - 1)]
        # remaining blocks are the leader's unshared prompt + suffix
        table = self._cache.make_table(shared + blocks, self.max_blocks)
        slot = _Slot(leader, shared + blocks, table, self._admit_seq,
                     shared=shared, keys=keys, registered=len(shared),
                     tier="host" if n_spilled else "device")
        slot.pos = p_len - 1 if full_cover else shared_tokens
        self._slots[free_sids[0]] = slot
        group.lane_sids[0] = free_sids[0]
        self._admit_seq += 1
        for r in range(1, k):
            lane = group.lanes[r]
            ext = lane_extras[r - 1]
            ftable = np.zeros((self.max_blocks,), np.int32)
            for j, b in enumerate(ext):
                ftable[m_prompt + j] = b
            # registered = n_full: the leader registers the shared
            # prompt chunks ONCE for the whole group
            fslot = _Slot(lane, list(ext), ftable, self._admit_seq,
                          registered=n_full, tier=slot.tier)
            fslot.hold = True
            self._slots[free_sids[r]] = fslot
            group.lane_sids[r] = free_sids[r]
            self._admit_seq += 1
        self._count("admitted", k)
        self._count("group.requests")
        self._count("group.lanes", k)
        if self._tel is not None:
            for r in range(k):
                lane_blocks = len(self._slots[free_sids[r]].blocks)
                self._tel.on_admit(
                    group.lanes[r].rid, free_sids[r], self.iteration,
                    (now - group.lanes[r].submitted_at) * 1e3,
                    blocks=lane_blocks)
        return True

    # -- preempt and resume (host KV tier) ---------------------------------
    def _try_resume(self, now):
        """Swap parked requests back in, oldest first, BEFORE any new
        admission — a preempted request already paid its queueing and
        prefill, so it outranks fresh arrivals for freed blocks. Stops
        at the first request that cannot be resumed (FIFO fairness: a
        small request must not starve a big one forever)."""
        while self._preempted:
            rec = self._preempted[0]
            if rec.not_before > self.iteration:
                return
            free_sid = next((i for i, s in enumerate(self._slots)
                             if s is None), None)
            if free_sid is None:
                return
            need = len(rec.host_blocks)
            floor = self.watermark_blocks if self.active_count else 0
            avail = self._cache.num_free
            if self._prefix is not None:
                avail += self._prefix.evictable_total()
            if avail - need < floor:
                return
            if self._prefix is not None \
                    and self._cache.num_free < need:
                self._prefix.evict_for(need)
            blocks = self._cache.allocate(need)
            if blocks is None:
                return
            self._preempted.pop(0)
            for hb, db in zip(rec.host_blocks, blocks):
                self._cache.swap_in_block(hb, db)
            self._cache.host.free(rec.host_blocks)
            table = self._cache.make_table(blocks, self.max_blocks)
            slot = _Slot(rec.req, list(blocks), table, self._admit_seq,
                         shared=(), keys=rec.keys,
                         registered=rec.registered, tier="host")
            slot.pos = rec.pos
            self._slots[free_sid] = slot
            self._admit_seq += 1
            self.resumes += 1
            if self._tel is not None:
                self._tel.on_admit(
                    rec.req.rid, free_sid, self.iteration,
                    (now - rec.req.submitted_at) * 1e3,
                    blocks=len(blocks))

    def _preempt_victim(self, exclude=None):
        """Pick the slot to preempt under block pressure: the DECODE
        lane with the longest remaining tail (most max_new_tokens left
        to generate) — it will hold its blocks longest, so parking it
        frees the most block-iterations per swap. Prefilling lanes are
        never victims (their KV is cheapest to hold right now and
        their position bookkeeping assumes an uninterrupted prompt
        walk)."""
        best, best_rem = None, -1
        for sid, slot in enumerate(self._slots):
            if slot is None or sid == exclude or slot.prefilling:
                continue
            if slot.req.group is not None:
                # forked lanes are pinned: a group was admitted with
                # its FULL reservation (never lazily), parking one lane
                # would strand its siblings' shared blocks, and the
                # lockstep beam commit assumes every lane planned
                continue
            rem = slot.req.max_new_tokens - len(slot.req.generated)
            if rem > best_rem:
                best_rem, best = rem, sid
        return best

    def _preempt_slot(self, sid):
        """Park slot `sid`'s request in the host tier: spill every
        written block device->host, release the slot (device blocks
        free; shared prefix blocks keep the index's device copy — the
        spill wrote a private host copy, so resume never depends on
        index survival), and queue a _Preempted record. The request's
        generated tokens, score, and stream state ride its _Request
        untouched, so the resumed stream is bitwise the uninterrupted
        one. `not_before` skips resume until the NEXT iteration — a
        chaos-injected preempt must actually park across a step, not
        bounce back inside the same plan(). Returns False (nothing
        changed) when the host pool cannot hold the blocks."""
        slot = self._slots[sid]
        used = self._cache.blocks_for_tokens(slot.pos)
        host_blocks = []
        for i in range(used):
            b = int(slot.table[i])
            hb = self._cache.spill_block(b)
            while hb is None and self._prefix is not None \
                    and self._prefix._drop_host_lru() is not None:
                hb = self._cache.spill_block(b)
            if hb is None:
                if host_blocks:
                    self._cache.host.free(host_blocks)
                return False
            host_blocks.append(hb)
        rec = _Preempted(
            slot.req, slot.pos, host_blocks, slot.keys,
            len(slot.req.prompt) // self._cache.block_size,
            self.iteration + 1)
        self._release_slot(sid)
        self._preempted.append(rec)
        self.preempts += 1
        return True

    def _ensure_blocks(self, sid, slot, n):
        """Lazy-mode mid-flight block growth: make the table cover the
        writes [pos, pos+n) before the plan captures it. Allocation
        order under pressure: free list, then prefix eviction, then
        preempting the longest-tail OTHER decode, then parking this
        lane itself. Returns False when the lane must sit this
        iteration out unplanned (or was itself preempted)."""
        bs = self._cache.block_size
        for bi in range((slot.pos + n - 1) // bs + 1):
            if int(slot.table[bi]) != 0:        # NULL-padded tail
                continue
            got = self._cache.allocate(1)
            if got is None and self._prefix is not None:
                self._prefix.evict_for(1)
                got = self._cache.allocate(1)
            while got is None:
                victim = self._preempt_victim(exclude=sid)
                if victim is None or not self._preempt_slot(victim):
                    break
                got = self._cache.allocate(1)
            if got is None:
                # last resort: park THIS lane — its host pledge
                # guarantees the space, and parked beats stuck
                if not slot.prefilling:
                    self._preempt_slot(sid)
                return False
            slot.table[bi] = got[0]
            slot.blocks.append(got[0])
        return True

    def _cow_block(self, slot, bi):
        """Copy slot's table[bi] to a fresh block and repoint. Spare
        priority: the group's pooled reserve, the slot's own admission
        spare, then a defensive allocate/evict. The abandoned block's
        ref routes by who else holds it: index-owned -> drop_block (the
        index keeps it), group-shared -> plain unref — EXCEPT that a
        group block whose refcount would hit zero is RETAINED into the
        group's spare pool instead of freed, keeping the group's
        worst-case divergence covered by its own reservation (a
        concurrent admission must never be able to steal it)."""
        b = int(slot.table[bi])
        group = slot.req.group
        if group is not None and group.spares:
            nb = group.spares.pop()
            slot.blocks.append(nb)
        elif slot.cow_spares:
            nb = slot.cow_spares.pop()
        else:
            # unplanned COW (defensive): evict, then allocate
            got = self._cache.allocate(1)
            if got is None and self._prefix is not None:
                self._prefix.evict_for(1)
                got = self._cache.allocate(1)
            if got is None:
                raise MemoryError(
                    f"copy-on-write of block {b} found no free "
                    f"block (pool exhausted)")
            nb = got[0]
            slot.blocks.append(nb)
        self._cache.cow_copy(b, nb)
        slot.table[bi] = nb
        if b in slot.blocks:
            slot.blocks.remove(b)
        if b in slot.shared:
            slot.shared.remove(b)
        if self._prefix is not None and self._prefix.owns_block(b):
            self._prefix.drop_block(b)  # this request's ref moves on
        elif group is not None and self._cache.refcount(b) == 1:
            group.spares.append(b)      # retain inside the reservation
        else:
            self._cache.unref(b)
        slot.cow_copies += 1
        if group is not None:
            group.cow_copies += 1
            self._count("group.cow_copies")
        return nb

    def _maybe_cow(self, slot, pos, n):
        """Copy-on-write guard, called with the block range this lane
        will WRITE this iteration ([pos, pos+n)): any shared block in
        range is first copied to a reserved fresh block and the table
        repointed; readers (the index, sibling lanes, other requests)
        keep the original. The full-cover admission path and fork-group
        lanes (prompt blocks aliased K ways, beam tables adopted at
        reorders) are the live hitters — but the guard is general: a
        shared block is NEVER written in place."""
        if self._prefix is None and slot.req.group is None:
            return
        bs = self._cache.block_size
        for bi in range(pos // bs, (pos + n - 1) // bs + 1):
            b = int(slot.table[bi])
            if b == 0 or not self._cache.is_shared(b):
                continue
            self._cow_block(slot, bi)

    def _force_cow(self, slot):
        """Chaos fork-storm: force a max-divergence COW of the block
        this lane will write next, shared or not — the burst path the
        deterministic tests drive without arranging real divergence.
        Returns True when a copy happened."""
        bs = self._cache.block_size
        bi = slot.next_pos // bs
        if bi >= slot.table.size or int(slot.table[bi]) == 0:
            return False
        group = slot.req.group
        if group is not None and not group.spares \
                and not self._cache.num_free:
            return False
        self._cow_block(slot, bi)
        return True

    def plan(self):
        """Build one iteration's fused-step inputs, or None when idle.
        Admission, cancels, and deadlines are resolved first, so the
        arrays always describe live lanes only. A truly idle call
        (nothing queued, active, or to cancel) does NOT count an
        iteration — the background worker's poll loop must not inflate
        the counter chaos plans key off."""
        with self._lock:
            if not (self._queue or self._cancel_rids or self._preempted
                    or any(s is not None for s in self._slots)):
                return None
            self.iteration += 1
            if self._chaos is not None:
                self._chaos.on_serving_iteration(self.iteration)
                if self._prefix is not None:
                    # deterministic eviction injection: the LRU path
                    # runs at an exact iteration, no pool pressure (or
                    # giant stream) required
                    for _ in range(self._chaos.serving_evictions_at(
                            self.iteration)):
                        if self._prefix.evict_lru() is not None:
                            self._chaos.serving_eviction_applied()
                    # deterministic SPILL injection: same idea, but
                    # only counts as applied when the eviction took the
                    # device->host path (host tier attached and not
                    # full), which is what the tier tests pin down
                    for _ in range(self._chaos.serving_spills_at(
                            self.iteration)):
                        before = self._prefix.counts["spills"]
                        if (self._prefix.evict_lru() is not None
                                and self._prefix.counts["spills"]
                                > before):
                            self._chaos.serving_spill_applied()
                if self._cache.host is not None:
                    # deterministic preempt injection: park a NAMED
                    # in-flight decode at an exact iteration (no pool
                    # pressure required); it resumes through the normal
                    # _try_resume path next iteration at the earliest
                    for rid in self._chaos.serving_preempts_at(
                            self.iteration):
                        for sid, slot in enumerate(self._slots):
                            if (slot is not None
                                    and slot.req.rid == rid
                                    and slot.req.group is None
                                    and not slot.prefilling):
                                if self._preempt_slot(sid):
                                    self._chaos \
                                        .serving_preempt_applied()
                                break
            now = self.now()
            self._apply_cancels_and_deadlines(now)
            self._admit(now)
            if self._preempted and not any(s is not None
                                           for s in self._slots):
                # a parked request is the only live work (a chaos
                # preempt can park the sole decode): an empty plan
                # would read as idle and stop the manual drive loop
                # with the request stranded — advance one iteration
                # (satisfying not_before) and resume right now
                self.iteration += 1
                self._admit(now)
            if self._chaos is not None:
                # fork-storm injection: force max-divergence COW bursts
                # on up to k live forked lanes at an exact iteration —
                # the burst path, testable without arranging real beam
                # divergence
                k_storm = self._chaos.fork_storms_at(self.iteration)
                if k_storm:
                    forced = 0
                    for slot in self._slots:
                        if forced >= k_storm:
                            break
                        if slot is None or not slot.plannable \
                                or slot.req.group is None \
                                or slot.prefilling:
                            continue
                        if self._force_cow(slot):
                            forced += 1
                    if forced:
                        self._chaos.fork_storm_applied(forced)
            s, c = self.num_slots, self.chunk

            def _plan_cols(slot):
                if slot.prefilling:
                    return min(c, len(slot.req.prompt) - slot.next_pos)
                sp = slot.req.sampling
                if self.spec_k and not (sp is not None and sp.do_sample):
                    # sampled lanes stay 1-column: draft acceptance is
                    # defined against the target's deterministic choice
                    return max(1, min(self.spec_k + 1, c,
                                      slot.req.max_new_tokens
                                      - len(slot.req.generated)
                                      - slot.emits_ahead))
                return 1

            # lazy-mode growth PRE-PASS: every lane's block needs are
            # settled before ANY table row is captured below — a
            # preemption during the array loop would leave lower-sid
            # rows pointing at blocks that were just spilled and freed
            starved = set()
            if self._cache.host is not None:
                for sid, slot in enumerate(self._slots):
                    if slot is None or slot.hold:
                        continue
                    if not self._ensure_blocks(sid, slot,
                                               _plan_cols(slot)):
                        starved.add(sid)
            tokens = np.zeros((s, c), np.int32)
            positions = np.zeros((s, c), np.int32)
            valid = np.zeros((s, c), bool)
            tables = np.full((s, self.max_blocks), 0, np.int32)
            decode_cols = np.zeros((s,), np.int32)
            limits = np.zeros((s,), np.int32)
            slot_ids, emitting = [], set()
            prefill_tokens = valid_columns = 0
            lanes = [] if self._tel is not None else None
            do_sample = np.zeros((s,), bool)
            temperature = np.ones((s,), np.float32)
            top_k_arr = np.zeros((s,), np.int32)
            top_p_arr = np.full((s,), 2.0, np.float32)
            rng_keys = np.zeros((s, 2), np.uint32)
            from_prev = np.zeros((s,), bool)
            guided_lanes = []
            needs_rows = False
            planned = []
            for sid, slot in enumerate(self._slots):
                if slot is not None:
                    slot.cols = slot.emit = 0
                # held slots are fork-group followers parked until the
                # leader's prefill completes — they own suffix blocks
                # but have no tokens to run yet; a done, ending or
                # exhausted lane has nothing left to feed
                if slot is None or sid in starved or not slot.plannable:
                    continue
                slot_ids.append(sid)
                planned.append(slot)
                req = slot.req
                start = slot.next_pos
                limits[sid] = len(req.prompt) + req.max_new_tokens
                if lanes is not None:
                    lanes.append(_lane_tuple(sid, slot))
                n = _plan_cols(slot)        # == the pre-pass's count
                valid_columns += n
                if slot.prefilling:
                    tokens[sid, :n] = req.prompt[start:start + n]
                    prefill_tokens += n
                    if self._tel is not None:
                        self._tel.on_prefill_chunk(req.rid,
                                                   self.iteration, n)
                    if start + n == len(req.prompt):
                        emitting.add(sid)
                else:
                    # decode lane: 1 column in plain mode; in spec mode
                    # q = min(k+1, chunk, remaining) verify columns —
                    # the engine fills 1..q-1 with draft proposals, and
                    # commit() accepts 1..q of the per-column outputs.
                    # Its token is the last one emitted: where a step
                    # not yet committed emits it, the device has it
                    decode_cols[sid] = n
                    if slot.emits_ahead:
                        from_prev[sid] = True
                    else:
                        tokens[sid, 0] = req.generated[-1]
                    emitting.add(sid)
                group = req.group
                sp = req.sampling
                if (sp is not None and sp.do_sample and sid in emitting
                        and (group is None or group.prefilled)):
                    # in-step stochastic sampling: the RNG key is a pure
                    # fold of (seed, lane, emit position) so replays and
                    # group failovers resample identically
                    do_sample[sid] = True
                    temperature[sid] = sp.temperature
                    top_k_arr[sid] = sp.top_k or 0
                    top_p_arr[sid] = (sp.top_p if sp.top_p is not None
                                      else 2.0)
                    rng_keys[sid] = fold_key(sp.seed, req.lane,
                                             start + n - 1)
                if req.guided is not None and sid in emitting:
                    guided_lanes.append((sid, req))
                    self._count("guided.masked_steps")
                if group is not None:
                    if group.kind == "beam" and not slot.prefilling:
                        needs_rows = True
                    if not group.prefilled and sid in emitting:
                        needs_rows = True
                # a shared block is never written in place: copy (to a
                # reserved spare) + repoint BEFORE the table row is
                # captured into the plan
                self._maybe_cow(slot, start, n)
                tables[sid] = slot.table
                positions[sid, :n] = np.arange(start, start + n)
                valid[sid, :n] = True
                slot.cols, slot.emit = n, int(sid in emitting)
                slot.ahead += n
                slot.emits_ahead += slot.emit
            if not slot_ids:
                return None
            self._count("prefill_tokens", prefill_tokens)
            return IterationPlan(
                tokens, positions, valid, tables, slot_ids, emitting,
                prefill_tokens, decode_cols=decode_cols, limits=limits,
                lanes_detail=tuple(lanes) if lanes is not None else None,
                queue_depth=len(self._queue)
                if lanes is not None else None,
                sample_ctl=(do_sample, temperature, top_k_arr,
                            top_p_arr, rng_keys),
                guided_lanes=tuple(guided_lanes),
                needs_rows=needs_rows, valid_columns=valid_columns,
                from_prev=from_prev, slots=planned,
                iteration=self.iteration)

    def _accept(self, plan, sid, ids, logps, fed_logps, draft_logps):
        """One decode lane's committed (token, logp) list + position
        advance. Column i's output is the target's next-token choice
        after fed column i; the fed columns 1..q-1 are the drafts.

        greedy: accept the longest prefix of drafts matching the
        target's own per-column argmax, then commit the target's next
        token after it — every committed id IS the target's greedy
        choice under the same context, so the stream is bitwise
        identical to plain decode (just fewer iterations).

        rejection (flagged, experimental): accept draft i with
        probability min(1, p_target(d_i)/p_draft(d_i)); on the first
        rejection commit the target argmax as the correction token
        (greedy correction stands in for residual resampling — see
        docs/serving.md for the documented deviation)."""
        q = int(plan.decode_cols[sid])
        if q == 1:
            return [(int(ids[sid, 0]), float(logps[sid, 0]))], 1
        toks = plan.tokens[sid]
        j = 0
        if self.spec_mode == "greedy":
            while j < q - 1 and int(toks[j + 1]) == int(ids[sid, j]):
                j += 1
            # along the accepted prefix ids[sid, i] == toks[i+1] (the
            # drafts), and ids[sid, j] is the target's own next token
            commits = [(int(ids[sid, i]), float(logps[sid, i]))
                       for i in range(j + 1)]
        else:
            commits = []
            while j < q - 1:
                # p_t(d_{j+1}) rides the fused step's fed-token logp
                # output; p_d from the draft step's proposal logps
                ratio = float(fed_logps[sid, j]) - float(
                    draft_logps[sid, j])
                if self._spec_rng.random() >= min(1.0, np.exp(ratio)):
                    break
                # an accepted draft is committed AS the draft token
                # (it may differ from the target argmax!) — the KV
                # written at its position is the draft's, so emitting
                # ids[sid, j] here would desynchronize the client
                # stream from the context the model attends to
                commits.append((int(toks[j + 1]),
                                float(fed_logps[sid, j])))
                j += 1
            # correction/bonus token after the accepted prefix is the
            # target's own choice (greedy correction — docs/serving.md)
            commits.append((int(ids[sid, j]), float(logps[sid, j])))
        self._count("spec.proposed", q - 1)
        self._count("spec.accepted", j)
        self._g_accept.set(
            self._mc["spec.accepted"].value()
            / max(self._mc["spec.proposed"].value(), 1))
        return commits, j + 1

    def commit(self, plan, next_ids, next_logps, fed_logps=None,
               draft_logps=None, rows=None):
        """Apply one fused step's outputs: advance positions, record
        emitted tokens (stream callbacks fire here), retire finished
        lanes. `next_ids`/`next_logps` are the fused step's PER-COLUMN
        argmax ids / chosen logps (S, C); a prefill lane reads its last
        valid column, a decode lane accepts 1..q columns (see
        _accept). `rows` (only when plan.needs_rows) carries the full
        log-prob rows — (S, V) plain or (S, C, V) per-column — that the
        host-side group strategies consume: fork-time sampling/beam
        seeding and per-iteration beam re-ranking. Returns the list of
        GenerationResults retired this iteration.

        A lane whose request ended at an earlier commit (EOS, found only
        once that step was fetched) while this step fed it is done: its
        outputs here are discarded, and its slot freed once no step not
        yet committed feeds it. A lane a cancel or deadline ended while
        this step was in flight ends after the tokens this step
        emits."""
        retired = []
        next_ids = np.asarray(next_ids)
        next_logps = np.asarray(next_logps)
        with self._lock:
            now = self.now()
            for sid, slot in zip(plan.slot_ids, plan.slots):
                if self._slots[sid] is slot:
                    slot.ahead -= int(plan.valid[sid].sum())
                    slot.emits_ahead -= int(sid in plan.emitting)
            # beam groups re-rank across their K lanes BEFORE the
            # per-lane loop: divergence remaps block tables and rewrites
            # lane streams, so the generic path below only applies the
            # pre-computed per-lane commits
            beam_overrides = self._commit_beam_groups(plan, rows)
            for sid, slot in zip(plan.slot_ids, plan.slots):
                if self._slots[sid] is not slot:
                    continue    # released under the step (cancel_all)
                if slot.done:
                    if not slot.ahead:
                        self._release_slot(sid)
                    continue
                finished = self._commit_lane(
                    plan, sid, slot, beam_overrides, next_ids,
                    next_logps, fed_logps, draft_logps, rows, now,
                    retired)
                if finished is not None:
                    retired.append(self._finish(slot.req, finished))
                    self._retire_lane(sid)
                elif slot.ending is not None and \
                        self._slots[sid] is slot and not slot.done:
                    exc, count_key = slot.ending
                    slot.ending = None
                    self._fail(slot.req, exc, count_key)
                    self._retire_lane(sid)
        return retired

    def _commit_lane(self, plan, sid, slot, beam_overrides, next_ids,
                     next_logps, fed_logps, draft_logps, rows, now,
                     retired):
        """One lane's part of commit(): advance its position, emit what
        it chose. Returns the finish reason or None."""
        req = slot.req
        group = req.group
        q = int(plan.decode_cols[sid]) if plan.decode_cols \
            is not None else 0
        if q == 0:
            # prefill lane: advance by the chunk fed; register
            # freshly-completed full prompt chunks into the prefix
            # index; emit only when the prompt finished
            n = int(plan.valid[sid].sum())
            slot.pos += n
            self._register_chunks(slot)
            if sid not in plan.emitting:
                return None
            if group is not None and not group.prefilled:
                # leader prefill complete: fork the group (K-1 table
                # aliases of the prompt blocks) and emit every lane's
                # first token host-side
                retired.extend(self._fork_group(
                    group, sid, slot, plan, rows, next_ids, next_logps,
                    n, now))
                return None
            commits = [(int(next_ids[sid, n - 1]),
                        float(next_logps[sid, n - 1]))]
        elif group is not None and group.kind == "beam":
            override = beam_overrides.get(sid)
            if override is None:
                return None     # group skipped this step (see above)
            commits, advance = override
            slot.pos += advance
        else:
            commits, advance = self._accept(
                plan, sid, next_ids, next_logps, fed_logps, draft_logps)
            slot.pos += advance
        finished = None
        for tok, lp in commits:
            finished = self._emit_token(req, tok, lp, now)
            if finished is not None:
                break       # later accepted tokens discarded
        return finished

    def _emit_token(self, req, tok, lp, now):
        """Record ONE committed token on `req`: score/stream/telemetry
        bookkeeping plus the guided-decoding automaton advance. Returns
        the finish reason ("eos" | "length") or None."""
        req.score += lp
        req.generated.append(tok)
        self._count("generated_tokens")
        if req.first_token_at is None:
            req.first_token_at = now
            if self._tel is not None:
                self._tel.on_first_token(
                    req.rid, self.iteration,
                    (now - req.submitted_at) * 1e3)
        else:
            itl = (now - req.last_token_at) * 1e3
            self._itl.observe(itl)
            if self._tel is not None:
                self._tel.on_token(req.rid, self.iteration, itl)
        req.last_token_at = now
        if req.stream is not None:
            try:
                req.stream(req.rid, tok)
            except Exception:  # noqa: BLE001 — a client
                pass    # callback must never kill the loop
        if req.guided is not None and req.guided_state is not None:
            # beam lanes carry eos on the GROUP (the lane itself never
            # eos-retires — finished hypotheses pad with forced eos
            # exactly like the dense reference), so resolve eos there
            eos = req.eos_id if req.group is None else req.group.eos_id
            if eos is None or tok != eos:
                nxt_state = req.guided.advance(req.guided_state, tok)
                if nxt_state is None:
                    # the in-step mask makes this unreachable in normal
                    # operation; counted (not raised) so a chaos
                    # mask-starve can't take the serving loop down
                    self._count("guided.violations")
                    req.guided_state = None
                else:
                    req.guided_state = nxt_state
        done_eos = req.eos_id is not None and tok == req.eos_id
        if done_eos:
            return "eos"
        if len(req.generated) >= req.max_new_tokens:
            return "length"
        return None

    def _fork_group(self, group, sid, slot, plan, rows, next_ids,
                    next_logps, n, now):
        """The group leader's prefill just finished: fan out into K
        lanes. Every follower's table adopts the leader's prompt blocks
        by reference (`fork_table` — one refcount bump per block, zero
        copies), each lane's first token is chosen host-side from the
        leader's final logit row (per-lane folded RNG for sampling, one
        k-way `beam_step` for beam), and followers leave `hold` so the
        next plan() runs them as ordinary decode lanes. Divergence
        after this point is handled by _maybe_cow: the first write into
        a still-shared block copies it to one of the group's reserved
        spares. Returns the GenerationResults retired at fork (only
        possible when max_new_tokens == 1)."""
        retired = []
        if group.failed:
            return retired      # cancel sweep will reclaim the slots
        req = slot.req
        p_len = len(req.prompt)
        bs = self._cache.block_size
        m_prompt = (p_len + bs - 1) // bs
        k = group.k
        row = None
        if rows is not None:
            row = np.asarray(rows[sid] if rows.ndim == 2
                             else rows[sid, n - 1], np.float32)
        # fork the tables BEFORE emitting: a lane retiring on its first
        # token releases through the group path, which unrefs the
        # prompt blocks it must therefore already hold
        src = [int(slot.table[i]) for i in range(m_prompt)]
        for rank in range(1, k):
            fsid = group.lane_sids.get(rank)
            if fsid is None:
                continue
            fslot = self._slots[fsid]
            forked = self._cache.fork_table(src)
            fslot.table[:m_prompt] = forked
            fslot.blocks = forked + fslot.blocks
            fslot.pos = p_len
            fslot.hold = False
        group.prefilled = True
        self._count("group.forks", k - 1)
        if group.kind == "beam":
            # seed exactly like the dense reference: lane 0 carries the
            # prompt at score 0, lanes 1..K-1 start at NEG_INF so the
            # first step picks the top-K tokens of one distribution
            rows_k = np.tile(row[None, :], (k, 1))
            scores0 = np.full((k,), NEG_INF, np.float32)
            scores0[0] = 0.0
            toks, _parents, scores, done = beam_step(
                rows_k, scores0, np.zeros((k,), bool), group.eos_id)
            group.scores = scores
            group.done = done
            lane_toks = [(int(toks[r]), float(scores[r]))
                         for r in range(k)]
        else:
            sp = group.sampling
            lane_toks = []
            for rank in range(k):
                if sp is not None and sp.do_sample:
                    key = fold_key(sp.seed, rank, p_len - 1)
                    tok, lp = host_sample(row, key, sp.temperature,
                                          sp.top_k, sp.top_p)
                else:
                    tok = int(next_ids[sid, n - 1])
                    lp = float(next_logps[sid, n - 1])
                lane_toks.append((int(tok), float(lp)))
        for rank in range(k):
            fsid = group.lane_sids.get(rank)
            if fsid is None:
                continue
            lane_req = self._slots[fsid].req
            tok, lp = lane_toks[rank]
            finished = self._emit_token(lane_req, tok, lp, now)
            if finished is not None:
                retired.append(self._finish(lane_req, finished))
                self._retire_lane(fsid)
        return retired

    def _commit_beam_groups(self, plan, rows):
        """Pre-pass over decode-phase beam groups: run the SAME top-K
        selection as the dense reference (`beam_step` per verify
        column), rewrite diverging lanes' streams/tables from their
        parents, and return {sid: (commits, advance)} for the generic
        commit loop. Beam reorder is pure host bookkeeping — parent
        tables are adopted by reference (ref new, then unref old;
        sole-ref leftovers are RETAINED as group spares so the
        admission-time reservation keeps covering every future COW)."""
        overrides = {}
        if plan.decode_cols is None:
            return overrides
        by_group = {}
        for sid in plan.slot_ids:
            slot = self._slots[sid]
            if slot is None or int(plan.decode_cols[sid]) == 0:
                continue
            g = slot.req.group
            if g is not None and g.kind == "beam" and g.prefilled:
                by_group.setdefault(g.gid, (g, []))[1].append(sid)
        for g, sids in by_group.values():
            if len(sids) != g.k or g.failed:
                continue    # a lane raced with a cancel: skip the step
                # (positions unchanged -> next iteration re-runs it)
            sids.sort(key=lambda s: self._slots[s].req.lane)
            k = g.k
            lane_reqs = [self._slots[s].req for s in sids]
            q = int(plan.decode_cols[sids[0]])
            sc = np.asarray(g.scores, np.float32)
            done = np.asarray(g.done, bool)
            ident = np.arange(k)
            steps = []      # (toks, parents, sc_after, sc_before)
            for j in range(q):
                rows_j = np.stack(
                    [np.asarray(rows[s] if rows.ndim == 2
                                else rows[s, j], np.float32)
                     for s in sids])
                toks, parents, sc_new, done_new = beam_step(
                    rows_j, sc, done, g.eos_id)
                steps.append((toks, parents, sc_new, sc))
                sc, done = sc_new, done_new
                if not bool(np.all(parents == ident)):
                    break   # divergence: later verify columns are
                    # conditioned on the wrong parent hypotheses
                if j + 1 < q and not all(
                        int(toks[i]) == int(plan.tokens[sids[i], j + 1])
                        for i in range(k)):
                    break   # a chosen token differs from the fed draft
            g.scores, g.done = sc, done
            n_steps = len(steps)
            if q > 1:
                self._count("spec.proposed", (q - 1) * k)
                self._count("spec.accepted", (n_steps - 1) * k)
            # snapshots BEFORE any mutation: a lane may adopt a parent
            # that itself adopts a different parent this same step
            snaps = [(list(r.generated), r.score, r.guided_state)
                     for r in lane_reqs]
            last_toks, last_parents, last_sc, last_prev = steps[-1]
            commits_by_lane = []
            for i in range(k):
                p = int(last_parents[i])
                if p == i:
                    commits = [(int(st_t[i]), float(st_a[i] - st_b[i]))
                               for st_t, _, st_a, st_b in steps[:-1]]
                else:
                    # adopt the parent's pre-step stream + state, then
                    # commit the PARENT's identity-step tokens so the
                    # appends reconstruct its chain
                    lane_reqs[i].generated = list(snaps[p][0])
                    lane_reqs[i].score = snaps[p][1]
                    lane_reqs[i].guided_state = snaps[p][2]
                    commits = [(int(st_t[p]), float(st_a[p] - st_b[p]))
                               for st_t, _, st_a, st_b in steps[:-1]]
                commits.append((int(last_toks[i]),
                                float(last_sc[i] - last_prev[p])))
                commits_by_lane.append(commits)
            if not bool(np.all(last_parents == ident)):
                self._reorder_beam_tables(g, sids, last_parents)
            for i, s in enumerate(sids):
                overrides[s] = (commits_by_lane[i], n_steps)
        return overrides

    def _reorder_beam_tables(self, group, sids, parents):
        """Apply a beam reorder to the K lanes' block tables: lane i
        whose parent p != i adopts a COPY of p's pre-step table, taking
        one ref on every live block FIRST, then dropping its old refs
        (sole-ref blocks are retained as group spares — returning them
        to the pool would quietly shrink the group's no-mid-flight-OOM
        reservation). The next write into any now-shared suffix block
        COWs from those spares via _maybe_cow."""
        old = [(self._slots[s].table.copy(), list(self._slots[s].blocks))
               for s in sids]
        moved = [i for i in range(group.k) if int(parents[i]) != i]
        for i in moved:
            new_tbl = old[int(parents[i])][0]
            live = [int(b) for b in new_tbl if b != 0]
            for b in live:
                self._cache.ref(b)
            sl = self._slots[sids[i]]
            sl.table = new_tbl.copy()
            sl.blocks = list(live)
            sl.shared = [b for b in sl.shared if b in live]
        for i in moved:
            for b in old[i][1]:
                if self._cache.refcount(b) == 1:
                    group.spares.append(b)
                else:
                    self._cache.unref(b)
        group.reorders += 1
        self._count("beam.reorders")

    def _register_chunks(self, slot):
        """Offer every freshly-prefilled FULL prompt chunk to the
        prefix index (the chain keys were computed once at admission —
        registration never re-hashes)."""
        if self._prefix is None:
            return
        bs = self._cache.block_size
        done = min(slot.pos, len(slot.req.prompt)) // bs
        if done <= slot.registered:
            return
        for i in range(slot.registered, done):
            parent = slot.keys[i - 1] if i else None
            if self._prefix.register(
                    slot.keys[i], parent,
                    slot.req.prompt[i * bs:(i + 1) * bs],
                    int(slot.table[i])):
                if int(slot.table[i]) not in slot.shared:
                    slot.shared.append(int(slot.table[i]))
        slot.registered = done

    def lane_block_for_prompt(self, prompt):
        """-> the FIRST table block of the active lane whose request
        prompt equals `prompt` and has advanced past position 0, or
        None. The chaos prompt-poison hook (engine.step) uses this to
        NaN a poison request's own KV wherever its failover replay
        lands — content-addressed, so the fault follows the request
        across replicas. Position >= 1 mirrors _poison_kv: a pos-0
        lane's block is fully overwritten by its own prefill write, so
        the NaN could never propagate."""
        with self._lock:
            for slot in self._slots:
                # the position the newest plan feeds from
                if slot is None or not slot.live \
                        or slot.next_pos - slot.cols < 1:
                    continue
                if np.array_equal(slot.req.prompt, prompt):
                    return int(slot.table[0])
        return None

    # -- introspection -----------------------------------------------------
    def lane_snapshot(self):
        """Per-lane occupancy: one tuple per ACTIVE slot in
        serving_telemetry.LANE_FIELDS order (slot, rid, pos,
        prefilling, admit_seq, generated, first_block); the flight
        dump expands these to dicts. Cold path only — the engine's
        per-iteration flight entry takes its lane detail from
        plan.lanes_detail (built inside plan()'s slot loop); this
        exists for callers without a plan in hand (the chaos
        poison fallback, telemetry-off fault triage)."""
        with self._lock:
            return tuple(_lane_tuple(sid, slot)
                         for sid, slot in enumerate(self._slots)
                         if slot is not None and slot.live)

    def stats(self):
        with self._lock:
            # watermark headroom in the unit it actually protects:
            # bytes ONE device keeps free. Block ids are replicated host
            # state, but under a head-sharded mesh each block costs
            # shard_pool_bytes()/num_blocks per device — the watermark's
            # byte value shrinks with the tp degree, the block count
            # does not.
            shard_block_bytes = (self._cache.shard_pool_bytes()
                                 // self._cache.num_blocks)
            return {
                "iteration": self.iteration,
                "queue_depth": len(self._queue),
                "active_slots": sum(s is not None for s in self._slots),
                "num_slots": self.num_slots,
                "blocks_total": self._cache.usable_blocks,
                "blocks_free": self._cache.num_free,
                "block_utilization": round(self._cache.utilization(), 4),
                "watermark_blocks": self.watermark_blocks,
                "watermark_shard_bytes": self.watermark_blocks
                * shard_block_bytes,
                "free_shard_bytes": self._cache.num_free
                * shard_block_bytes,
                "prefix": self._prefix.stats()
                if self._prefix is not None else None,
                "preempts": self.preempts,
                "resumes": self.resumes,
                "preempted_depth": len(self._preempted),
                "host_blocks_free": self._cache.host.num_free
                if self._cache.host is not None else None,
                "spec_k": self.spec_k,
                "spec_mode": self.spec_mode if self.spec_k else None,
                **dict(self.counts),
            }
