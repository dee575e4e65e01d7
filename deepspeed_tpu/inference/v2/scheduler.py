"""Continuous-batching serving loop with Dynamic SplitFuse scheduling.

The reference keeps this loop in DeepSpeed-MII (external repo; in-repo
support is ``scheduling_utils.py`` — SURVEY §2 "DeepSpeed-MII / FastGen
scheduler"). Shipping it in-tree makes the TPU engine self-contained:
requests enter a queue; each step the scheduler packs (a) one decode token
for every running sequence and (b) prompt *chunks* from pending requests,
splitting long prompts so every forward has near-constant token count — the
Dynamic SplitFuse property that keeps TTFT low while decode throughput
stays flat.

Speculative decoding (``proposer`` + greedy sampling; spec/,
docs/SERVING.md "Speculative decoding") rides the same packing: a decode
row carries ``[certain_token, draft_1..draft_K]`` instead of one token —
structurally a K+1-token prefill chunk — the forward returns per-position
logits, ``verify_greedy`` accepts the longest draft prefix the target's
argmax agrees with, and rejected tokens are rolled back with
``engine.trim_sequence``. The emitted stream is byte-identical to
speculation off; with no proposer the scheduler is byte-for-byte the
historical one.

One step in flight (docs/SERVING.md "A step in flight"): the forward draws
each row's next token on the device and keeps it there
(``engine.next_ids``); the next step's one-token rows say "my token is my
slot's id" (``DEVICE_TOKEN``) and are planned from counts alone, so
``step`` dispatches step n+1 *before* it reads step n's ids, and the host's
turn — pack, stage, commit — runs while the device works. A scheduler
given a host ``sample_fn`` or a proposer needs every token on the host
before it can plan, and runs the same code with nothing left in flight.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from collections import OrderedDict, deque
from typing import Callable, Deque, Dict, List, Optional

import numpy as np

from ...telemetry import NOOP_TRACER
from ...utils.logging import logger
from .engine_v2 import DEVICE_TOKEN, FORWARD_ONLY, InferenceEngineV2
from .scheduling_utils import SchedulingResult
from .spec import DraftProposer, verify_greedy


@dataclasses.dataclass
class Request:
    uid: int
    prompt_tokens: List[int]
    max_new_tokens: int = 64
    eos_token_id: Optional[int] = None
    # serving hooks (serving/replica.py): per-token delivery and a terminal
    # notification with a finish reason ("eos" | "length" | "cancelled")
    on_token: Optional[Callable[[int, int], None]] = None
    on_finish: Optional[Callable[["Request", str], None]] = None
    # admission overhaul (docs/SERVING.md "Admission and preemption"):
    # shed_rank orders preemption victim selection (higher = lower
    # urgency class, preempted first — the serving layer passes its
    # class shed rank); preempt_count caps how often one sequence may
    # be spilled (the starvation guard); total_blocks is the reserved
    # total projected KV need recorded at admission
    shed_rank: int = 0
    preempt_count: int = 0
    total_blocks: int = 0
    # state
    prompt_fed: int = 0
    prefix_matched: int = -1     # tokens served from the prefix cache
    #                              (-1 = lookup not yet performed)
    generated: List[int] = dataclasses.field(default_factory=list)
    # the logits ``generated[-1]`` was (or the first token will be) drawn
    # from: an array, or a row of a put's handle that is copied from the
    # device only when read (``np.asarray``)
    last_logits: Optional[object] = None
    # rows dispatched for this request whose draw has not been read yet
    in_flight: int = 0
    done: bool = False
    finish_reason: Optional[str] = None
    # telemetry (docs/OBSERVABILITY.md): set by submit() when the
    # scheduler's tracer is enabled and the caller passed a trace id;
    # spans holds the open prefill/decode stage spans
    trace_id: Optional[str] = None
    spans: Optional[Dict[str, object]] = None

    @property
    def prompt_remaining(self) -> int:
        return len(self.prompt_tokens) - self.prompt_fed


class _LogitsRow:
    """Row ``i`` of a put's logits, read from the device when asked."""

    __slots__ = ("handle", "i")

    def __init__(self, handle, i: int):
        self.handle, self.i = handle, i

    def __array__(self, dtype=None, copy=None):
        row = np.asarray(self.handle)[self.i]
        return row if dtype is None else row.astype(dtype)


class _Flight:
    """A dispatched step whose tokens have not been read: its plan, the
    put's handle, which rows draw a token (decode rows and the rows that
    complete a prompt), the tokens it fed by uid, whether its commit
    reads the logits (a host sampler's, a verification's: else the ids
    the forward drew), and its open ``forward`` span."""

    __slots__ = ("plan", "handle", "draws", "fed", "verify_width",
                 "reads_logits", "fspan")

    def __init__(self, plan, handle, draws, verify_width, reads_logits,
                 fspan):
        self.plan, self.handle, self.draws = plan, handle, draws
        self.fed = {req.uid: len(chunk) for req, chunk, _ in plan}
        self.verify_width, self.reads_logits = verify_width, reads_logits
        self.fspan = fspan


class ContinuousBatchingScheduler:
    def __init__(self, engine: InferenceEngineV2,
                 sample_fn: Optional[Callable] = None,
                 proposer: Optional[DraftProposer] = None,
                 max_draft_tokens: int = 4,
                 tracer=None, trace_label: str = "scheduler",
                 prefill_only: bool = False,
                 decode_reserve_tokens: int = 0):
        self.engine = engine
        # disaggregated serving roles (docs/SERVING.md "Disaggregated
        # serving"): a prefill-only scheduler never decodes — a request
        # whose prompt completes is finished with reason "prefilled" and
        # its KV left RESIDENT for the handoff export; a decode-role
        # scheduler reserves part of each step's token budget so queued
        # prompt chunks can never blow up the forward a decode rides in.
        # Defaults (False / 0) keep the historical scheduler byte for
        # byte.
        self.prefill_only = bool(prefill_only)
        self.decode_reserve_tokens = int(decode_reserve_tokens)
        # telemetry: per-forward spans under ``trace_label``'s trace and
        # per-request prefill/decode stage spans (docs/OBSERVABILITY.md).
        # The default NOOP tracer keeps the historical hot path: one
        # ``enabled`` attribute check per step.
        self.tracer = tracer if tracer is not None else NOOP_TRACER
        self.trace_label = trace_label
        # the engine opens one ``dispatch`` span a forward on the same
        # tracer, inside this scheduler's ``stage``
        engine.tracer = self.tracer
        self.pending: Deque[Request] = deque()
        self.running: Dict[int, Request] = {}
        self.finished: Dict[int, Request] = {}
        # a host sampler has to see the logits before the next step can be
        # planned; without one the forward's own greedy draw is the token
        self.sample_fn = sample_fn
        self._draw = sample_fn or (lambda logits: int(np.argmax(logits)))
        self._budget = engine.config.max_ragged_batch_size
        self._max_seqs = engine.config.max_ragged_sequence_count
        self._chunk = engine.config.max_chunk_tokens
        # speculative decoding: only lossless under greedy sampling — a
        # custom sample_fn silently wins over the proposer (documented)
        self.max_draft_tokens = max_draft_tokens
        self.proposer = proposer
        if proposer is not None and sample_fn is not None:
            logger.warning(
                "speculative decoding requires greedy sampling; custom "
                "sample_fn given — proposer disabled for this scheduler")
            self.proposer = None
        # whether a step may be left in flight while the next is planned:
        # not when a token has to be on the host first (a sampler's
        # logits, a proposer's context)
        self._run_ahead = sample_fn is None and self.proposer is None
        self._flight: Optional[_Flight] = None  # dispatched, unread
        self._stepping = False                  # inside step()
        self._done_now: List[int] = []          # finished since step() said
        self._step_stats = {"steps": 0, "steps_overlapped": 0,
                            "steps_starved": 0}
        self._spec_stats = {"proposed": 0, "accepted": 0, "emitted": 0,
                            "decode_rows": 0}
        self._proposer_warned = False
        # admission overhaul (docs/SERVING.md "Admission and
        # preemption"), read from the ENGINE config so bare schedulers
        # (benchmark/, tests) and the serving stack share one wiring point
        # (``ServingFrontend`` stamps ``ServingConfig.admission`` onto
        # each replica engine via ``engine.configure_admission`` before
        # building the replica's scheduler). All-default = the
        # historical chunk-by-chunk admission byte for byte.
        ecfg = engine.config
        self.reservation = bool(getattr(ecfg, "admission_reservation",
                                        False))
        self.oversubscription_factor = float(getattr(
            ecfg, "admission_oversubscription_factor", 1.0))
        self.preempt_enabled = bool(getattr(
            ecfg, "admission_preemption_enabled", False))
        self.victim_policy = str(getattr(
            ecfg, "admission_victim_policy", "lowest_class"))
        self.max_preemptions_per_seq = int(getattr(
            ecfg, "admission_max_preemptions_per_seq", 2))
        # parked (preempted) sequences, resume order = preemption order:
        # uid -> {"req", "tokens", "stashed", "last_logits", "fed",
        #         "n_blocks", "total_blocks"}
        self.preempted: "OrderedDict[int, dict]" = OrderedDict()
        self._preempt_stats = {"preempted": 0, "resumed": 0}
        self._parked_blocks = 0           # device blocks parked seqs held
        self._last_shortfall = 0          # blocks the pending head is short
        self._preempt_events: List[dict] = []   # drained by the replica
        self._spill_times: List[float] = []     # → preempt_spill_s
        self._resume_times: List[float] = []    # → preempt_resume_s

    @property
    def spec_enabled(self) -> bool:
        return self.proposer is not None

    def step_stats(self) -> Dict[str, int]:
        """Monotonic counters: ``steps`` dispatched, and of them
        ``steps_overlapped`` — dispatched while the step before was still
        unread, so that the host's turn ran behind the device's — and
        ``steps_starved``: of those, the steps whose predecessor had
        already finished on the device when their first forward was
        handed over (``PutLogits.ran_dry``), so that the device had run
        dry and the host set the pace."""
        return dict(self._step_stats)

    def spec_stats(self) -> Dict[str, int]:
        """Monotonic speculative-decoding counters: ``proposed``/
        ``accepted`` draft tokens, ``emitted`` decode tokens, and
        ``decode_rows`` (decode row-forwards — each would have emitted
        exactly one token without speculation, so tokens-per-forward =
        emitted / decode_rows). ``proposed`` counts drafts that reached
        verification — drafts discarded by the admission degrade path
        were never judged and don't count; ``accepted`` counts only
        *delivered* drafts (a draft verified beyond an EOS is trimmed,
        not delivered), so acceptance_rate describes the streams the
        requests actually received."""
        return dict(self._spec_stats)

    def submit(self, uid: int, prompt_tokens: List[int],
               max_new_tokens: int = 64, eos_token_id: Optional[int] = None,
               on_token: Optional[Callable[[int, int], None]] = None,
               on_finish: Optional[Callable[[Request, str], None]] = None,
               trace_id: Optional[str] = None, shed_rank: int = 0):
        req = Request(uid, list(prompt_tokens), max_new_tokens,
                      eos_token_id, on_token, on_finish,
                      shed_rank=int(shed_rank))
        if trace_id is not None and self.tracer.enabled:
            # the prefill stage starts at scheduler submission so the
            # request's span chain stays gap-free: any wait for a packing
            # slot is prefill time from the request's point of view
            req.trace_id = trace_id
            req.spans = {"prefill": self.tracer.begin(
                "prefill", trace_id=trace_id,
                attrs={"prompt_tokens": len(req.prompt_tokens),
                       # joins the ``dispatch`` spans that fed it (``uids``)
                       "uid": uid})}
        self.pending.append(req)

    def submit_prefilled(self, uid: int, prompt_tokens: List[int],
                         last_logits, max_new_tokens: int = 64,
                         eos_token_id: Optional[int] = None,
                         on_token: Optional[Callable[[int, int], None]] = None,
                         on_finish: Optional[Callable[["Request", str],
                                                      None]] = None,
                         trace_id: Optional[str] = None,
                         shed_rank: int = 0) -> Request:
        """Resume a sequence whose prompt KV was imported from a
        prefill-role replica (``engine.import_sequence`` must have run
        first): the request enters ``running`` directly with the prompt
        marked fed and the source's final-position logits, so the first
        token is drawn (on the host, at the next step) from exactly the
        logits the source would have drawn it from — byte-lossless under
        greedy decoding (docs/SERVING.md "Disaggregated serving"). Where
        the imported KV is short of ``prompt_tokens`` — a sequence
        evacuated in mid-decode, whose last delivered token had not been
        fed yet — the rest is fed as a prompt chunk and ``last_logits``
        is not read."""
        req = Request(uid, list(prompt_tokens), max_new_tokens,
                      eos_token_id, on_token, on_finish,
                      shed_rank=int(shed_rank))
        req.prompt_fed = min(len(req.prompt_tokens),
                             self.engine.query(uid)[0])
        req.prefix_matched = 0       # no lookup: the KV arrived whole
        req.last_logits = np.asarray(last_logits)
        if self.reservation:
            # the imported blocks are already resident; reserve the
            # remaining decode need. A shortfall here is repaired by the
            # preemption pass (or, with preemption off, was prevented by
            # the replica's pre-import headroom check) — the import
            # cannot be un-done from here, so the ledger records it
            # unconditionally rather than lying by omission.
            req.total_blocks = self._total_blocks(req)
            if not self.engine.try_reserve(uid, req.total_blocks):
                self.engine.force_reserve(uid, req.total_blocks)
        if trace_id is not None and self.tracer.enabled:
            # no prefill stage here (it ran on the source replica); the
            # decode span opens at the first emitted token as usual
            req.trace_id = trace_id
            req.spans = {}
        self.running[uid] = req
        return req

    def cancel(self, uid: int) -> bool:
        """Abort a request wherever it is; frees its KV blocks immediately
        (serving's cancel path — the blocks go back to the pool this step,
        not when the sequence would have finished). Returns False for
        unknown/already-finished uids."""
        if uid in self.running:
            self._settle()      # its row in flight, if any, is read first
        req = self.running.pop(uid, None)
        if req is None:
            # a preempted (parked) sequence holds no device blocks —
            # drop its spilled payload and settle terminally
            entry = self.preempted.pop(uid, None)
            if entry is not None:
                req = entry["req"]
                self._parked_blocks -= entry["n_blocks"]
                self.engine.preempt_discard(uid)
        if req is None:
            for r in self.pending:
                if r.uid == uid:
                    req = r
                    self.pending.remove(r)
                    break
        if req is None or req.done:
            return False
        self.engine.flush(uid)
        if self.proposer is not None:       # drop draft state mid-speculation
            self.proposer.release(uid)
        self._end_request_spans(req, "cancelled")
        req.done = True
        req.finish_reason = "cancelled"
        req.last_logits = None      # not its last put's logits, kept alive
        self.finished[uid] = req
        if req.on_finish is not None:
            req.on_finish(req, "cancelled")
        return True

    def evacuate(self, uid: int) -> Optional[Dict[str, object]]:
        """Detach a sequence for migration to ANOTHER replica
        (docs/SERVING.md "Elastic autoscaling"): remove it from this
        scheduler's structures and free its device blocks WITHOUT
        settling it — no ``done`` mark, no ``on_finish`` callback; the
        serving layer re-queues the request and its stream continues
        elsewhere. For a fully-prefilled running sequence the resident
        KV is exported first (the PR 11 spill representation) and
        returned as a staged-handoff payload (``last_logits`` included)
        so the destination replica imports instead of re-prefilling;
        anything else — pending, mid-prefill, parked — returns ``None``
        and the caller re-prefills from prompt + delivered tokens (the
        failover resume semantics, lossless under greedy decoding).
        Returns ``None`` also for unknown/finished uids (nothing to
        move)."""
        payload = None
        if uid in self.running:
            self._settle()      # its row in flight, if any, is read first
        req = self.running.pop(uid, None)
        if req is not None:
            if (req.prompt_remaining == 0 and not req.done
                    and req.last_logits is not None):
                try:
                    payload = self.engine.export_sequence(uid)
                except Exception as e:
                    logger.warning(f"evacuation KV export for sequence "
                                   f"{uid} failed ({e!r}); falling back "
                                   "to re-prefill")
                    payload = None
                if payload is not None:
                    payload["last_logits"] = np.asarray(req.last_logits)
        else:
            # parked sequence: its device blocks are already free and
            # its payload sits in the preempt stash — drop the stash
            # (the re-prefill path is simpler than re-plumbing a parked
            # import across replicas) and hand the request back
            entry = self.preempted.pop(uid, None)
            if entry is not None:
                req = entry["req"]
                self._parked_blocks -= entry["n_blocks"]
                self.engine.preempt_discard(uid)
        if req is None:
            for r in self.pending:
                if r.uid == uid:
                    req = r
                    self.pending.remove(r)
                    break
        if req is None or req.done:
            return None
        try:
            self.engine.flush(uid)     # frees blocks + releases reservation
        except Exception:
            pass
        if self.proposer is not None:   # drop draft state mid-speculation
            self.proposer.release(uid)
        self._end_request_spans(req, "evacuated")
        return payload

    @property
    def has_work(self) -> bool:
        return bool(self.pending or self.running or self.preempted
                    or self._flight is not None)

    def _pack(self):
        """Dynamic SplitFuse packing: decodes first, then prompt chunks.

        Planning from counts — what a request has been fed and how many
        tokens it has drawn, the one in flight included — so a step is
        planned before the step ahead of it has been read: a decode row
        whose token is still on the device carries ``DEVICE_TOKEN``. No
        request state moves here (a put that fails before its dispatch can
        be retried; ``_dispatch`` moves it), but for a sequence handed
        over with its logits, whose first token is drawn from them here.
        Admission is checked incrementally for decodes AND prompt chunks,
        deferring what doesn't fit to the next step."""
        uids: List[int] = []
        chunks: List[List[int]] = []
        plan: List[tuple] = []        # (req, chunk, is_decode)
        budget = self._budget

        # prompt candidates (running-but-prefilling, then pending) are
        # pulled and prefix-matched up front, BEFORE any admission check:
        # match_prefix pins shared blocks (refcounts), which moves them
        # out of the evictable count admission reads — matching after an
        # admit() could invalidate that admission and turn the engine's
        # re-check in put() into a SchedulingError. One-time per request;
        # a no-op returning 0 when the cache is disabled. Matched blocks
        # stay shared across deferral/retry until finish/cancel flushes.
        if self.reservation:
            # admission overhaul (docs/SERVING.md "Admission and
            # preemption"): repair any force-reserve over-commitment,
            # resume parked sequences oldest-first while seats and
            # headroom allow, then admit pending work under total-block
            # reservation — a request that cannot reserve its whole
            # projected need WAITS instead of part-prefilling the pool
            # into a wedge.
            self._maybe_restore_headroom()
            self._resume_preempted()
            new_candidates = self._admit_pending_reserved()
        else:
            new_candidates = []
            while (self.pending
                   and len(self.running) + len(new_candidates) < self._max_seqs):
                new_candidates.append(self.pending.popleft())
        candidates: List[Request] = [r for r in self.running.values()
                                     if r.prompt_remaining > 0]
        for req in candidates + new_candidates:
            self._match_prefix_for(req)

        def admit(req, chunk) -> bool:
            ok = self.engine.can_schedule(uids + [req.uid],
                                          [len(c) for c in chunks] + [len(chunk)])
            if ok != SchedulingResult.Success:
                return False
            uids.append(req.uid)
            chunks.append(chunk)
            return True

        # (a) one token for every running (decode) sequence that fits —
        # plus up to max_draft_tokens proposer drafts when speculating
        # (the chunk is then verified like a K+1-token prefill chunk)
        for uid, req in list(self.running.items()):
            if self.prefill_only:
                break     # prefill-role: decode rows never pack here
            if req.prompt_remaining > 0 or budget <= 0:
                continue  # still prefilling (below) / out of budget (defer)
            drawn = len(req.generated) + req.in_flight
            if not drawn:
                # handed over with the logits of its prompt's last
                # position (``submit_prefilled``): the first token is
                # drawn from them, on the host
                self._deliver(req, self._draw(np.asarray(req.last_logits)))
                drawn = 1
            if req.done or drawn >= req.max_new_tokens:
                continue  # its last token is drawn: nothing more to feed
            tok = DEVICE_TOKEN if req.in_flight else req.generated[-1]
            chunk = [tok]
            if self.proposer is not None:
                # cap drafts so the chunk fits every static budget; the
                # last draft slot is pointless when the request can emit
                # at most one more token anyway
                k = min(self.max_draft_tokens, budget - 1, self._chunk - 1,
                        req.max_new_tokens - len(req.generated) - 1)
                if k > 0:
                    drafts = self._propose(req, k)
                    if drafts:
                        chunk = [tok] + [int(d) for d in drafts[:k]]
            if admit(req, chunk):
                plan.append((req, chunk, True))
                budget -= len(chunk)
            elif len(chunk) > 1 and admit(req, [tok]):
                # speculative chunk didn't fit (KV pressure / seq-len
                # ceiling) — degrade to plain decode rather than defer
                plan.append((req, [tok], True))
                budget -= 1
        # (b) prompt chunks: running-but-prefilling first, then pending.
        # A decode-role scheduler holds back the UNUSED part of its
        # decode reservation from prompt chunks — the forward a decode
        # row rides in stays small even under a queued-prompt burst.
        # Clamped so at least one prompt token can always be scheduled
        # (an over-sized reservation must degrade prefill, not wedge it).
        reserve = 0
        if self.decode_reserve_tokens > 0:
            decode_used = self._budget - budget
            reserve = max(0, self.decode_reserve_tokens - decode_used)
            reserve = min(reserve, max(0, budget - 1))
        prompt_budget = budget - reserve
        deferred: List[Request] = []
        for req in candidates + new_candidates:
            scheduled = False
            if prompt_budget > 0 and len(uids) < self._max_seqs:
                take = min(req.prompt_remaining, prompt_budget, self._chunk)
                chunk = req.prompt_tokens[req.prompt_fed:req.prompt_fed + take]
                if admit(req, chunk):
                    plan.append((req, chunk, False))
                    budget -= take
                    prompt_budget -= take
                    scheduled = True
            if not scheduled and req.uid not in self.running:
                deferred.append(req)           # new request deferred
        # back to the head of the queue in arrival order (one appendleft
        # each would put them back reversed, and which request is
        # prefilled next would depend on who else happened to be waiting)
        self.pending.extendleft(reversed(deferred))
        return uids, chunks, plan

    def _match_prefix_for(self, req: Request) -> None:
        """One-time prefix-cache lookup for a candidate (no-op once
        done, or when the cache is disabled — returns 0, creates
        nothing). Matched blocks stay shared across deferral/retry
        until finish/cancel flushes."""
        if req.prefix_matched >= 0:
            return
        # tiered KV memory (docs/SERVING.md "KV tiering"): count how
        # many of this request's matched blocks came back from the
        # host/disk tier — only when tracing, the extra stats read is
        # off the default hot path
        tier_fn = (getattr(self.engine, "tier_stats", None)
                   if req.spans is not None else None)
        restored0 = tier_fn()["restored"] if tier_fn else 0
        req.prefix_matched = self.engine.match_prefix(
            req.uid, req.prompt_tokens)
        if req.prefix_matched > 0:
            req.prompt_fed = req.prefix_matched
        if req.spans is not None:
            # cache outcome as a span attribute — the "where did
            # this TTFT go" answer includes what was skipped
            req.spans["prefill"].set("prefix_matched_tokens",
                                     req.prefix_matched)
            if tier_fn:
                req.spans["prefill"].set(
                    "kv_tier_restored_blocks",
                    tier_fn()["restored"] - restored0)

    # ------------------- reservation admission + preemption (tentpole;
    # docs/SERVING.md "Admission and preemption") -------------------------
    def _total_blocks(self, req: Request) -> int:
        """A request's TOTAL projected KV block need: every token that
        will ever sit in the cache — prompt plus the generation budget
        still owed (``generated`` stays populated across a preemption
        re-prefill, where the delivered tokens were folded into the
        prompt). Clamped to the pool size: a request the pool can never
        hold whole is admitted best-effort and defers at the tail
        exactly as the historical path did, instead of blocking the
        queue forever behind an unsatisfiable reservation."""
        bs = self.engine.config.kv_block_size
        total = (len(req.prompt_tokens)
                 + max(0, req.max_new_tokens - len(req.generated)))
        return min(-(-total // bs), self.engine.config.kv_blocks)

    def _admit_pending_reserved(self) -> List[Request]:
        """Pull pending requests under total-block reservation. FIFO
        within an urgency class (skipping a blocked peer would starve
        large requests), but a blocked head does NOT hold back
        strictly-more-urgent work behind it — that work may be able to
        reserve (or preempt) where the head could not. The unmet need
        is published as the reservation shortfall."""
        out: List[Request] = []
        self._last_shortfall = 0
        blocked_rank: Optional[int] = None    # most urgent rank blocked
        i = 0
        while (i < len(self.pending)
               and len(self.running) + len(out) < self._max_seqs):
            req = self.pending[i]
            if blocked_rank is not None and req.shed_rank >= blocked_rank:
                i += 1
                continue
            if self._try_admit(req):
                del self.pending[i]
                out.append(req)
            else:
                blocked_rank = (req.shed_rank if blocked_rank is None
                                else min(blocked_rank, req.shed_rank))
                i += 1
        return out

    def _try_admit(self, req: Request) -> bool:
        """Reservation admission for one request: prefix-match first
        (cached blocks credit against the need), then reserve the total
        projected block count. On shortfall, preemption (when enabled)
        may spill strictly-lower-urgency victims to the KV tier; a
        request that still cannot reserve is rolled back — its matched
        blocks released back to the cache — and waits."""
        total = self._total_blocks(req)
        self._match_prefix_for(req)
        if self.engine.try_reserve(req.uid, total):
            req.total_blocks = total
            return True
        if self.preempt_enabled and self._preempt_for(req, total):
            if self.engine.try_reserve(req.uid, total):
                req.total_blocks = total
                return True
        # rollback: the sequence keeps nothing while it waits (pinned
        # shared blocks would shrink everyone else's headroom); the
        # match re-runs on the next attempt
        self.engine.flush(req.uid)
        req.prefix_matched = -1
        req.prompt_fed = 0
        self._last_shortfall = max(
            self._last_shortfall,
            total - max(0, self.engine.reservation_headroom()))
        return False

    def _victim_order(self, req: Request, blocks: int):
        """Sort key for victim selection, LARGEST preempted first.
        ``lowest_class`` (default): lowest urgency class first (highest
        shed_rank), then most blocks (frees the most memory), then
        least progress (wastes the least work). ``most_blocks`` /
        ``least_progress`` re-order the tie-breakers for workloads that
        care more about one axis."""
        progress = req.prompt_fed + len(req.generated)
        if self.victim_policy == "most_blocks":
            return (blocks, req.shed_rank, -progress)
        if self.victim_policy == "least_progress":
            return (-progress, req.shed_rank, blocks)
        return (req.shed_rank, blocks, -progress)

    def _eligible_victims(self, min_rank: Optional[int] = None) -> List[tuple]:
        """(req, blocks) preemption candidates, best victim first.
        ``min_rank`` (admission-driven preemption) requires a victim of
        STRICTLY lower urgency than the newcomer — preempting peer work
        to admit identical work is pure churn, so same-class overload
        waits instead. ``max_preemptions_per_seq`` makes a sequence
        immune after that many spills (the starvation cap)."""
        out = []
        for uid, req in self.running.items():
            if req.preempt_count >= self.max_preemptions_per_seq:
                continue
            if min_rank is not None and req.shed_rank <= min_rank:
                continue
            # count only blocks a flush would actually return to the
            # available pool — prefix blocks other sequences share free
            # nothing, and spilling a victim for headroom that never
            # materializes is pure churn
            blocks = self.engine.freeable_blocks_of(uid)
            if blocks <= 0:
                continue         # nothing reclaimable to spill
            out.append((req, blocks))
        out.sort(key=lambda t: self._victim_order(*t), reverse=True)
        return out

    def _preempt_for(self, req: Request, total: int) -> bool:
        """Admission-driven preemption: spill strictly-lower-urgency
        victims until ``req`` can reserve, bounded by the
        oversubscription cap (total committed blocks — resident
        reservations plus parked sequences — may not exceed
        ``oversubscription_factor x kv_blocks``; at the default 1.0
        parking a victim to admit new work would always overflow the
        cap, so a factor > 1 is what turns preemptive admission on).
        Returns False without touching anything when the eligible
        victims cannot cover the shortfall — pointless churn."""
        committed = (self.engine.reserved_total_blocks()
                     + sum(e["total_blocks"] for e in self.preempted.values()))
        cap = self.oversubscription_factor * self.engine.config.kv_blocks
        if committed + total > cap:
            return False
        self._settle()      # victims are chosen among sequences at rest
        victims = self._eligible_victims(min_rank=req.shed_rank)
        have = self.engine.query(req.uid)[1]     # prefix-matched credit
        shortfall = (max(0, total - have)
                     - max(0, self.engine.reservation_headroom()))
        freeable = sum(b for _, b in victims)
        if freeable < shortfall:
            return False
        freed = 0
        for victim, blocks in victims:
            if freed >= shortfall:
                break
            self._preempt(victim)
            freed += blocks      # the FREEABLE count, not the export size
        return True

    def _maybe_restore_headroom(self) -> None:
        """Repair a negative reservation headroom (a ``force_reserve``
        over-commitment from a KV-handoff import) by spilling victims —
        any urgency class; the import already happened, so the only
        alternative is exactly the deferred-forever wedge this overhaul
        removes."""
        if not self.preempt_enabled:
            return
        while self.engine.reservation_headroom() < 0:
            self._settle()  # victims are chosen among sequences at rest
            victims = self._eligible_victims()
            if not victims:
                return
            self._preempt(victims[0][0])

    def _preempt(self, req: Request) -> int:
        """Spill one running sequence: export its KV (pool slabs +
        kv_quant scales) into the preemption store — the ``TieredKVStore``
        when a tier is configured — free its device blocks, and park it
        for a later byte-lossless resume. Returns the blocks freed."""
        t0 = time.perf_counter()
        self._settle()
        uid = req.uid
        payload = self.engine.export_sequence(uid)
        n_blocks = int(payload["n_blocks"]) if payload else 0
        if payload is not None:
            self.engine.preempt_stash(uid, payload)
        # everything delivered or fed: the fed prompt and the generation.
        # The exported KV encodes all of it but a last drawn token, which
        # had not been fed yet — what import_sequence replays into the
        # prefix index is cut to the KV's length at resume
        tokens = req.prompt_tokens[:req.prompt_fed] + list(req.generated)
        self.engine.flush(uid)        # frees blocks + releases reservation
        self.running.pop(uid, None)
        if self.proposer is not None:
            self.proposer.release(uid)
        req.preempt_count += 1
        self.preempted[uid] = {
            "req": req, "tokens": tokens, "stashed": payload is not None,
            # read only by a sequence that has drawn nothing yet
            "last_logits": None if req.generated else req.last_logits,
            "fed": req.prompt_fed,
            "n_blocks": n_blocks,
            "total_blocks": req.total_blocks or self._total_blocks(req)}
        self._parked_blocks += n_blocks
        self._preempt_stats["preempted"] += 1
        self._preempt_events.append({"uid": uid, "blocks": n_blocks})
        self._spill_times.append(time.perf_counter() - t0)
        if len(self._spill_times) > 4096:        # bounded when undrained
            del self._spill_times[:2048]
        return n_blocks

    def _resume_preempted(self) -> None:
        """Bring parked sequences back, oldest first, while a seat and
        full-reservation headroom exist (strict FIFO: resuming younger,
        smaller sequences over the head would starve it). The spilled
        payload imports byte-losslessly — the resumed sequence decodes
        from the token it was parked with; a payload the tier
        dropped (byte bounds, disk corruption) degrades to a greedy
        re-prefill of prompt + delivered tokens, the failover resume
        semantics."""
        for uid in list(self.preempted):
            if len(self.running) >= self._max_seqs:
                return
            entry = self.preempted[uid]
            total = entry["total_blocks"]
            if total > self.engine.reservation_headroom():
                return
            t0 = time.perf_counter()
            req: Request = entry["req"]
            payload = (self.engine.preempt_restore_payload(uid)
                       if entry["stashed"] else None)
            if payload is not None:
                try:
                    self.engine.import_sequence(
                        uid, payload,
                        tokens=entry["tokens"][:payload["seen_tokens"]])
                except Exception as e:
                    logger.warning(
                        f"preemption resume import for sequence {uid} "
                        f"failed ({e!r}); re-prefilling")
                    payload = None
            if payload is not None:
                req.prompt_fed = entry["fed"]
                req.last_logits = entry["last_logits"]
            else:
                # lost payload: re-prefill everything the KV held. The
                # delivered tokens fold into the prompt (KV order is
                # prompt-then-generation) while ``generated`` keeps the
                # budget accounting; greedy decoding of this prefix
                # continues the stream byte-identically.
                req.prompt_tokens = list(entry["tokens"]) + \
                    req.prompt_tokens[entry["fed"]:]
                req.prompt_fed = 0
                req.prefix_matched = -1
                req.last_logits = None
            self.engine.force_reserve(uid, total)
            req.total_blocks = total
            del self.preempted[uid]
            self._parked_blocks -= entry["n_blocks"]
            self.running[uid] = req
            self._preempt_stats["resumed"] += 1
            self._resume_times.append(time.perf_counter() - t0)
            if len(self._resume_times) > 4096:
                del self._resume_times[:2048]

    # ---------------------------------------------- preemption observability
    def preempt_stats(self) -> Dict[str, int]:
        """Monotonic counters: sequences ``preempted`` (spilled to the
        tier) and ``resumed`` (brought back) — the serving layer
        delta-publishes them as ``sequences_preempted`` /
        ``sequences_resumed``."""
        return dict(self._preempt_stats)

    def preempted_resident_blocks(self) -> int:
        """Device blocks the currently-parked sequences held when they
        were spilled — the footprint preemption is keeping off the pool
        (the ``preempted_resident_blocks`` gauge)."""
        return self._parked_blocks

    def reserve_shortfall_blocks(self) -> int:
        """Blocks the pending head is short of reserving, as of the
        last packing pass (the ``queue_wait_blocks`` gauge; 0 with
        reservation off or nothing waiting)."""
        return self._last_shortfall

    def drain_preempt_times(self):
        """(spill wall times, resume wall times) since the last drain —
        the serving layer observes them into ``preempt_spill_s`` /
        ``preempt_resume_s``."""
        spills, self._spill_times = self._spill_times, []
        resumes, self._resume_times = self._resume_times, []
        return spills, resumes

    def drain_preempt_events(self) -> List[dict]:
        """Per-preemption records since the last drain — the replica
        journals each as a ``sequence_preempted`` ops event."""
        out, self._preempt_events = self._preempt_events, []
        return out

    def _propose(self, req: Request, k: int) -> List[int]:
        """Fetch drafts, isolating the scheduler from proposer faults —
        proposers are advisory, so any exception degrades to "no drafts"
        (warned once) instead of killing the serving step loop. Proposers
        with a bounded lookback (``context_window``) get only that tail,
        saving a full-history list rebuild per decode row per step."""
        win = getattr(self.proposer, "context_window", None)
        if win is None:
            ctx = req.prompt_tokens + req.generated
        else:
            gen = req.generated
            if len(gen) >= win:
                ctx = gen[len(gen) - win:]
            else:
                ctx = (req.prompt_tokens[max(0, len(req.prompt_tokens)
                                             - (win - len(gen))):] + gen)
        try:
            return self.proposer.propose(req.uid, ctx, k)
        except Exception as e:
            if not self._proposer_warned:
                self._proposer_warned = True
                logger.warning(f"draft proposer failed ({e!r}); "
                               "continuing without speculation for the "
                               "affected steps")
            return []

    # ----------------------------------------------------------- telemetry
    def _note_first_token(self, req: Request) -> None:
        """Request-trace stage transition at the first emitted token:
        prefill ends (this instant IS the TTFT endpoint) and the decode
        stage opens."""
        if req.spans is None:
            return
        sp = req.spans.pop("prefill", None)
        if sp is not None:
            sp.end()
        req.spans["decode"] = self.tracer.begin("decode",
                                                trace_id=req.trace_id)

    def _end_request_spans(self, req: Request, reason: str) -> None:
        if req.spans is None:
            return
        dec = req.spans.get("decode")
        if dec is not None:
            dec.set("generated", len(req.generated))
            dec.set("finish_reason", reason)
        for sp in req.spans.values():
            sp.end()
        req.spans = None

    def step(self) -> List[int]:
        """Dispatch one engine forward and retire the one before it;
        returns the uids of the requests finished since the last call.

        Traced (docs/OBSERVABILITY.md "Trace model"), a step is a ``step``
        span on the scheduler's trace (``overlapped``: whether its forward
        was dispatched while the one before was unread; ``starved``:
        whether that one had finished on the device before this one's
        first forward got there) with one child per
        phase, each mirrored into an open profiler session as
        ``ds:<name>`` — of the step it dispatches:

        - ``pack``: :meth:`_pack` — admission, prefix matching and the
          chunks, planned from counts (no sampling: a decode row's token
          is its sequence's last draw, on the device or on the host;
          drafts are proposed here);
        - ``stage``: the host part of ``engine.put`` — scheduling check,
          KV allocation, ``batch.finalize``, uploads and the dispatch
          (its child ``dispatch``, one a forward: the engine's);
          attrs are the engine's record of the put (``last_put``);

        and then of the step before it, which ran meanwhile:

        - ``fetch``: the wait for that forward and the copy back of what
          its commit reads — the drawn ids, a few bytes a row (the
          logits, for a host sampler or a verification);
        - ``commit``: the per-row loop — append, stream ``on_token``,
          finish, flush and ``on_finish`` (inside ``spec_verify`` on a
          speculative step).

        A scheduler that cannot run ahead (``sample_fn``, a proposer)
        retires the step it has just dispatched: the same four phases,
        all of one step. The ``forward`` span runs from a step's dispatch
        to the end of its fetch. One call site each, traced or not: a
        disabled tracer hands out the shared no-op span."""
        tracer = self.tracer
        self._stepping = True
        try:
            with tracer.span("step", trace_id=self.trace_label) as span:
                with tracer.span("pack"):
                    uids, chunks, plan = self._pack()
                # (packing may have retired it: a preemption does)
                ahead, newer = self._flight, None
                starved = False
                if uids:
                    newer = self._dispatch(uids, chunks, plan)
                    # with a step in flight, had the device finished it
                    # before this one's first forward got there? (the put
                    # asked as it handed it over, without waiting)
                    starved = ahead is not None and newer.handle.ran_dry
                    self._step_stats["steps"] += 1
                    self._step_stats["steps_overlapped"] += ahead is not None
                    self._step_stats["steps_starved"] += starved
                span.set("overlapped", bool(uids) and ahead is not None)
                span.set("starved", starved)
                if ahead is not None:
                    self._retire(ahead, newer)
                self._flight = newer
                if not self._run_ahead:
                    self._settle()
        finally:
            self._stepping = False
        done, self._done_now = self._done_now, []
        return done

    def _dispatch(self, uids, chunks, plan) -> _Flight:
        """Put one planned step and move what is planned from counts."""
        tracer = self.tracer
        traced = tracer.enabled
        # verification width: the widest speculative decode chunk this
        # step, bucketed (pow2) to bound compiled-program variants.
        # Steps with no drafts in flight — pure prefill, draft-less
        # decode — take the exact historical path.
        spec_w = max((len(c) for _, c, d in plan if d and len(c) > 1),
                     default=0)
        speculative = self.proposer is not None and spec_w > 0
        # speculative step: right-aligned trailing-position logits for
        # verification; the prefix-cache hash chain is committed
        # per-row at the commit, once rejected drafts have been trimmed
        # (the index must never see tokens a trim can roll back)
        W = self.engine.batch._bucket(spec_w, self._chunk) \
            if speculative else 0
        put_kw = {"verify_width": W, "defer_commit": True} \
            if speculative else {}
        fspan = None
        if traced:
            fspan = tracer.begin(
                "forward", trace_id=self.trace_label,
                attrs={"n_seqs": len(uids),
                       "n_tokens": int(sum(len(c) for c in chunks))})
            if speculative:
                fspan.set("verify_width", W)
        with tracer.span("stage") as sspan:
            handle = self.engine.put(uids, chunks, **put_kw)
            if traced:
                record = self.engine.last_put
                fspan.attrs.update(record)
                # ``stage`` keeps the keys it had: the benchmark's
                # agreement test holds them to its own wrapper's
                sspan.attrs.update(
                    {k: v for k, v in record.items()
                     if not k.startswith(FORWARD_ONLY)})
            reads_logits = speculative or self.sample_fn is not None
            if reads_logits:
                handle.prefetch()
            # the forward is out: the counts the next step is planned
            # from move now, the values follow when the step is retired
            draws = []
            for req, chunk, is_decode in plan:
                if not is_decode:
                    req.prompt_fed += len(chunk)
                    self.running[req.uid] = req
                draws.append(req.prompt_remaining == 0)
                req.in_flight += draws[-1]
        return _Flight(plan, handle, draws, W, reads_logits, fspan)

    def _settle(self) -> None:
        """Retire what is in flight, so that every running request is at
        rest — its delivered tokens are all it has drawn: what a call
        that reads or edits a sequence from outside the step does first
        (``cancel``, ``evacuate``, a preemption). Outside ``step`` the
        retirement is a ``step`` span of its own."""
        flight, self._flight = self._flight, None
        if flight is None:
            return
        with contextlib.nullcontext() if self._stepping else \
                self.tracer.span("step", trace_id=self.trace_label,
                                 attrs={"overlapped": False,
                                        "starved": False}):
            self._retire(flight, None)

    def _retire(self, flight: _Flight, newer: Optional[_Flight]) -> None:
        """Read a dispatched step's tokens and commit its rows; ``newer``:
        the step dispatched behind it, still in flight."""
        tracer = self.tracer
        W = flight.verify_width
        logits = tokens = None
        with tracer.span("fetch"):
            if flight.reads_logits:
                logits = np.asarray(flight.handle)
            else:
                tokens = flight.handle.next_tokens()
        vspan = None
        if flight.fspan is not None:
            flight.fspan.end()
            if W:
                # host-side verify/trim/commit of this step, as its
                # own span
                vspan = tracer.begin("spec_verify",
                                     trace_id=self.trace_label,
                                     attrs={"verify_width": W})
        with tracer.span("commit"):
            self._commit(flight, logits, tokens, newer)
        if vspan is not None:
            vspan.end()

    def _commit(self, flight: _Flight, logits, tokens,
                newer: Optional[_Flight]) -> None:
        """Commit one retired step's rows: ``tokens`` [rows], the
        forward's own greedy draws, or ``logits`` for a host sampler
        ([rows, vocab]) and a verification ([rows, W, vocab])."""
        handle, W = flight.handle, flight.verify_width
        for i, (req, chunk, is_decode) in enumerate(flight.plan):
            req.in_flight -= flight.draws[i]
            if req.done:
                # ended while this row flew (it ran past an EOS the host
                # had not seen): nothing of it is delivered, and its
                # blocks went back with the sequence
                continue
            if W and is_decode:
                # row i's valid positions are right-aligned: the last
                # len(chunk) slots
                self._apply_verified(req, chunk,
                                     logits[i, logits.shape[1] - len(chunk):])
                continue
            if W:
                self.engine.commit_tokens(req.uid, chunk)
            elif chunk[0] == DEVICE_TOKEN:
                # the token this row was fed on the device is the one
                # delivered last; the row behind it is unrecorded too
                self.engine.commit_tokens(
                    req.uid, req.generated[-1:],
                    newer.fed.get(req.uid, 0) if newer else 0)
            # (a verification's slot W-1 is the row's last valid position)
            row = None if logits is None else \
                logits[i, -1] if W else logits[i]
            req.last_logits = _LogitsRow(handle, i) if row is None else row
            if not flight.draws[i]:
                continue  # mid-prefill: a token once the prompt is done
            if self.prefill_only:
                # prompt complete on a prefill-role scheduler: stop here.
                # The KV is deliberately NOT flushed — the serving layer
                # exports it for the decode-role handoff and flushes once
                # the payload is staged (docs/SERVING.md "Disaggregated
                # serving"); last_logits carries the final-position
                # logits the destination draws its first token from
                req.last_logits = np.asarray(req.last_logits)
                self._finish(req, "prefilled")
                continue
            if is_decode:
                self._spec_stats["decode_rows"] += 1
                self._spec_stats["emitted"] += 1
            self._deliver(req, int(tokens[i]) if row is None
                          else self._draw(row))

    def _deliver(self, req: Request, tok: int) -> None:
        """One drawn token: appended, streamed, and the request finished
        if it was its last."""
        if not req.generated:
            self._note_first_token(req)
        req.generated.append(tok)
        if req.on_token is not None:
            req.on_token(req.uid, tok)
        ended = req.eos_token_id is not None and tok == req.eos_token_id
        if ended or len(req.generated) >= req.max_new_tokens:
            self._finish(req, "eos" if ended else "length")

    def _finish(self, req: Request, reason: str) -> None:
        req.done = True
        req.finish_reason = reason
        self._end_request_spans(req, reason)
        self.finished[req.uid] = req
        self.running.pop(req.uid, None)
        if reason != "prefilled":
            self.engine.flush(req.uid)
            # (a finished request would keep its last put's logits on
            # the device for as long as ``finished`` keeps the request)
            req.last_logits = None
        if self.proposer is not None:
            self.proposer.release(req.uid)
        self._done_now.append(req.uid)
        if req.on_finish is not None:
            req.on_finish(req, reason)

    def _apply_verified(self, req: Request, chunk: List[int],
                        rows: np.ndarray) -> None:
        """Verify one speculative decode row and commit the outcome:
        accept the longest target-agreeing draft prefix, trim the rejected
        tail out of the KV cache, advance the prefix-cache chain with the
        surviving tokens only, and stream what the row has proven — the
        accepted drafts and the token after them (``chunk[0]`` was
        delivered when it was drawn) — stopping at EOS, exactly where
        plain greedy decoding would have stopped."""
        kept, last = verify_greedy(chunk, rows)
        emitted = kept[1:] + [int(np.argmax(rows[last]))]
        if req.eos_token_id is not None and req.eos_token_id in emitted:
            # tokens the target accepted beyond EOS are never delivered —
            # truncate BEFORE trim/commit/stats so the KV state, the
            # prefix chain, and the counters all describe exactly the
            # stream the request receives
            emitted = emitted[:emitted.index(req.eos_token_id) + 1]
            kept = kept[:len(emitted) + 1]
        accepted = len(kept) - 1
        rejected = len(chunk) - len(kept)
        if rejected:
            self.engine.trim_sequence(req.uid, rejected)
        self.engine.commit_tokens(req.uid, kept)
        req.last_logits = rows[last]
        self._spec_stats["decode_rows"] += 1
        self._spec_stats["proposed"] += len(chunk) - 1
        self._spec_stats["accepted"] += accepted
        self._spec_stats["emitted"] += len(emitted)
        if req.spans is not None:
            # accumulate this request's speculation outcome on its decode
            # span — "how many of MY tokens came from accepted drafts"
            dec = req.spans.get("decode")
            if dec is not None:
                a = dec.attrs
                a["spec_proposed"] = a.get("spec_proposed", 0) + len(chunk) - 1
                a["spec_accepted"] = a.get("spec_accepted", 0) + accepted
        for t in emitted:
            if req.done:
                break
            self._deliver(req, t)

    def run_to_completion(self, max_steps: int = 10000) -> Dict[int, Request]:
        steps = 0
        while self.has_work and steps < max_steps:
            self.step()
            steps += 1
        return self.finished
