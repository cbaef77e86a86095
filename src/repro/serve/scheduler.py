"""Serving scheduler: pure policy over a CacheManager and an Executor.

The top of the three-layer decomposition (HEROv2's offload manager, grown
up): requests land in a **Mailbox** (the hardware mailbox), and each
``step()`` drains it, decides *which* sequences admit, chunk, promote,
preempt, or decode, and dispatches the executor's compiled TargetRegions.
This module owns **scheduling state only** — the mailbox, the request sets
(``prefilling`` → ``prefilled_wait`` → ``active``, plus the tiered pool's
cold set), victim selection, and the token-budget packing. Page accounting
belongs to serve/kvcache.py, stack composition to serve/cache.py, tier
movement to serve/tiering.py, prefix lookup to serve/prefix_cache.py, and
everything device-shaped (compiled steps, sampling, the tp mesh) to
serve/executor.py.

Continuous batching with chunked prefill (``chunked=True``) fuses prefill
and decode into one **token-budgeted** step loop: each iteration packs one
decode token per stream first and fair-shares the remainder over
mid-prefill residents as prompt chunks in admission order. Admission is
partial-prefill-aware (prompt pages only; the decode worst case tops up at
promotion); a preempted half-prefilled request resumes at its chunk offset.
Shared-prefix caching rides in front of admission when the cache stack has
a prefix layer.

Token movement discipline: dispatches return device-resident sampled ids;
the scheduler queues them with their completion logic and materialises the
whole iteration's ids in ONE ``Executor.fetch_token_ids`` transfer —
value-dependent effects (stream emission, prefix insertion, decode
promotion, slot release) run in dispatch order once the host values land.

Overlapped execution (PR 8, ``overlap=True`` on the chunked path): the
step pipeline is split around that one blocking fetch so iteration k's
device step hides iteration k+1's host work. Each ``step()`` first runs
the *shadow phase* — policy pass, swap-in landings, the admission drain
(cold resumes start their host→dev DMAs via ``swap_in_start`` instead of
blocking), and the mailbox-head prefetch — while the PREVIOUS step's
dispatches are still in flight on the device. Only then does the one
``fetch_token_ids`` block (the *commit*: the previous iteration's queued
consumers emit tokens, finish requests, release pages, and promote
completed prefills). Everything whose outcome feeds the packer — waiter
promotion, decode-slot selection, chunk packing — runs *after* the commit
against exact state, so the budget/fair-share/no-starvation invariants are
decided from the same state the synchronous loop would see. Two queues
make this safe: dispatches append to ``_fetch_queue``; at step end it
becomes ``_commit_queue`` for the next step's commit point. Consumers
carry identity guards (the dispatched ``(slot, req)`` pair must still
match) so a request preempted while its step was in flight is discarded —
the swap-out captured the pre-decode state and greedy determinism
re-derives the identical token on resume, keeping streams bit-identical
to the synchronous loop (``overlap=False`` restores it exactly).

Observability + policy (PR 6): every iteration publishes its signals —
queue depth, resident sets, token counters, TTFT/ITL/queue-latency
histograms, plus whatever the cache stack and executor report — to the
engine's :class:`repro.serve.metrics.MetricsBus` (observe-only; a disabled
bus leaves streams bit-identical). An optional
:class:`repro.serve.policy.SchedulerPolicy` consumes those signals in
exactly three hook points: a mailbox reorder/shed pass at the top of each
step (priority classes + aging, typed :class:`~repro.serve.policy.ShedVerdict`
rejections recorded on ``self.shed``), an admission-concurrency gate inside
the drain loop (a quiet "not yet" — no refusal stat, no pool churn), and a
prefill-allowance clamp when packing chunks (ITL-target budget shaping,
floored at one token per mid-prefill resident so fair-share survives).
Policy never touches pages and never changes which tokens an admitted
request streams.

Tracing (PR 7): the scheduler is also the tracing root — every ``step()``
runs inside a :class:`repro.serve.trace.Tracer` ``iteration`` span, with
``schedule``/``policy``/``commit``/``decode_inputs``/``prefill_chunk``
phase spans below it (``submit`` spans between iterations), all of them
also on the JAX profiler's clock as ``serve.*`` annotations, and request
lifecycle state recorded at every transition (``queued → admitted →
prefill → decode → finished/shed/preempted → resumed``). The tracer's
injected clock is the scheduler's ONLY time source (``self.tracer.now()``
replaces every direct ``time.perf_counter()`` call), so an engine built
with a fake clock is time-deterministic end to end. After each iteration
closes, its exclusive stall buckets are published to the bus as
``stall_pct_{schedule,fetch,dma,other}`` histograms — only when tracing is
enabled, so a disabled tracer leaves ``metrics_snapshot()`` (and streams)
bit-identical.

Invariants (tests/test_scheduler_properties.py):

  * **Bit-identical streams**: scheduling decisions (chunking, preemption,
    promotion order, prefix reuse, tensor parallelism) may change *when*
    tokens are computed, never *which* tokens a greedy request streams.
  * A request is in exactly one of: mailbox, prefilling, prefilled_wait,
    active, cold (tiered), or finished; every admitted request eventually
    finishes (the deadlock breakers guarantee rotation terminates).
  * Stats never lie about totals: decode + prefill-chunk tokens per
    iteration never exceed the budget, and accounting closes at drain (no
    page, reservation, or slot leaks).
  * Exactly one host transfer of token ids per chunked-mode iteration (and
    at most one per dispatch phase on the legacy dense/monolithic paths).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.offload import Mailbox
from repro.models import transformer
from repro.serve import trace
from repro.serve.executor import Executor
from repro.serve.metrics import MetricsBus, percentiles
from repro.serve.policy import SchedulerPolicy
from repro.serve.prefix_cache import PrefixMatch


@dataclasses.dataclass
class Request:
    seq_id: int
    prompt: np.ndarray          # [L] int32
    max_new: int = 16
    priority: int = 0           # SLO class (larger = more urgent; policy-read)
    deadline_s: Optional[float] = None  # admission deadline after t_submit
    t_submit: float = 0.0
    t_first: float = 0.0        # wall time of the first emitted token (TTFT)
    prefill_pos: int = 0        # prompt tokens whose KV has been written
    tokens_out: Optional[List[int]] = None
    t_tokens: Optional[List[float]] = None   # wall time of each emitted token
    done: bool = False
    verdict: Optional[object] = None    # ShedVerdict when policy rejected it


class Scheduler:
    """Mailbox-batched continuous scheduling over one cache stack.

    ``pool`` is a :class:`repro.serve.cache.CacheManager` stack (paged
    family) or a dense :class:`repro.serve.kvcache.CachePool`; ``executor``
    dispatches the compiled steps. The feature *policy* flags (``paged``,
    ``tiered``, ``chunked``) mirror the stack composition — the Engine
    façade derives them from its config so the two can never disagree.
    """

    def __init__(self, cfg: transformer.ModelConfig, pool, executor: Executor,
                 *, n_slots: int, greedy: bool = True, paged: bool = False,
                 tiered: bool = False, chunked: bool = False,
                 token_budget: Optional[int] = None,
                 preempt_quantum: int = 1, overlap: bool = True,
                 metrics: Optional[MetricsBus] = None,
                 policy: Optional[SchedulerPolicy] = None,
                 tracer: Optional[trace.Tracer] = None):
        self.cfg = cfg
        self.pool = pool
        self.executor = executor
        self.greedy = greedy
        self.paged = paged
        self.tiered = tiered
        self.chunked = chunked
        # overlapped execution only exists on the unified chunked step loop
        # (the legacy dense/monolithic paths flush per phase)
        self.overlap = bool(overlap and chunked and paged)
        self.bus = metrics if metrics is not None else MetricsBus(enabled=False)
        self.tracer = tracer if tracer is not None else trace.null_tracer()
        self.policy = policy
        self.shed: List[Request] = []              # policy-rejected requests
        self._ever_admitted: set = set()           # seq_ids that held pages
        self.prefix = getattr(pool, "prefix", None)
        self.mailbox = Mailbox(depth=256)
        self.active: Dict[int, Request] = {}       # slot -> decoding request
        self.prefilling: Dict[int, Request] = {}   # slot -> mid-prompt req
        self.prefilled_wait: Dict[int, Request] = {}  # awaiting promotion
        self.stats = {"decode_steps": 0, "prefills": 0, "batch_occupancy": [],
                      "admission_refusals": 0, "preemptions": 0,
                      "preempted_mid_prefill": 0, "evictions_reprefill": 0,
                      "swap_out_count": 0, "swap_in_count": 0,
                      "swap_out_bytes": 0, "swap_in_bytes": 0,
                      "prefill_chunks": 0, "prefill_chunk_tokens": 0,
                      "decode_tokens": 0, "cow_forks": 0,
                      "prefix_hits": 0, "prefix_full_hits": 0,
                      "prefix_shared_tokens": 0, "shed": 0,
                      "admission_order": [],
                      "queue_lat_s": [], "ttft_s": [], "itl_s": [],
                      "iter_log": []}
        self._fetch_queue: List[Tuple[Any, Callable]] = []
        self._commit_queue: List[Tuple[Any, Callable]] = []  # overlap: prev it.
        self._finished: List[Request] = []
        self._iterations = 0         # step() calls: the profiler's step number
        if self.paged:
            self._admit_stalled = False
            self._pending_swapins: List[Tuple[Request, Any]] = []
            self._inflight_decode: Dict[int, Request] = {}  # last dispatch
            self._shadow_activated: set = set()  # slots resumed this step
            self._last_decoded = np.zeros(n_slots, np.int64)
            self._admitted_at = np.zeros(n_slots, np.int64)
            self._resident_since = np.zeros(n_slots, np.int64)
            self._chunks_done = np.zeros(n_slots, np.int64)
            self._admit_clock = 0
            self.preempt_quantum = max(1, preempt_quantum)
            if self.chunked:
                if token_budget is None:
                    token_budget = n_slots + 4 * pool.page_tokens
                if token_budget <= n_slots:
                    raise ValueError(
                        f"token_budget ({token_budget}) must exceed n_slots "
                        f"({n_slots}): decode tokens are packed first, so a "
                        "smaller budget could never schedule a prefill chunk")
                self.token_budget = int(token_budget)

    # -- host API ----------------------------------------------------------
    def submit(self, req: Request) -> bool:
        with self.tracer.span("submit"):
            req.t_submit = self.tracer.now()
            req.t_first = 0.0
            req.prefill_pos = 0
            req.tokens_out = []
            req.t_tokens = []
            req.verdict = None
            self.bus.inc("requests_submitted")
            self.tracer.request_state(req.seq_id, "queued")
            return self.mailbox.put(req)

    @property
    def idle(self) -> bool:
        """True when nothing is resident, queued, or in flight."""
        return (not self.active and not self.prefilling
                and not self.prefilled_wait and len(self.mailbox) == 0
                and not getattr(self, "_pending_swapins", None)
                and not self._fetch_queue and not self._commit_queue)

    def extract_unadmitted(self) -> List[Request]:
        """Remove and return every mailbox request that holds NO engine
        state — the fleet-drain hook. A draining replica stops admitting
        and hands its never-admitted queue to siblings, but requests with
        resident pages (ever-admitted returnees, tiered-cold residents)
        must finish here: moving them would strand allocator accounting.
        The kept requests are requeued in their original order."""
        pending = self.mailbox.drain(len(self.mailbox))
        keep: List[Request] = []
        out: List[Request] = []
        for req in pending:
            (out if self._sheddable(req) else keep).append(req)
        for req in reversed(keep):
            self.mailbox.requeue(req)
        return out

    def step(self) -> List[Request]:
        """One engine iteration. Chunked mode: the unified token-budgeted
        step, flushed with exactly one host transfer of sampled ids.
        Otherwise: one admission pass + (if anything is resident) one decode
        dispatch, each phase flushed once. Returns the requests that
        finished this iteration."""
        self._finished = []
        with self.tracer.iteration(self._iterations):
            self._policy_pass()
            decoded = False
            if self.chunked:
                decoded = self._step_chunked()
                if not self.overlap:
                    self._flush_tokens()
            elif self.paged:
                with self.tracer.span("schedule"):
                    self._admit_paged()
                self._flush_tokens()
                if self.active:
                    self._dispatch_decode_paged()
                    self._flush_tokens()
                    decoded = True
            else:
                with self.tracer.span("schedule"):
                    self._admit()
                self._flush_tokens()
                if self.active:
                    self._dispatch_decode_dense()
                    self._flush_tokens()
            if self.tiered and decoded and not self.overlap:
                # double-buffer: with this step's releases applied, start the
                # head-of-queue resume's host→dev DMAs now; they overlap the
                # upcoming admission pass and land at the top of the next step
                # (the overlapped loop prefetches inside its shadow phase)
                self._start_prefetch()
            self._publish_metrics()
        self._iterations += 1
        self._publish_stall()
        return self._finished

    def run(self, max_steps: int = 1000) -> List[Request]:
        finished: List[Request] = []
        for _ in range(max_steps):
            if self.idle:
                break
            finished.extend(self.step())
        return finished

    # -- SLO policy hooks ---------------------------------------------------
    def _in_system(self) -> int:
        """Resident-request count the admission gate reasons about: hot
        residents plus (tiered) the cold set — an in-flight prefetch stays
        in ``cold_seqs()`` until it lands, so it is already covered."""
        n = len(self.active) + len(self.prefilling) + len(self.prefilled_wait)
        if self.tiered:
            n += len(self.pool.cold_seqs())
        return n

    def _sheddable(self, req: Request) -> bool:
        """Only requests that hold NO engine state may shed: never-admitted
        mailbox entries. Cold residents (pages in the host tier) and
        evict-reprefill returnees (emptied but once-admitted) must survive —
        shedding them would strand accounting or retract emitted tokens."""
        if req.seq_id in self._ever_admitted:
            return False
        if self.tiered and self.pool.is_cold(req.seq_id):
            return False
        return True

    def _policy_pass(self) -> None:
        """Reorder/shed the mailbox under the policy, once per step, BEFORE
        any drain: the line the admission loop sees is already in effective-
        priority order with the over-cap tail rejected. Clearing the
        admission stall when the head changed (or anything shed) lets the
        reordered head be tried instead of waiting out the old head's
        refusal."""
        if self.policy is None or len(self.mailbox) == 0:
            return
        with self.tracer.span("policy"):
            pending = self.mailbox.drain(len(self.mailbox))
            if not pending:
                return
            head_before = pending[0]
            keep, shed = self.policy.plan(
                pending, now=self.tracer.now(), in_system=self._in_system(),
                sheddable=self._sheddable)
            for req, verdict in shed:
                req.verdict = verdict
                req.done = True
                self.shed.append(req)
                self.stats["shed"] += 1
                self.tracer.request_state(req.seq_id, "shed")
            for req in reversed(keep):
                self.mailbox.requeue(req)
            if getattr(self, "_admit_stalled", False) and \
                    (shed or not keep or keep[0] is not head_before):
                self._admit_stalled = False

    def _note_first_admit(self, req: Request) -> None:
        """First-admission bookkeeping shared by every admission path."""
        self._ever_admitted.add(req.seq_id)
        self.stats["admission_order"].append(int(req.seq_id))
        lat = self.tracer.now() - req.t_submit
        self.stats["queue_lat_s"].append(lat)
        self.bus.observe("queue_lat_s", lat)
        self.bus.inc("admissions")
        self.tracer.request_instant(req.seq_id, "admitted")
        if self.policy is not None:
            self.policy.note_admitted(req)

    def _publish_metrics(self) -> None:
        """End-of-step bus publication: scheduler gauges + counter totals,
        then whatever the cache stack reports (pages, tiers, prefix).
        Observe-only — a disabled bus makes this a no-op."""
        bus = self.bus
        if not bus.enabled:
            return
        s = self.stats
        bus.set("queue_depth", len(self.mailbox))
        bus.set("active", len(self.active))
        bus.set("prefilling", len(self.prefilling))
        bus.set("prefilled_wait", len(self.prefilled_wait))
        bus.set("in_system", self._in_system())
        for k in ("decode_steps", "prefills", "decode_tokens",
                  "prefill_chunks", "prefill_chunk_tokens",
                  "admission_refusals", "preemptions",
                  "preempted_mid_prefill", "evictions_reprefill",
                  "cow_forks", "prefix_hits", "prefix_full_hits",
                  "prefix_shared_tokens"):
            bus.set_total(k, s.get(k, 0))
        n_admitted = len(s.get("admission_order") or [])
        if self.prefix is not None and n_admitted:
            bus.set("prefix_hit_rate", s.get("prefix_hits", 0) / n_admitted)
        publish = getattr(self.pool, "publish_metrics", None)
        if publish is not None:
            publish(bus)

    def _publish_stall(self) -> None:
        """Publish the just-closed iteration's exclusive stall buckets as
        ``stall_pct_*`` histogram observations. Runs AFTER the iteration
        span exits (buckets are only final at close) and only when tracing
        is enabled — so a disabled tracer leaves ``metrics_snapshot()``
        bit-identical to an untraced engine."""
        if not self.tracer.enabled or not self.bus.enabled:
            return
        entry = self.tracer.last_iteration()
        if entry is None or entry["dur"] <= 0.0:
            return
        for bucket, sec in entry["buckets"].items():
            self.bus.observe(f"stall_pct_{bucket}",
                             100.0 * sec / entry["dur"])

    # -- deferred token materialisation ------------------------------------
    def _queue_fetch(self, ids_dev, consumer: Callable) -> None:
        self._fetch_queue.append((ids_dev, consumer))

    def _flush_tokens(self) -> None:
        """Materialise every queued id array in one device→host transfer and
        run the value-dependent completions in dispatch order."""
        if not self._fetch_queue:
            return
        queue, self._fetch_queue = self._fetch_queue, []
        vals = self.executor.fetch_token_ids([a for a, _ in queue])
        with self.tracer.span("commit"):
            for (_, consumer), v in zip(queue, vals):
                consumer(v)

    def _flush_commit(self) -> None:
        """Overlap-mode commit point: materialise the PREVIOUS iteration's
        queued ids in one blocking transfer and run its completions. Runs
        after the shadow phase, so the host work above it overlapped the
        device step whose tokens land here."""
        if not self._commit_queue:
            return
        queue, self._commit_queue = self._commit_queue, []
        vals = self.executor.fetch_token_ids([a for a, _ in queue])
        with self.tracer.span("commit"):
            for (_, consumer), v in zip(queue, vals):
                consumer(v)
        # the in-flight map is only meaningful between a dispatch and its
        # commit (the shadow COW pre-fork keys on it) — drop it now so a
        # dispatch-free iteration can't leave a stale pair behind
        self._inflight_decode = {}

    def _emit(self, req: Request, tok: int) -> None:
        req.tokens_out.append(tok)
        now = self.tracer.now()
        if req.t_first == 0.0:
            req.t_first = now
            self.stats["ttft_s"].append(now - req.t_submit)
            self.bus.observe("ttft_s", now - req.t_submit)
        elif req.t_tokens:
            gap = now - req.t_tokens[-1]
            self.stats["itl_s"].append(gap)
            self.bus.observe("itl_s", gap)
        req.t_tokens.append(now)

    # -- dense path --------------------------------------------------------
    def _admit(self):
        while True:
            free = int(np.sum(self.pool.seq_ids < 0))
            if free == 0:
                break
            if self.policy is not None and \
                    not self.policy.may_admit(len(self.active)):
                break
            reqs = self.mailbox.drain(1)
            if not reqs:
                break
            req = reqs[0]
            slot = self.pool.alloc_slot(req.seq_id)
            L = len(req.prompt)
            toks = np.zeros((self.pool.n_slots, L), np.int32)
            toks[slot] = req.prompt
            tok_dev, self.pool.caches = self.executor.prefill_slot(
                jnp.asarray(toks), self.pool.caches, slot, L)
            self._queue_fetch(
                tok_dev, lambda v, req=req: self._emit(req, int(v[0])))
            req.prefill_pos = L
            self.pool.lengths[slot] = L + 1
            self.active[slot] = req
            self._note_first_admit(req)
            self.tracer.request_state(req.seq_id, "decode")
            self.stats["prefills"] += 1

    def _dispatch_decode_dense(self):
        B = self.pool.n_slots
        toks = np.zeros((B, 1), np.int32)
        for slot, req in self.active.items():
            toks[slot, 0] = req.tokens_out[-1]
        # single shared cache_pos: slots decode at their own lengths; we use
        # per-slot validity masks inside attention, so pass max length
        pos = int(self.pool.lengths.max()) - 1
        ids_dev, self.pool.caches = self.executor.decode_dense(
            jnp.asarray(toks), self.pool.caches, jnp.asarray(pos, jnp.int32))
        self.stats["decode_steps"] += 1
        self.stats["decode_tokens"] += len(self.active)
        self.stats["batch_occupancy"].append(len(self.active) / B)
        slots = list(self.active)
        self._queue_fetch(
            ids_dev, lambda v, slots=slots: self._finish_decode_dense(slots, v))

    def _finish_decode_dense(self, slots: List[int], vals: np.ndarray):
        for slot in slots:
            req = self.active[slot]
            self._emit(req, int(vals[slot]))
            self.pool.lengths[slot] += 1
            if len(req.tokens_out) >= req.max_new or \
               self.pool.lengths[slot] >= self.pool.max_seq - 1:
                req.done = True
                self._finished.append(req)
                del self.active[slot]
                self.pool.free_slot(slot)
                self.tracer.request_state(req.seq_id, "finished")

    # -- paged scheduling state --------------------------------------------
    def _activate(self, slot: int, req: Request, first_admit: bool):
        self._admit_clock += 1
        self._admitted_at[slot] = self._admit_clock
        self._last_decoded[slot] = self.stats["decode_steps"]
        self._resident_since[slot] = self.stats["decode_steps"]
        self._chunks_done[slot] = 0
        if self.overlap:
            self._shadow_activated.add(slot)
        if self.chunked and req.prefill_pos < len(req.prompt):
            self.prefilling[slot] = req
            state = "prefill"
        elif self.chunked and not self.pool.has_decode_reservation(
                req.seq_id, len(req.prompt), req.max_new):
            self.prefilled_wait[slot] = req
            state = "prefill"
        else:
            self.active[slot] = req
            state = "decode"
        if first_admit:
            self._note_first_admit(req)
        self.tracer.request_state(req.seq_id, state)

    def _pick_victim(self, exclude: Optional[int] = None) -> Optional[int]:
        """LRU preemption victim: least-recently-decoded resident, oldest
        admission breaking ties (all residents decode together, so the
        tie-break usually decides). A decoding resident is exempt until it
        has decoded ``preempt_quantum`` steps in its current residency, and a
        mid-prefill resident until it has landed one chunk — every admitted
        sequence makes progress before it can be evicted again, which is
        what guarantees the rotation terminates."""
        candidates = dict(self.active)
        if self.chunked:
            candidates.update(self.prefilled_wait)
            candidates.update(self.prefilling)
        best, best_key = None, None
        for slot in candidates:
            if slot == exclude:
                continue
            if self.overlap and slot in self._shadow_activated:
                # resumed in this step's shadow: it has not reached the
                # post-commit pack yet, so evicting it re-queues a fully
                # paid swap-in having made zero progress. With the one-step
                # commit lag every resumed residency would be stolen by the
                # same shadow's admission pass before its first dispatch
                # and the rotation never terminates — this makes the sync
                # loop's "the last resumes survive the admission pass"
                # property explicit
                continue
            if slot in self.active:
                quantum = self.preempt_quantum
                if self.overlap and \
                        self._inflight_decode.get(slot) is self.active[slot]:
                    # overlap: the last dispatched token is still in flight
                    # and would be DISCARDED by a preemption now — it is not
                    # progress yet, so it cannot count toward the quantum
                    # (otherwise a 1-quantum rotation livelocks: every
                    # residency's only token dies uncommitted)
                    quantum += 1
                if self.stats["decode_steps"] - self._resident_since[slot] \
                   < quantum:
                    continue
            if slot in self.prefilling and self._chunks_done[slot] == 0:
                continue
            if not self.pool.can_swap_out(slot):
                continue
            key = (self._last_decoded[slot], self._admitted_at[slot])
            if best_key is None or key < best_key:
                best, best_key = slot, key
        return best

    def _preempt_until(self, can_fit, exclude: Optional[int] = None) -> bool:
        """Evict LRU residents to host DRAM until ``can_fit()`` passes.
        Returns False (leaving partial evictions in place — their capacity
        stays freed) when no eligible victim remains."""
        while not can_fit():
            victim = self._pick_victim(exclude)
            if victim is None:
                return False
            vreq = self.active.pop(victim, None)
            if vreq is None:
                vreq = self.prefilling.pop(victim, None)
                if vreq is not None:
                    self.stats["preempted_mid_prefill"] += 1
                else:
                    vreq = self.prefilled_wait.pop(victim)
            self.pool.swap_out(victim)
            self.tracer.request_state(vreq.seq_id, "preempted")
            # back of the queue: the waiting request goes first, the victim
            # resumes in FIFO turn (front-requeue only if the mailbox is
            # full — never lose a request)
            if not self.mailbox.put(vreq):
                self.mailbox.requeue(vreq)
            self.stats["preemptions"] += 1
            self._sync_swap_stats()
        return True

    def _sync_swap_stats(self):
        self.stats["swap_out_count"] = self.pool.swap_out_count
        self.stats["swap_in_count"] = self.pool.swap_in_count
        self.stats["swap_out_bytes"] = self.pool.swap_out_bytes
        self.stats["swap_in_bytes"] = self.pool.swap_in_bytes

    def _finish_pending_swapin(self):
        while self._pending_swapins:
            req, token = self._pending_swapins.pop(0)
            slot = self.pool.swap_in_finish(token)
            self.tracer.request_instant(req.seq_id, "resumed")
            self._activate(slot, req, first_admit=False)
            self._sync_swap_stats()

    def _admit_paged(self):
        """Admit by page availability: the drain stops at the first request
        the pool cannot take (requeued at the front, FIFO preserved).

        Untiered, a refusal *stalls* admission until a release frees
        capacity — otherwise every decode step would drain/refuse/requeue the
        same head request, inflating the refusal stat and churning the
        mailbox lock. Tiered, a refusal instead preempts the LRU resident
        (pages swap out to host DRAM) and the stall clears every pass:
        decode steps expire residency quanta, so a retry can make progress —
        only total-capacity exhaustion leaves the head waiting.

        Chunked, admission reserves only the *prompt* pages (partial-prefill-
        aware): the request enters ``self.prefilling`` and the step loop
        slices its prompt into token-budgeted chunks; no prefill is
        dispatched here."""
        if self.tiered:
            if not self.active:
                # no decode step will run to land the prefetch — finish it
                # here so the run loop always makes progress
                self._finish_pending_swapin()
            self._admit_stalled = False
        if getattr(self, "_admit_stalled", False):
            return
        while True:
            reqs = self.mailbox.drain(1)
            if not reqs:
                break
            req = reqs[0]
            if self.tiered and self.pool.is_cold(req.seq_id):
                # resume path: restore the preempted sequence's pages from
                # host DRAM (no re-prefill — its KV and tokens_out survive;
                # a half-prefilled request resumes at its chunk offset)
                if not self.pool.can_resume(req.seq_id) and \
                   not self._preempt_until(
                        lambda: self.pool.can_resume(req.seq_id)):
                    self.mailbox.requeue(req)
                    self.stats["admission_refusals"] += 1
                    self._admit_stalled = True
                    break
                if self.overlap:
                    # shadow phase: start the host→dev page DMAs now and keep
                    # draining — the slot and pages are claimed immediately
                    # (capacity accounting stays exact), the wait + scatter
                    # land at the top of the next step's shadow, under the
                    # device step dispatched below
                    self._pending_swapins.append(
                        (req, self.pool.swap_in_start(req.seq_id)))
                    self._sync_swap_stats()
                    continue
                slot = self.pool.swap_in(req.seq_id)
                self.tracer.request_instant(req.seq_id, "resumed")
                self._activate(slot, req, first_admit=False)
                self._sync_swap_stats()
                continue
            if self.policy is not None and \
                    not self.policy.may_admit(self._in_system()):
                # concurrency gate: a quiet "not yet" — the head stays
                # queued with no refusal stat and no pool churn (cold
                # resumes above are exempt: they are already in-system)
                self.mailbox.requeue(req)
                break
            L = len(req.prompt)
            if not self.pool.admissible_ever(L, req.max_new):
                # could never fit even on an idle pool: reject outright so it
                # doesn't head-of-line-block the drain forever
                self.stats["rejected"] = self.stats.get("rejected", 0) + 1
                continue
            if self.chunked:
                while True:
                    # longest-cached-prefix lookup: the request adopts the
                    # match's ref-counted pages and prefills only the
                    # unshared suffix (re-matched after every eviction —
                    # an evicted match page may have been freed)
                    match = self._prefix_match(req)
                    if self.pool.can_admit_prefill(
                            L, req.max_new, len(match.pages), match.length):
                        break
                    # cache eviction can only fix a PAGE shortage; when the
                    # refusal is slot-bound (or the request can never fit),
                    # flushing the index would cost every future hit for
                    # zero capacity — and only entries whose page actually
                    # frees (refcount 1) are worth dropping
                    if self.prefix is not None and \
                            np.any(self.pool.seq_ids < 0) and \
                            self.pool.admissible_ever(L, req.max_new) and \
                            self.pool.evict_cached(1, require_free=True):
                        continue   # reclaimed a cache-pinned page: retry
                    if self.tiered and self._preempt_until(
                            lambda: self.pool.can_admit_prefill(
                                L, req.max_new, len(match.pages),
                                match.length)):
                        continue
                    self.mailbox.requeue(req)
                    self.stats["admission_refusals"] += 1
                    self._admit_stalled = True
                    match = None
                    break
                if match is None:
                    break
                slot = self.pool.admit_prefill(req.seq_id, L,
                                               shared_pages=match.pages,
                                               match_len=match.length)
                if match.length:
                    req.prefill_pos = match.length
                    self.pool.lengths[slot] = match.length
                    self.stats["prefix_hits"] += 1
                    self.stats["prefix_shared_tokens"] += match.length
                if match.first_token is not None:
                    self.stats["prefix_full_hits"] += 1
                    # exact full-prompt hit: the cached greedy continuation
                    # IS this request's first token — prefill is skipped
                    # entirely and the request promotes straight to decode
                    self._emit(req, match.first_token)
                self._activate(slot, req, first_admit=True)
                continue
            if not self.pool.can_admit(L, req.max_new):
                if not (self.tiered and self._preempt_until(
                        lambda: self.pool.can_admit(L, req.max_new))):
                    self.mailbox.requeue(req)
                    self.stats["admission_refusals"] += 1
                    self._admit_stalled = True
                    break
            slot = self.pool.admit(req.seq_id, L, req.max_new)
            # dense B=1 prefill over the prompt, cache padded to a page
            # multiple, then scattered into this sequence's pages
            S_p = self.pool.padded_len(L)
            caches = transformer.init_caches(self.cfg, 1, S_p)
            toks = jnp.asarray(req.prompt[None, :].astype(np.int32))
            tok_dev, caches = self.executor.prefill_dense(toks, caches)
            self.pool.write_prefill(slot, caches, L)
            self._queue_fetch(
                tok_dev, lambda v, req=req: self._emit(req, int(v[0])))
            req.prefill_pos = L
            self._activate(slot, req, first_admit=True)
            self.stats["prefills"] += 1

    def _prefix_match(self, req: Request) -> PrefixMatch:
        """Prefix-cache lookup for a fresh request (no KV written yet). The
        cached first token is honoured only on the greedy path — otherwise
        the match is trimmed so at least one position is re-computed."""
        if self.prefix is None or req.prefill_pos or req.tokens_out:
            return PrefixMatch(length=0, pages=[])
        m = self.pool.match(req.prompt)
        if m.first_token is not None and not self.greedy:
            length = min(m.length, len(req.prompt) - 1)
            m = PrefixMatch(length=length,
                            pages=m.pages[:self.pool.pages_for(length)])
        return m

    def _dispatch_decode_paged(self, slots: Optional[List[int]] = None):
        if self.tiered and not self.overlap:
            # land the prefetch started at the end of the previous step: its
            # host→dev DMA has been overlapping the admission pass (and any
            # prefill dispatches) in between; the resumed slot joins this
            # decode batch (the overlapped loop lands prefetches in its
            # shadow phase instead — finishing here would block on DMAs
            # started only this step)
            self._finish_pending_swapin()
        if slots is None:
            slots = sorted(self.active)
        with self.tracer.span("decode_inputs"):
            B = self.pool.max_batch
            toks = np.zeros((B, 1), np.int32)
            mask = np.zeros(B, bool)
            for slot in slots:
                req = self.active[slot]
                toks[slot, 0] = req.tokens_out[-1]
                mask[slot] = True
                # a shared page at the write position is COW-forked before
                # the divergent write (first decode after a full-prefix hit,
                # or a donor decoding into its cache-shared tail page); the
                # fork page was pre-reserved, so neither call below can fail
                if self.prefix is not None and self.pool.cow_unshare(
                        slot, int(self.pool.lengths[slot])):
                    self.stats["cow_forks"] += 1
                # map the write position (lengths[slot]) before dispatch;
                # the decode reservation guarantees this never fails
                self.pool.ensure(slot, int(self.pool.lengths[slot]) + 1)
            tables = jnp.asarray(self.pool.device_page_tables())
            lengths = jnp.asarray(self.pool.lengths.astype(np.int32))
            # mid-prefill / unpromoted slots are resident but must not decode
            active = jnp.asarray(mask)
            toks = jnp.asarray(toks)
        ids_dev, self.pool.pages = self.executor.decode_paged(
            toks, self.pool.pages, tables, lengths, active)
        self.stats["decode_steps"] += 1
        self.stats["decode_tokens"] += len(slots)
        self.stats["batch_occupancy"].append(len(slots) / B)
        for slot in slots:
            self._last_decoded[slot] = self.stats["decode_steps"]
        used = self.pool.used_bytes()
        self.stats["peak_used_bytes"] = max(
            self.stats.get("peak_used_bytes", 0), used)
        in_system = len(self.active) + len(self.prefilling) + \
            len(self.prefilled_wait)
        if self.tiered:
            # an in-flight prefetch stays in cold_seqs() until it lands, so
            # the cold count already covers it — no separate pending term
            in_system += len(self.pool.cold_seqs())
            self.stats["peak_host_bytes"] = max(
                self.stats.get("peak_host_bytes", 0),
                self.pool.host_used_bytes())
        self.stats["peak_in_system"] = max(
            self.stats.get("peak_in_system", 0), in_system)
        pairs = [(slot, self.active[slot]) for slot in slots]
        if self.overlap:
            self._inflight_decode = dict(pairs)
        self._queue_fetch(
            ids_dev,
            lambda v, pairs=pairs: self._finish_decode_paged(pairs, v))

    def _finish_decode_paged(self, pairs: List[Tuple[int, Request]],
                             vals: np.ndarray):
        for slot, req in pairs:
            if self.active.get(slot) is not req:
                # preempted (overlap mode) while its step was in flight: the
                # swap-out captured the pre-decode KV and this token is
                # discarded — the greedy resume re-derives it bit-identically
                continue
            self._emit(req, int(vals[slot]))
            self.pool.lengths[slot] += 1
            # paged lengths count KV rows (dense counts rows + the pending
            # token), hence the -2: both paths stop at the same stream length
            if len(req.tokens_out) >= req.max_new or \
               self.pool.lengths[slot] >= self.pool.max_seq - 2:
                req.done = True
                self._finished.append(req)
                del self.active[slot]
                self.pool.release(slot)
                self.tracer.request_state(req.seq_id, "finished")
                self._admit_stalled = False       # capacity freed: retry admits

    def _start_prefetch(self):
        """If the mailbox head is a preempted (cold) sequence the hot tier
        can take right now, start its host→dev page DMAs; they are finished
        (waited + scattered) at the top of the next decode step, so the
        transfer overlaps the admission pass in between (AutoDMA's
        load/execute phasing, lifted to the serving level)."""
        if self._pending_swapins or not self.pool.cold_seqs():
            return
        head = self.mailbox.drain(1)
        if not head:
            return
        req = head[0]
        if self.pool.is_cold(req.seq_id) and self.pool.can_resume(req.seq_id):
            self._pending_swapins.append(
                (req, self.pool.swap_in_start(req.seq_id)))
        else:
            self.mailbox.requeue(req)

    # -- chunked prefill: the unified token-budgeted step ------------------
    def _step_chunked(self) -> bool:
        """One unified engine iteration (continuous batching with chunked
        prefill):

          1. land any in-flight swap-in prefetch (tiered),
          2. admission pass — prompt-only page reservations,
          3. promote prefilled waiters whose decode worst case now fits,
          4. pack the token budget: one decode token per decoding stream
             first, then fair-share the remainder over mid-prefill residents
             as prompt chunks,
          5. dispatch the chunks, then one decode step over the streams.

        A request whose whole prompt fits in the leftover budget is admitted,
        prefilled, and streams its first token within this single iteration —
        it never queues behind another request's whole prefill. Returns True
        iff a decode step was dispatched.

        Overlap mode splits the iteration around the commit point: steps
        1–2 (plus the prefetch start and a COW pre-fork pass) run FIRST, in
        the shadow of the previous iteration's in-flight device step; then
        the single blocking fetch commits that iteration's tokens; steps
        3–5 run after it, against exact post-commit state — so promotion,
        decode-slot selection, and chunk packing decide from the same state
        the synchronous loop would see, and the budget/fair-share
        invariants hold bit-for-bit."""
        if self.overlap:
            with self.tracer.span("schedule"):
                # -- shadow phase: previous step still in flight -----------
                self._shadow_activated.clear()
                if self.tiered:
                    self._finish_pending_swapin()
                self._admit_paged()
                if self.prefix is not None:
                    self._cow_prefork()
                if self.tiered:
                    self._start_prefetch()
                if self.tiered and not self.active and not self.prefilling:
                    # nothing will dispatch this step, so the pending
                    # resumes' DMAs have no device window to hide behind
                    # anyway — land them now and let this step decode
                    # instead of going idle (in deep-rotation mixes the
                    # preempt+swap-in step would otherwise dispatch nothing
                    # and leave the NEXT shadow phase naked)
                    self._finish_pending_swapin()
            # -- commit: the one blocking fetch (previous iteration) -------
            self._flush_commit()
            with self.tracer.span("schedule"):
                # -- exact-state phase: promote + pack post-commit ---------
                self._promote_waiters()
                decode_slots = sorted(self.active)
                mid_prefill = sorted(
                    int(r.seq_id) for r in self.prefilling.values())
                budget_left = self.token_budget - len(decode_slots)
                if self.policy is not None:
                    budget_left = self.policy.prefill_allowance(
                        budget_left, len(self.prefilling))
                chunks = self._pack_chunks(budget_left)
        else:
            with self.tracer.span("schedule"):
                if self.tiered:
                    self._finish_pending_swapin()
                self._admit_paged()
                self._promote_waiters()
                decode_slots = sorted(self.active)
                mid_prefill = sorted(
                    int(r.seq_id) for r in self.prefilling.values())
                budget_left = self.token_budget - len(decode_slots)
                if self.policy is not None:
                    # ITL-target mix shaping: squeeze the prefill share down
                    # to its floor (one token per mid-prefill resident) when
                    # decode latency is over target — fair-share/
                    # no-starvation survives
                    budget_left = self.policy.prefill_allowance(
                        budget_left, len(self.prefilling))
                chunks = self._pack_chunks(budget_left)
        for slot, req, start, size in chunks:
            self._run_chunk(slot, req, start, size)
        if decode_slots:
            self._dispatch_decode_paged(decode_slots)
        self.stats["iter_log"].append({
            "decode_tokens": len(decode_slots),
            "prefill_tokens": int(sum(c[3] for c in chunks)),
            "prefill_budget": int(max(0, budget_left)),
            "chunks": [(int(r.seq_id), int(start), int(size))
                       for _, r, start, size in chunks],
            "mid_prefill": mid_prefill,
        })
        if self.overlap:
            # this iteration's consumers become the NEXT iteration's commit;
            # the shadow phase above never queues fetches, so the handoff is
            # a straight swap
            self._commit_queue = self._fetch_queue
            self._fetch_queue = []
        return bool(decode_slots)

    def _cow_prefork(self) -> None:
        """Shadow-phase COW pre-fork (overlap mode, prefix stack): fork the
        shared page each in-flight decode slot will write at its NEXT
        dispatch, while the device step is still hiding the copy. The fork
        position is the post-commit write position (``lengths+1``);
        finishing slots are skipped — their fork page lies outside the
        decode reservation, and the synchronous loop never forks them. The
        dispatch-time ``cow_unshare`` then finds the page already private
        and is a no-op, so fork counts match the synchronous loop."""
        for slot, req in self.active.items():
            if self._inflight_decode.get(slot) is not req:
                continue          # no token in flight: lengths not advancing
            L = int(self.pool.lengths[slot])
            if len(req.tokens_out) + 1 >= req.max_new or \
                    L + 1 >= self.pool.max_seq - 2:
                continue          # finishes at commit
            if self.pool.cow_unshare(slot, L + 1):
                self.stats["cow_forks"] += 1

    def _pack_chunks(self, budget_left: int
                     ) -> List[Tuple[int, Request, int, int]]:
        """Fair-share the post-decode budget over mid-prefill residents in
        admission order: whenever the remainder covers them all, every one
        makes progress, and the shortest remaining prompt finishes first
        within its share — a short request admitted this iteration starts
        streaming this iteration instead of queueing behind a long prefill."""
        if budget_left <= 0 or not self.prefilling:
            return []
        order = sorted(self.prefilling, key=lambda s: self._admitted_at[s])
        remaining = {s: len(self.prefilling[s].prompt)
                     - self.prefilling[s].prefill_pos for s in order}
        share = dict.fromkeys(order, 0)
        left = budget_left
        while left > 0:
            live = [s for s in order if share[s] < remaining[s]]
            if not live:
                break
            quantum = max(1, left // len(live))
            for s in live:
                take = min(quantum, remaining[s] - share[s], left)
                share[s] += take
                left -= take
                if left == 0:
                    break
        return [(s, self.prefilling[s], self.prefilling[s].prefill_pos,
                 share[s]) for s in order if share[s] > 0]

    def _run_chunk(self, slot: int, req: Request, start: int, size: int):
        """Dispatch one prompt chunk ``[start, start+size)``: its KV lands in
        the slot's already-reserved pages; when the chunk completes the
        prompt, its sampled first token is queued for this iteration's flush
        (emission + prefix insertion + promotion run once the value lands)."""
        with self.tracer.span("prefill_chunk", seq_id=int(req.seq_id),
                              start=int(start), size=int(size)):
            if self.prefix is not None and self.pool.cow_unshare(slot, start):
                # the first chunk after a mid-page prefix match diverges
                # inside the shared partially-filled page: fork it first
                self.stats["cow_forks"] += 1
            table_row = jnp.asarray(self.pool.page_table_row(slot))
            toks = jnp.asarray(
                req.prompt[start:start + size][None, :].astype(np.int32))
            tok_dev, self.pool.pages = self.executor.prefill_chunk(
                toks, self.pool.pages, table_row,
                jnp.asarray(start, jnp.int32))
        req.prefill_pos = start + size
        self.pool.lengths[slot] = req.prefill_pos
        self._chunks_done[slot] += 1
        self.stats["prefill_chunks"] += 1
        self.stats["prefill_chunk_tokens"] += size
        if req.prefill_pos >= len(req.prompt):
            self._queue_fetch(
                tok_dev,
                lambda v, slot=slot, req=req:
                    self._finish_chunk_prefill(slot, req, int(v[0])))

    def _finish_chunk_prefill(self, slot: int, req: Request, tok: int):
        """Prompt completed: stream the first token, index the prompt in the
        prefix cache, and attempt promotion to the decode set."""
        self._emit(req, tok)
        self.stats["prefills"] += 1
        if self.prefilling.get(slot) is not req:
            # preempted (overlap mode) while the completing chunk was in
            # flight: the swap-out captured the full prompt KV (prefill_pos
            # advanced eagerly at dispatch) and tokens_out[-1] now carries
            # this first token, so the resume activates straight past
            # prefill — nothing to promote or index here
            return
        del self.prefilling[slot]
        if self.prefix is not None and self.greedy:
            # index the completed prompt: its pages become claimable by
            # later arrivals, its greedy first token makes an exact
            # re-arrival skip prefill entirely
            self.pool.insert(req.seq_id, req.prompt, tok)
        if self.pool.reserve_decode(req.seq_id, len(req.prompt),
                                    req.max_new):
            self.active[slot] = req
            self.tracer.request_state(req.seq_id, "decode")
        else:
            self.prefilled_wait[slot] = req

    def _promote_waiters(self):
        """FIFO promotion of prefilled waiters into the decode set: top the
        reservation up to the decode worst case. Tiered, a blocked head may
        preempt LRU residents. When nothing is decoding or prefilling (so no
        release can ever arrive) the youngest waiter is evicted and
        re-prefills later — the oldest always eventually promotes
        (``admissible_ever`` bounds its worst case by the pool size)."""
        while True:
            order = sorted(self.prefilled_wait,
                           key=lambda s: self._admitted_at[s])
            if not order:
                return
            head = order[0]
            req = self.prefilled_wait[head]
            L = len(req.prompt)
            ok = self.pool.reserve_decode(req.seq_id, L, req.max_new)
            if not ok and self.prefix is not None:
                # reclaim cache-pinned pages before escalating to preemption
                # (require_free: dropping a still-adopted page frees nothing)
                while not self.pool.can_reserve_decode(
                        req.seq_id, L, req.max_new) and \
                        self.pool.evict_cached(1, require_free=True):
                    pass
                ok = self.pool.reserve_decode(req.seq_id, L, req.max_new)
            if not ok and self.tiered:
                ok = self._preempt_until(
                    lambda: self.pool.can_reserve_decode(
                        req.seq_id, L, req.max_new),
                    exclude=head) and \
                    self.pool.reserve_decode(req.seq_id, L, req.max_new)
            if not ok and not self.active and not self.prefilling and \
                    len(order) > 1:
                self._evict_reprefill(order[-1])
                continue
            if not ok:
                return
            del self.prefilled_wait[head]
            self.active[head] = req
            self.tracer.request_state(req.seq_id, "decode")

    def _evict_reprefill(self, slot: int):
        """Promotion-deadlock breaker (untiered, or tiered with the host
        budget exhausted): drop the youngest waiter's KV and requeue it — it
        re-prefills from scratch later. Greedy streams are deterministic per
        request, so the recomputed prefix is bit-identical; the already-
        emitted first token is retracted and re-derived."""
        req = self.prefilled_wait.pop(slot)
        self.pool.release(slot)
        req.prefill_pos = 0
        if req.tokens_out:
            req.tokens_out.pop()
            req.t_tokens.pop()
        if req.t_first:
            # the first token was retracted with its emission: drop its TTFT
            # sample too, so the stat reflects the token the user will get
            try:
                self.stats["ttft_s"].remove(req.t_first - req.t_submit)
            except ValueError:
                pass
            req.t_first = 0.0
        self.mailbox.requeue(req)
        self.tracer.request_state(req.seq_id, "queued")
        self.stats["evictions_reprefill"] += 1
        self._admit_stalled = False

    # -- hero_perf-style counter summary ----------------------------------
    def stats_summary(self) -> Dict[str, Any]:
        """Engine counters in report form: occupancy, swap traffic,
        preemptions, chunked-prefill token split, host-transfer counts,
        queue-latency percentiles (submit → admission), TTFT percentiles
        (submit → first token), and inter-token-latency percentiles. Every
        aggregate is guarded for the empty-engine case — a fresh or idle
        engine reports zeros, never a numpy error (the percentile math is
        serve/metrics.py's pure-Python :func:`~repro.serve.metrics.quantile`,
        which encodes that hardening)."""
        occ = self.stats.get("batch_occupancy") or []
        out = {
            "decode_steps": self.stats.get("decode_steps", 0),
            "prefills": self.stats.get("prefills", 0),
            "mean_occupancy": float(np.mean(occ)) if occ else 0.0,
            "admission_refusals": self.stats.get("admission_refusals", 0),
            "preemptions": self.stats.get("preemptions", 0),
            "preempted_mid_prefill": self.stats.get("preempted_mid_prefill", 0),
            "evictions_reprefill": self.stats.get("evictions_reprefill", 0),
            "swap_out_count": self.stats.get("swap_out_count", 0),
            "swap_in_count": self.stats.get("swap_in_count", 0),
            "swap_out_bytes": self.stats.get("swap_out_bytes", 0),
            "swap_in_bytes": self.stats.get("swap_in_bytes", 0),
            "prefill_chunks": self.stats.get("prefill_chunks", 0),
            "prefill_chunk_tokens": self.stats.get("prefill_chunk_tokens", 0),
            "decode_tokens": self.stats.get("decode_tokens", 0),
            "cow_forks": self.stats.get("cow_forks", 0),
            "prefix_hits": self.stats.get("prefix_hits", 0),
            "prefix_full_hits": self.stats.get("prefix_full_hits", 0),
            "prefix_shared_tokens": self.stats.get("prefix_shared_tokens", 0),
            "peak_used_bytes": self.stats.get("peak_used_bytes", 0),
            "peak_host_bytes": self.stats.get("peak_host_bytes", 0),
            "peak_in_system": self.stats.get("peak_in_system", 0),
            "token_fetches": self.executor.stats.get("token_fetches", 0),
            "tokens_fetched": self.executor.stats.get("tokens_fetched", 0),
            "tp": self.executor.tp,
        }
        if self.chunked:
            iters = self.stats.get("iter_log") or []
            out["token_budget"] = self.token_budget
            out["max_iter_tokens"] = max(
                (e["decode_tokens"] + e["prefill_tokens"] for e in iters),
                default=0)
        if self.prefix is not None:
            out.update(self.prefix.stats())
        out["shed"] = self.stats.get("shed", 0)
        out.update(percentiles(self.stats.get("queue_lat_s") or [],
                               prefix="queue_lat_", suffix="_s"))
        out.update(percentiles(self.stats.get("ttft_s") or [],
                               prefix="ttft_", suffix="_s"))
        out.update(percentiles(self.stats.get("itl_s") or [],
                               prefix="itl_", suffix="_s"))
        return out
