"""Batched serving engines for quantized models (continuous batching).

Two engines share the :class:`Request` lifecycle (submit → waiting queue →
prefill → shared batched decode with per-slot positions → finished) and the
greedy sampler; weights may be dense bf16 or QuantizedTensor (the PTQ
artifact) — both engines are agnostic, and the Pallas dequant-GEMM engages
on TPU.

:class:`ServingEngine` — the **contiguous** baseline: every slot reserves
``max_seq`` KV memory up front, prompts prefill in one padded shot into a
per-slot cache.  Kept as the numerical oracle (the paged engine must match
it token-for-token on bf16 KV) and as the benchmark baseline
(benchmarks/bench_serve.py).

:class:`PagedServingEngine` — the production path (DESIGN.md
§Paged-serving): KV lives in a shared pool of fixed-size pages
(serve/kv_cache.py), admission is gated by free *pages* instead of free
slots, prompts stream in **chunked prefills** interleaved with decode steps
(long prompts never stall the running batch), matching prompt prefixes
share pages (hash-chain prefix cache + copy-on-write partial hits), and
when the pool runs dry a sequence is **preempted** — its pages freed, the
request requeued, and later resumed by deterministic re-prefill of
prompt + already-generated tokens (greedy decode makes the final output
identical to an uninterrupted run).  Decode attends through
``ops.paged_attention`` — the Pallas paged kernel on TPU, the XLA gather
fallback elsewhere.

SLO scheduling (DESIGN.md §Resilience): requests carry an optional
``deadline_ms`` (relative to submit) and an integer ``priority`` (higher =
more important).  Under ``scheduler="slo"`` (the default) the engine

* admits in ``(priority desc, deadline asc, arrival)`` order — low-priority
  requests **park** in the queue under sustained pressure instead of
  competing for pages;
* **sheds** a request at admission when its deadline is *provably*
  unmeetable — the optimistic lower bound (its own prefill chunks + decode
  steps at the fastest step cost ever observed, i.e. assuming zero queueing
  and zero pool pressure) already overshoots the deadline;
* **expires** queued or running requests the moment their deadline passes
  (pages freed, partial output kept) instead of burning pool on work
  nobody can use;
* preempts by **deadline/priority**: the victim is the lowest-priority,
  most-slack, newest sequence — not simply the newest.

Every request leaves with a terminal ``status``: ``completed`` (all tokens,
never preempted), ``preempted_resumed`` (all tokens, survived ≥1
preemption — token-identical to an uninterrupted run by the deterministic
resume contract), ``shed``, or ``deadline_missed``.  ``scheduler="fifo"``
keeps the legacy FIFO/preempt-newest behaviour and ignores deadlines — the
benchmark baseline for the SLO scheduler.

Fault injection: both engines consult ``fault_point("engine.step")`` at the
top of :meth:`step` — before any state mutation — so an injected transient
fault is counted and retried as a pure no-op step; page allocation runs
through the ``pool.alloc`` injection point (a denial spike exercises
preemption and, if nothing else holds pages, self-preemption and retry
rather than a crash).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.faults import TransientFault, fault_point
from repro.models import (
    decode_step,
    init_cache,
    init_paged_cache,
    paged_decode_step,
    paged_prefill_chunk,
    paged_verify_tokens,
    prefill,
)
from repro.models.model import ModelPlan
from repro.serve.kv_cache import NULL_PAGE, PagePool, page_nbytes
from repro.serve.spec import DraftManager, SpecConfig, maybe_hoist

__all__ = ["Request", "ServingEngine", "PagedServingEngine", "TERMINAL_STATUSES"]

TERMINAL_STATUSES = ("completed", "preempted_resumed", "shed", "deadline_missed")

_INF = float("inf")


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray  # (n,) int32
    max_new_tokens: int = 16
    deadline_ms: Optional[float] = None  # SLO deadline, relative to submit
    priority: int = 0  # higher = more important (scheduler="slo" only)
    output: Optional[list] = None
    done: bool = False
    status: str = "pending"  # terminal: one of TERMINAL_STATUSES
    error: Optional[str] = None  # set when shed (the clear rejection reason)
    submit_t: Optional[float] = None  # engine-clock timestamps
    first_token_t: Optional[float] = None
    finish_t: Optional[float] = None
    n_preemptions: int = 0
    submit_order: int = -1  # arrival tie-break (assigned by the engine)
    # Speculative-decoding accounting (all zero when the engine doesn't
    # speculate): commit rounds this request went through, proposals the
    # target scored, and how many it accepted.  Every round commits
    # accepted + 1 tokens (the bonus token), so
    # ``len(output) == n_draft_accepted + n_spec_rounds`` exactly — the
    # accounting test pins this identity.
    n_spec_rounds: int = 0
    n_draft_tokens: int = 0
    n_draft_accepted: int = 0

    def acceptance_rate(self) -> Optional[float]:
        """Fraction of proposed draft tokens the target accepted (None
        when nothing was ever proposed for this request)."""
        if self.n_draft_tokens == 0:
            return None
        return self.n_draft_accepted / self.n_draft_tokens

    def deadline_at(self) -> float:
        """Absolute engine-clock deadline (inf when no SLO attached)."""
        if self.deadline_ms is None or self.submit_t is None:
            return _INF
        return self.submit_t + self.deadline_ms / 1e3


class ServingEngine:
    """Contiguous-slot engine: per-slot ``max_seq`` KV reservation.

    Prefills are right-padded to ``prefill_pad`` buckets so one prefill
    executable serves all prompt lengths; the prompt's *last real token*
    is replayed as the first decode so padding never pollutes the
    distribution (pad positions remain invalid: each slot's validity mask
    is its own position).  Recurrent state (mamba, short conv) would fold
    the replayed token in twice and the pads after it, so the prefill is
    told its length, the prompt before its last token, and stops there.
    """

    def __init__(
        self,
        plan: ModelPlan,
        params,
        *,
        max_batch: int = 4,
        max_seq: int = 512,
        prefill_pad: int = 32,
        record_logits: bool = False,
        clock: Optional[Callable[[], float]] = None,
    ):
        self.plan = plan
        self.params = params
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.prefill_pad = prefill_pad
        self.record_logits = record_logits
        self.clock = clock or time.monotonic
        self.logit_trace: dict[int, list] = {}

        self.cache = init_cache(plan, max_batch, max_seq)
        self.slot_req: list[Optional[Request]] = [None] * max_batch
        self.slot_pos = np.zeros(max_batch, np.int64)
        self.queue: list[Request] = []
        self.finished: list[Request] = []
        self._last_tok = np.zeros((max_batch, 1), np.int32)
        self._submitted = 0

        self._decode = jax.jit(lambda p, t, c, pos: decode_step(plan, p, t, c, pos))
        self._prefill = jax.jit(lambda p, b, c: prefill(plan, p, b, c))
        self.n_decode_steps = 0
        self.n_prefills = 0
        self.n_prefill_tokens = 0  # real prompt tokens (pad excluded)
        self.n_transient_faults = 0

    # ------------------------------------------------------------------
    def submit(self, req: Request):
        # Same admission contract as the paged engine: every generated token
        # occupies a cache position, so prompt + max_new must fit the window.
        # In particular a prompt that exactly fills the window
        # (len == max_seq) cannot decode even token 0 — its replay decode
        # would have nowhere left to advance — and is rejected here instead
        # of silently finishing with an empty output (and a longer prompt
        # used to crash prefill with an opaque broadcast error).
        if len(req.prompt) + req.max_new_tokens > self.max_seq:
            raise ValueError(
                f"request {req.rid} cannot fit: prompt {len(req.prompt)} + "
                f"max_new {req.max_new_tokens} > max_seq {self.max_seq}"
            )
        req.output = []
        req.status = "queued"
        req.submit_t = self.clock()
        req.submit_order = self._submitted
        self._submitted += 1
        self.queue.append(req)

    def _admit(self):
        for slot in range(self.max_batch):
            if self.slot_req[slot] is not None or not self.queue:
                continue
            req = self.queue.pop(0)
            n = len(req.prompt)
            pad = min(-(-n // self.prefill_pad) * self.prefill_pad, self.max_seq)
            toks = np.zeros((1, pad), np.int32)
            toks[0, :n] = req.prompt
            tmp_cache = init_cache(self.plan, 1, self.max_seq)
            _, tmp_cache = self._prefill(
                self.params, {"tokens": jnp.asarray(toks), "length": jnp.int32(n - 1)},
                tmp_cache,
            )
            self.n_prefills += 1
            self.n_prefill_tokens += n
            self.cache = jax.tree.map(
                lambda big, one: jax.lax.dynamic_update_slice(
                    big, one.astype(big.dtype), (0, slot) + (0,) * (big.ndim - 2)
                ),
                self.cache,
                tmp_cache,
            )
            self.slot_req[slot] = req
            # Positions [n, pad) hold pad-token kv; decode from position n by
            # replaying the last real token — the mask (pos<len) hides pads.
            self.slot_pos[slot] = n - 1
            self._last_tok[slot, 0] = int(req.prompt[-1])

    def _retire(self):
        for i, req in enumerate(self.slot_req):
            if req is None:
                continue
            if len(req.output) >= req.max_new_tokens or self.slot_pos[i] >= self.max_seq - 1:
                req.done = True
                req.status = "completed"
                req.finish_t = self.clock()
                self.finished.append(req)
                self.slot_req[i] = None

    def step(self) -> bool:
        try:
            fault_point("engine.step")
        except TransientFault:
            # Nothing mutated yet — a pure no-op step; retry next time.
            self.n_transient_faults += 1
            return True
        self._admit()
        self._retire()  # max_new_tokens == 0 finishes without a decode
        active = [i for i, r in enumerate(self.slot_req) if r is not None]
        if not active:
            return False
        pos = jnp.asarray(self.slot_pos, jnp.int32)
        logits, self.cache = self._decode(
            self.params, jnp.asarray(self._last_tok), self.cache, pos
        )
        self.n_decode_steps += 1
        logits = np.asarray(logits.astype(jnp.float32))
        now = self.clock()
        for i in active:
            tok = int(np.argmax(logits[i]))
            if self.record_logits:
                self.logit_trace.setdefault(self.slot_req[i].rid, []).append(
                    logits[i]
                )
            self._last_tok[i, 0] = tok
            req = self.slot_req[i]
            if not req.output:
                req.first_token_t = now
            req.output.append(tok)
            self.slot_pos[i] += 1
        self._retire()
        return True

    def run(self, max_steps: int = 10_000):
        steps = 0
        while (self.queue or any(r is not None for r in self.slot_req)) and steps < max_steps:
            if not self.step():
                break
            steps += 1
        return self.finished


@dataclasses.dataclass
class _Seq:
    """Per-lane scheduler state of the paged engine."""

    req: Request
    tokens: list  # prompt + generated so far (resume recomputes from this)
    pages: list  # position-ordered page ids
    n_prefilled: int  # positions [0, n_prefilled) hold valid KV
    n_target: int  # == len(tokens) at admission; prefill ends here
    hashed_upto: int = 0  # pages registered into the prefix cache so far
    order: int = 0  # admission order (the final preemption tie-break)


class PagedServingEngine:
    """Paged-KV engine: shared page pool, chunked prefill, prefix cache,
    SLO-aware scheduling with preemption-by-eviction.  See the module
    docstring for the scheduler contract; on bf16 KV its outputs are
    token-identical to :class:`ServingEngine` (tests/test_paged_serve.py)."""

    def __init__(
        self,
        plan: ModelPlan,
        params,
        *,
        max_batch: int = 8,
        max_seq: int = 512,
        page_size: int = 16,
        n_pages: Optional[int] = None,
        prefill_chunk: int = 64,
        prefix_cache: bool = True,
        record_logits: bool = False,
        scheduler: str = "slo",
        spec: Optional[SpecConfig] = None,
        clock: Optional[Callable[[], float]] = None,
    ):
        if scheduler not in ("slo", "fifo"):
            raise ValueError(f"unknown scheduler {scheduler!r}; expected slo|fifo")
        if spec is not None and spec.draft_plan.cfg.vocab != plan.cfg.vocab:
            raise ValueError(
                f"draft vocab {spec.draft_plan.cfg.vocab} != target vocab "
                f"{plan.cfg.vocab}: draft proposals would not be target tokens"
            )
        self.plan = plan
        self.params = params
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.page_size = page_size
        self.pages_per_seq = -(-max_seq // page_size)
        if n_pages is None:
            n_pages = 1 + max_batch * self.pages_per_seq  # ample: no preemption
        self.n_pages = n_pages
        self.prefill_chunk = prefill_chunk
        self.prefix_cache = prefix_cache
        self.record_logits = record_logits
        self.scheduler = scheduler
        self.clock = clock or time.monotonic

        self.cache = init_paged_cache(plan, n_pages, page_size)
        self.pool = PagePool(n_pages, page_size)
        self.table = np.full((max_batch, self.pages_per_seq), NULL_PAGE, np.int32)
        self._dev_table = None  # rebuilt lazily when self.table changes
        self.lanes: list[Optional[_Seq]] = [None] * max_batch
        self.queue: list[Request] = []
        self.finished: list[Request] = []
        self.slot_pos = np.zeros(max_batch, np.int64)
        self._last_tok = np.zeros((max_batch, 1), np.int32)
        self._admitted = 0
        self._submitted = 0
        self.logit_trace: dict[int, list] = {}

        # The page pool is donated (same policy as launch/specs.py serve
        # specs): each step updates the pool in place instead of allocating
        # and copying a second full pool — self.cache is always reassigned
        # from the result, so the consumed buffer is never reused.
        self._decode = jax.jit(
            lambda p, t, c, pos, pt, pw: paged_decode_step(plan, p, t, c, pos, pt, pw),
            donate_argnums=(2,),
        )
        self._chunk = jax.jit(
            lambda p, t, c, pt, off: paged_prefill_chunk(plan, p, t, c, pt, off),
            donate_argnums=(2,),
        )
        # COW page copy: every leaf is (n_periods, n_pages, ...).
        self._copy_page = jax.jit(
            lambda c, s, d: jax.tree.map(lambda a: a.at[:, d].set(a[:, s]), c),
            donate_argnums=(0,),
        )

        # Speculative decoding (DESIGN.md §Speculative-serving): a draft
        # stack proposes, one fused γ+1-position verify scores, the longest
        # target-greedy prefix + bonus token commits.  The verify runs the
        # decode step over B·γ+1 *virtual lanes* — decode-path KV bytes and
        # arithmetic per position — so speculative greedy output is
        # token-identical to the plain loop.
        self.spec = spec
        self.spec_mgr: Optional[DraftManager] = None
        if spec is not None:
            self.spec_mgr = DraftManager(
                spec, pool=self.pool, n_pages=n_pages, max_batch=max_batch,
                max_seq=max_seq, page_size=page_size,
                prefill_chunk=prefill_chunk,
            )
            self._verify_fn = jax.jit(
                lambda p, t, c, pos, pt, wp: paged_verify_tokens(
                    plan, p, t, c, pos, pt, wp
                ),
                donate_argnums=(2,),
            )
            # Verify-path weight view: where the GEMM dispatch would take
            # the XLA reference anyway (off-TPU), quantized leaves are
            # pre-dequantized ONCE (models/common.HoistedDequant) so the
            # γ+1-position scan doesn't re-dequantize loop-invariant
            # weights every position — bitwise-identical results, so the
            # token-identity invariant is untouched.  The legacy L=1
            # branch keeps self.params: its cost feeds the provable-shed
            # floor and its bytes are the pre-speculation hot path.
            self._verify_params = maybe_hoist(params, spec.hoist_dequant)

        self.n_decode_steps = 0
        self.n_prefill_chunks = 0
        self.n_prefill_tokens = 0
        self.n_prefix_hit_tokens = 0
        self.n_cow_hits = 0
        self.n_guard_copies = 0  # replay-target copies off registered pages
        self.n_preemptions = 0
        self.n_shed = 0
        self.n_deadline_missed = 0
        self.n_transient_faults = 0
        # Speculative counters (stay zero without a SpecConfig).
        self.n_spec_rounds = 0
        self.n_draft_tokens = 0
        self.n_draft_accepted = 0
        # Fastest step costs ever observed (engine clock): the optimistic
        # per-step floor behind provable-shed admission.  None until the
        # first measurement — admission cannot *prove* anything without
        # cost evidence, so it never sheds cold.
        self._min_decode_s: Optional[float] = None
        self._min_chunk_s: Optional[float] = None
        # KV pages streamed by decode attention: Σ over decode steps and
        # active lanes of ceil(context/page_size) — the roofline's
        # context_pages term, measured.  Periods are folded in by
        # :meth:`kv_read_bytes` (every page id spans all layers).
        self.n_kv_page_reads = 0

    def kv_read_bytes(self) -> int:
        """Decode-attention KV bytes implied by the page-read counter, in
        the same units as roofline.paged_kv_bytes_per_token — measured
        counterpart of the predicted bytes/token (benchmarks/report.py
        renders them side by side)."""
        hp = self.plan.heads
        per_page = page_nbytes(
            self.page_size, hp.kv_pad, hp.head_dim,
            self.plan.cfg.n_periods, self.plan.kv_cache_dtype,
        )
        return self.n_kv_page_reads * per_page

    def acceptance_rate(self) -> Optional[float]:
        """Engine-wide draft acceptance (None before any proposal)."""
        if self.n_draft_tokens == 0:
            return None
        return self.n_draft_accepted / self.n_draft_tokens

    # ------------------------------------------------------------------
    def submit(self, req: Request):
        need = -(-(len(req.prompt) + req.max_new_tokens) // self.page_size)
        if need > self.n_pages - 1 or len(req.prompt) + req.max_new_tokens > self.max_seq:
            raise ValueError(
                f"request {req.rid} cannot fit: needs {need} pages / "
                f"{len(req.prompt) + req.max_new_tokens} positions"
            )
        req.output = []
        req.status = "queued"
        req.submit_t = self.clock()
        req.submit_order = self._submitted
        self._submitted += 1
        self.queue.append(req)

    def _finish(self, req: Request, status: str, error: Optional[str] = None):
        req.done = True
        req.status = status
        req.error = error
        req.finish_t = self.clock()
        if status == "shed":
            self.n_shed += 1
        elif status == "deadline_missed":
            self.n_deadline_missed += 1
        self.finished.append(req)

    def _release_lane(self, lane: int):
        seq = self.lanes[lane]
        for p in seq.pages:
            self.pool.release(p)
        self.lanes[lane] = None
        self._set_row(lane, [])
        if self.spec_mgr is not None:  # draft pages go with the lane
            self.spec_mgr.release_lane(lane)

    def _dev_table_now(self):
        if self._dev_table is None:
            self._dev_table = jnp.asarray(self.table)
        return self._dev_table

    def _set_row(self, lane: int, pages: list):
        self.table[lane] = NULL_PAGE
        self.table[lane, : len(pages)] = pages
        self._dev_table = None

    # -- SLO bookkeeping ------------------------------------------------
    def _queue_pick(self) -> int:
        """Index into ``self.queue`` of the next request to admit.

        ``fifo``: strict arrival order (preempted requests re-queue at the
        front).  ``slo``: highest priority first, then earliest deadline,
        then arrival — which is exactly how low-priority requests *park*
        under sustained pressure: they stay queued (holding no pages)
        while urgent work flows past them.
        """
        if self.scheduler == "fifo" or len(self.queue) == 1:
            return 0
        return min(
            range(len(self.queue)),
            key=lambda i: (
                -self.queue[i].priority,
                self.queue[i].deadline_at(),
                self.queue[i].submit_order,
            ),
        )

    def _provably_unmeetable(self, req: Request) -> Optional[str]:
        """A rejection reason when even the *optimistic* completion bound —
        the request's own prefill chunks plus its remaining decode steps at
        the fastest per-step cost ever observed, assuming zero queueing and
        zero pool pressure — overshoots the deadline.  Conservative by
        construction: real pressure only makes it later."""
        if req.deadline_ms is None:
            return None
        if self._min_decode_s is None:
            return None  # no cost evidence yet: nothing is provable
        now = self.clock()
        deadline = req.deadline_at()
        T = len(req.prompt) + len(req.output)
        n_chunks = -(-T // self.prefill_chunk)
        remaining = req.max_new_tokens - len(req.output)
        t_min = n_chunks * (self._min_chunk_s or 0.0) + remaining * self._min_decode_s
        if now + t_min > deadline:
            return (
                f"deadline {req.deadline_ms:.1f}ms provably unmeetable: "
                f"optimistic completion needs {t_min * 1e3:.1f}ms "
                f"({n_chunks} prefill chunks + {remaining} decode steps at "
                f"best-observed step cost) but only "
                f"{max(deadline - now, 0.0) * 1e3:.1f}ms remain"
            )
        return None

    def _expire_deadlines(self):
        """Terminate queued/running requests whose deadline has passed —
        partial output is kept, pages are freed immediately (degradation
        ladder rung 4: stop burning pool on work nobody can use)."""
        if self.scheduler != "slo":
            return
        now = self.clock()
        expired = [r for r in self.queue if r.deadline_at() <= now]
        for req in expired:
            self.queue.remove(req)
            self._finish(req, "deadline_missed")
        for lane, seq in enumerate(self.lanes):
            if seq is not None and seq.req.deadline_at() <= now:
                req = seq.req
                self._release_lane(lane)
                self._finish(req, "deadline_missed")

    # -- admission ------------------------------------------------------
    def _admit(self):
        for lane in range(self.max_batch):
            if self.lanes[lane] is not None or not self.queue:
                continue
            req = self.queue[self._queue_pick()]
            if self.scheduler == "slo":
                reason = self._provably_unmeetable(req)
                if reason is not None:
                    self.queue.remove(req)
                    self._finish(req, "shed", reason)
                    continue
            if req.max_new_tokens <= 0:  # nothing to generate: skip the pool
                self.queue.remove(req)
                self._finish(req, "completed")
                continue
            toks = list(map(int, req.prompt)) + list(req.output)
            T = len(toks)
            tt = tuple(toks)
            pages, n_cached, cow_src = [], 0, None
            if self.prefix_cache:
                pages, n_cached = self.pool.match_full(tt)
                cow_src = self.pool.match_partial(tt, n_cached)
            need = -(-T // self.page_size) - len(pages)
            fresh = self.pool.alloc(need)
            if fresh is None:  # head-of-line blocking keeps priority order
                for p in pages:
                    self.pool.release(p)
                break
            if cow_src is not None and fresh:
                # Copy-on-write partial hit: the first fresh page starts as
                # a copy of the cached page; the matched tail of the prompt
                # is then already-valid KV.
                self.cache = self._copy_page(self.cache, cow_src, fresh[0])
                n_cached = T
                self.n_cow_hits += 1
            elif pages and n_cached >= T:
                # Full-coverage hit: the replay decode will write position
                # T-1, and replay bytes are decode-path, not prefill-path
                # (≈1 ulp apart) — never write a shared page; give this
                # sequence a private copy of the last one (COW), which also
                # keeps its first-step logits bit-identical to a cold run.
                repl = self.pool.alloc(1)
                if repl is None:
                    for p in pages:
                        self.pool.release(p)
                    # Livelock audit: a full-coverage hit needs matched
                    # pages + 1 private COW page.  When that exceeds every
                    # page the pool could ever produce, no amount of
                    # waiting or eviction helps — the matched pages
                    # themselves exhaust the pool, and retrying each step
                    # re-matches them forever.  Reject with a clear error
                    # instead of livelocking the step loop.
                    if -(-T // self.page_size) + 1 > self.n_pages - 1:
                        self.queue.remove(req)
                        self._finish(
                            req, "shed",
                            f"request {req.rid} unsatisfiable: full prefix-"
                            f"cache hit needs {-(-T // self.page_size)} "
                            f"matched pages + 1 replay copy-on-write page, "
                            f"but the pool holds only {self.n_pages - 1} "
                            "allocatable pages — admission would livelock",
                        )
                        continue
                    break
                self.cache = self._copy_page(self.cache, pages[-1], repl[0])
                self.pool.release(pages[-1])
                pages[-1] = repl[0]
                self.n_cow_hits += 1
            self.queue.remove(req)
            seq = _Seq(
                req=req, tokens=toks, pages=pages + fresh,
                n_prefilled=n_cached, n_target=T,
                hashed_upto=len(pages), order=self._admitted,
            )
            self._admitted += 1
            self.n_prefix_hit_tokens += n_cached
            self.lanes[lane] = seq
            self._set_row(lane, seq.pages)
            if seq.n_prefilled >= T:
                self._arm_decode(lane, seq)

    def _arm_decode(self, lane: int, seq: _Seq):
        # The replay decode writes position T-1 with decode-path bytes
        # (≈1 ulp from the prefill-path bytes).  If that page is already
        # registered in the prefix cache (page-aligned prompt: its final
        # page registered the moment prefill filled it), give the sequence
        # a private copy so registered content stays prefill-pure — a
        # later warm hit must read exactly what a cold prefill would have
        # written.  Shared (ref > 1) replay targets can't reach here: the
        # full-coverage admission branch already COWed them.
        pg = (seq.n_target - 1) // self.page_size
        pid = seq.pages[pg]
        if pid in self.pool.key_of:
            repl = self.pool.alloc(1)
            if repl is not None:
                self.cache = self._copy_page(self.cache, pid, repl[0])
                self.pool.release(pid)
                seq.pages[pg] = repl[0]
                self.table[lane, pg] = repl[0]
                self._dev_table = None
                self.n_guard_copies += 1
            else:
                # Pool dry: write in place, but drop the registration so no
                # future prefix hit reads the mutated bytes.
                self.pool._unregister(pid)
        self.slot_pos[lane] = seq.n_target - 1  # replay the last known token
        self._last_tok[lane, 0] = seq.tokens[-1]
        if self.spec_mgr is not None:
            self.spec_mgr.attach(lane, seq)

    # -- chunked prefill -------------------------------------------------
    def _register_ready(self, seq: _Seq):
        psz = self.page_size
        while (seq.hashed_upto + 1) * psz <= seq.n_prefilled:
            i = seq.hashed_upto
            self.pool.register(seq.pages[i], tuple(seq.tokens[: (i + 1) * psz]))
            seq.hashed_upto = i + 1

    def _prefill_step(self) -> bool:
        """Run ONE prompt chunk (the oldest unfinished prefill) — prefill
        interleaves with decode instead of stalling the batch.  Chunks are
        always padded to ``prefill_chunk`` so a single executable serves
        every (offset, tail) shape: pad positions scatter into the null
        page or into not-yet-valid slots that decode rewrites before any
        length mask exposes them."""
        cand = [
            (s.order, lane, s)
            for lane, s in enumerate(self.lanes)
            if s is not None and s.n_prefilled < s.n_target
        ]
        if not cand:
            return False
        _, lane, seq = min(cand)
        off = seq.n_prefilled
        C = min(self.prefill_chunk, seq.n_target - off)
        buf = np.zeros((1, self.prefill_chunk), np.int32)
        buf[0, :C] = seq.tokens[off : off + C]
        t0 = self.clock()
        self.cache = self._chunk(
            self.params, jnp.asarray(buf), self.cache,
            self._dev_table_now()[lane : lane + 1], np.int32(off),
        )
        dt = self.clock() - t0
        if dt > 0:
            self._min_chunk_s = dt if self._min_chunk_s is None else min(self._min_chunk_s, dt)
        seq.n_prefilled += C
        self.n_prefill_chunks += 1
        self.n_prefill_tokens += C
        if self.prefix_cache:
            self._register_ready(seq)
        if seq.n_prefilled >= seq.n_target:
            self._arm_decode(lane, seq)
        return True

    # -- decode ----------------------------------------------------------
    def _preempt(self, lane: int):
        seq = self.lanes[lane]
        self._release_lane(lane)
        seq.req.n_preemptions += 1
        if self.scheduler == "fifo":
            self.queue.insert(0, seq.req)  # resume ASAP; output so far is kept
        else:
            # slo: _queue_pick favours the earliest submit_order within a
            # priority class, so the preempted request still resumes ahead
            # of later arrivals of equal urgency.
            self.queue.append(seq.req)
        self.n_preemptions += 1

    def _victim(self, victims: list) -> int:
        """Preemption victim: under ``slo``, evict the lowest-priority,
        most-slack (latest-deadline), newest sequence; under ``fifo``, the
        newest.  With no deadlines and uniform priorities the two policies
        coincide (the legacy determinism tests pin this)."""
        if self.scheduler == "fifo":
            return max(victims, key=lambda i: self.lanes[i].order)
        now = self.clock()
        return max(
            victims,
            key=lambda i: (
                -self.lanes[i].req.priority,
                self.lanes[i].req.deadline_at() - now,
                self.lanes[i].order,
            ),
        )

    def _decode_ready(self):
        return [
            i for i, s in enumerate(self.lanes)
            if s is not None and s.n_prefilled >= s.n_target
        ]

    def _ensure_capacity(self) -> list[int]:
        """Grow each decoding lane's page list to cover its write position,
        preempting by deadline/priority when the pool runs dry."""
        while True:
            active = self._decode_ready()
            blocked = None
            for i in active:
                seq = self.lanes[i]
                pg = int(self.slot_pos[i]) // self.page_size
                if pg < len(seq.pages):
                    continue
                got = self.pool.alloc(1)
                if got is None:
                    blocked = i
                    break
                seq.pages.append(got[0])
                self.table[i, pg] = got[0]
                self._dev_table = None
            if blocked is None:
                return self._decode_ready()
            victims = self._decode_ready() + [
                j for j, s in enumerate(self.lanes)
                if s is not None and s.n_prefilled < s.n_target
            ]
            victim = self._victim(victims)
            if victim == blocked and len(victims) == 1:
                seq = self.lanes[blocked]
                need = -(-(len(seq.req.prompt) + seq.req.max_new_tokens)
                         // self.page_size)
                if need > self.n_pages - 1:
                    raise RuntimeError(
                        "page pool too small for a single sequence"
                    )  # pragma: no cover — submit() bounds prevent this
                # The pool *can* hold this sequence, so the failure is a
                # transient denial (e.g. an injected exhaustion spike):
                # preempt the blocked sequence itself — its pages free, the
                # request requeues, and a later step resumes it
                # deterministically once allocation succeeds again.
            self._preempt(victim)

    def _decode_step(self) -> bool:
        """One decode round, decomposed into the propose → verify → commit
        contract (DESIGN.md §Speculative-serving).  Without a SpecConfig,
        propose returns empty proposals and verify runs the legacy
        single-token decode call — bit-for-bit the pre-speculation step
        loop (tests pin `record_logits` equality)."""
        active = self._ensure_capacity()
        if not active:
            return False
        proposals = self._propose(active)
        logits = self._verify(active, proposals)
        self._commit(active, proposals, logits)
        return True

    def _propose(self, active: list[int]) -> dict:
        """Draft proposals per active lane: ``{lane: [tokens]}`` (all
        empty without speculation).  The per-lane budget caps the depth
        so a verify round never writes past ``prompt + max_new - 2`` —
        γ overrunning ``max_new`` degrades to a shorter proposal, never
        an overshoot.  Draft page allocation happens inside the manager
        and degrades on a dry pool; it cannot preempt."""
        if self.spec_mgr is None:
            return {i: [] for i in active}
        items = []
        for i in active:
            seq = self.lanes[i]
            budget = min(
                seq.req.max_new_tokens - len(seq.req.output) - 1,
                self.max_seq - 2 - int(self.slot_pos[i]),
            )
            items.append((i, seq, int(self.slot_pos[i]), budget))
        return self.spec_mgr.propose(items)

    def _verify(self, active: list[int], proposals: dict) -> np.ndarray:
        """Score every lane's replay token + proposal in one target
        forward; returns fp32 logits (B, L, V).  With no proposals
        anywhere the legacy single-decode executable runs (L = 1) — the
        non-speculative hot path, and the only branch that feeds the
        provable-shed cost floor with true single-step costs.  Target
        lookahead pages are grown here; a dry pool *clamps the proposal*
        (speculation degrades) rather than preempting — only the legacy
        slot-position coverage in `_ensure_capacity` may preempt."""
        for i in active:
            if not proposals[i]:
                continue
            seq = self.lanes[i]
            d = len(proposals[i])
            while d:
                pg = (int(self.slot_pos[i]) + d) // self.page_size
                if pg < len(seq.pages):
                    break
                got = self.pool.alloc(1)
                if got is None:
                    d -= 1
                    continue
                seq.pages.append(got[0])
                self.table[i, len(seq.pages) - 1] = got[0]
                self._dev_table = None
            proposals[i] = proposals[i][:d]
        spec_round = any(proposals[i] for i in active)

        if not spec_round:
            write_page = np.full(self.max_batch, NULL_PAGE, np.int32)
            pos = np.zeros(self.max_batch, np.int32)
            for i in active:
                seq = self.lanes[i]
                pos[i] = self.slot_pos[i]
                write_page[i] = seq.pages[int(self.slot_pos[i]) // self.page_size]
                self.n_kv_page_reads += -(-(int(self.slot_pos[i]) + 1) // self.page_size)
            t0 = self.clock()
            logits, self.cache = self._decode(
                self.params, jnp.asarray(self._last_tok), self.cache,
                jnp.asarray(pos), self._dev_table_now(), jnp.asarray(write_page),
            )
            self.n_decode_steps += 1
            logits = np.asarray(logits.astype(jnp.float32))
            dt = self.clock() - t0
            if dt > 0:
                self._min_decode_s = dt if self._min_decode_s is None else min(self._min_decode_s, dt)
            return logits[:, None]

        L = self.spec.gamma + 1  # one executable for every outcome
        toks = np.zeros((self.max_batch, L), np.int32)
        wp = np.full((self.max_batch, L), NULL_PAGE, np.int32)
        pos = np.zeros(self.max_batch, np.int32)
        for i in active:
            seq = self.lanes[i]
            p0 = int(self.slot_pos[i])
            pos[i] = p0
            toks[i, 0] = self._last_tok[i, 0]
            props = proposals[i]
            toks[i, 1 : 1 + len(props)] = props
            for j in range(len(props) + 1):
                wp[i, j] = seq.pages[(p0 + j) // self.page_size]
                self.n_kv_page_reads += -(-(p0 + j + 1) // self.page_size)
        t0 = self.clock()
        logits, self.cache = self._verify_fn(
            self._verify_params, jnp.asarray(toks), self.cache, jnp.asarray(pos),
            self._dev_table_now(), jnp.asarray(wp),
        )
        self.n_decode_steps += 1
        self.n_spec_rounds += 1
        logits = np.asarray(logits.astype(jnp.float32))
        dt = self.clock() - t0
        if dt > 0:
            # Per-position floor: a scan position is never cheaper than
            # this, so the provable-shed bound stays a true lower bound.
            per = dt / L
            self._min_decode_s = per if self._min_decode_s is None else min(self._min_decode_s, per)
        return logits

    def _commit(self, active: list[int], proposals: dict, logits: np.ndarray):
        """Greedy acceptance per lane: commit the longest prefix of the
        proposal matching the target's own argmaxes, plus the bonus
        argmax at the first disagreement (= the whole round when nothing
        was proposed).  Every committed token is a target argmax over
        decode-path KV — exactly the non-speculative token stream, which
        is the engine's headline identity.  Draft pages past the new
        frontier roll back to the pool here."""
        now = self.clock()
        for i in active:
            seq = self.lanes[i]
            props = proposals[i]
            greedy = [int(np.argmax(logits[i, j])) for j in range(len(props) + 1)]
            a = 0
            while a < len(props) and props[a] == greedy[a]:
                a += 1
            if self.spec_mgr is not None:
                seq.req.n_spec_rounds += 1
                seq.req.n_draft_tokens += len(props)
                seq.req.n_draft_accepted += a
                self.n_draft_tokens += len(props)
                self.n_draft_accepted += a
            for j in range(a + 1):
                tok = greedy[j]
                if self.record_logits:
                    self.logit_trace.setdefault(seq.req.rid, []).append(
                        logits[i, j]
                    )
                self._last_tok[i, 0] = tok
                if not seq.req.output:
                    seq.req.first_token_t = now
                seq.req.output.append(tok)
                seq.tokens.append(tok)
                self.slot_pos[i] += 1
            if self.spec_mgr is not None:
                self.spec_mgr.commit(i, int(self.slot_pos[i]))

    def _retire(self):
        for i, seq in enumerate(self.lanes):
            if seq is None or seq.n_prefilled < seq.n_target:
                continue
            req = seq.req
            if len(req.output) >= req.max_new_tokens or self.slot_pos[i] >= self.max_seq - 1:
                self._release_lane(i)
                self._finish(
                    req,
                    "preempted_resumed" if req.n_preemptions else "completed",
                )

    # ------------------------------------------------------------------
    def step(self) -> bool:
        try:
            fault_point("engine.step")
        except TransientFault:
            # Raised before any state mutation: this step is a pure no-op
            # and the next one sees exactly the pre-fault scheduler state.
            self.n_transient_faults += 1
            return True
        self._expire_deadlines()
        self._admit()
        progressed = self._prefill_step()
        # Nothing can decode yet (cold start / post-preemption ramp): drain
        # prefills instead of burning empty steps — time-to-first-token.
        while progressed and not self._decode_ready():
            if not self._prefill_step():
                break
        progressed |= self._decode_step()
        self._retire()
        # Queued work with an idle engine and no progress means admission
        # was blocked by a transient allocation denial (nothing else holds
        # pages that could ever be freed) — keep stepping so the denial
        # window can pass, instead of reporting a dead engine.
        if not progressed and self.queue and not any(
            s is not None for s in self.lanes
        ):
            return True
        return progressed

    def run(self, max_steps: int = 10_000):
        steps = 0
        while (self.queue or any(s is not None for s in self.lanes)) and steps < max_steps:
            if not self.step():
                break
            steps += 1
        return self.finished
