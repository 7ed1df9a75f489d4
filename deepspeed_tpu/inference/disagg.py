"""Host-side bookkeeping for disaggregated prefill/decode serving.

Heavy-traffic serving splits into two phases with opposite resource
profiles: prefill is compute-bound (one big batched matmul pass over
the prompt), decode is bandwidth-bound (one token per request per
dispatch, reads dominated by KV traffic). Interleaving them in one
loop makes every prefill dispatch stall every in-flight request's next
token. Disaggregation runs them as separate worker loops — a *prefill
worker* fed by the admission window and a *decode worker* that owns
token generation — connected by a **page handoff**: a completed
prefill's KV state transfers to the decode loop by moving block-table
ownership.

Two handoff modes (the engine picks per config):

- **shared pool** (same mesh): the pages already live where decode
  reads them — the handoff is a zero-copy host bookkeeping move
  (this module), exactly like a refcount transfer. Cost: queue time
  only.
- **separate pools** (optionally separate meshes): only the LIVE pages
  (``ceil(prompt / page_size)`` — never the full reservation) are
  exported from the prefill pool, shipped to the decode mesh, and
  scattered into the decode pool (the jit half lives in
  ``inference/engine.py``). The wire cost is priced per hop by the
  PR 6 ``LinkModel`` (:func:`price_handoff` duck-types it, so this
  module stays import-clean).

This module is the pure host-side half — the handoff queue, transfer
records, wire pricing, and the dispatch ledger (one row a device
dispatch of every engine, with its stamps), whose ordering also pins
"no decode dispatch waits behind a prefill dispatch" (the decode phase
of every engine step runs FIRST). Nothing here imports jax (pinned
source-level by tests/unit/test_inference.py, like scheduler/paging/
buckets/draft): handoff POLICY is unit-testable in microseconds and
cannot perturb the compiled program set.
"""

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

__all__ = ["HandoffRecord", "HandoffQueue", "HandoffStats",
           "DispatchTrace", "MigrationRecord", "price_handoff"]


@dataclass
class HandoffRecord:
    """One completed prefill awaiting decode-side adoption.

    ``first_token`` is the token the prefill dispatch sampled — it is
    NOT released to the request until the decode worker claims the
    handoff (TTFT honestly includes handoff wait). ``live_pages`` is
    the page count actually holding prompt K/V (what a cross-pool
    transfer must move); the slot's full reservation never travels.
    """
    uid: int
    slot: int
    first_token: int
    live_pages: int
    prompt_tokens: int
    t_ready: float
    attempts: int = 0


class HandoffQueue:
    """FIFO of completed prefills between the worker loops.

    The decode worker drains it at the START of its phase; a claim can
    fail (decode pool can't reserve the request's lifetime pages yet)
    and the record is then re-queued — decode-side memory pressure
    backpressures the handoff, never the prefill loop. Counters feed
    ``engine.debug_state()`` and the ``serve_handoff`` trail rows.
    """

    def __init__(self, clock=time.perf_counter):
        self._clock = clock
        self._q: List[HandoffRecord] = []
        self.total_handoffs = 0       # claims completed
        self.total_requeues = 0       # claims bounced (pool pressure)
        self.total_dropped = 0        # records voided by eviction
        self.peak_depth = 0

    def __len__(self) -> int:
        return len(self._q)

    def push(self, rec: HandoffRecord) -> None:
        self._q.append(rec)
        self.peak_depth = max(self.peak_depth, len(self._q))

    def drain(self) -> List[HandoffRecord]:
        """Take every waiting record (the decode phase claims them in
        arrival order; unclaimable ones come back via :meth:`requeue`)."""
        out, self._q = self._q, []
        return out

    def requeue(self, rec: HandoffRecord) -> None:
        """Put a record back at the FRONT (its arrival order survives a
        bounced claim — the retry next step precedes newer handoffs)."""
        rec.attempts += 1
        self._q.insert(0, rec)
        self.total_requeues += 1

    def claimed(self, rec: HandoffRecord) -> float:
        """Account one completed claim; returns the record's total
        queue wait in ms."""
        self.total_handoffs += 1
        return (self._clock() - rec.t_ready) * 1e3

    def dropped(self, rec: HandoffRecord) -> None:
        """The request was evicted while its handoff waited — the
        record is void (its pages were already freed by the
        scheduler's eviction path)."""
        self.total_dropped += 1

    def pop(self, uid: int) -> Optional[HandoffRecord]:
        """Remove and return the queued record for ``uid`` (None if no
        record waits). Cancellation uses this: a record left in the
        queue after its slot is evicted would sit as a phantom entry
        until the next claim drain — or forever, if the eviction made
        the scheduler idle and the serving loop exits."""
        for i, rec in enumerate(self._q):
            if rec.uid == uid:
                del self._q[i]
                return rec
        return None

    def debug_state(self) -> Dict[str, int]:
        return {"depth": len(self._q), "peak_depth": self.peak_depth,
                "handoffs": self.total_handoffs,
                "requeues": self.total_requeues,
                "dropped": self.total_dropped}


def price_handoff(n_pages: int, page_bytes: int, link,
                  axis: str = "inter", hops: int = 1) -> float:
    """Modeled wire cost (ms) of moving ``n_pages`` pages across
    ``hops`` links, priced by a ``runtime/comm_autotune.LinkModel``
    (duck-typed: anything with ``bytes_per_us(axis)`` /
    ``latency_us(axis)``). Same-pool handoffs cost 0 — no bytes move.
    The priced figure rides the ``serve_handoff`` event row next to
    the measured wall time, so a handoff that costs more than the
    model predicts is visible per request."""
    if n_pages <= 0 or hops <= 0:
        return 0.0
    bytes_moved = float(n_pages) * float(page_bytes)
    us = hops * (link.latency_us(axis)
                 + bytes_moved / link.bytes_per_us(axis))
    return us / 1e3


@dataclass
class MigrationRecord:
    """One in-flight request's complete portable state: everything a
    destination engine needs to resume decode at the same
    ``cache_position`` with bitwise-identical outputs (ISSUE 16 live
    KV migration — the cross-*replica* sibling of the cross-pool
    :class:`HandoffRecord`).

    ``kslab``/``vslab`` are the live pages' K/V contents gathered by
    the warmup-compiled export program, trimmed to ``live_pages``
    (shape ``(layers, live_pages, page_size, kv_heads * head_dim)``:
    the pool's own row layout with the page axis trimmed, one token a
    row and heads major within it; host numpy — they ship as the raw binary segment of an RPC frame).
    Quantized (int8) pools additionally carry
    ``kscale_slab``/``vscale_slab`` — the per-token-row fp32 scales,
    shape ``(layers, live_pages, page_size, kv_heads * scale_blocks)``
    — so migrated pages stay int8 on the wire and the destination
    scatters payload + scales as one leaf-generic import. An fp-pool
    record leaves them None; the destination engine rejects any
    payload/scale combination its own pool geometry can't hold.
    Resume is bitwise because sampling keys derive from
    ``(request seed, absolute position)`` — never from batch
    composition or wall clock — and clocks are shipped as *elapsed*
    durations (``elapsed_ms`` since submit, ``queue_wait_ms``,
    ``ttft_ms``), not absolute host times, because source and
    destination perf counters share no epoch.
    """
    uid: int
    prompt: List[int]
    max_new_tokens: int
    temperature: float
    seed: int
    eos_id: Optional[int]
    priority: int
    position: int                 # next write position (cache rows
    pending_tok: int              # 0..position-1 are live content)
    tokens: List[int]             # generated so far (incl. pending)
    live_pages: int               # pages with real content
    page_bytes: int               # source pool page size (pricing)
    ttft_ms: Optional[float]
    queue_wait_ms: float
    elapsed_ms: float             # clock() - t_submit at export time
    draft_proposed: int = 0
    draft_accepted: int = 0
    weight_version: Optional[str] = None
    # distributed-trace context (ISSUE 18): the router-stamped trace id
    # and the hop ordinal AT EXPORT TIME ride the record so the
    # destination's ``serve_migrate_in`` row (hop + 1) links to the
    # source's ``serve_migrate_out`` — request lineage survives replica
    # death. Durations-not-absolute-times doctrine unchanged: trace ids
    # are opaque strings, alignment stays in ``clock_sync`` rows.
    trace_id: Optional[str] = None
    hop: int = 0
    kslab: Optional[object] = None    # numpy (layers, live, kvh, ps, hd)
    vslab: Optional[object] = None
    kscale_slab: Optional[object] = None  # fp32 (layers, live, kvh, ps, nb)
    vscale_slab: Optional[object] = None  # (int8 pools only)

    def to_header(self) -> Dict:
        """The JSON-able half (slabs ride the frame's binary segment —
        see rpc.migration_to_wire)."""
        return {
            "uid": self.uid, "prompt": list(self.prompt),
            "max_new_tokens": self.max_new_tokens,
            "temperature": self.temperature, "seed": self.seed,
            "eos_id": self.eos_id, "priority": self.priority,
            "position": self.position, "pending_tok": self.pending_tok,
            "tokens": list(self.tokens),
            "live_pages": self.live_pages,
            "page_bytes": self.page_bytes, "ttft_ms": self.ttft_ms,
            "queue_wait_ms": self.queue_wait_ms,
            "elapsed_ms": self.elapsed_ms,
            "draft_proposed": self.draft_proposed,
            "draft_accepted": self.draft_accepted,
            "weight_version": self.weight_version,
            "trace_id": self.trace_id, "hop": self.hop,
        }

    @property
    def nbytes(self) -> int:
        return sum(int(getattr(s, "nbytes", 0)) for s in (
            self.kslab, self.vslab, self.kscale_slab, self.vscale_slab))


class DispatchTrace:
    """The dispatch ledger: the engine's one record of its device
    dispatches, one row a dispatch, built for every engine.

    A row holds ``seq`` (its ordinal since the engine was built),
    ``step`` (``engine._steps`` as the dispatch was ISSUED), the
    dispatch's CLASS (the compiled
    program it ran: ``("decode", table width)``, ``("prefill", batch
    bucket, prompt bucket)``, ``("verify", width)``, ``("chunk", batch
    bucket, chunk tokens, shards)``, ``("handoff",)``; its first word is
    the row's ``kind``), four stamps of ``time.perf_counter()`` and one
    count (what the dispatch read is on its ``serve/*`` span, which
    carries the row's ``seq``). The engine reads a dispatch's tokens
    after it has issued the NEXT dispatch, and a row is written when its
    tokens arrive, so rows are in the order issued and a row's stamps
    are the host's, not the program's:

    - ``t_begin``: the first host work since the row before was done,
      admission apart (:meth:`begin`: ``serve/plan`` for a decode, the
      batch's build for a prefill, whose phase admits before it for all
      its batches; with the read deferred, the build of the dispatch
      issued while this row's program ran);
    - ``t_issued``: where the host starts to block for this row's
      tokens (:meth:`issued`; read at once: the jitted call has
      returned);
    - ``t_ready``: the host holds the result (:meth:`ready`, stamped as
      the ``serve/*/wait`` span closes; it returns the milliseconds
      since ``t_begin``, which is what the engine reports as a
      dispatch's wall time: the rows tile the host's clock, so no two
      wall times overlap);
    - ``t_done``: record and metrics for this dispatch are finished
      (:meth:`record`, which writes the row);
    - ``tokens``: what the scheduler's ``total_tokens`` grew by since
      the row before, so the rows' sum over any interval is that
      counter's difference.

    The rows cut the timeline into contiguous intervals (the row
    before's ``t_done`` to this row's), each in three legs: BEFORE (to
    ``t_issued``: the host's work since the row before: admit, plan,
    build and call of the dispatch issued meanwhile, and whatever the
    caller did between two steps), WAIT (to ``t_ready``: what the host
    truly waited) and AFTER (to ``t_done``).

    The interleaving pin of disaggregated and chunked serving reads the
    same rows as pure ordering: within every step, all decode/verify
    ordinals precede all prefill ordinals (the engine's disagg step
    runs its decode phase first; chunked prefill slips its at-most-one
    "chunk" dispatch between them, after every decode of the step).

    Preallocated columns, a ring of ``cap`` rows (several whole
    benchmark runs) with the overwritten ones counted in ``dropped``;
    written nowhere. ``profiling.spans.last_dispatch_ledger()`` finds
    the one of the engine last built or closed."""

    DECODE_KINDS = ("decode", "verify", "handoff")
    COLUMNS = ("step", "class_id", "t_begin", "t_issued", "t_ready",
               "t_done", "tokens")

    def __init__(self, cap: int = 16384, clock=time.perf_counter):
        self.cap = int(cap)
        self._clock = clock
        self.total = 0              # rows ever recorded: the next seq
        self.classes: List[Tuple] = []          # class_id -> class
        self._class_ids: Dict[Tuple, int] = {}
        self._cols = {name: [0] * self.cap for name in self.COLUMNS}
        # the open row
        self.t_begin = clock()
        self.t_issued = self.t_ready = 0.0
        self._tokens_seen = 0
        self._busy_s = 0.0

    def begin(self) -> None:
        self.t_begin = self._clock()

    def issued(self) -> None:
        self.t_issued = self._clock()

    def ready(self) -> float:
        self.t_ready = t = self._clock()
        return (t - self.t_begin) * 1e3

    def record(self, step: int, kind: str, *program,
               tokens_total: Optional[int] = None) -> None:
        now = self._clock()
        cls = (kind,) + program
        class_id = self._class_ids.get(cls)
        if class_id is None:
            class_id = self._class_ids[cls] = len(self.classes)
            self.classes.append(cls)
        if tokens_total is None:
            tokens_total = self._tokens_seen
        i, c = self.total % self.cap, self._cols
        c["step"][i] = int(step)
        c["class_id"][i] = class_id
        c["t_begin"][i] = self.t_begin
        c["t_issued"][i] = self.t_issued or now
        c["t_ready"][i] = self.t_ready or now
        c["t_done"][i] = now
        c["tokens"][i] = tokens_total - self._tokens_seen
        self._tokens_seen = tokens_total
        self._busy_s += now - self.t_begin
        self.total += 1
        self.t_begin = now
        self.t_issued = self.t_ready = 0.0

    @property
    def dropped(self) -> int:
        return max(self.total - self.cap, 0)

    def serve_seconds(self) -> float:
        """Seconds inside dispatches so far: every row's ``t_begin`` to
        ``t_done``, and the open row's ``t_begin`` to ``t_ready``."""
        if self.t_ready:
            return self._busy_s + self.t_ready - self.t_begin
        return self._busy_s

    def _kept(self, name: str) -> List:
        """One column of the kept rows, in ``seq`` order."""
        n = min(self.total, self.cap)
        cut = self.total % self.cap if self.total > self.cap else 0
        col = self._cols[name]
        return col[cut:n] + col[:cut]

    def table(self) -> Dict[str, List]:
        """The kept rows in ``seq`` order, a list a column (``seq``,
        ``kind`` and ``cls`` beside ``COLUMNS``)."""
        out = {name: self._kept(name) for name in self.COLUMNS}
        n = len(out["step"])
        out["seq"] = list(range(self.total - n, self.total))
        out["cls"] = [self.classes[k] for k in out["class_id"]]
        out["kind"] = [cls[0] for cls in out["cls"]]
        return out

    def rows(self) -> List[Tuple[int, str]]:
        return [(step, self.classes[k][0]) for step, k in zip(
            self._kept("step"), self._kept("class_id"))]

    def decode_first_fraction(self) -> Optional[float]:
        """Fraction of traced steps where every decode-phase dispatch
        precedes every prefill dispatch of the same step (1.0 = the
        never-blocked-behind-prefill pin holds; None = no step mixed
        both phases, nothing to measure)."""
        by_step: Dict[int, List[str]] = {}
        for step, kind in self.rows():
            by_step.setdefault(step, []).append(kind)
        mixed = ok = 0
        for kinds in by_step.values():
            if "prefill" not in kinds or not any(
                    k in self.DECODE_KINDS for k in kinds):
                continue
            mixed += 1
            first_prefill = kinds.index("prefill")
            if all(k == "prefill" for k in kinds[first_prefill:]):
                ok += 1
        return (ok / mixed) if mixed else None


@dataclass
class HandoffStats:
    """Rolling same-process aggregates for ``debug_state()`` (the
    event rows carry per-request detail; this is the cheap live
    view)."""
    count: int = 0
    queue_ms_sum: float = 0.0
    transfer_ms_sum: float = 0.0
    bytes_moved: int = 0
    pages_moved: int = 0

    def record(self, queue_ms: float, transfer_ms: float,
               pages: int, nbytes: int) -> None:
        self.count += 1
        self.queue_ms_sum += queue_ms
        self.transfer_ms_sum += transfer_ms
        self.pages_moved += pages
        self.bytes_moved += nbytes

    def snapshot(self) -> Dict[str, float]:
        n = max(self.count, 1)
        return {"handoffs": self.count,
                "queue_ms_mean": round(self.queue_ms_sum / n, 3),
                "transfer_ms_mean": round(self.transfer_ms_sum / n, 3),
                "pages_moved": self.pages_moved,
                "bytes_moved": self.bytes_moved}
