"""Structured trace spans and named scopes: the program's own names in
the one ``jax.profiler`` trace.

Host side, ``trace_span("serve/decode", active=3)`` is one context
manager with two sinks:
- a ``jax.profiler.TraceAnnotation`` carrying the keyword arguments —
  the span shows up inside a captured trace on the profiler's clock (the
  device planes' clock), and ``ProfileData`` gives the arguments back as
  the event's ``stats``; with no trace being taken it costs 1.9 us
  bare and 3.4 us with nine arguments (the chip's host, PR 39; a
  dispatch ledger row beside it 1.5 us); and
- a Chrome-trace JSON "complete" event into a
  :class:`ChromeTraceRecorder`, when one is attached — loadable in
  ``chrome://tracing`` / Perfetto without capturing a full XLA trace
  (host wall-clock only, no device sync: dispatch-side phase structure).

Device side, ``scope("mlp")`` is ``jax.named_scope`` held to the
registry below: the name reaches each HLO operation's ``op_name``
metadata (``jit(_micro_step)/.../transpose(jvp(mlp))/dot_general``) and
costs nothing at run time. Innermost wins; forward and backward are
told apart by the ``jvp(...)`` / ``transpose(jvp(...))`` wrappers
autodiff puts into the path.

``DEVICE_SCOPES`` and ``HOST_SPANS`` are the one registry of both kinds
of name: the program opens them, and the benchmark's reader
(``benchmarks/core/program_trace.py``) imports them to know what to
keep. docs/observability.md "Trace spans" says where each is opened.
"""

import json
import os
import threading
import time
from contextlib import contextmanager, nullcontext
from typing import List, Optional

import jax

try:
    from jax.profiler import TraceAnnotation as _annotation
except Exception:        # an odd jax profiler degrades to timing-only
    def _annotation(name, **kwargs):
        return nullcontext()

__all__ = ["ChromeTraceRecorder", "trace_span", "set_default_recorder",
           "get_default_recorder", "last_dispatch_ledger",
           "compile_ledger", "scope", "DEVICE_SCOPES", "HOST_SPANS"]

# scopes inside the compiled programs (jax.named_scope)
DEVICE_SCOPES = (
    "embed", "ln", "attn_proj", "attn_core", "kv_write", "kv_gather",
    "attn_cached", "mlp", "weight_cast", "loss_head", "lm_head", "sample",
    "grad_clip", "loss_scale", "opt_update",
    # a stack of mixed layer kinds (models/smallthinker.py): the kind of
    # the layer AROUND `attn_core`, which stays innermost and so still
    # covers the kernels of both kinds; and the routed expert layer
    "attn_window", "attn_global",
    "moe_route", "moe_dispatch", "moe_experts",
    # served hybrids (models/solar_open2.py): the delta-rule layers'
    # projections, convolution and gates, their prefill recurrence and
    # their one-token state update; the softmax layers' output gate; the
    # shared expert beside the routed ones
    "kda_proj", "kda_scan", "kda_state", "attn_gate", "moe_shared",
    # state-space mixers (models/granite_hybrid.py): projections,
    # convolution, step, gated norm; the chunked prefill recurrence; the
    # one-token state update and prefill's write of final state and tail
    "ssd_proj", "ssd_scan", "ssd_state",
    # latent attention (models/axk1.py): both query projections and the
    # norm between; W_kva, the latent's norm and the rotary key; the
    # queries carried into the latent's space and the context out of it
    # (decode); the latent rows expanded to keys and values (prefill);
    # the output projection. The reader itself stays `attn_core`, the
    # pool's write `kv_write`
    "mla_q", "mla_latent", "mla_absorb", "mla_expand", "mla_out",
    # a later CHUNK of a prompt (models/kimi_linear.py): the prefix's
    # part of its latent attention: the rows earlier chunks wrote
    # gathered through the block table, expanded, put to the flash
    # kernel a block at a time and merged into the own rows' softmax
    "mla_prefix",
    # gated short convolutions among rotary attention (models/lfm2.py):
    # a convolution layer's two products (W_in, W_out); its gates, the
    # convolution and the tail; an attention layer's norm a head and
    # rotation of queries and keys, between `attn_proj` and `attn_core`
    "conv_proj", "conv_core", "attn_norm_rope",
    # attention over a learned selection (models/keye_vl2.py,
    # ops/attention/indexed.py): the indexer's projections and its
    # scores of every token a query may see; the exact choice of the
    # best; the softmax over the chosen rows (decode: read by (page,
    # offset); a chunk: its own rows under the selection's mask); a
    # chunk's part over the rows earlier chunks wrote
    "indexer", "select", "sparse_attn", "sparse_prefix",
)

# host phase spans (TraceAnnotation), each parent before its children
HOST_SPANS = (
    "train_batch", "data", "train/dispatch", "train/tail",
    "forward", "backward", "step", "eval",
    "pipe/stack_batch", "pipe/train_batch", "pipe/eval_batch",
    "serve/admit", "serve/plan",
    "serve/prefill", "serve/prefill/build", "serve/prefill/dispatch",
    "serve/prefill/wait",
    "serve/chunk", "serve/chunk/build", "serve/chunk/dispatch",
    "serve/chunk/wait",
    "serve/verify", "serve/verify/build", "serve/verify/dispatch",
    "serve/verify/wait",
    "serve/decode", "serve/decode/build", "serve/decode/dispatch",
    "serve/decode/wait",
    "serve/record", "serve/metrics",
    # set-up (profiling/recompile.py ``setup_span``: each also a row of
    # the compile ledger): the package's import; an engine's
    # construction with its parameters' placement and cast, its cache
    # tree's or optimizer state's allocation and the building of its
    # jitted functions; warm-up; one warmed call (or the first
    # train_batch) with the program it builds
    "setup/import", "setup/engine", "setup/engine/params",
    "setup/engine/state", "setup/engine/programs",
    "setup/warmup", "setup/program",
)


def scope(name: str):
    """``jax.named_scope`` for a registered device scope; an unknown
    name is refused when the program is traced."""
    if name not in DEVICE_SCOPES:
        raise ValueError(f"device scope {name!r} is not in "
                         f"profiling.spans.DEVICE_SCOPES")
    return jax.named_scope(name)


class ChromeTraceRecorder:
    """Accumulates Chrome-trace 'X' (complete) events; ``dump(path)``
    writes the standard ``{"traceEvents": [...]}`` container.

    The buffer is bounded (``max_events``, oldest dropped first, with a
    count of what was shed) so a multi-day run cannot grow host memory
    without limit; the viewers care about the recent window anyway."""

    def __init__(self, max_events: int = 100_000):
        self.events: List[dict] = []
        self.max_events = int(max_events)
        self.dropped = 0
        self._lock = threading.Lock()
        self._origin = time.perf_counter()
        self._lanes: set = set()

    def _append(self, *evs: dict) -> None:
        """Append under the lock, then shed past ``max_events`` (oldest
        first, count kept in ``dropped``) — the one shedding policy for
        both the thread-span and lane paths."""
        with self._lock:
            self.events.extend(evs)
            if len(self.events) > self.max_events:
                shed = len(self.events) - self.max_events
                del self.events[:shed]
                self.dropped += shed

    def add(self, name: str, t0: float, t1: float, **extra) -> None:
        ev = {"name": name, "ph": "X", "cat": "deepspeed_tpu",
              "ts": (t0 - self._origin) * 1e6,       # chrome wants µs
              "dur": max(t1 - t0, 0.0) * 1e6,
              "pid": os.getpid(), "tid": threading.get_ident()}
        if extra:
            ev["args"] = extra
        self._append(ev)

    # the lane-id memo only suppresses duplicate thread_name metadata
    # rows; past this many distinct lanes it resets (a re-emitted
    # metadata row is harmless, an unbounded per-request set is a leak
    # on a long-running serving daemon)
    _LANES_CAP = 10_000

    def add_lane(self, lane: int, lane_name: str, name: str,
                 t0: float, t1: float, **extra) -> None:
        """A complete event on a NAMED virtual lane (``tid = lane``)
        instead of the calling thread — the serving tracer draws each
        request's queue_wait/prefill/decode phases on its own
        per-request lane (``lane`` = request uid, ``lane_name`` =
        "req <uid>"). The first event on a lane also emits the
        ``thread_name`` metadata row so Perfetto labels it; if the
        bounded buffer later sheds that row, the lane falls back to
        its numeric tid — cosmetic only."""
        lane = int(lane)
        ev = {"name": name, "ph": "X", "cat": "deepspeed_tpu/serve",
              "ts": (t0 - self._origin) * 1e6,
              "dur": max(t1 - t0, 0.0) * 1e6,
              "pid": os.getpid(), "tid": lane}
        if extra:
            ev["args"] = extra
        if lane not in self._lanes:
            if len(self._lanes) >= self._LANES_CAP:
                self._lanes.clear()
            self._lanes.add(lane)
            self._append(
                {"name": "thread_name", "ph": "M",
                 "pid": os.getpid(), "tid": lane,
                 "args": {"name": lane_name}}, ev)
        else:
            self._append(ev)

    def dump(self, path: str) -> str:
        with self._lock:
            payload = {"traceEvents": list(self.events),
                       "displayTimeUnit": "ms"}
            if self.dropped:
                payload["otherData"] = {
                    "dropped_events": self.dropped,
                    "note": "oldest events shed by the bounded buffer"}
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(payload, f)
        os.replace(tmp, path)  # readable mid-run, never half-written
        return path


_default_recorder: Optional[ChromeTraceRecorder] = None


def set_default_recorder(rec: Optional[ChromeTraceRecorder]) -> None:
    global _default_recorder
    _default_recorder = rec


def get_default_recorder() -> Optional[ChromeTraceRecorder]:
    return _default_recorder


# the dispatch ledger (inference/disagg.py ``DispatchTrace``) of the
# serving engine last built or closed in this process: whoever drove the
# engine can read its rows after ``close()``
_last_ledger = None


def _keep_dispatch_ledger(ledger) -> None:
    """``InferenceEngine`` hands its ledger in as it is built and again
    in ``close()``."""
    global _last_ledger
    _last_ledger = ledger


def last_dispatch_ledger():
    """The dispatch ledger of the serving engine last built or closed
    in this process, or None."""
    return _last_ledger


# the compile ledger (profiling/recompile.py ``CompileLedger``): one for
# the process, handed in as that module is imported
_compile_ledger = None


def _keep_compile_ledger(ledger) -> None:
    global _compile_ledger
    _compile_ledger = ledger


def compile_ledger():
    """The process's compile ledger: a row a program built or loaded, a
    row a ``setup/*`` span (docs/observability.md "The compile
    ledger")."""
    return _compile_ledger


@contextmanager
def trace_span(name: str, recorder: Optional[ChromeTraceRecorder] = None,
               **extra):
    """Context manager wrapping a phase in both sinks: the annotation
    (with ``extra`` as its arguments) always, the recorder's event only
    when one is attached."""
    rec = recorder if recorder is not None else _default_recorder
    t0 = time.perf_counter() if rec is not None else 0.0
    try:
        with _annotation(name, **extra):
            yield
    finally:
        if rec is not None:
            rec.add(name, t0, time.perf_counter(), **extra)
