"""The compile ledger: every program this process builds or loads is one
row, every phase of set-up one span, and the engines' recompile
accounting reads the same rows.

Silent steady-state recompiles are the classic TPU perf killer: a shape
or dtype drift (last short batch, a python float promoted differently,
a debug flag flipping a static arg) quietly re-pays tens of seconds of
XLA compile inside what looks like a training step. And a start-up is
mostly programs: traced, lowered, then compiled or loaded from the
persistent cache, one after another, while an operator waits.

JAX announces all of it through ``jax.monitoring``, in the calling
thread, inside the call that builds: a span each for the trace, the
lowering and the backend compile (``fun_name``, a duration), and inside
the backend's span whether the persistent cache was asked, whether it
answered, and what the answer saved. The listeners below fire only when
a program is built and make one row of :class:`CompileLedger` a
``backend_compile_duration`` event (the event the benchmark's own
counter counts). The ledger is process-wide and always on, a ring as the
dispatch ledger is; ``profiling.spans.compile_ledger()`` finds it.

A :class:`TrackedFunction` (the engines' jitted entry points, wrapped by
a :class:`CompileTracker`) adds what JAX cannot know: the wrap's name,
the wall time of the call that built the program, the engine's step, and
on a steady-state rebuild WHAT CHANGED between this call's arguments and
the ones the function was built for before. Detection of a tracked build
is exact, not heuristic: jax's jit functions expose ``_cache_size()``
(the C++ dispatch cache population); a call that grows it compiled, and
claims the rows its thread made since it began.

:func:`setup_span` opens a ``setup/*`` host span (``profiling.spans``:
the profiler's annotation, the Chrome recorder) and keeps it as a row
too, because no profiler trace runs during set-up; a program's row names
the innermost one open as it was built, else ``steady``.

Clocks. Every stamp is ``time.perf_counter()``, the dispatch ledger's
and the ``serve/*`` spans' clock. JAX times its spans on ``time.time()``;
that clock is not converted: a listener runs as JAX's span closes, stamps
``perf_counter()`` there as the span's end and takes the begin as the
end less JAX's duration (two clocks an offset apart drift by what NTP
slews; a duration does not).
"""

import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

import jax

from deepspeed_tpu.profiling import spans as _spans
from deepspeed_tpu.utils.logging import logger

__all__ = ["CompileEvent", "CompileLedger", "CompileTracker",
           "TrackedFunction", "setup_span"]

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
_JAX_SPANS = (TRACE, LOWER, BACKEND)
_CACHE_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
# fired as the entry is WRITTEN: a miss the next process will not repeat
_CACHE_WRITTEN = "/jax/compilation_cache/cache_misses"
_CACHE_SECONDS = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "retrieval_s",
    "/jax/compilation_cache/compile_time_saved_sec": "saved_s"}

STEADY = "steady"


class CompileEvent(NamedTuple):
    fn_name: str
    count: int          # 1 for the function's first compile, 2, 3, ...
    wall_ms: float      # wall time of the call that compiled (compile
                        # + first dispatch; the actionable number)
    step: int           # engine step at which it happened


def _arg_signature(args, kwargs) -> Dict[str, str]:
    """{argument path: "dtype[shape]"} of a call: what a steady-state
    rebuild is explained by. Computed only when a build is seen."""
    def leaf(x):
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            return f"{x.dtype}[{','.join(str(d) for d in x.shape)}]"
        return f"{type(x).__name__} {repr(x)[:32]}"
    return {root + jax.tree_util.keystr(path): leaf(x)
            for root, tree in (("args", args), ("kwargs", kwargs))
            for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]}


def _signature_diff(before: Dict[str, str],
                    after: Dict[str, str]) -> List[Tuple]:
    """(path, before, after) of every leaf that differs; None for a leaf
    only one of the calls had."""
    return [(path, before.get(path), after.get(path))
            for path in dict.fromkeys([*before, *after])
            if before.get(path) != after.get(path)]


class _Thread(threading.local):
    """What the listeners join by: JAX's events arrive in the thread
    that builds, inside the call that builds."""

    def __init__(self):
        self.depth = 0          # JAX's spans open in this thread
        self.build = {}         # the program being built: what its
        #                         events have said so far
        self.spans = []         # open setup/* spans, innermost last
        # this thread's newest rows, for the tracked call that built
        # them to claim as it returns (older ones are nobody's, or a
        # call's that raised)
        self.rows = deque(maxlen=64)


_THREAD = _Thread()


class CompileLedger:
    """One row a program built or loaded, one row a ``setup/*`` span.

    A program's row: ``seq``; ``fun_name`` (JAX's, of the backend
    event: ``jit(prefill)``); ``name`` and ``step`` (the wrap's name and
    the tracker's step where the call came through a
    :class:`TrackedFunction`, else None); ``cls`` (what the innermost
    open span carried: the dispatch ledger's class of a warmed serving
    program); ``phase`` (the innermost open ``setup/*`` span, else
    ``steady``); ``t_begin``, ``t_end`` (the building call for a tracked
    row, else the first of JAX's spans to the backend's end);
    ``trace_s``, ``lower_s``, ``backend_s``; ``cache`` (``hit``,
    ``miss``, or ``not_asked``: the cache is off or JAX refused this
    program) with ``written`` (a miss that was kept: with
    ``jax_persistent_cache_min_compile_time_secs`` above a program's
    compile time it misses in every process), ``retrieval_s`` and JAX's
    own ``saved_s`` on a hit; ``call_s`` (the wall time of the tracked
    call, else None); ``changed`` (a tracked ``steady`` row: ``(path,
    before, after)`` of every argument leaf that differs from the call
    the function was built for before).

    Only the outermost of JAX's nested trace spans is kept (an inner
    ``jax.jit`` traced inside it is its time). A program built INSIDE
    another's trace (``ensure_compile_time_eval``) has its own row with
    the backend's time alone; the outer row's ``trace_s`` holds it too.

    Rings of ``cap`` rows, the overwritten ones counted in ``dropped``;
    written nowhere.
    """

    def __init__(self, cap: int = 4096):
        self.cap = int(cap)
        self.total = 0                          # programs ever: next seq
        self._programs: deque = deque(maxlen=self.cap)
        self._spans: deque = deque(maxlen=self.cap)
        self._lock = threading.Lock()
        self._warned = False

    @property
    def dropped(self) -> int:
        return max(self.total - self.cap, 0)

    def table(self) -> Dict[str, List[dict]]:
        """``{"programs": [...], "spans": [...]}``: copies of the kept
        rows, programs by ``seq``, spans as they closed."""
        with self._lock:
            return {"programs": [dict(r) for r in self._programs],
                    "spans": [dict(r) for r in self._spans]}

    # ------------------------------------------------- JAX's listeners
    def listen(self) -> None:
        jax.monitoring.register_scalar_listener(self._span_opens)
        jax.monitoring.register_event_duration_secs_listener(
            self._span_closes)
        jax.monitoring.register_event_listener(self._cache_says)

    @staticmethod
    def _span_opens(event, value, **kw):
        if event in _JAX_SPANS:
            thread = _THREAD
            thread.depth += 1
            if thread.depth == 1 and (
                    event == TRACE or
                    event == LOWER and LOWER in thread.build):
                # a new program begins: what a trace, or a trace and a
                # lowering, that was never compiled (eval_shape,
                # lower()) left behind is dropped. A program whose
                # jaxpr pjit had kept begins at its lowering
                thread.build = {}

    def _span_closes(self, event, duration, **kw):
        if event in _JAX_SPANS:
            thread = _THREAD
            now = time.perf_counter()
            thread.depth = max(thread.depth - 1, 0)
            if event == BACKEND:
                try:
                    self._built(thread, now, duration, kw.get("fun_name"))
                except Exception:
                    # this runs inside JAX's compile: telemetry must
                    # never break a build
                    self._lost()
            elif thread.depth == 0:
                thread.build[event] = (now - duration, now)
        elif event in _CACHE_SECONDS:
            _THREAD.build[_CACHE_SECONDS[event]] = duration

    @staticmethod
    def _cache_says(event, **kw):
        if event == _CACHE_ASKED:
            _THREAD.build["cache"] = "miss"
        elif event == _CACHE_HIT:
            _THREAD.build["cache"] = "hit"
        elif event == _CACHE_WRITTEN:
            _THREAD.build["written"] = True

    def _built(self, thread, now, backend_s, fun_name) -> None:
        """The backend's span closed: one row."""
        build = thread.build
        nested = thread.depth > 0
        t_trace, t_lower = (None, None) if nested else (
            build.get(TRACE), build.get(LOWER))
        span = thread.spans[-1] if thread.spans else None
        row = {
            "seq": 0, "fun_name": fun_name, "name": None, "step": None,
            "cls": span["cls"] if span is not None else None,
            "phase": span["name"] if span is not None else STEADY,
            "t_begin": min(t[0] for t in (t_trace, t_lower,
                                          (now - backend_s,)) if t),
            "t_end": now,
            "trace_s": t_trace[1] - t_trace[0] if t_trace else 0.0,
            "lower_s": t_lower[1] - t_lower[0] if t_lower else 0.0,
            "backend_s": backend_s,
            "cache": build.get("cache", "not_asked"),
            "written": build.get("written", False),
            "retrieval_s": build.get("retrieval_s"),
            "saved_s": build.get("saved_s"),
            "call_s": None, "changed": None}
        if nested:
            # the outer program's trace goes on: only the cache's words
            # were this program's
            for key in ("cache", "written", "retrieval_s", "saved_s"):
                build.pop(key, None)
        else:
            thread.build = {}
        thread.rows.append(row)
        with self._lock:
            row["seq"] = self.total
            self.total += 1
            self._programs.append(row)

    def _lost(self) -> None:
        if not self._warned:
            self._warned = True
            logger.warning("compile ledger: a row was lost", exc_info=True)

    def _keep_span(self, row: dict) -> None:
        with self._lock:
            self._spans.append(row)


_LEDGER = CompileLedger()
_LEDGER.listen()
_spans._keep_compile_ledger(_LEDGER)


@contextmanager
def setup_span(name: str, cls: Optional[Tuple] = None,
               t0: Optional[float] = None):
    """A ``setup/*`` phase: :func:`profiling.spans.trace_span` (so a
    trace taken over set-up shows it on the profiler's clock) and a row
    of the compile ledger (``name``, ``t0``, ``t1``, ``parent``,
    ``cls``). Programs built inside name it as their phase and take its
    ``cls``. ``t0`` backdates the row (the package's import began before
    this module could be imported)."""
    if name not in _spans.HOST_SPANS or not name.startswith("setup/"):
        raise ValueError(f"{name!r} is not a setup/* span of "
                         f"profiling.spans.HOST_SPANS")
    thread = _THREAD
    row = {"name": name,
           "t0": time.perf_counter() if t0 is None else t0, "t1": None,
           "parent": thread.spans[-1]["name"] if thread.spans else None,
           "cls": cls}
    thread.spans.append(row)
    args = {"cls": " ".join(str(c) for c in cls)} if cls else {}
    try:
        with _spans.trace_span(name, **args):
            yield row
    finally:
        thread.spans.pop()
        row["t1"] = time.perf_counter()
        _LEDGER._keep_span(row)


class TrackedFunction:
    """Transparent wrapper over a jit-compiled callable: calls pass
    through unchanged; compiles are observed and reported to the owning
    tracker. ``lower``/other attributes forward to the wrapped function
    (the HLO-audit tests call ``.lower()`` on engine step functions)."""

    def __init__(self, fn: Callable, name: str, tracker: "CompileTracker"):
        self._fn = fn
        self._name = name
        self._tracker = tracker
        fn._cache_size          # a jit function: refused here if not
        self._signature: Optional[Dict[str, str]] = None

    def __call__(self, *args, **kwargs):
        # nothing here but what a dispatch needs (the jit's bound
        # `_cache_size` is looked up a call and never kept: jaxlib's
        # bound-method object is invisible to the cycle collector, and
        # an engine that held one would never be freed)
        fn = self._fn
        before = fn._cache_size()
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        t1 = time.perf_counter()
        self._tracker._record_dispatch(self._name)
        if fn._cache_size() > before:
            self._close_build(t0, t1, args, kwargs)
        return out

    def _close_build(self, t0: float, t1: float, args, kwargs) -> None:
        """The call that built has returned: the tracker counts it, with
        what changed where the ledger can say."""
        changed = None
        try:
            changed = self._claim(t0, t1, args, kwargs)
        except Exception:
            _LEDGER._lost()     # telemetry must never break the step
        self._tracker._record(self._name, (t1 - t0) * 1e3, changed)

    def _claim(self, t0: float, t1: float, args, kwargs):
        """The rows this thread made since the call began are the
        call's: they take its name, its stamps, the step and, outside
        set-up, what changed (returned). A donated argument is gone by
        now, but its shape and dtype are its aval's and stay readable."""
        made, rows = _THREAD.rows, []
        while made and made[-1]["t_end"] >= t0:
            rows.append(made.pop())
        signature = _arg_signature(args, kwargs)
        changed = None
        if self._signature is not None and any(
                row["phase"] == STEADY for row in rows):
            changed = _signature_diff(self._signature, signature)
        self._signature = signature
        step = int(self._tracker._step_provider())
        for row in rows:
            row["name"], row["step"] = self._name, step
            row["t_begin"], row["t_end"] = t0, t1
            row["call_s"] = t1 - t0
            if row["phase"] == STEADY:
                row["changed"] = changed
        return changed

    def lower(self, *args, **kwargs):
        return self._fn.lower(*args, **kwargs)

    def __getattr__(self, item):
        return getattr(self._fn, item)


class CompileTracker:
    """Per-engine compile accounting over the process's ledger.

    ``step_provider`` supplies the current host step for event
    attribution; ``warn_after`` is the step past which any re-compile of
    an already-compiled function is treated as steady-state (warned
    loudly, once per function, with the arguments that changed).
    ``on_event`` (optional) receives each CompileEvent — the engine's
    Observer appends them to the run's event log.
    """

    def __init__(self, step_provider: Optional[Callable[[], int]] = None,
                 warn_after: int = 1,
                 on_event: Optional[Callable[[CompileEvent], None]] = None):
        self._step_provider = step_provider or (lambda: 0)
        self.warn_after = int(warn_after)
        self.on_event = on_event
        self.counts: Dict[str, int] = {}
        self.compile_ms: Dict[str, float] = {}
        # every CALL of a wrapped function, compiled or cached — the
        # host-dispatch accounting the dispatch-count tests pin (one
        # batch_step dispatch per train_batch on the fused path)
        self.dispatch_counts: Dict[str, int] = {}
        self.events: List[CompileEvent] = []
        self._warned_fns = set()

    def wrap(self, fn: Callable, name: str) -> TrackedFunction:
        return TrackedFunction(fn, name, self)

    @property
    def total_compiles(self) -> int:
        return sum(self.counts.values())

    @property
    def total_dispatches(self) -> int:
        return sum(self.dispatch_counts.values())

    def _record_dispatch(self, name: str) -> None:
        self.dispatch_counts[name] = self.dispatch_counts.get(name, 0) + 1

    @property
    def total_compile_ms(self) -> float:
        return sum(self.compile_ms.values())

    def _record(self, name: str, wall_ms: float, changed=None) -> None:
        self.counts[name] = self.counts.get(name, 0) + 1
        self.compile_ms[name] = self.compile_ms.get(name, 0.0) + wall_ms
        step = int(self._step_provider())
        ev = CompileEvent(fn_name=name, count=self.counts[name],
                          wall_ms=wall_ms, step=step)
        self.events.append(ev)
        if self.counts[name] > 1 and step > self.warn_after and \
                name not in self._warned_fns:
            self._warned_fns.add(name)
            what = "; ".join(f"{path}: {was} -> {now}"
                             for path, was, now in (changed or [])[:4])
            logger.warning(
                f"steady-state recompile: {name!r} compiled again at step "
                f"{step} (compile #{self.counts[name]}, "
                f"{wall_ms:.0f} ms call"
                + (f"; changed {what}" if what else "")
                + "). A shape/dtype changed between "
                "steps — on TPU this silently re-pays full XLA "
                "compilation per occurrence; pin batch shapes (drop the "
                "last short batch) or pad to a fixed bucket.")
        if self.on_event is not None:
            try:
                self.on_event(ev)
            except Exception:
                pass  # telemetry must never break the step

    def summary(self) -> dict:
        return {
            "total_compiles": self.total_compiles,
            "total_compile_ms": round(self.total_compile_ms, 3),
            "total_dispatches": self.total_dispatches,
            "per_fn": {n: {"count": c,
                           "wall_ms": round(self.compile_ms.get(n, 0.0), 3),
                           "dispatches": self.dispatch_counts.get(n, 0)}
                       for n, c in sorted(self.counts.items())},
        }
