"""Recompilation tracking for the engine's jit-compiled step functions.

Silent steady-state recompiles are the classic TPU perf killer: a shape
or dtype drift (last short batch, a python float promoted differently,
a debug flag flipping a static arg) quietly re-pays tens of seconds of
XLA compile inside what looks like a training step. The reference's
eager runtime cannot have this failure mode, so it has no analog — here
every compiled entry point is wrapped in a :class:`CompileTracker` that
counts compiles, records compile wall time, and WARNS when a function
compiles again after the run reached steady state.

Detection is exact, not heuristic: jax's jit functions expose
``_cache_size()`` (the C++ dispatch cache population); a call that grows
it compiled. A signature-set fallback covers jax builds without it.
"""

import time
from typing import Callable, Dict, List, NamedTuple, Optional

from deepspeed_tpu.utils.logging import logger

__all__ = ["CompileEvent", "CompileTracker", "TrackedFunction"]


class CompileEvent(NamedTuple):
    fn_name: str
    count: int          # 1 for the function's first compile, 2, 3, ...
    wall_ms: float      # wall time of the call that compiled (compile
                        # + first dispatch; the actionable number)
    step: int           # engine step at which it happened


def _arg_signature(args, kwargs):
    """Shape/dtype fingerprint of a call — the fallback compile detector
    when ``_cache_size`` is unavailable. Read BEFORE dispatch (donated
    buffers are gone after)."""
    import numpy as np

    def leaf_sig(x):
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            return (np.shape(x), str(x.dtype))
        return (type(x).__name__, repr(x)[:32])
    import jax
    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
    return (treedef, tuple(leaf_sig(x) for x in leaves))


class TrackedFunction:
    """Transparent wrapper over a jit-compiled callable: calls pass
    through unchanged; compiles are observed and reported to the owning
    tracker. ``lower``/other attributes forward to the wrapped function
    (the HLO-audit tests call ``.lower()`` on engine step functions)."""

    def __init__(self, fn: Callable, name: str, tracker: "CompileTracker"):
        self._fn = fn
        self._name = name
        self._tracker = tracker
        self._seen_signatures = set()
        self._has_cache_size = hasattr(fn, "_cache_size")

    def _cache_size(self) -> Optional[int]:
        if not self._has_cache_size:
            return None
        try:
            return self._fn._cache_size()
        except Exception:
            self._has_cache_size = False
            return None

    def __call__(self, *args, **kwargs):
        before = self._cache_size()
        sig = None
        if before is None:
            sig = _arg_signature(args, kwargs)
            compiled_guess = sig not in self._seen_signatures
        t0 = time.perf_counter()
        out = self._fn(*args, **kwargs)
        wall_ms = (time.perf_counter() - t0) * 1e3
        if before is not None:
            after = self._cache_size()
            compiled = after is not None and after > before
        else:
            compiled = compiled_guess
            self._seen_signatures.add(sig)
        self._tracker._record_dispatch(self._name)
        if compiled:
            self._tracker._record(self._name, wall_ms)
        return out

    def lower(self, *args, **kwargs):
        return self._fn.lower(*args, **kwargs)

    def __getattr__(self, item):
        return getattr(self._fn, item)


class CompileTracker:
    """Per-engine compile accounting.

    ``step_provider`` supplies the current host step for event
    attribution; ``warn_after`` is the step past which any re-compile of
    an already-compiled function is treated as steady-state (warned
    loudly, once per function). ``on_event`` (optional) receives each
    CompileEvent — the engine's Observer appends them to the run's
    event log.
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

    def _record(self, name: str, wall_ms: float) -> None:
        self.counts[name] = self.counts.get(name, 0) + 1
        self.compile_ms[name] = self.compile_ms.get(name, 0.0) + wall_ms
        step = int(self._step_provider())
        ev = CompileEvent(fn_name=name, count=self.counts[name],
                          wall_ms=wall_ms, step=step)
        self.events.append(ev)
        if self.counts[name] > 1 and step > self.warn_after and \
                name not in self._warned_fns:
            self._warned_fns.add(name)
            logger.warning(
                f"steady-state recompile: {name!r} compiled again at step "
                f"{step} (compile #{self.counts[name]}, "
                f"{wall_ms:.0f} ms call). A shape/dtype changed between "
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
