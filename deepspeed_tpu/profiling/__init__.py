"""Unified profiling & telemetry layer.

One opt-in config section (``observability: {}``) wires four probes
through the engine:

- **FLOPs/MFU profiler** (:mod:`.flops`): cost-analysis of the compiled
  micro-step → model FLOPs, bytes accessed, per-step MFU against a
  peak-FLOPs device registry.
- **The compile ledger** (:mod:`.recompile`): one row a program the
  process builds or loads (JAX's trace, lowering and backend seconds,
  cache hit or miss, the ``setup/*`` span it was built in); every
  compiled entry point is wrapped, compile counts/wall-times are
  recorded and steady-state recompiles (the silent TPU perf killer)
  warn loudly, with the arguments that changed.
- **HBM watermarks** (:mod:`.memory`): structured
  ``device.memory_stats()`` samples at step boundaries, with per-phase
  deltas and a run peak (host-RSS fallback on backends without
  allocator stats).
- **Trace spans** (:mod:`.spans`): ``trace_span("forward")`` shows up in
  captured XLA traces *and* in a standalone Chrome-trace JSON.

Everything lands as ``(tag, value, step)`` scalars on the monitor AND
in a crash-safe JSONL event log (``events.jsonl``) that
``tools/obs_report.py`` renders into a run summary. The x-axis is
cumulative samples, matching the reference's tensorboard convention.

:class:`Observer` is the engine-facing facade; the probe modules are
importable standalone.
"""

import os
from typing import Any, Dict, Optional, Tuple

from deepspeed_tpu.profiling.flops import (
    FlopsProfile, compute_mfu, format_profile, peak_flops_per_device,
    profile_jit_fn)
from deepspeed_tpu.profiling.memory import MemoryWatermark, memory_snapshot
from deepspeed_tpu.profiling.recompile import (CompileEvent, CompileTracker,
                                               TrackedFunction)
from deepspeed_tpu.profiling.spans import (ChromeTraceRecorder,
                                           get_default_recorder,
                                           set_default_recorder, trace_span)
from deepspeed_tpu.utils.logging import log_dist, logger

__all__ = [
    "Observer", "FlopsProfile", "CompileTracker", "CompileEvent",
    "TrackedFunction", "MemoryWatermark", "memory_snapshot",
    "ChromeTraceRecorder", "trace_span", "set_default_recorder",
    "get_default_recorder", "compute_mfu", "peak_flops_per_device",
    "profile_jit_fn",
]

# scalar tags (pinned by tests/unit/test_observability.py and consumed
# by tools/obs_report.py — change both together)
TAG_FLOPS = "Observability/flops_per_step"
TAG_BYTES = "Observability/bytes_accessed"
TAG_MFU = "Observability/mfu"
TAG_RECOMPILES = "Observability/recompiles"
TAG_COMPILE_MS = "Observability/compile_ms_total"
TAG_MEM_IN_USE = "Memory/bytes_in_use"
TAG_MEM_PEAK = "Memory/peak_bytes_in_use"
TAG_MEM_DELTA = "Memory/step_delta_bytes"
# async-pipeline host-overhead counters (docs/performance.md "Async
# step pipeline"; rendered by tools/obs_report.py)
TAG_DISPATCHES = "Observability/dispatches"       # cumulative jit calls
TAG_HOST_SYNCS = "Observability/host_syncs"       # cumulative forced syncs
TAG_HOST_GAP = "Observability/host_gap_ms"        # per-step host gap time
# serving telemetry tags, re-exported into this registry from their
# canonical home (utils/monitor.py write_serving_metrics, which writes
# them; stdlib-only tools/obs_report.py mirrors the strings and the
# pair is pinned by tests/unit/test_inference.py)
from deepspeed_tpu.utils.monitor import (  # noqa: E402,F401
    TAG_SERVE_CHUNK_DISPATCHES, TAG_SERVE_DECODE_ATTN,
    TAG_SERVE_FLEET_QDEPTH, TAG_SERVE_GOODPUT,
    TAG_SERVE_HANDOFF, TAG_SERVE_KV_PAGES, TAG_SERVE_KV_POOL_BPT,
    TAG_SERVE_MIGRATIONS, TAG_SERVE_OCCUPANCY, TAG_SERVE_PREFIX_HIT,
    TAG_SERVE_QUANT_LOGIT_ERR, TAG_SERVE_QUEUE_DEPTH,
    TAG_SERVE_QUEUE_WAIT, TAG_SERVE_REPLICA_RESTARTS,
    TAG_SERVE_SHED_RATE, TAG_SERVE_SLO, TAG_SERVE_SPEC_ACCEPT,
    TAG_SERVE_TBT, TAG_SERVE_TBT_MAX, TAG_SERVE_TOKEN_LATENCY,
    TAG_SERVE_TOKENS_IN_FLIGHT, TAG_SERVE_TPS, TAG_SERVE_TTFT,
    TAG_SERVE_WEIGHT_VERSION)
# elastic / async-checkpoint plane (ISSUE 10), same canonical-home
# arrangement (utils/monitor.py write_elastic_metrics writes them;
# obs_report mirrors; pinned by tests/unit/test_elastic.py)
from deepspeed_tpu.utils.monitor import (  # noqa: E402,F401
    TAG_CKPT_PENDING, TAG_CKPT_RESTARTS, TAG_CKPT_SNAPSHOT_MS,
    TAG_CKPT_WRITE_MS)
# health plane (ISSUE 15), same canonical-home arrangement (utils/
# health.py writes it via the monitor; obs_report mirrors; pinned by
# tests/unit/test_health.py)
from deepspeed_tpu.utils.monitor import (  # noqa: E402,F401
    TAG_HEALTH_ALERTS)


class Observer:
    """Engine-facing facade over the probes.

    Construction is cheap and always succeeds; when ``enabled`` is
    False (config off, or non-zero rank — telemetry is rank-0 like the
    monitor) every method is a no-op/passthrough, so the engine wires
    it unconditionally. Instrumentation failures degrade to warnings:
    observability must never take down a training step.
    """

    def __init__(self, cfg: Dict[str, Any], monitor=None, rank: int = 0,
                 device=None, num_devices: Optional[int] = None):
        self.cfg = cfg
        self.monitor = monitor
        self.enabled = bool(cfg.get("enabled")) and rank == 0
        self._device = device
        self._num_devices = num_devices
        self._log = None
        self.compile_tracker: Optional[CompileTracker] = None
        self.memory: Optional[MemoryWatermark] = None
        self.recorder: Optional[ChromeTraceRecorder] = None
        self.flops_profiles: Dict[str, FlopsProfile] = {}
        self._step_provider = lambda: 0
        self._closed = False
        if not self.enabled:
            return

        events_dir = cfg.get("events_dir") or "/tmp/deepspeed_tpu_obs"
        try:
            from deepspeed_tpu.utils.monitor import _JsonlWriter
            self._log = _JsonlWriter(
                events_dir, max_mb=cfg.get("events_max_mb", 0) or 0)
        except Exception as e:
            logger.warning(f"observability: event log unavailable "
                           f"({e}); scalars go to the monitor only")
        # route every monitor scalar (loss, lr, step time, comm bytes,
        # checkpoint events) into the event log too, so obs_report sees
        # one complete record even when tensorboard is off
        if self.monitor is not None and self._log is not None:
            self.monitor.mirror = self._log

        self.compile_tracker = CompileTracker(
            step_provider=lambda: self._step_provider(),
            warn_after=int(cfg.get("recompile_warn_after", 1)),
            on_event=self._on_compile_event)
        if cfg.get("memory_watermarks", True):
            self.memory = MemoryWatermark(device)
        self.recorder = ChromeTraceRecorder()
        self._chrome_path = cfg.get("chrome_trace_path") or None
        self._chrome_last_dump = 0.0  # monotonic secs; 0 = never dumped
        # the engine has no shutdown hook; close() (idempotent) seals
        # the compile summary + final chrome trace at interpreter exit
        import atexit
        atexit.register(self.close)
        log_dist(f"observability: enabled (events -> "
                 f"{os.path.join(events_dir, 'events.jsonl')})", ranks=[0])

    # ------------------------------------------------------------ sinks
    def set_step_provider(self, fn) -> None:
        """Host-step source for compile-event attribution (the engine's
        ``_host_global_step`` mirror — no device sync)."""
        self._step_provider = fn

    def scalar(self, tag: str, value, step: int) -> None:
        """One (tag, value, step) record to monitor + event log."""
        if not self.enabled:
            return
        if self.monitor is not None:
            self.monitor.write_scalar(tag, value, step)
        elif self._log is not None:
            self._log.add_scalar(tag, value, step)

    def event(self, kind: str, **fields) -> None:
        """One structured (non-scalar) event row in the JSONL log."""
        if self._log is not None:
            self._log.add_event(kind, **fields)

    def _on_compile_event(self, ev: CompileEvent) -> None:
        self.event("compile", fn=ev.fn_name, count=ev.count,
                   wall_ms=round(ev.wall_ms, 3), step=ev.step)

    def record_comm_plan(self, **plan_fields) -> None:
        """One ``comm_plan`` event row: the collective autotuner's
        decision (algo/block/hierarchy), its cost-model evidence, and
        any calibration result (runtime/comm_autotune.py) — rendered by
        tools/obs_report.py next to the per-step comm bytes so a run's
        wire numbers carry the WHY of the exchange that produced them."""
        self.event("comm_plan", **plan_fields)

    # ------------------------------------------------------------ probes
    def wrap_jit(self, fn, name: str):
        """Wrap a jit-compiled callable for compile tracking; identity
        when disabled (existing code sees the raw jit function)."""
        if not self.enabled or self.compile_tracker is None:
            return fn
        return self.compile_tracker.wrap(fn, name)

    def span(self, name: str, **extra):
        """Phase span: XLA TraceAnnotation always (near-free, shows in
        captured traces even with observability off), Chrome-trace event
        when enabled. trace_span itself never raises from
        instrumentation (one guard, at import)."""
        return trace_span(name, recorder=self.recorder, **extra)

    def wants_flops_profile(self, name: str) -> bool:
        return (self.enabled and bool(self.cfg.get("flops_profiler", True))
                and name not in self.flops_profiles)

    def maybe_profile_flops(self, name: str, fn, args: Tuple,
                            samples: int = 0) -> Optional[FlopsProfile]:
        """One-time cost-analysis of a compiled entry point (an AOT
        re-compile — opt-in cost, absorbed by the persistent compile
        cache on re-runs). Writes the FLOPs/bytes scalars and logs the
        reference-style profile block."""
        if not self.wants_flops_profile(name):
            return self.flops_profiles.get(name)
        try:
            prof = profile_jit_fn(fn, args, name=name, device=self._device,
                                  num_devices=self._num_devices)
        except Exception as e:
            logger.warning(f"observability: cost analysis of {name!r} "
                           f"failed ({e!r}); MFU will not be reported")
            # sentinel so we don't retry (and re-fail) every step
            prof = FlopsProfile(name=name, flops=0.0, bytes_accessed=0.0,
                                peak_flops_per_device=0.0, device_kind="?",
                                num_devices=0)
            self.flops_profiles[name] = prof
            return prof
        self.flops_profiles[name] = prof
        self.scalar(TAG_FLOPS, prof.flops, samples)
        self.scalar(TAG_BYTES, prof.bytes_accessed, samples)
        self.event("flops_profile", fn=name, flops=prof.flops,
                   bytes_accessed=prof.bytes_accessed,
                   peak_flops_per_device=prof.peak_flops_per_device,
                   device_kind=prof.device_kind,
                   num_devices=prof.num_devices,
                   compile_ms=round(prof.compile_ms or 0.0, 3))
        log_dist(format_profile(prof), ranks=[0])
        return prof

    # --------------------------------------------------------- per step
    def mfu(self, step_time_ms: Optional[float],
            micro_steps_per_step: int = 1,
            program: str = "micro_step") -> Optional[float]:
        """Model FLOPs utilization for one step time, from the profiled
        program, or None when either is missing. cost_analysis flops
        are PER-DEVICE (FlopsProfile docstring) so the denominator is
        the per-device peak — the ratio equals global-flops /
        all-device-peak. The engine calls this at telemetry-flush
        barriers with the window-averaged step time (per-dispatch wall
        clock is not device time once the host runs ahead of an async
        device)."""
        if not self.enabled or not step_time_ms:
            return None
        prof = (self.flops_profiles.get(program)
                or self.flops_profiles.get("micro_step"))
        if prof is None or prof.flops <= 0:
            return None
        return compute_mfu(prof.flops * max(micro_steps_per_step, 1),
                           step_time_ms / 1e3,
                           prof.peak_flops_per_device)

    def write_mfu(self, step_time_ms: Optional[float], samples: int,
                  micro_steps_per_step: int = 1,
                  program: str = "micro_step") -> Optional[float]:
        """Compute AND emit the MFU scalar for one honest step time —
        the single emission path (the engine calls it at telemetry
        flush barriers with the window-averaged time)."""
        mfu = self.mfu(step_time_ms, micro_steps_per_step, program)
        if mfu is not None:
            self.scalar(TAG_MFU, mfu, samples)
        return mfu

    def on_step(self, samples: int, step_time_ms: Optional[float],
                micro_steps_per_step: int = 1,
                program: str = "micro_step",
                host_gap_ms: Optional[float] = None,
                host_syncs: Optional[int] = None) -> None:
        """Step-boundary emission: MFU, recompile + dispatch counters,
        memory watermarks; Chrome trace refreshed on disk.
        ``micro_steps_per_step`` scales the profiled program's FLOPs up
        to the full optimizer step (gradient accumulation runs the
        compiled micro-step N times per reported step time; the fused
        ``batch_step`` program already covers the window, so its caller
        passes 1). ``program`` names the profiled entry point.
        ``host_gap_ms``/``host_syncs`` are the async-pipeline host
        overhead counters (time the host spent outside the dispatch,
        cumulative forced device syncs)."""
        if not self.enabled:
            return
        self.write_mfu(step_time_ms, samples, micro_steps_per_step,
                       program)
        if self.compile_tracker is not None:
            self.scalar(TAG_RECOMPILES, self.compile_tracker.total_compiles,
                        samples)
            self.scalar(TAG_COMPILE_MS, self.compile_tracker.total_compile_ms,
                        samples)
            self.scalar(TAG_DISPATCHES,
                        self.compile_tracker.total_dispatches, samples)
        if host_gap_ms is not None:
            self.scalar(TAG_HOST_GAP, host_gap_ms, samples)
        if host_syncs is not None:
            self.scalar(TAG_HOST_SYNCS, host_syncs, samples)
        if self.memory is not None:
            snap = self.memory.sample("step")
            if snap is not None:
                self.scalar(TAG_MEM_IN_USE, snap["bytes_in_use"], samples)
                self.scalar(TAG_MEM_PEAK, self.memory.peak_bytes, samples)
                self.scalar(TAG_MEM_DELTA, snap["delta_bytes"], samples)
        if self._chrome_path and self.recorder is not None:
            # throttled: rewriting the whole trace JSON is O(buffered
            # events) — once early (so the file exists mid-run), then at
            # most every few seconds; close() writes the final state
            import time as _time
            now = _time.monotonic()
            if self._chrome_last_dump == 0.0 or \
                    now - self._chrome_last_dump > 5.0:
                try:
                    self.recorder.dump(self._chrome_path)
                    self._chrome_last_dump = now
                except Exception:
                    pass
        if self._log is not None:
            self._log.flush()

    def close(self) -> None:
        if self._closed or not self.enabled:
            return
        self._closed = True
        # drop the atexit pin: without this, the registry (via the
        # step_provider closure) would keep the engine — and its
        # on-device state — alive for the whole process lifetime
        import atexit
        try:
            atexit.unregister(self.close)
        except Exception:
            pass
        self._step_provider = lambda: 0
        if self._chrome_path and self.recorder is not None:
            try:
                self.recorder.dump(self._chrome_path)
            except Exception:
                pass
        if self.compile_tracker is not None:
            self.event("compile_summary", **self.compile_tracker.summary())
        if self.monitor is not None and \
                getattr(self.monitor, "mirror", None) is self._log:
            self.monitor.mirror = None
        if self._log is not None:
            self._log.close()
            self._log = None
