"""Training metrics monitor (tensorboard).

Reference: the engine's tensorboardX integration
(``deepspeed/runtime/engine.py:14,151-156,780-790,922-936``): rank 0 writes
``Train/Samples/train_loss``, ``Train/Samples/lr``,
``Train/Samples/loss_scale`` and per-timer scalars under
``Train/Samples/<timer>``.

TPU build: ``torch.utils.tensorboard`` (torch-cpu is in the image) when
available; otherwise a JSONL event log with the same (tag, value, step)
records so metrics are never silently dropped. Construction mirrors the
reference's ``get_summary_writer`` naming scheme
(``<base>/<job_name>_<host>`` under ``DLWS_JOB_ID``/``DLTS_JOB_ID`` when
set).
"""

import json
import math
import os
import socket
import time
from typing import Dict, Optional

from deepspeed_tpu.utils.logging import logger

__all__ = ["TensorBoardMonitor", "get_summary_writer", "Histogram"]

# serving telemetry tags (written by write_serving_metrics for the
# inference engine; x-axis = cumulative generated tokens). Canonical
# home — profiling/__init__.py re-exports them into its tag registry;
# stdlib-only tools/obs_report.py mirrors the strings (pinned together
# by tests/unit/test_inference.py).
TAG_SERVE_TTFT = "Serve/ttft_ms"                    # per admitted request
TAG_SERVE_TOKEN_LATENCY = "Serve/token_latency_ms"  # per decode dispatch
TAG_SERVE_TPS = "Serve/tokens_per_sec"              # cumulative rate
TAG_SERVE_QUEUE_DEPTH = "Serve/queue_depth"         # waiting requests
TAG_SERVE_OCCUPANCY = "Serve/batch_occupancy"       # active / total slots
TAG_SERVE_KV_PAGES = "Serve/kv_pages_in_use"        # paged pool occupancy
TAG_SERVE_TOKENS_IN_FLIGHT = "Serve/tokens_in_flight"  # live cache tokens
TAG_SERVE_PREFIX_HIT = "Serve/prefix_hit_rate"      # prompt tokens reused
TAG_SERVE_DECODE_ATTN = "Serve/decode_attn_path"    # 1 = pallas paged
#                                                     kernel, 0 = gather
# request-granular serving plane (ISSUE 9): latency decomposition +
# SLO/goodput accounting (inference/tracing.py ServeTracer)
TAG_SERVE_QUEUE_WAIT = "Serve/queue_wait_ms"        # per admitted request
TAG_SERVE_TBT = "Serve/tbt_ms"                      # per decode dispatch
#                                  (mean per-request time-between-tokens)
TAG_SERVE_SLO = "Serve/slo_attainment"              # finished-in-SLO frac
TAG_SERVE_GOODPUT = "Serve/goodput_tokens_per_s"    # within-SLO tokens/s
# disagg + speculative decoding plane (ISSUE 13): draft acceptance per
# verify dispatch and the prefill->decode handoff leg of TTFT
TAG_SERVE_SPEC_ACCEPT = "Serve/spec_accept_rate"    # accepted/proposed
#                                                     per verify dispatch
TAG_SERVE_HANDOFF = "Serve/handoff_ms"              # per claimed handoff
#                                                     (queue + transfer)
# fleet plane (ISSUE 14): the multi-replica router's shed ladder,
# aggregate queue, and live-weight-swap stamp (inference/fleet.py)
TAG_SERVE_SHED_RATE = "Serve/shed_rate"             # shed / submitted
TAG_SERVE_FLEET_QDEPTH = "Serve/fleet_queue_depth"  # sum of replica queues
TAG_SERVE_WEIGHT_VERSION = "Serve/weight_version"   # committed swap
#                                                     ordinal (0 = boot)
# process-fleet plane (ISSUE 16): live KV-page migrations between
# replicas and supervised child relaunches (inference/fleet.py)
TAG_SERVE_MIGRATIONS = "Serve/migrations"           # live requests moved
TAG_SERVE_REPLICA_RESTARTS = "Serve/replica_restarts"  # supervised
# quantized-serving plane (ISSUE 17): static pool cost per token of KV
# capacity (int8 pools land near half the bf16 figure) and the offline
# quantized-vs-fp-oracle max logit error probe (engine.
# record_quant_logit_err — the serving path never pays for the oracle)
TAG_SERVE_KV_POOL_BPT = "Serve/kv_pool_bytes_per_token"
TAG_SERVE_QUANT_LOGIT_ERR = "Serve/quant_logit_err"
# chunked-prefill plane (ISSUE 19): long prompts land as fixed-size
# chunk dispatches interleaved with decode — the dispatch counter plus
# the per-step WORST time-between-tokens (the bound chunking pins; the
# mean alone would hide a whole-prompt prefill stall)
TAG_SERVE_CHUNK_DISPATCHES = "Serve/chunk_dispatches"  # cumulative
TAG_SERVE_TBT_MAX = "Serve/tbt_max_ms"              # per decode dispatch
# elastic / async-checkpoint plane (ISSUE 10): snapshot-vs-write split
# of every save, the async writer's backlog, and how many times the
# supervisor has relaunched this run. Canonical home — profiling/
# __init__.py re-exports them; tools/obs_report.py mirrors the strings
# (pinned together by tests/unit/test_elastic.py).
TAG_CKPT_SNAPSHOT_MS = "Checkpoint/snapshot_ms"     # device->host copy
TAG_CKPT_WRITE_MS = "Checkpoint/write_ms"           # stage/commit protocol
TAG_CKPT_PENDING = "Checkpoint/pending_saves"       # async writer backlog
TAG_CKPT_RESTARTS = "Checkpoint/restarts"           # supervisor relaunches
# health plane (ISSUE 15): cumulative numeric-anomaly alert count from
# utils/health.py's detectors (nan_loss / loss_spike / ... — the pinned
# HEALTH_REASONS vocabulary rides in the per-alert "health" event rows).
# Canonical home — profiling/__init__.py re-exports it; tools/
# obs_report.py mirrors the string (pinned by tests/unit/test_health.py).
TAG_HEALTH_ALERTS = "Health/alerts"                 # cumulative alerts


class Histogram:
    """Bounded log-bucketed latency histogram (the serving-plane
    percentile sink).

    Last-value scalars can't answer "what was p99 TTFT" without keeping
    every sample; this keeps geometrically-spaced buckets instead —
    memory is bounded by the value range (``O(decades x
    bins_per_decade)`` integer counts, ~300 entries for ns..hours at
    the default resolution), so a serving daemon can record millions of
    requests without growing the host heap. Percentiles are
    approximate: relative error is one bucket width
    (``10^(1/bins_per_decade)`` — ~7.5% at the default 32/decade),
    which is telemetry-grade, not benchmark-grade. Exact ``min``,
    ``max``, ``count`` and ``sum`` ride along for free.
    """

    def __init__(self, bins_per_decade: int = 32, floor: float = 1e-3):
        self.bins_per_decade = int(bins_per_decade)
        self.floor = float(floor)       # values below land in bucket 0
        self._buckets: Dict[int, int] = {}
        self.count = 0
        self.sum = 0.0
        self.min: Optional[float] = None
        self.max: Optional[float] = None

    def _bucket(self, v: float) -> int:
        if v <= self.floor:
            return 0
        return 1 + int(math.log10(v / self.floor) * self.bins_per_decade)

    def _bucket_value(self, b: int) -> float:
        if b == 0:
            return self.floor
        # geometric midpoint of the bucket's span
        return self.floor * 10.0 ** ((b - 0.5) / self.bins_per_decade)

    def record(self, v) -> None:
        v = float(v)
        if not math.isfinite(v):
            return
        b = self._bucket(v)
        self._buckets[b] = self._buckets.get(b, 0) + 1
        self.count += 1
        self.sum += v
        self.min = v if self.min is None else min(self.min, v)
        self.max = v if self.max is None else max(self.max, v)

    def record_many(self, values) -> None:
        """``record`` for each of ``values`` in order, the same counts
        and sums to the bit: one call a decode step instead of one a
        token (256 slots: the per-token calls were a fifth of the
        host's section of a step)."""
        buckets, floor, bins = self._buckets, self.floor, self.bins_per_decade
        isfinite, log10 = math.isfinite, math.log10
        count, total, lo, hi = self.count, self.sum, self.min, self.max
        last = b = None
        for v in values:
            v = float(v)
            if v != last:       # a step's samples are mostly one value
                if not isfinite(v):
                    continue
                last = v
                b = 0 if v <= floor else 1 + int(log10(v / floor) * bins)
            buckets[b] = buckets.get(b, 0) + 1
            count += 1
            total += v
            if lo is None or v < lo:
                lo = v
            if hi is None or v > hi:
                hi = v
        self.count, self.sum, self.min, self.max = count, total, lo, hi

    @property
    def mean(self) -> Optional[float]:
        return self.sum / self.count if self.count else None

    def percentile(self, q: float) -> Optional[float]:
        """Approximate q-quantile (q in [0, 1]); exact at the
        extremes (q=0 -> min, q=1 -> max)."""
        if not self.count:
            return None
        if q <= 0:
            return self.min
        if q >= 1:
            return self.max
        rank = q * (self.count - 1)
        seen = 0
        for b in sorted(self._buckets):
            seen += self._buckets[b]
            if seen > rank:
                # clamp the bucket estimate into the exact bounds
                return min(max(self._bucket_value(b), self.min), self.max)
        return self.max

    def snapshot(self) -> dict:
        """The report-facing summary (rounded; JSON-friendly)."""
        r = (lambda v: round(v, 3) if v is not None else None)
        return {"count": self.count, "mean": r(self.mean),
                "p50": r(self.percentile(0.50)),
                "p95": r(self.percentile(0.95)),
                "p99": r(self.percentile(0.99)),
                "min": r(self.min), "max": r(self.max)}


class _JsonlWriter:
    """Fallback SummaryWriter look-alike: one JSON object per scalar.

    Crash-safe by construction: the file is opened line-buffered, so
    every record hits the OS the moment it is written — a preempted run
    loses at most the line being formatted, never a buffered backlog.
    Also usable as a context manager, and the fd is reclaimed on GC
    (``__del__``) so abandoned writers don't leak descriptors.

    Schema (pinned by tests/unit/test_monitor.py; tools/obs_report.py
    relies on it): scalar rows are ``{"tag": str, "value": float,
    "step": int}``; structured rows carry ``{"event": str, ...}``.

    ``max_mb`` > 0 turns on size-based rotation: when the live file
    exceeds the limit it is atomically renamed to
    ``events.jsonl.<seq>`` (``os.replace`` — a crash mid-rollover
    leaves either the old name or the new, never a torn file) and a
    fresh ``events.jsonl`` opens, so a long serving run's event log is
    bounded per segment instead of growing without limit.
    ``tools/obs_report.py`` reads the rotated segments back in
    sequence order before the live file.
    """

    def __init__(self, log_dir: str, max_mb: float = 0.0):
        os.makedirs(log_dir, exist_ok=True)
        self.path = os.path.join(log_dir, "events.jsonl")
        self.max_bytes = int(float(max_mb or 0.0) * 2 ** 20)
        self._seq = 1 + max(
            (int(n.rsplit(".", 1)[1])
             for n in os.listdir(log_dir)
             if n.startswith("events.jsonl.")
             and n.rsplit(".", 1)[1].isdigit()), default=0)
        self._open()

    def _open(self):
        self._f = open(self.path, "a", buffering=1)
        self._bytes = self._f.tell()        # append mode: current size

    def _write_line(self, line: str):
        self._f.write(line)
        self._bytes += len(line)
        if self.max_bytes and self._bytes >= self.max_bytes:
            self._rotate()

    def _rotate(self):
        self._f.close()
        os.replace(self.path, f"{self.path}.{self._seq}")
        self._seq += 1
        self._open()

    def add_scalar(self, tag, value, step):
        if self._f is None:
            return
        self._write_line(json.dumps(
            {"tag": str(tag), "value": float(value), "step": int(step)})
            + "\n")

    def add_event(self, kind, **fields):
        """One structured (non-scalar) record, e.g. a compile event.

        Every row is stamped with ``t`` — wall-clock epoch seconds —
        unless the caller supplied one. Event rows are the only record
        the fleet merger (``obs_report --fleet``) can align across
        process boundaries, and alignment needs a shared-epoch clock
        plus the per-replica ``clock_sync`` offsets; ``time.time()`` is
        that clock. Host-side only — never a device sync."""
        if self._f is None:
            return
        row = {"event": str(kind)}
        row.update(fields)
        row.setdefault("t", round(time.time(), 6))
        self._write_line(json.dumps(row, default=str) + "\n")

    def flush(self):
        if self._f is not None:
            self._f.flush()

    def close(self):
        if self._f is not None:
            self._f.close()
            self._f = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


def _make_writer(log_dir: str):
    """torch SummaryWriter, or the JSONL fallback when unavailable."""
    try:
        from torch.utils.tensorboard import SummaryWriter
        return SummaryWriter(log_dir=log_dir)
    except Exception as e:
        logger.warning(f"tensorboard unavailable ({e}); falling back to "
                       f"JSONL event log in {log_dir}")
        return _JsonlWriter(log_dir)


def get_summary_writer(name: str = "DeepSpeedTPUJobName",
                       base: str = os.path.join(os.path.expanduser("~"),
                                                "tensorboard")):
    """(reference ``engine.py:246-254``) Build a SummaryWriter under
    ``<base>/<infra job id>/logs/<name>_<host>``."""
    if "DLWS_JOB_ID" in os.environ:
        infra_job_id = os.environ["DLWS_JOB_ID"]
    elif "DLTS_JOB_ID" in os.environ:
        infra_job_id = os.environ["DLTS_JOB_ID"]
    else:
        infra_job_id = "unknown-job-id"
    summary_writer_dir_name = os.path.join(infra_job_id, "logs")
    return _make_writer(os.path.join(base, summary_writer_dir_name,
                                     name + "_" + socket.gethostname()))


class TensorBoardMonitor:
    """Engine-facing wrapper: no-ops unless enabled and on rank 0.

    ``mirror`` (optional, set by the observability layer) receives a
    copy of every scalar — typically a :class:`_JsonlWriter` — so one
    crash-safe ``events.jsonl`` records the full run even when the
    tensorboard writer is the binary torch one (or disabled entirely).
    """

    def __init__(self, enabled: bool, output_path: Optional[str] = None,
                 job_name: Optional[str] = None, rank: int = 0):
        self.enabled = bool(enabled) and rank == 0
        self.writer = None
        self.mirror = None
        if self.enabled:
            if output_path:
                self.writer = _make_writer(os.path.join(
                    output_path, job_name or "DeepSpeedTPUJobName"))
            else:
                self.writer = get_summary_writer(
                    name=job_name or "DeepSpeedTPUJobName")

    def _writes(self) -> bool:
        return self.writer is not None or self.mirror is not None

    def write_scalar(self, tag: str, value, step: int):
        if self.writer is not None:
            self.writer.add_scalar(tag, float(value), int(step))
        if self.mirror is not None:
            self.mirror.add_scalar(tag, float(value), int(step))

    def write_train_metrics(self, *, loss=None, lr=None, loss_scale=None,
                            samples: int = 0, flush: bool = True):
        """The reference's per-step scalars (engine.py:780-790, 922-936):
        x-axis is cumulative sample count. ``flush=False`` lets the
        engine's deferred-telemetry ring write a whole window of
        records and flush once at the end."""
        if not self._writes():
            return
        if loss is not None:
            self.write_scalar("Train/Samples/train_loss", loss, samples)
        if lr is not None:
            self.write_scalar("Train/Samples/lr", lr, samples)
        if loss_scale is not None:
            self.write_scalar("Train/Samples/loss_scale", loss_scale,
                              samples)
        if flush:
            self.flush()

    def write_checkpoint_event(self, *, action: str, ok: bool = True,
                               duration_ms=None, samples: int = 0):
        """Checkpoint durability telemetry: ``save``/``load`` durations and
        ``fallback`` events (a tag skipped as uncommitted or corrupt), so
        preemption recovery is visible on the same samples x-axis as loss."""
        if not self._writes():
            return
        if duration_ms is not None:
            self.write_scalar(f"Train/Samples/checkpoint_{action}_ms",
                              duration_ms, samples)
        self.write_scalar(f"Train/Samples/checkpoint_{action}_ok",
                          1.0 if ok else 0.0, samples)
        self.flush()

    def write_elastic_metrics(self, *, snapshot_ms=None, write_ms=None,
                              pending_saves=None, restarts=None,
                              samples: int = 0, flush: bool = True):
        """Elastic-resilience telemetry (ISSUE 10): the snapshot-vs-write
        decomposition of each save (the snapshot is the only part the
        step loop waits for under ``checkpoint.async_save``), the async
        writer's backlog, and the supervisor restart count of this
        incarnation — all on the samples x-axis, so a preemption storm
        is visible right next to the loss curve. ``write_ms`` rows may
        be emitted from the background writer thread (one line-buffered
        write; safe under the GIL)."""
        if not self._writes():
            return
        if snapshot_ms is not None:
            self.write_scalar(TAG_CKPT_SNAPSHOT_MS, snapshot_ms, samples)
        if write_ms is not None:
            self.write_scalar(TAG_CKPT_WRITE_MS, write_ms, samples)
        if pending_saves is not None:
            self.write_scalar(TAG_CKPT_PENDING, pending_saves, samples)
        if restarts is not None:
            self.write_scalar(TAG_CKPT_RESTARTS, restarts, samples)
        if flush:
            self.flush()

    def write_comm_metrics(self, *, bytes_per_step=None,
                           compression_ratio=None, samples: int = 0,
                           mode: Optional[str] = None):
        """Per-step data-parallel communication telemetry (TPU-native
        extension): modeled wire bytes per rank per optimizer step and
        the compression ratio vs a dense fp32 ring allreduce — so a
        quantized_comm config change shows up on the same samples x-axis
        as loss/throughput. ``mode`` tags WHICH exchange produced the
        bytes (e.g. ``"hierarchical-twohop+overlap"``; the comm
        autotuner's choice): strings can't ride the scalar stream, so a
        ``comm_mode`` event row lands in the mirror log whenever the
        mode changes — obs_report shows it per run."""
        if not self._writes():
            return
        if bytes_per_step is not None:
            self.write_scalar("Train/Samples/comm_bytes_per_step",
                              bytes_per_step, samples)
        if compression_ratio is not None:
            self.write_scalar("Train/Samples/comm_compression_ratio",
                              compression_ratio, samples)
        if mode is not None and \
                mode != getattr(self, "_last_comm_mode", None):
            self._last_comm_mode = mode
            if self.mirror is not None:
                self.mirror.add_event("comm_mode", mode=str(mode),
                                      step=int(samples))
        # like every other write_* method: without the flush, comm
        # telemetry buffered in the writer is lost on crash/preemption
        self.flush()

    def write_serving_metrics(self, *, ttft_ms=None, token_latency_ms=None,
                              tokens_per_sec=None, queue_depth=None,
                              batch_occupancy=None, kv_pages_in_use=None,
                              tokens_in_flight=None, prefix_hit_rate=None,
                              decode_attn_path=None, queue_wait_ms=None,
                              tbt_ms=None, slo_attainment=None,
                              goodput_tokens_per_s=None,
                              spec_accept_rate=None, handoff_ms=None,
                              shed_rate=None, fleet_queue_depth=None,
                              weight_version=None, migrations=None,
                              replica_restarts=None,
                              kv_pool_bytes_per_token=None,
                              quant_logit_err=None,
                              chunk_dispatches=None, tbt_max_ms=None,
                              tokens: int = 0, flush: bool = True):
        """Serving telemetry (inference engine; TPU-native extension —
        the reference snapshot is training-only): time-to-first-token
        per admitted request, per-decode-step token latency, cumulative
        tokens/s, request-queue depth and decode-slot occupancy, plus
        the paged-cache view (pool pages in use, live cache tokens in
        flight, prefix-cache hit rate over prompt tokens, and WHICH
        decode attention ran — 1.0 = fused Pallas paged kernel, 0.0 =
        the gather fallback, so a silent fallback is visible in run
        reports; the engine also logs a ``decode_attn_path`` event row
        with the reason, mirroring the comm autotuner's
        which-exchange-compiled telemetry). The request-granular plane
        (inference/tracing.py) adds the latency decomposition and SLO
        view: queue wait per admitted request, mean per-request
        time-between-tokens per decode dispatch, the fraction of
        finished requests that met the configured SLO, and the
        within-SLO token rate — so throughput and *goodput* are
        distinct numbers. The x-axis is cumulative generated tokens
        (the serving analog of the training samples axis). Tags are
        pinned by tests/unit/test_inference.py and rendered by
        tools/obs_report.py's serving section."""
        if not self._writes():
            return
        if ttft_ms is not None:
            self.write_scalar(TAG_SERVE_TTFT, ttft_ms, tokens)
        if token_latency_ms is not None:
            self.write_scalar(TAG_SERVE_TOKEN_LATENCY, token_latency_ms,
                              tokens)
        if tokens_per_sec is not None:
            self.write_scalar(TAG_SERVE_TPS, tokens_per_sec, tokens)
        if queue_depth is not None:
            self.write_scalar(TAG_SERVE_QUEUE_DEPTH, queue_depth, tokens)
        if batch_occupancy is not None:
            self.write_scalar(TAG_SERVE_OCCUPANCY, batch_occupancy,
                              tokens)
        if kv_pages_in_use is not None:
            self.write_scalar(TAG_SERVE_KV_PAGES, kv_pages_in_use, tokens)
        if tokens_in_flight is not None:
            self.write_scalar(TAG_SERVE_TOKENS_IN_FLIGHT,
                              tokens_in_flight, tokens)
        if prefix_hit_rate is not None:
            self.write_scalar(TAG_SERVE_PREFIX_HIT, prefix_hit_rate,
                              tokens)
        if decode_attn_path is not None:
            self.write_scalar(TAG_SERVE_DECODE_ATTN, decode_attn_path,
                              tokens)
        if queue_wait_ms is not None:
            self.write_scalar(TAG_SERVE_QUEUE_WAIT, queue_wait_ms, tokens)
        if tbt_ms is not None:
            self.write_scalar(TAG_SERVE_TBT, tbt_ms, tokens)
        if tbt_max_ms is not None:
            self.write_scalar(TAG_SERVE_TBT_MAX, tbt_max_ms, tokens)
        if chunk_dispatches is not None:
            self.write_scalar(TAG_SERVE_CHUNK_DISPATCHES,
                              chunk_dispatches, tokens)
        if slo_attainment is not None:
            self.write_scalar(TAG_SERVE_SLO, slo_attainment, tokens)
        if goodput_tokens_per_s is not None:
            self.write_scalar(TAG_SERVE_GOODPUT, goodput_tokens_per_s,
                              tokens)
        if spec_accept_rate is not None:
            self.write_scalar(TAG_SERVE_SPEC_ACCEPT, spec_accept_rate,
                              tokens)
        if handoff_ms is not None:
            self.write_scalar(TAG_SERVE_HANDOFF, handoff_ms, tokens)
        if shed_rate is not None:
            self.write_scalar(TAG_SERVE_SHED_RATE, shed_rate, tokens)
        if fleet_queue_depth is not None:
            self.write_scalar(TAG_SERVE_FLEET_QDEPTH, fleet_queue_depth,
                              tokens)
        if weight_version is not None:
            self.write_scalar(TAG_SERVE_WEIGHT_VERSION, weight_version,
                              tokens)
        if migrations is not None:
            self.write_scalar(TAG_SERVE_MIGRATIONS, migrations, tokens)
        if replica_restarts is not None:
            self.write_scalar(TAG_SERVE_REPLICA_RESTARTS,
                              replica_restarts, tokens)
        if kv_pool_bytes_per_token is not None:
            self.write_scalar(TAG_SERVE_KV_POOL_BPT,
                              kv_pool_bytes_per_token, tokens)
        if quant_logit_err is not None:
            self.write_scalar(TAG_SERVE_QUANT_LOGIT_ERR,
                              quant_logit_err, tokens)
        if flush:
            self.flush()

    def write_timer_values(self, timer_values: dict, samples: int = 0):
        """Per-timer milliseconds (engine.py:950-974 pattern)."""
        if not self._writes():
            return
        for name, ms in timer_values.items():
            self.write_scalar(f"Train/Samples/{name}", ms, samples)
        # same contract as every other write_* method: without the
        # flush, timer telemetry buffered in the writer is lost on
        # crash/preemption
        self.flush()

    def flush(self):
        if self.writer is not None:
            self.writer.flush()
        if self.mirror is not None:
            self.mirror.flush()

    def close(self):
        if self.writer is not None:
            self.writer.close()
            self.writer = None
        self.mirror = None  # owned by the observability layer, not closed
