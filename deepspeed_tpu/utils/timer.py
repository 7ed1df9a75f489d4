"""Wall-clock and throughput timers.

TPU-native analog of the reference's ``deepspeed/utils/timer.py``:
- SynchronizedWallClockTimer (timer.py:20) used cuda.synchronize(); here we
  block on JAX async dispatch with ``jax.block_until_ready`` hooks or plain
  ``jax.effects_barrier()`` when no array is at hand.
- ThroughputTimer (timer.py:100) reports samples/sec.
"""

import time
from typing import Dict, List, Optional

from deepspeed_tpu.utils.logging import log_dist


def _device_sync():
    """Drain the async dispatch queue so wall-clock timings are honest."""
    try:
        import jax
        jax.effects_barrier()
    except Exception:
        pass


class Timer_:
    """One named timer (reference timer.py:23)."""

    def __init__(self, name: str, synchronize: bool = True):
        self.name_ = name
        self.elapsed_ = 0.0
        self.started_ = False
        self.start_time = 0.0
        self.synchronize = synchronize

    def start(self):
        assert not self.started_, f"timer {self.name_} has already been started"
        if self.synchronize:
            _device_sync()
        self.start_time = time.perf_counter()
        self.started_ = True

    def stop(self, reset: bool = False):
        assert self.started_, f"timer {self.name_} is not started"
        if self.synchronize:
            _device_sync()
        if reset:
            self.elapsed_ = time.perf_counter() - self.start_time
        else:
            self.elapsed_ += time.perf_counter() - self.start_time
        self.started_ = False

    def reset(self):
        self.elapsed_ = 0.0
        self.started_ = False

    def elapsed(self, reset: bool = True) -> float:
        started = self.started_
        if started:
            self.stop()
        elapsed_ = self.elapsed_
        if reset:
            self.reset()
        if started:
            self.start()
        return elapsed_


class SynchronizedWallClockTimer:
    """Group of named timers (reference timer.py:20)."""

    def __init__(self, synchronize: bool = True):
        self.timers: Dict[str, Timer_] = {}
        self.synchronize = synchronize

    def __call__(self, name: str) -> Timer_:
        if name not in self.timers:
            self.timers[name] = Timer_(name, synchronize=self.synchronize)
        return self.timers[name]

    @staticmethod
    def memory_stats() -> Optional[dict]:
        """Structured device-memory sample: ``{"bytes_in_use",
        "peak_bytes_in_use", "source"}`` (``source: "host"`` = RSS
        fallback on backends without allocator stats), or None when
        nothing is readable. The observability layer writes these as
        per-step scalars (profiling/memory.py owns the sampling)."""
        try:
            from deepspeed_tpu.profiling.memory import memory_snapshot
            return memory_snapshot()
        except Exception:
            return None

    @staticmethod
    def memory_usage() -> str:
        stats = SynchronizedWallClockTimer.memory_stats()
        if stats is None:
            return "mem stats unavailable"
        in_use = stats["bytes_in_use"] / (1024**3)
        peak = stats["peak_bytes_in_use"] / (1024**3)
        src = "" if stats["source"] == "device" else f" ({stats['source']})"
        return f"mem in_use={in_use:.2f} GB peak={peak:.2f} GB{src}"

    def log(self, names: List[str], normalizer: float = 1.0, reset: bool = True,
            ranks: Optional[List[int]] = None, memory_breakdown: bool = False):
        assert normalizer > 0.0
        string = "time (ms)"
        for name in names:
            if name in self.timers:
                elapsed_time = self.timers[name].elapsed(reset=reset) * 1000.0 / normalizer
                string += f" | {name}: {elapsed_time:.2f}"
        if memory_breakdown:
            string += " | " + self.memory_usage()
        log_dist(string, ranks=ranks or [0])


class ThroughputTimer:
    """Samples/sec reporting (reference timer.py:100)."""

    def __init__(self, batch_size: int, num_workers: int = 1, start_step: int = 2,
                 steps_per_output: int = 50, monitor_memory: bool = False, logging_fn=None):
        self.start_time = 0.0
        self.end_time = 0.0
        self.started = False
        self.batch_size = max(1, batch_size)
        self.num_workers = num_workers
        self.start_step = start_step
        self.epoch_count = 0
        self.local_step_count = 0
        self.total_step_count = 0
        self.total_elapsed_time = 0.0
        self.steps_per_output = steps_per_output
        self.monitor_memory = monitor_memory
        self.logging = logging_fn or log_dist

    def update_epoch_count(self):
        self.epoch_count += 1
        self.local_step_count = 0

    def start(self):
        self.started = True
        if self.total_step_count >= self.start_step:
            # NO device sync here: a per-step barrier serializes the async
            # dispatch pipeline.
            # We sync only at reporting boundaries, which makes the
            # *cumulative* time — and therefore avg samples/sec — honest.
            self.start_time = time.perf_counter()

    def stop(self, report_speed: bool = True):
        if not self.started:
            return
        self.started = False
        self.total_step_count += 1
        self.local_step_count += 1
        if self.total_step_count > self.start_step:
            will_report = (report_speed and
                           self.local_step_count % self.steps_per_output == 0)
            if will_report:
                _device_sync()
            self.end_time = time.perf_counter()
            duration = self.end_time - self.start_time
            self.total_elapsed_time += duration
            if will_report:
                self.logging(
                    f"epoch={self.epoch_count}/step={self.local_step_count}: "
                    f"{self.avg_samples_per_sec():.2f} samples/sec, "
                    f"batch_time={duration * 1000.0:.2f} ms")

    def avg_samples_per_sec(self) -> float:
        if self.total_step_count > self.start_step and self.total_elapsed_time > 0:
            samples = self.batch_size * self.num_workers
            avg_time_per_step = self.total_elapsed_time / (self.total_step_count - self.start_step)
            return samples / avg_time_per_step
        return float("-1")
