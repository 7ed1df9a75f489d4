"""What the harness asks of the machine: the chips, the peaks table, the
compile cache's one fixed place, a count of compilations, peak memory.
"""

import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
# the same directory the program's own helper
# (deepspeed_tpu/utils/platform.py) would choose in this checkout: set
# first, so that both engines find it active and take it
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


class NoAccelerator(SystemExit):
    pass


def require_chips(chips, rehearse_cpu=False):
    """The devices a cell runs on. Fails (no result line) unless JAX's
    first device is a TPU and `chips` of them are there. A rehearsal on
    the CPU is let through here and never prints a result line."""
    import jax
    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu" and not rehearse_cpu:
        raise NoAccelerator(
            f"benchmarks: JAX's first device is {d0.platform!r} "
            f"({d0.device_kind!r}), not a TPU: no number is printed")
    if len(devices) < chips:
        raise NoAccelerator(
            f"benchmarks: the cell needs {chips} chips, JAX reports "
            f"{len(devices)}")
    return devices[:chips]


def peaks_for(device_kind, rehearse_cpu=False):
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        if rehearse_cpu:
            return {"flops_per_s": 1e12, "bytes_per_s": 1e11,
                    "memory_bytes": 1e9}
        raise KeyError(f"device kind {device_kind!r} is not in "
                       "benchmarks/peaks.json: add it with its source")
    return table[device_kind]


def enable_compile_cache():
    """JAX's persistent compilation cache at one fixed path inside the
    checkout (or where JAX_COMPILATION_CACHE_DIR says), every program
    kept however short its compile."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return os.environ["JAX_COMPILATION_CACHE_DIR"]
    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR


class CompileCounter:
    """Backend compilations and persistent-cache hits in this process,
    from JAX's own monitoring events: `compiles` counts programs that
    were built or loaded (either way a stall), `cache_hits` those that
    came from disk."""

    def __init__(self):
        import jax
        self.compiles = self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def memory_peak_bytes(devices):
    """Peak bytes in use on the fullest chip, where the backend says."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks)) if peaks else 0
