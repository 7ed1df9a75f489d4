"""FLOPs / MFU profiler over XLA's compiled-program cost model.

TPU-native analog of the reference's ``flops_profiler`` (which walks
nn.Module hooks counting matmul shapes): here the compiled program *is*
the model, so the authoritative count comes from
``jit(fn).lower(...).compile().cost_analysis()`` — the same numbers the
XLA scheduler itself uses. That makes the profile exact for whatever
actually runs (fused backward, remat re-computation, quantized
collectives included), not an eager-mode estimate.

MFU is reported against a small peak-FLOPs device registry (bf16 MXU
peaks for the TPU generations this repo targets). The CPU platform gets
a nominal figure so CPU test runs still produce a well-defined
fraction; any other device that is not in the table is an error.
"""

import time
from typing import Any, NamedTuple, Optional

from deepspeed_tpu.utils.logging import log_dist

__all__ = [
    "FlopsProfile", "PEAK_FLOPS_REGISTRY", "peak_flops_per_device",
    "normalize_cost_analysis", "profile_compiled", "profile_jit_fn",
    "compute_mfu", "format_profile",
]

# Peak dense bf16 FLOP/s per chip. Sources: TPU v4 275 TFLOP/s,
# v5e 197 TFLOP/s, v5p 459 TFLOP/s (cloud TPU system docs; v5e matches
# benchmarks/peaks.json). Matching is by
# substring on ``device.device_kind`` lowercased, most specific first.
PEAK_FLOPS_REGISTRY = (
    ("tpu v5p", 459e12),
    ("tpu v5 lite", 197e12),   # v5e reports device_kind "TPU v5 lite"
    ("tpu v5e", 197e12),
    ("tpu v5", 459e12),
    ("tpu v4", 275e12),
)
# Nominal placeholder so MFU stays a well-defined positive fraction on
# the CPU platform (tests, forced-CPU bench children). Deliberately NOT
# a measured CPU peak: CPU MFU values are only meaningful relative to
# each other within one run. Never applied to an accelerator.
CPU_NOMINAL_PEAK_FLOPS = 1e11


class FlopsProfile(NamedTuple):
    """One compiled program's cost-model record.

    NB: for a GSPMD-partitioned program, XLA's ``cost_analysis()``
    reports the **per-device** partition's cost (verified on the
    8-device mesh: a data-sharded matmul reports 2m^3/8), so ``flops``
    and ``bytes_accessed`` here are per-device per invocation. MFU must
    therefore be computed against the per-device peak; multiply by
    ``num_devices`` for cluster-wide totals."""
    name: str
    flops: float               # per-DEVICE FLOPs per invocation
    bytes_accessed: float      # per-DEVICE HBM bytes per invocation
    peak_flops_per_device: float
    device_kind: str
    num_devices: int
    compile_ms: Optional[float] = None

    @property
    def flops_total(self) -> float:
        """Cluster-wide FLOPs per invocation."""
        return self.flops * max(self.num_devices, 1)

    @property
    def arithmetic_intensity(self) -> float:
        """FLOPs per byte of memory traffic (roofline x-coordinate)."""
        return self.flops / self.bytes_accessed if self.bytes_accessed else 0.0


def peak_flops_per_device(device=None):
    """``(peak_flops, label)`` for a jax device (first local device when
    None). The CPU platform gets the nominal placeholder with a
    ``+nominal-peak`` label so reports can't silently claim real MFU;
    an accelerator whose ``device_kind`` is not in the registry raises
    — an MFU against a made-up peak is worse than none."""
    if device is None:
        import jax
        device = jax.local_devices()[0]
    kind = str(device.device_kind)
    low = kind.lower()
    for needle, peak in PEAK_FLOPS_REGISTRY:
        if needle in low:
            return peak, kind
    if device.platform == "cpu":
        return CPU_NOMINAL_PEAK_FLOPS, f"{kind}+nominal-peak"
    raise ValueError(
        f"no peak FLOP/s entry for device kind {kind!r} (platform "
        f"{device.platform!r}); add it to PEAK_FLOPS_REGISTRY with its "
        "source")


def normalize_cost_analysis(cost: Any) -> dict:
    """Normalize ``compiled.cost_analysis()`` (a dict, or None when the
    backend reports nothing — cost analysis is best-effort on some
    platforms) to ``{"flops": float, "bytes_accessed": float}``."""
    if cost is None:
        cost = {}
    flops = float(cost.get("flops", 0.0) or 0.0)
    nbytes = float(cost.get("bytes accessed", 0.0) or 0.0)
    return {"flops": max(flops, 0.0), "bytes_accessed": max(nbytes, 0.0)}


def _shape_specs(args):
    """Pytree of ShapeDtypeStructs mirroring ``args`` — lowering needs
    only avals, and spec'ing avoids touching possibly-donated buffers.
    Shardings are carried over when present: without them the AOT
    compile would produce a REPLICATED program whose FLOPs/bytes differ
    from the partitioned step that actually runs on a multi-device
    mesh (and whose compile can be far more expensive)."""
    import jax
    import numpy as np
    from jax.sharding import Sharding

    def spec(x):
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            shd = getattr(x, "sharding", None)
            if isinstance(shd, Sharding):
                return jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                            sharding=shd)
            return jax.ShapeDtypeStruct(np.shape(x), x.dtype)
        return x
    return jax.tree_util.tree_map(spec, args)


def profile_compiled(compiled, name: str, device=None,
                     num_devices: Optional[int] = None,
                     compile_ms: Optional[float] = None) -> FlopsProfile:
    """Cost-model record of an already-compiled jax stages.Compiled."""
    import jax
    cost = normalize_cost_analysis(compiled.cost_analysis())
    peak, kind = peak_flops_per_device(device)
    if num_devices is None:
        num_devices = len(jax.devices())
    return FlopsProfile(name=name, flops=cost["flops"],
                        bytes_accessed=cost["bytes_accessed"],
                        peak_flops_per_device=peak, device_kind=kind,
                        num_devices=num_devices, compile_ms=compile_ms)


def profile_jit_fn(fn, args, name: str = "step", device=None,
                   num_devices: Optional[int] = None) -> FlopsProfile:
    """Lower + compile ``fn`` at ``args``' shapes and return its cost
    record. ``fn`` is any jit-wrapped callable exposing ``.lower``; args
    may be live arrays OR already-donated ones (only shapes are read).

    NB: this performs an AOT compile — jax does not share the dispatch
    cache with ``lower().compile()`` — so callers should treat it as a
    one-time, opt-in cost (the persistent compile cache absorbs it on
    re-runs)."""
    specs = _shape_specs(args)
    t0 = time.perf_counter()
    compiled = fn.lower(*specs).compile()
    dt_ms = (time.perf_counter() - t0) * 1e3
    return profile_compiled(compiled, name, device=device,
                            num_devices=num_devices, compile_ms=dt_ms)


def compute_mfu(flops_per_step: float, step_time_s: float,
                peak_flops: float) -> float:
    """Model FLOPs utilization: achieved FLOP/s over peak. Pass
    matching scopes — per-device flops (what ``cost_analysis`` reports
    for partitioned programs) against the per-device peak, or global
    flops against the all-device peak; the ratio is the same."""
    if step_time_s <= 0 or peak_flops <= 0:
        return 0.0
    return flops_per_step / step_time_s / peak_flops


def format_profile(profile: FlopsProfile,
                   step_time_ms: Optional[float] = None) -> str:
    """Reference-flops_profiler-style block, logged once per program."""
    lines = [
        f"flops profiler: {profile.name}",
        f"  device               : {profile.device_kind} "
        f"x{profile.num_devices} "
        f"(peak {profile.peak_flops_per_device / 1e12:.1f} TFLOP/s/dev)",
        f"  flops per step/dev   : {profile.flops / 1e9:.3f} GFLOP",
        f"  bytes accessed/dev   : {profile.bytes_accessed / 2**20:.2f} MiB",
        f"  arithmetic intensity : "
        f"{profile.arithmetic_intensity:.2f} FLOP/byte",
    ]
    if profile.compile_ms is not None:
        lines.append(f"  cost-model compile   : {profile.compile_ms:.0f} ms")
    if step_time_ms:
        mfu = compute_mfu(profile.flops, step_time_ms / 1e3,
                          profile.peak_flops_per_device)
        lines.append(f"  step time            : {step_time_ms:.2f} ms")
        lines.append(f"  MFU                  : {mfu * 100:.2f}%")
    return "\n".join(lines)


def log_profile(profile: FlopsProfile,
                step_time_ms: Optional[float] = None) -> None:
    log_dist(format_profile(profile, step_time_ms), ranks=[0])
