"""Scan-amortized device timing for op-level micro-benchmarks.

One copy of the measurement protocol shared by ``bench.py``,
``tools/autotune_blocks.py`` and ``tools/ab_coarse_sparse.py`` (it grew
up in the autotune harness; the copies had started to diverge):

- N grad evals are chained inside ONE dispatch via ``lax.scan`` with a
  tiny gradient feedback into the operands, so XLA can neither hoist
  loop-invariant work nor dedupe the iterations, and the result is a
  scalar.  A per-call timing loop instead pays the host's per-dispatch
  latency N times AND eagerly transfers every full-tensor gradient to
  the host.
- A measurement window must clear an ``floor_mult x RTT`` noise floor or
  the RTT subtraction is itself noise; the scan length is rescaled until
  one does.  A combo that can never clear the floor RAISES — a noise
  reading must never be reported as a measurement (a 20 ms window
  against 66 ms RTT once "measured" 0.00 ms and poisoned the block
  table).
- Refinement windows below the floor (RTT jitter ate them) are
  discarded rather than min()'d in.

Reference analog: the GemmTest autotuner's repeated-timing loop
(csrc/includes/gemm_test.h:27) — here the enemy is dispatch latency,
not cublas algo variance.
"""

import time

import numpy as np

__all__ = ["NoiseFloorError", "measure_rtt", "scan_grad_seconds"]


class NoiseFloorError(RuntimeError):
    """No measurement window cleared the RTT-noise floor.

    Distinct from kernel/compile failures on purpose: callers that fall
    back to a different kernel on ``Exception`` must NOT treat a
    measurement failure as a kernel failure (that would silently publish
    a worse-kernel row where the protocol demands an error row)."""


def measure_rtt():
    """Round-trip of a cached trivial dispatch + scalar fetch, min of 3."""
    import jax
    import jax.numpy as jnp

    zf = jax.jit(lambda: jnp.zeros(()))
    np.asarray(zf())
    ts = []
    for _ in range(3):
        t0 = time.perf_counter()
        np.asarray(zf())
        ts.append(time.perf_counter() - t0)
    return min(ts)


def scan_grad_seconds(grad_fn, args, rtt, *, start_len=8, max_len=4096,
                      windows=3, floor_mult=8.0, min_floor=0.25,
                      feedback=1e-6, grow_rounds=5, beat=None):
    """Seconds per ``grad_fn(*args)`` eval, measured scan-amortized.

    ``grad_fn`` must return one gradient per positional arg (i.e.
    ``jax.grad(loss, argnums=tuple(range(len(args))))``).  Returns
    ``(seconds_per_eval, scan_length_used)``.  Raises ``NoiseFloorError``
    when no window can clear the RTT-noise floor.  ``beat`` (optional
    zero-arg callable) is invoked after every completed device fetch so
    a caller's stall watchdog can distinguish a slow-but-alive compile
    from a dead device.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    def build(length):
        def many(*xs):
            def body(carry, _):
                gs = grad_fn(*carry)
                return tuple(x + feedback * g.astype(x.dtype)
                             for x, g in zip(carry, gs)), ()
            out, _ = lax.scan(body, tuple(xs), None, length=length)
            return jnp.sum(out[0].astype(jnp.float32))
        return jax.jit(many)

    floor = max(floor_mult * rtt, min_floor)
    n, g, w = start_len, None, None
    measured_n = start_len
    for _ in range(grow_rounds):
        measured_n = n
        g = build(n)
        np.asarray(g(*args))      # compile + settle
        if beat is not None:
            beat()
        t0 = time.perf_counter()
        np.asarray(g(*args))
        w = time.perf_counter() - t0 - rtt
        if beat is not None:
            beat()
        if w >= floor:
            break
        if n >= max_len:
            break                 # raise below: floor unreachable
        if w > 0.5 * rtt:
            # trustworthy-enough window: grow by the measured ratio
            factor = int(np.ceil(floor / w * 1.5))
        else:
            # jitter swallowed the window (w ~ 0 or negative): a ratio
            # would explode; grow geometrically instead
            factor = 8
        n = min(n * min(max(factor, 2), 64), max_len)
    if w is None or w < floor:
        raise NoiseFloorError(
            f"window {(w or 0) * 1e3:.1f} ms never cleared the "
            f"{floor * 1e3:.0f} ms RTT-noise floor at scan length "
            f"{measured_n}")
    best = w
    for _ in range(windows - 1):
        t0 = time.perf_counter()
        np.asarray(g(*args))
        w2 = time.perf_counter() - t0 - rtt
        if beat is not None:
            beat()
        if w2 >= floor:           # jitter can eat a refinement window
            best = min(best, w2)
    return best / n, n
