"""The benchmark's own arithmetic on raw samples: the median-block rate
and the spread the contract's bounds are set from.
No JAX, no program code: `benchmarks/tests` holds each to a hand-worked
case."""

import statistics


def median_block(block_seconds, steps_per_block, tokens_per_step, chips):
    """The training rate from consecutive blocks of a fixed number of
    steps, each ended by one sync: tokens in a block over the MEDIAN
    block time, per chip. A stall on the host costs one block, not the
    run; what the median hides comes back as `stall_share`: the
    window's wall time over blocks x median block time, less one (%).
    """
    if not block_seconds:
        raise ValueError("no block was measured")
    med = statistics.median(block_seconds)
    rate = steps_per_block * tokens_per_step / med / chips
    stall = (sum(block_seconds) / (len(block_seconds) * med) - 1.0) * 100.0
    return {"tokens_per_s_per_chip": rate, "median_block_s": med,
            "stall_share_pct": stall, "blocks": len(block_seconds),
            "step_ms": med / steps_per_block * 1e3}


def spread(values):
    """Distance between the first and third quartile as a share of the
    median, the way the contract measures a metric's spread."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
