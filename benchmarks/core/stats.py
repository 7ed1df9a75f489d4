"""The benchmark's own arithmetic on raw samples: the median-block rate
and the two spreads a bound is set from and gated by.
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


def driver_spread(values):
    """A set's largest value less its smallest, leaving out the ONE run
    farthest from the set's median where that narrows it, as a share of
    the median: what the driver's check counts when it asks whether a
    new or re-measured cell is too noisy for its bound (the mean of two
    sets' spreads may be at most half the bound). With six runs it is
    wider than `spread`, which is a distance between quartiles."""
    values = sorted(values)
    if len(values) < 2:
        raise ValueError("a spread needs two runs or more")
    med = statistics.median(values)
    if len(values) > 2:
        # the farthest run is one of the two ends
        far_low = med - values[0] >= values[-1] - med
        kept = values[1:] if far_low else values[:-1]
        if kept[-1] - kept[0] < values[-1] - values[0]:
            values = kept
    return (values[-1] - values[0]) / med
