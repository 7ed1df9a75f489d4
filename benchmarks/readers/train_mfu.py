"""Model FLOP/s utilization of the compiled train step (%): operations
the forward and backward passes of one step require (6N + 12·L·h·s per
token, recomputation not counted) over the step program's device time
from the trace, over the peaks table's FLOP/s. The host's gaps between
steps are not in it (`train_host_gap_ms` has them)."""

from core import flops
from loader import load_module


def read(view, facts, ctx, pattern):
    if view is None or "tokens_per_step" not in facts:
        return None
    ms = load_module("readers", "module_device_ms").read(
        view, facts, ctx, pattern)
    if not ms:
        return None
    m = facts["model"]
    per_token = flops.train_flops_per_token(
        facts["n_params"], m["layers"], m["hidden"], m["seq"])
    per_chip = per_token * facts["tokens_per_step"] / facts["chips"]
    return 100.0 * per_chip / (ms / 1e3) / ctx.peaks["flops_per_s"]
