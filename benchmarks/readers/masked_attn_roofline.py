"""The training attention kernels' share of their roofline (%) where the
layers' masks differ: the least time for the pairs INSIDE each layer's
mask (`core/sparse_counts.attention_kernel_cost`, `facts["model"]
["pairs_by_layer"]`) over the measured time of the custom calls under
the scopes `scopes`."""

from core import flops, sparse_counts
from loader import load_module


def read(view, facts, ctx, scopes, step_pattern):
    m = facts.get("model", {})
    if view is None or "pairs_by_layer" not in m:
        return None
    ms = load_module("readers", "scope_ms_per_step").read(
        view, facts, ctx, scopes, step_pattern, opcode="custom-call")
    if not ms:
        return None
    need = sparse_counts.attention_kernel_cost(
        m["micro_batch_per_chip"], m["heads"], m["kv_heads"],
        m["head_dim"], m["seq"], m["pairs_by_layer"])
    least, bound = flops.roofline_seconds(*need, ctx.peaks)
    ctx.log(f"masked attention: least {least * 1e3:.3f} ms a step (bound "
            f"by {bound}), measured {ms:.3f} ms")
    return 100.0 * least / (ms / 1e3)
