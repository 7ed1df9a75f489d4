"""The training attention kernels' share of their roofline (%): the
least time the chip could take for the step's attention
(`core/flops.flash_attention_train_cost` over the peaks table) over the
kernels' measured time per step."""

from core import flops
from loader import load_module


def read(view, facts, ctx, op_pattern, step_pattern):
    if view is None or "model" not in facts:
        return None
    ns = load_module("readers", "op_ms_per_step").per_step_ns(
        view, op_pattern, step_pattern)
    if not ns:
        return None
    m = facts["model"]
    need_flops, need_bytes = flops.flash_attention_train_cost(
        m["micro_batch_per_chip"], m["heads"], m["seq"],
        m["hidden"] // m["heads"], m["layers"])
    least, bound = flops.roofline_seconds(need_flops, need_bytes, ctx.peaks)
    ctx.log(f"attention: least {least * 1e3:.3f} ms a step (bound by "
            f"{bound}), measured {ns / 1e6:.3f} ms")
    return 100.0 * least / (ns / 1e9)
