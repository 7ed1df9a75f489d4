"""Model FLOP/s utilization of the compiled train step (%) of a stack
with routed experts and masked attention: the operations one step
requires by `core/sparse_counts.train_step_flops` (dense parameters
every token uses, the assignments that LANDED in a traced step, the
pairs inside each layer's mask; nothing recomputed is counted) over the
step program's device time from the trace, over the peaks table's
FLOP/s."""

from core import sparse_counts
from loader import load_module


def read(view, facts, ctx, pattern):
    landed = facts.get("experts", {}).get("landed_per_traced_step")
    if view is None or not landed:
        return None
    ms = load_module("readers", "module_device_ms").read(
        view, facts, ctx, pattern)
    if not ms:
        return None
    m = facts["model"]
    need = sparse_counts.train_step_flops(
        facts["tokens_per_step"] / facts["chips"], m["dense_params_used"],
        landed, m["expert_params"], m["micro_batch_per_chip"], m["heads"],
        m["head_dim"], m["pairs_by_layer"])
    ctx.log(f"train step: {need / 1e12:.3f} TFLOP required, "
            f"{ms:.3f} ms on the device")
    return 100.0 * need / (ms / 1e3) / ctx.peaks["flops_per_s"]
