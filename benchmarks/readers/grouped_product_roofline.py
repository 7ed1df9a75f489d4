"""The experts' grouped products' share of their roofline (%): the least
time the chip could take for the assignments that LANDED in a traced
step (`core/sparse_counts.grouped_product_cost` over the peaks table)
over the measured time of the custom calls under the scopes `scopes`."""

from core import flops, sparse_counts
from loader import load_module


def read(view, facts, ctx, scopes, step_pattern):
    landed = facts.get("experts", {}).get("landed_per_traced_step")
    if view is None or not landed:
        return None
    ms = load_module("readers", "scope_ms_per_step").read(
        view, facts, ctx, scopes, step_pattern, opcode="custom-call")
    if not ms:
        return None
    m = facts["model"]
    need = sparse_counts.grouped_product_cost(
        landed, m["experts_held"] * m["layers"], m["hidden"], m["ffn"], 3)
    least, bound = flops.roofline_seconds(*need, ctx.peaks)
    ctx.log(f"grouped products: {landed:.0f} assignments landed a traced "
            f"step, least {least * 1e3:.3f} ms (bound by {bound}), "
            f"measured {ms:.3f} ms")
    return 100.0 * least / (ms / 1e3)
