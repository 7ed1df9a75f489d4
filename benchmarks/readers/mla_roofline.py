"""A served latent-attention model's shares of the roofline (%), each
the least time the chip could take by `core/mla_counts` (and
`core/hybrid_counts.held_tables_bytes` for the experts' tables) and the
peaks table, over a measured device time. `what` picks the count:

- `mla_decode`: the absorbed reader, the larger of the live tokens'
  latent rows over the HBM peak and its products over the MXU peak (the
  run's own mean live tokens), over the time under `scopes` (`opcode`
  narrows it to the kernel alone) in a program matching `step_pattern`;
- `moe_experts`: the held tables of every expert layer read once a
  step, over the time under `scopes`;
- `decode_step`: every held weight once and the live tokens' latent
  rows, over the whole program's device time (`scopes` null);
- `prefill_step`: the whole model's operations for the REAL prompt
  tokens of the traced prefills (`serve/prefill`'s `real_tokens`) over
  the prefill programs' device time (`scopes` null).

It returns None where the program carries no such scope or the facts no
such sizes (another architecture; a program from before this one).
"""

from core import hybrid_counts as hc
from core import mla_counts as mc
from core import program_trace as pt
from loader import load_module


def read(view, facts, ctx, what, step_pattern, scopes=None, opcode=None):
    m = facts.get("model", {})
    if view is None or "latent_width" not in m:
        return None
    if scopes is None:
        ms = load_module("readers", "module_device_ms").read(
            view, facts, ctx, step_pattern)
    else:
        ms = load_module("readers", "scope_ms_per_step").read(
            view, facts, ctx, scopes, step_pattern, opcode=opcode)
    if not ms:
        return None
    peak_bytes, peak_flops = (ctx.peaks["bytes_per_s"],
                              ctx.peaks["flops_per_s"])
    latent = (m["layers"], m["latent_width"], m["rope_width"])
    if what == "mla_decode":
        least, unit = mc.latent_decode_least_s(
            facts["mean_live_tokens"], m["layers"], m["heads"],
            m["latent_width"], m["rope_width"], peak_bytes, peak_flops)
        need = least * (peak_bytes if unit == "B" else peak_flops)
    elif what == "moe_experts":
        need, unit = m["expert_layers"] * hc.held_tables_bytes(
            m["experts_held"], m["hidden"], m["ffn"]), "B"
        least = need / peak_bytes
    elif what == "decode_step":
        need, unit = mc.decode_step_bytes(
            m["weight_bytes"], facts["mean_live_tokens"], *latent), "B"
        least = need / peak_bytes
    elif what == "prefill_step":
        program = pt.load(ctx.trace_dir)
        prefills = [(ev[3]["real_tokens"], ev[3].get("batch", 1))
                    for ev in pt.spans_named(program, "serve/prefill")
                    if "real_tokens" in ev[3]] if program else []
        if not prefills:
            return None
        need, unit = sum(mc.prefill_model_flops(
            t, rows, m["params_met_per_token"], m["head_params"],
            m["layers"], m["heads"], m["key_width"], m["value_width"])
            for t, rows in prefills) / len(prefills), "FLOP"
        least = need / peak_flops
    else:
        raise ValueError(f"mla_roofline: no count named {what!r}")
    ctx.log(f"{what}: {need / 1e9:.3f} G{unit} a run, least "
            f"{least * 1e3:.3f} ms, measured {ms:.3f} ms")
    return 100.0 * least / (ms / 1e3)
