"""A served hybrid's shares of the roofline (%), each the least time the
chip could take by `core/hybrid_counts` and the peaks table over a
measured device time. `what` picks the count:

- `kda_state`: the state pool read once and written once for the decode
  program's rows plus the layer's activations, every recurrent layer,
  over the time under `scopes` in a program matching `step_pattern`;
- `kda_scan`: the recurrence's operations for the REAL prompt tokens of
  the traced prefills (`serve/prefill`'s `real_tokens`), counted from
  the sequential form, over the time under `scopes`;
- `moe_experts`: the held tables of every layer read once a step, over
  the time under `scopes`;
- `decode_step`: every held weight once, the active slots' state twice
  and the live keys and values, over the whole program's device time
  (`scopes` null).

It returns None where the program carries no such scope or the facts no
such sizes (another architecture; a program from before this one).
"""

from core import hybrid_counts as hc
from core import program_trace as pt
from loader import load_module


def read(view, facts, ctx, what, step_pattern, scopes=None):
    m = facts.get("model", {})
    if view is None or "recurrent_layers" not in m:
        return None
    if scopes is None:
        ms = load_module("readers", "module_device_ms").read(
            view, facts, ctx, step_pattern)
    else:
        ms = load_module("readers", "scope_ms_per_step").read(
            view, facts, ctx, scopes, step_pattern)
    if not ms:
        return None
    peak_bytes, peak_flops = (ctx.peaks["bytes_per_s"],
                              ctx.peaks["flops_per_s"])
    if what == "kda_state":
        need = m["recurrent_layers"] * hc.delta_rule_decode_bytes(
            facts["num_slots"] + 1, m["kda_heads"], m["kda_key_dim"],
            m["kda_value_dim"], m["kda_tail_bytes_per_layer"])
        least = need / peak_bytes
    elif what == "moe_experts":
        need = m["layers"] * hc.held_tables_bytes(
            m["experts_held"], m["hidden"], m["ffn"])
        least = need / peak_bytes
    elif what == "decode_step":
        need = hc.hybrid_decode_step_bytes(
            m["weight_bytes"], facts["mean_active_slots"],
            m["state_bytes_per_slot"], facts["mean_live_tokens"],
            m["kv_bytes_per_token"])
        least = need / peak_bytes
    elif what == "kda_scan":
        program = pt.load(ctx.trace_dir)
        spans = [ev[3] for ev in pt.spans_named(program, "serve/prefill")
                 if "real_tokens" in ev[3]] if program else []
        if not spans:
            return None
        tokens = sum(a["real_tokens"] for a in spans) / len(spans)
        need = tokens * m["recurrent_layers"] * hc.delta_rule_token_flops(
            m["kda_heads"], m["kda_key_dim"], m["kda_value_dim"])
        least = need / peak_flops
    else:
        raise ValueError(f"hybrid_roofline: no count named {what!r}")
    ctx.log(f"{what}: {need / 1e9:.3f} G{'FLOP' if what == 'kda_scan' else 'B'} "
            f"a run, least {least * 1e3:.3f} ms, measured {ms:.3f} ms")
    return 100.0 * least / (ms / 1e3)
