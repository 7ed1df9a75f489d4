"""A served delta-rule + latent-attention hybrid's shares of the
roofline (%), each the least time the chip could take by
`core/kimi_counts` and the peaks table over a measured device time.
`what` picks the count:

- `kda_scan`: the recurrence's operations for the real tokens of the
  traced prefill-program dispatches, over the time under `scopes` in a
  program matching `step_pattern`;
- `kda_state`: the state pool's rows read and written, every delta-rule
  layer, over the time under `scopes`;
- `mla_decode`: the absorbed reader's least time over the latent layers
  (`opcode` narrows the time to the kernel alone);
- `mla_prefix`: the prefix part of the chunks' latent attention, from
  the spans' `prefix_pairs`, over the time under `scopes`;
- `moe_experts`: the held tables of every expert layer read once;
- `decode_step`: weights, active state twice, live latent rows, over the
  whole program's device time (`scopes` null);
- `prefill_step`: the whole model's operations of the traced dispatches
  over the prefill programs' device time (`scopes` null).

A dispatch of the prefill program is a `serve/chunk` span (its
arguments `real_tokens`, `rows`, `prefix_pairs`, `own_pairs`) or a
`serve/prefill` span (a prompt of exactly one chunk: `real_tokens` and
`batch`, no prefix); the counts are the mean over both, as the measured
time is the mean over the program's runs. Returns None where the
program carries no such scope or span or the facts no such sizes
(another architecture; a program from before this one).
"""

from core import hybrid_counts as hc
from core import kimi_counts as kc
from core import program_trace as pt
from loader import load_module


def _dispatches(ctx):
    """[(real tokens, rows, prefix pairs, own pairs)] of the traced
    dispatches of the prefill program."""
    program = pt.load(ctx.trace_dir)
    if program is None:
        return []
    out = [(a["real_tokens"], a["rows"], a["prefix_pairs"], a["own_pairs"])
           for a in (ev[3] for ev in pt.spans_named(program, "serve/chunk"))
           if "prefix_pairs" in a]
    for a in (ev[3] for ev in pt.spans_named(program, "serve/prefill")):
        if "real_tokens" in a:
            rows = max(a.get("batch", 1), 1)
            out.append((a["real_tokens"], rows, 0,
                        a["real_tokens"] ** 2 / rows))
    return out


def read(view, facts, ctx, what, step_pattern, scopes=None, opcode=None):
    m = facts.get("model", {})
    if view is None or m.get("family") != "kimi_linear":
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
    unit = "B"
    if what in ("kda_scan", "mla_prefix", "prefill_step"):
        runs = _dispatches(ctx)
        if not runs:
            return None
        unit = "FLOP"
        if what == "kda_scan":
            each = [kc.scan_flops(t, m["kda_layers"], m["kda_heads"],
                                  m["kda_key_dim"], m["kda_value_dim"])
                    for t, _, _, _ in runs]
        elif what == "mla_prefix":
            each = [m["latent_layers"] * kc.prefix_attention_flops(
                m["heads"], m["key_width"], m["value_width"], p)
                for _, _, p, _ in runs]
        else:
            each = [kc.chunk_model_flops(t, r, p, o, m)
                    for t, r, p, o in runs]
        need = sum(each) / len(each)
        least = need / peak_flops
    elif what == "kda_state":
        need = kc.state_decode_bytes(facts["num_slots"] + 1, m)
        least = need / peak_bytes
    elif what == "mla_decode":
        least, unit = kc.latent_decode_least_s(
            facts["mean_live_tokens"], m, peak_bytes, peak_flops)
        need = least * (peak_bytes if unit == "B" else peak_flops)
    elif what == "moe_experts":
        need = m["expert_layers"] * hc.held_tables_bytes(
            m["experts_held"], m["hidden"], m["ffn"])
        least = need / peak_bytes
    elif what == "decode_step":
        need = kc.decode_step_bytes(facts["mean_active_slots"],
                                    facts["mean_live_tokens"], m)
        least = need / peak_bytes
    else:
        raise ValueError(f"kimi_roofline: no count named {what!r}")
    ctx.log(f"{what}: {need / 1e9:.3f} G{unit} a run, least "
            f"{least * 1e3:.3f} ms, measured {ms:.3f} ms")
    return 100.0 * least / (ms / 1e3)
