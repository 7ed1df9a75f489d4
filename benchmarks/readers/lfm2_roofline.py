"""A served short-convolution + attention hybrid's shares of the
roofline (%), each the least time the chip could take by
`core/lfm2_counts` and the peaks table over a measured device time.
`what` picks the count:

- `moe_experts`: the decode step's expert products: every held expert's
  three tables read once a layer (the program works every held expert
  on every row, and with 1,024 assignments a layer over 64 experts none
  goes without a row) and the landed assignments' rows in and out, or
  the landed assignments' arithmetic, whichever bounds (from the facts'
  sizes and the `serve/decode` spans' `landed`, the mean over the
  traced steps), over the time under `scopes` in a program matching
  `step_pattern`;
- `decode_step`: weights once, live keys and values, the active slots'
  tails twice, over the whole program's device time (`scopes` null);
- `prefill_step`: the whole model's operations of the traced
  `serve/prefill` dispatches (`real_tokens`, `batch`) over the prefill
  programs' device time (`scopes` null).

Returns None where the program carries no such scope, span or argument
or the facts no such sizes (another architecture; a program from before
this one).
"""

from core import lfm2_counts as lc
from core import program_trace as pt
from loader import load_module


def _span_args(ctx, span, needs):
    program = pt.load(ctx.trace_dir)
    if program is None:
        return []
    return [ev[3] for ev in pt.spans_named(program, span)
            if all(k in ev[3] for k in needs)]


def read(view, facts, ctx, what, step_pattern, scopes=None):
    m = facts.get("model", {})
    if view is None or m.get("family") != "lfm2":
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
    if what == "moe_experts":
        # a span carries the counters of the step before it: the first
        # of an engine's has none yet
        steps = [a for a in _span_args(ctx, "serve/decode", ("landed",))
                 if a["landed"]]
        if not steps:
            return None
        least, unit = lc.expert_half_least_s(
            m["expert_layers"] * m["experts_held"],
            sum(a["landed"] for a in steps) / len(steps),
            m["hidden"], m["ffn"], peak_bytes, peak_flops)
    elif what == "decode_step":
        least, unit = lc.decode_step_bytes(
            facts["mean_active_slots"], facts["mean_live_tokens"],
            m) / peak_bytes, "B"
    elif what == "prefill_step":
        runs = _span_args(ctx, "serve/prefill", ("real_tokens",))
        if not runs:
            return None
        each = []
        for a in runs:
            rows = max(a.get("batch", 1), 1)
            # rows of unequal lengths have more pairs than this: least
            each.append(lc.prefill_model_flops(
                a["real_tokens"], rows, a["real_tokens"] ** 2 / rows, m))
        least, unit = sum(each) / len(each) / peak_flops, "FLOP"
    else:
        raise ValueError(f"lfm2_roofline: no count named {what!r}")
    ctx.log(f"{what}: bound by {unit}, least {least * 1e3:.3f} ms, "
            f"measured {ms:.3f} ms")
    return 100.0 * least / (ms / 1e3)
