"""A served state-space hybrid's shares of the roofline (%), each the
least time the chip could take by `core/ssd_counts` and the peaks table
over a measured device time. `what` picks the count:

- `ssd_state`: the state pool read once and written once for the decode
  program's rows plus the layers' activations and tails, every Mamba
  layer, over the time under `scopes` in a program matching
  `step_pattern`;
- `ssd_scan`: the recurrence's operations for the REAL prompt tokens of
  the traced prefills (`serve/prefill`'s `real_tokens`), counted from
  the sequential form, over the time under `scopes`;
- `prefill_step`: the whole model's operations for those real tokens
  (`core/ssd_counts.prefill_model_flops`) over the prefill programs'
  device time (`scopes` null).

It returns None where the program carries no such scope or the facts no
such sizes (another architecture; a program from before this one).
"""

from core import program_trace as pt
from core import ssd_counts as sc
from loader import load_module


def _traced_prefills(ctx):
    """[(real tokens, batch bucket)] of the traced prefill dispatches."""
    program = pt.load(ctx.trace_dir)
    return [(ev[3]["real_tokens"], ev[3].get("batch", 1))
            for ev in pt.spans_named(program, "serve/prefill")
            if "real_tokens" in ev[3]] if program else []


def read(view, facts, ctx, what, step_pattern, scopes=None):
    m = facts.get("model", {})
    if view is None or "ssd_heads" not in m:
        return None
    if scopes is None:
        ms = load_module("readers", "module_device_ms").read(
            view, facts, ctx, step_pattern)
    else:
        ms = load_module("readers", "scope_ms_per_step").read(
            view, facts, ctx, scopes, step_pattern)
    if not ms:
        return None
    shape = (m["ssd_heads"], m["ssd_head_dim"], m["ssd_state_dim"])
    if what == "ssd_state":
        need = m["recurrent_layers"] * sc.ssd_decode_bytes(
            facts["num_slots"] + 1, *shape, m["ssd_tail_bytes_per_layer"])
        least, unit = need / ctx.peaks["bytes_per_s"], "B"
    elif what in ("ssd_scan", "prefill_step"):
        prefills = _traced_prefills(ctx)
        if not prefills:
            return None
        if what == "ssd_scan":
            need = sum(t for t, _ in prefills) * m["recurrent_layers"] \
                * sc.ssd_token_flops(*shape)
        else:
            need = sum(sc.prefill_model_flops(
                t, rows, m["params_met_per_token"], m["head_params"],
                m["softmax_layers"], m["heads"], m["head_dim"],
                m["recurrent_layers"], *shape) for t, rows in prefills)
        need /= len(prefills)
        least, unit = need / ctx.peaks["flops_per_s"], "FLOP"
    else:
        raise ValueError(f"ssd_roofline: no count named {what!r}")
    ctx.log(f"{what}: {need / 1e9:.3f} G{unit} a run, least "
            f"{least * 1e3:.3f} ms, measured {ms:.3f} ms")
    return 100.0 * least / (ms / 1e3)
