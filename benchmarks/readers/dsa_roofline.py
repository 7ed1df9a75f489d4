"""A served learned-sparse-attention decoder's shares of the roofline
(%), each the least time the chip could take by `core/dsa_counts` and
the peaks table over a measured device time. `what` picks the count:

- `indexer_decode`: the scored tokens' indexer keys read once a layer
  (bytes), over the time under `scopes` in the decode program;
- `sparse_decode`: the selected tokens' keys and values read once a
  layer (bytes), over the time under `scopes` in the decode program;
- `indexer_prefill`: the indexer's products over the (query, key) pairs
  the traced chunks scored (operations), over the time under `scopes`
  in one prefill program;
- `sparse_prefill`: the attention's products over the pairs the traced
  chunks selected (operations), over the time under `scopes`;
- `moe_experts`: the held tables of every layer read once (bytes);
- `decode_step`: weights, scored indexer keys, selected rows, over the
  whole decode program's device time (`scopes` null);
- `prefill_step`: the whole model's operations of the traced chunk
  dispatches over the prefill programs' device time (`scopes` null).

The counts come from the arguments the program's `serve/decode` and
`serve/chunk` spans carry (`scored_tokens`, `selected_tokens`; a chunk's
`real_tokens` and `rows`), the mean over the traced dispatches, as the
measured time is the mean over the program's runs. Returns None where
the program carries no such scope or span argument or the facts no such
sizes (another architecture; a program from before this one).
"""

from core import dsa_counts as dc
from core import program_trace as pt
from loader import load_module


def _spans(ctx, name, keys):
    program = pt.load(ctx.trace_dir)
    if program is None:
        return []
    return [tuple(a[k] for k in keys)
            for a in (ev[3] for ev in pt.spans_named(program, name))
            if all(k in a for k in keys)]


def _mean(values):
    return sum(values) / len(values)


def read(view, facts, ctx, what, step_pattern, scopes=None, opcode=None):
    m = facts.get("model", {})
    if view is None or m.get("family") != "keye_vl2":
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
    if what in ("indexer_prefill", "sparse_prefill", "prefill_step"):
        runs = _spans(ctx, "serve/chunk", ("real_tokens", "rows",
                                           "scored_tokens",
                                           "selected_tokens"))
        if not runs:
            return None
        unit = "FLOP"
        if what == "indexer_prefill":
            need = _mean([dc.indexer_flops(s, m) for _, _, s, _ in runs])
        elif what == "sparse_prefill":
            need = _mean([dc.selected_flops(c, m) for *_, c in runs])
        else:
            need = _mean([dc.chunk_model_flops(*run, m) for run in runs])
        least = need / peak_flops
    elif what == "moe_experts":
        unit, need = "B", dc.held_tables_bytes(m)
        least = need / peak_bytes
    elif what in ("indexer_decode", "sparse_decode", "decode_step"):
        runs = _spans(ctx, "serve/decode", ("scored_tokens",
                                            "selected_tokens"))
        if not runs:
            return None
        unit = "B"
        if what == "indexer_decode":
            need = _mean([dc.indexer_bytes(s, m) for s, _ in runs])
        elif what == "sparse_decode":
            need = _mean([dc.selected_bytes(c, m) for _, c in runs])
        else:
            need = _mean([dc.decode_step_bytes(s, c, m) for s, c in runs])
        least = need / peak_bytes
    else:
        raise ValueError(f"dsa_roofline: no count named {what!r}")
    ctx.log(f"{what}: {need / 1e9:.3f} G{unit} a run, least "
            f"{least * 1e3:.3f} ms, measured {ms:.3f} ms")
    return 100.0 * least / (ms / 1e3)
