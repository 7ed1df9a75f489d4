"""The decode program's share of the HBM roofline (%): every weight
once at the engine's compute width plus the keys and values of the live
tokens (`core/flops.decode_step_bytes`), over the table's bytes/s, over
the program's measured device time."""

from core import flops
from loader import load_module


def read(view, facts, ctx, pattern):
    if view is None or "mean_live_tokens" not in facts:
        return None
    ms = load_module("readers", "module_device_ms").read(view, facts, ctx, pattern)
    if not ms:
        return None
    m = facts["model"]
    need = flops.decode_step_bytes(facts["n_params"],
                                   facts["mean_live_tokens"],
                                   m["layers"], m["hidden"])
    least = need / ctx.peaks["bytes_per_s"]
    ctx.log(f"decode: {need / 1e9:.3f} GB a step, least "
            f"{least * 1e3:.3f} ms, measured {ms:.3f} ms")
    return 100.0 * least / (ms / 1e3)
