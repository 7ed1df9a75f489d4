"""`core/kimi_counts.py` on hand-worked cases, and
`readers/kimi_roofline.py` on facts without a trace
(`python -m pytest benchmarks/tests -q`; no JAX)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from core import hybrid_counts, kimi_counts  # noqa: E402
from loader import load_module  # noqa: E402

# the cell's sizes, as `families/kimi_linear.describe_served` gives them
CELL = {"family": "kimi_linear", "layers": 9, "expert_layers": 8,
        "hidden": 2304, "latent_layers": 2, "heads": 32,
        "latent_width": 512, "shared_key_width": 64, "key_width": 192,
        "value_width": 128, "kda_layers": 7, "kda_heads": 32,
        "kda_key_dim": 128, "kda_value_dim": 128,
        "kda_tail_bytes_per_layer": 3 * 12288 * 2,
        "state_bytes_per_slot": 7 * 32 * 128 * 128 * 4 + 7 * 3 * 12288 * 2,
        "experts_held": 32, "ffn": 1024, "experts_per_token": 8,
        "router_outputs": 256, "params_met_per_token": 461_000_000,
        "head_params": 20480 * 2304, "weight_bytes": 2 * 2_366_229_344}


def test_a_chunks_attention_by_hand():
    """ISSUE 48's words: a chunk's prefix attention is 2 x 32 x (192 +
    128) x real x start, its own rows half of real squared."""
    # 3 real queries after 5 rows of prefix, 2 heads, keys of 4, values
    # of 2: 15 pairs x 2 heads x 2 x (4 + 2)
    assert kimi_counts.prefix_attention_flops(2, 4, 2, 3 * 5) == 360
    # ... and over their own 3 rows: 9 pairs at the causal half
    assert kimi_counts.own_attention_flops(2, 4, 2, 3 * 3) == 108
    real, start = 2048, 6144
    assert kimi_counts.prefix_attention_flops(32, 192, 128, real * start) \
        == 2 * 32 * 320 * real * start
    assert kimi_counts.own_attention_flops(32, 192, 128, real * real) \
        == 32 * 320 * real * real
    # a last chunk of ONE token after 16k of prefix is nearly all prefix
    assert kimi_counts.prefix_attention_flops(32, 192, 128, 16384) > \
        1000 * kimi_counts.own_attention_flops(32, 192, 128, 1)


def test_the_recurrence_is_counted_from_the_sequential_form():
    token = hybrid_counts.delta_rule_token_flops(32, 128, 128)
    assert token == 32 * (7 * 128 * 128 + 256)
    assert kimi_counts.scan_flops(2048, 7, 32, 128, 128) == 2048 * 7 * token
    # 2,048 tokens of seven layers: 52.7 GFLOP, 0.27 ms at the peak
    assert round(kimi_counts.scan_flops(2048, 7, 32, 128, 128) / 1e9, 1) \
        == 52.7


def test_a_chunk_dispatch_adds_up():
    real, rows, start = 2048 + 1000, 2, 4096
    prefix, own = 2048 * start, 2048 ** 2 + 1000 ** 2
    whole = kimi_counts.chunk_model_flops(real, rows, prefix, own, CELL)
    parts = (2 * real * CELL["params_met_per_token"]
             + 2 * rows * CELL["head_params"]
             + kimi_counts.scan_flops(real, 7, 32, 128, 128)
             + 2 * (kimi_counts.prefix_attention_flops(32, 192, 128, prefix)
                    + kimi_counts.own_attention_flops(32, 192, 128, own)))
    assert whole == parts
    # a row at position 0 with no prefix costs less than one behind 4k
    assert kimi_counts.chunk_model_flops(real, rows, 0, own, CELL) < whole


def test_the_cells_decode_step_by_hand():
    """ISSUE 48's arithmetic: 4.73 GB of tables, 64 slots' state read
    and written (1.95 GB), 570k live tokens' two latent rows (1.31 GB):
    about 8 GB, 9.8 ms at 819 GB/s."""
    need = kimi_counts.decode_step_bytes(64, 570e3, CELL)
    assert round(CELL["weight_bytes"] / 1e9, 2) == 4.73
    assert round(2 * 64 * CELL["state_bytes_per_slot"] / 1e9, 2) == 1.95
    assert round(570e3 * 2 * 1152 / 1e9, 2) == 1.31
    assert round(need / 1e9, 1) == 8.0
    assert round(need / 819e9 * 1e3, 1) == 9.8
    # the state update alone, over all 65 rows of the pool
    state = kimi_counts.state_decode_bytes(65, CELL)
    assert round(state / 1e9, 2) == 2.02
    least, bound = kimi_counts.latent_decode_least_s(570e3, CELL, 819e9,
                                                     197e12)
    assert bound == "B" and round(least * 1e3, 2) == 1.6


def test_the_reader_returns_nothing_without_a_trace_or_the_family():
    reader = load_module("readers", "kimi_roofline")

    class Ctx:
        peaks = {"bytes_per_s": 819e9, "flops_per_s": 197e12}
        trace_dir = "/nonexistent"
        log = staticmethod(lambda msg: None)
    facts = {"model": CELL, "num_slots": 64, "mean_live_tokens": 5e5,
             "mean_active_slots": 60.0}
    for what in ("kda_scan", "kda_state", "mla_decode", "mla_prefix",
                 "moe_experts", "decode_step", "prefill_step"):
        assert reader.read(None, facts, Ctx, what, "decode") is None
    # another architecture's facts: nothing, and no error
    other = {"model": {"layers": 5, "latent_width": 512}}
    assert reader.read({"devices": []}, other, Ctx, "decode_step",
                       "decode") is None
