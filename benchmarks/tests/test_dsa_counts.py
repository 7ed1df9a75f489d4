"""`core/dsa_counts.py` on hand-worked cases, and
`readers/dsa_roofline.py` on facts without a trace
(`python -m pytest benchmarks/tests -q`; no JAX)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from core import dsa_counts  # noqa: E402
from loader import load_module  # noqa: E402

# the cell's sizes, as `families/keye_vl2.describe_served` gives them
CELL = {"family": "keye_vl2", "layers": 6, "expert_layers": 6,
        "hidden": 2048, "heads": 32, "kv_heads": 4, "head_dim": 128,
        "indexer_heads": 16, "indexer_dim": 64, "topk": 2048,
        "experts_held": 16, "ffn": 768, "experts_per_token": 8,
        "router_outputs": 128,
        "params_met_per_token": 6 * (18_874_624 + 2_261_120 + 262_144
                                     + 4_718_592),
        "head_params": 18992 * 2048, "weight_bytes": 2 * 659_190_016}


def test_a_scored_and_a_selected_pair_by_hand():
    """ISSUE 55's words: the indexer 2 x 16 x 64 a scored pair and 128 B
    a scored key; attention 4 x 128 x 32 a selected pair and 2,048 B a
    selected row."""
    assert dsa_counts.indexer_pair_flops(16, 64) == 2048
    assert dsa_counts.indexer_key_bytes(64) == 128
    assert dsa_counts.selected_pair_flops(32, 128) == 16384
    assert dsa_counts.selected_row_bytes(4, 128) == 2048
    # 3 queries that score 5, 6 and 7 keys, 2 heads of 4: 18 pairs x 16
    assert dsa_counts.indexer_pair_flops(2, 4) * (5 + 6 + 7) == 288
    # the six layers
    assert dsa_counts.indexer_flops(18, CELL) == 6 * 18 * 2048
    assert dsa_counts.selected_flops(18, CELL) == 6 * 18 * 16384
    assert dsa_counts.indexer_bytes(18, CELL) == 6 * 18 * 128
    assert dsa_counts.selected_bytes(18, CELL) == 6 * 18 * 2048


def test_the_cells_decode_step_by_hand():
    """16 rows at the cell's mean context (37k): 4.7 MB of indexer keys
    and 4.2 MB of selected rows a row a layer, where every live token's
    keys and values would be 76 MB; with the 1.32 GB of tables 2.17 GB a
    step, 2.6 ms at 819 GB/s."""
    live, rows = 37_000, 16
    assert round(live * 128 / 1e6, 1) == 4.7
    assert round(2048 * 2048 / 1e6, 1) == 4.2
    assert round(live * 2048 / 1e6) == 76
    need = dsa_counts.decode_step_bytes(rows * live, rows * 2048, CELL)
    assert need == (CELL["weight_bytes"] + 6 * rows * live * 128
                    + 6 * rows * 2048 * 2048)
    assert round(need / 1e9, 2) == 2.18
    assert round(need / 819e9 * 1e3, 1) == 2.7
    # the held tables alone: 16 experts x 3 x 2,048 x 768 x 2 B a layer
    assert dsa_counts.held_tables_bytes(CELL) == 6 * 16 * 4_718_592 * 2
    # a row of at most 2,048 positions selects what it scores
    short = dsa_counts.decode_step_bytes(1000, 1000, CELL)
    assert short - CELL["weight_bytes"] == 6 * 1000 * (128 + 2048)


def test_a_chunk_dispatch_adds_up():
    """A chunk of 2,048 real tokens that starts at 30,720: its queries
    score 2,048 x 30,720 + 2,048 x 2,049 / 2 pairs and select 2,048
    each."""
    real, start = 2048, 30720
    scored = real * start + real * (real + 1) // 2
    selected = real * 2048
    whole = dsa_counts.chunk_model_flops(real, 1, scored, selected, CELL)
    assert whole == (2 * real * CELL["params_met_per_token"]
                     + 2 * CELL["head_params"]
                     + 6 * scored * 2048 + 6 * selected * 16384)
    # the indexer's products (0.80 T) outweigh the selected attention's
    # (0.41 T): scoring every live token is the larger half at 30k
    assert round(dsa_counts.indexer_flops(scored, CELL) / 1e12, 2) == 0.8
    assert round(dsa_counts.selected_flops(selected, CELL) / 1e12, 2) == 0.41
    # a dense-masked reader's products over every live key, which the
    # count does NOT grant it: 15 times the selected pairs'
    assert round(scored / selected, 1) == 15.5
    first = dsa_counts.chunk_model_flops(real, 1, real * (real + 1) // 2,
                                         real * (real + 1) // 2, CELL)
    assert first < whole


def test_the_reader_returns_nothing_without_a_trace_or_the_family():
    reader = load_module("readers", "dsa_roofline")

    class Ctx:
        peaks = {"bytes_per_s": 819e9, "flops_per_s": 197e12}
        trace_dir = "/nonexistent"
        log = staticmethod(lambda msg: None)
    facts = {"model": CELL, "num_slots": 16, "mean_live_tokens": 5e5,
             "mean_active_slots": 15.0}
    for what in ("indexer_decode", "sparse_decode", "indexer_prefill",
                 "sparse_prefill", "moe_experts", "decode_step",
                 "prefill_step"):
        assert reader.read(None, facts, Ctx, what, "decode") is None
    # another architecture's facts: nothing, and no error
    other = {"model": {"family": "kimi_linear", "layers": 9}}
    assert reader.read({"devices": []}, other, Ctx, "decode_step",
                       "decode") is None
