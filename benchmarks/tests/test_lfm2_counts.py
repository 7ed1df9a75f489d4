"""`core/lfm2_counts.py` on hand-worked cases, and
`readers/lfm2_roofline.py` on facts without a trace
(`python -m pytest benchmarks/tests -q`; no JAX)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from core import lfm2_counts  # noqa: E402
from loader import load_module  # noqa: E402

# the cell's sizes, as `families/lfm2.describe_served` gives them
CELL = {"family": "lfm2", "layers": 10, "expert_layers": 8, "hidden": 2048,
        "conv_layers": 8, "conv_width": 3, "attention_layers": 2,
        "heads": 32, "kv_heads": 8, "head_dim": 64,
        "kv_bytes_per_token": 2 * 2 * 8 * 64 * 2,
        "tail_bytes_per_slot": 8 * 2 * 2048 * 2,
        "experts_held": 64, "ffn": 1536, "experts_per_token": 4,
        "router_outputs": 64,
        "params_met_per_token": (8 * 16_783_360 + 2 * 10_485_888
                                 + 2 * 72_351_744
                                 + 8 * (131_136 + 4 * 9_437_184)),
        "head_params": 65536 * 2048,
        "weight_bytes": 2 * 5_267_090_176}
PEAK_BYTES, PEAK_FLOPS = 819e9, 197e12


def test_the_expert_half_is_bound_by_its_tables_at_the_cells_rows():
    """ISSUE 51's words: 256 rows x 4 over 64 experts a layer: the
    tables of eight layers are 9.67 GB, 11.8 ms at the HBM's peak; the
    routed rows' arithmetic is 0.8 ms."""
    landed, tables = 8 * 1024, 8 * 64
    least, bound = lfm2_counts.expert_half_least_s(
        tables, landed, 2048, 1536, PEAK_BYTES, PEAK_FLOPS)
    assert bound == "B"
    table_bytes = 3 * tables * 2048 * 1536 * 2
    assert round(table_bytes / 1e9, 2) == 9.66
    rows_bytes = 2 * landed * 2048 * 2
    assert least == (table_bytes + rows_bytes) / PEAK_BYTES
    assert 11.7e-3 < least < 11.95e-3
    flops_s = 6 * 2048 * 1536 * landed / PEAK_FLOPS
    assert round(flops_s * 1e3, 2) == 0.78
    # every held expert on every row: 16 times the arithmetic
    every = 6 * 2048 * 1536 * (8 * 64 * 256) / PEAK_FLOPS
    assert round(every * 1e3, 1) == 12.6 and every > least
    # an expert with no row is not read: one table set less a layer
    fewer, _ = lfm2_counts.expert_half_least_s(
        tables - 8, landed, 2048, 1536, PEAK_BYTES, PEAK_FLOPS)
    assert round((least - fewer) * PEAK_BYTES) == 8 * 3 * 2048 * 1536 * 2
    # many rows on few tables: the arithmetic bounds
    _, bound = lfm2_counts.expert_half_least_s(
        1, 4096, 2048, 1536, PEAK_BYTES, PEAK_FLOPS)
    assert bound == "FLOP"


def test_a_convolution_token_beside_its_products():
    """2 x 3 x 2,048 for the taps beside the gate product and the gate:
    nothing beside 2 x 16.8M for W_in and W_out."""
    assert lfm2_counts.short_conv_token_flops(2048, 3) == (6 + 2) * 2048
    assert lfm2_counts.short_conv_token_flops(2048, 3) < 1e-3 * 2 * 16_783_360


def test_a_prefill_dispatch_adds_up():
    real, rows = 455, 1
    whole = lfm2_counts.prefill_model_flops(real, rows, real * real, CELL)
    parts = (2 * real * CELL["params_met_per_token"]
             + 2 * rows * CELL["head_params"]
             + real * 8 * 8 * 2048
             + 2 * 2 * 32 * 64 * real * real)
    assert whole == parts
    # 1.2 GFLOP a token: the mixers, the dense layers, four experts a
    # layer of eight
    assert round(2 * CELL["params_met_per_token"] / 1e9, 2) == 1.21
    # attention's least scores are nothing beside the products here
    assert lfm2_counts.own_attention_flops(32, 64, real * real) * 2 \
        < 0.01 * whole


def test_a_decode_step_reads_weights_pages_and_tails():
    need = lfm2_counts.decode_step_bytes(250.0, 150_000.0, CELL)
    assert need == (2 * 5_267_090_176 + 150_000 * 4096
                    + 2 * 250 * 65_536)
    # 10.53 GB of weights, 0.61 of live keys and values, 0.03 of tails
    assert round(need / 1e9, 2) == 11.18


def test_the_reader_finds_nothing_without_a_trace_or_another_family():
    reader = load_module("readers", "lfm2_roofline")
    facts = {"model": CELL, "mean_active_slots": 250.0,
             "mean_live_tokens": 150_000.0}
    for what in ("moe_experts", "decode_step", "prefill_step"):
        assert reader.read(None, facts, None, what, "decode") is None
    other = {"model": {"family": "kimi_linear"}}
    assert reader.read({}, other, None, "decode_step", "decode") is None
