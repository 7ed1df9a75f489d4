"""`core/mla_counts.py` on hand-worked cases, and `readers/mla_roofline.py`
on facts without a trace (`python -m pytest benchmarks/tests -q`; no
JAX)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from core import mla_counts  # noqa: E402
from loader import load_module  # noqa: E402


def test_the_readers_bytes_and_products_by_hand():
    # 10 live tokens, 2 layers, a row of 6 + 2 bfloat16 values: 16 B a
    # row, read once for keys and values
    assert mla_counts.latent_row_bytes(6, 2) == 16
    assert mla_counts.latent_decode_bytes(10, 2, 6, 2) == 10 * 2 * 16
    # 3 heads: a query against the 8 lanes (2 x 8) and a probability
    # against the 6 (2 x 6), a head a token a layer
    assert mla_counts.latent_decode_flops(10, 2, 3, 6, 2) == \
        10 * 2 * 3 * (16 + 12)


def test_the_cells_decode_step_by_hand():
    """ISSUE 43's arithmetic: 526k live tokens x 5 layers x 1,152 B =
    3.03 GB = 3.7 ms at 819 GB/s; 526k x 64 x (576 + 512) x 2 x 5 = 366
    GFLOP = 1.9 ms at 197 TFLOP/s: bound by bytes."""
    live = 526e3
    assert mla_counts.latent_row_bytes(512, 64) == 1152
    assert round(mla_counts.latent_decode_bytes(live, 5, 512, 64) / 1e9,
                 2) == 3.03
    assert round(mla_counts.latent_decode_flops(live, 5, 64, 512, 64)
                 / 1e9) == 366
    least, bound = mla_counts.latent_decode_least_s(
        live, 5, 64, 512, 64, 819e9, 197e12)
    assert bound == "B" and round(least * 1e3, 1) == 3.7
    # at a peak of bytes four times as high the products bound it
    least, bound = mla_counts.latent_decode_least_s(
        live, 5, 64, 512, 64, 4 * 819e9, 197e12)
    assert bound == "FLOP" and round(least * 1e3, 2) == 1.86
    # FLOP a byte of the reader: 64 x 2 x 1,088 / 1,152 = 121
    assert round(64 * 2 * (576 + 512) / 1152) == 121
    # the whole step: 6.98 GB of weights and the rows
    assert round(mla_counts.decode_step_bytes(6.98e9, live, 5, 512, 64)
                 / 1e9, 2) == 10.01


def test_the_least_expanded_scores_of_prompts_that_attend_to_themselves():
    # one prompt of 10, 2 heads, keys of 6 and values of 4: the causal
    # half of 2 x 6 and of 2 x 4 a pair
    assert mla_counts.expanded_scores_flops(2, 6, 4, 10, 1) == 2 * 10 * 100
    # the same tokens in two prompts: least where they are equal, half
    assert mla_counts.expanded_scores_flops(2, 6, 4, 10, 2) == 1000
    # a prompt of 2,200 tokens at the published widths: 64 heads x (192
    # + 128) x 2,200 = 45 MFLOP a token a layer
    per_token = mla_counts.expanded_scores_flops(64, 192, 128, 2200, 1) / 2200
    assert round(per_token / 1e6) == 45
    whole = mla_counts.prefill_model_flops(
        2200, 1, 1.0e9, 1.5e8, 5, 64, 192, 128)
    assert whole == 2 * 2200 * 1.0e9 + 2 * 1.5e8 + 5 * 2200 * per_token


class _Ctx:
    peaks = {"bytes_per_s": 819e9, "flops_per_s": 197e12}
    trace_dir = "/nonexistent"
    log = staticmethod(lambda msg: None)


def test_the_reader_finds_nothing_without_a_trace_or_the_sizes():
    read = load_module("readers", "mla_roofline").read
    facts = {"model": {"latent_width": 512}, "mean_live_tokens": 1.0}
    assert read(None, facts, _Ctx, "mla_decode", "decode",
                ["attn_core"]) is None
    # another architecture's facts: nothing, and no error
    assert read({}, {"model": {"layers": 24}}, _Ctx, "mla_decode",
                "decode", ["attn_core"]) is None
    assert read({}, {}, _Ctx, "decode_step", "decode") is None
