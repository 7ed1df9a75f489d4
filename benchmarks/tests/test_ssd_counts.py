"""`core/ssd_counts.py` on hand-worked cases, and `readers/ssd_roofline.py`
on facts without a trace (`python -m pytest benchmarks/tests -q`; no
JAX)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from core import ssd_counts  # noqa: E402
from loader import load_module  # noqa: E402


def test_one_token_of_the_recurrence_by_hand():
    # 2 heads of 3 channels over a state width of 4: 24 cells x 5 (decay
    # 1, dt x B^T added 2, S C 2) + a head's vectors (dt x 3, D x 3, the
    # sum 3, dt A 1)
    assert ssd_counts.ssd_token_flops(2, 3, 4) == 24 * 5 + 2 * (9 + 1)
    # the cell's layer: 5 a cell of 128 x 64 x 128, 5.27 MFLOP a token
    got = ssd_counts.ssd_token_flops(128, 64, 128)
    assert got == 5 * 128 * 64 * 128 + 128 * (3 * 64 + 1)
    assert round(got / 1e6, 2) == 5.27


def test_the_one_token_updates_bytes_by_hand():
    # 3 rows of 2 heads x 3 x 4 float32: state read and written 2 x 3 x
    # 24 x 4 B; x in and y out 2 x 6, B and C 2 x 4, dt 2: 22 float32 a
    # row; the tail (10 B a row) read and written
    got = ssd_counts.ssd_decode_bytes(3, 2, 3, 4, 10)
    assert got == 2 * 3 * 24 * 4 + 3 * 22 * 4 + 2 * 3 * 10
    # the cell: 65 rows of 128 x 64 x 128, a tail of 3 x 8,448 bfloat16:
    # 0.556 GB a layer, 5.0 GB over the nine
    layer = ssd_counts.ssd_decode_bytes(65, 128, 64, 128, 3 * 8448 * 2)
    assert round(9 * layer / 1e9, 2) == 5.01


def test_the_least_scores_of_prompts_that_attend_to_themselves():
    # one prompt of 10, 2 heads of 4: 2 x 2 x 4 x 100
    assert ssd_counts.causal_scores_flops(2, 4, 10, 1) == 1600
    # the same tokens in two prompts: least where they are equal, half
    assert ssd_counts.causal_scores_flops(2, 4, 10, 2) == 800
    assert ssd_counts.causal_scores_flops(2, 4, 10, 0) == 1600


def test_a_prefills_model_operations_by_hand():
    # 10 tokens in one prompt, 100 parameters met a token, a head of 50,
    # one softmax layer of 2 heads of 4, two recurrent layers of 2 x 3 x 4
    got = ssd_counts.prefill_model_flops(10, 1, 100, 50, 1, 2, 4, 2, 2, 3, 4)
    assert got == 2 * 10 * 100 + 2 * 50 + 1600 + 10 * 2 * (24 * 5 + 20)
    # the cell's mean request by ISSUE 41: 2,300 tokens x 1,626M
    # parameters met = 7.5 TFLOP, the scores 0.04, the recurrence 0.11
    got = ssd_counts.prefill_model_flops(
        2300, 1, 1.626e9, 205.5e6, 1, 32, 128, 9, 128, 64, 128)
    assert 7.6e12 < got < 7.7e12


class _Ctx:
    peaks = {"bytes_per_s": 819e9, "flops_per_s": 197e12}
    trace_dir = "/nonexistent"
    log = staticmethod(lambda msg: None)


def test_the_reader_finds_nothing_for_another_architecture_or_no_trace():
    read = load_module("readers", "ssd_roofline").read
    params = dict(what="ssd_state", step_pattern="decode",
                  scopes=["ssd_state"])
    # no trace
    assert read(None, {"model": {"ssd_heads": 128}}, _Ctx, **params) is None
    # a family with no state-space layer (solar_open2's facts)
    assert read({"planes": []}, {"model": {"recurrent_layers": 3}}, _Ctx,
                **params) is None
