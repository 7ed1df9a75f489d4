"""`core/sparse_counts.py` and `core/scope_within.py` on hand-worked
cases (`python -m pytest benchmarks/tests -q`; no JAX)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from core import scope_within, sparse_counts  # noqa: E402


def test_pairs_inside_a_mask():
    # 4 positions, causal: 1 + 2 + 3 + 4
    assert sparse_counts.mask_pairs(4) == 10
    # a window of 2: 1 + 2 + 2 + 2
    assert sparse_counts.mask_pairs(4, 2) == 7
    assert sparse_counts.mask_pairs(4, 4) == sparse_counts.mask_pairs(4, 9) \
        == 10
    # the cell's two kinds of layer, as ISSUE 32 states them
    assert sparse_counts.mask_pairs(8192) == 33_558_528
    assert sparse_counts.mask_pairs(8192, 4096) == 25_167_872


def test_attention_kernel_cost_by_hand():
    # 1 row, 2 query heads over 1 key-value head of 4, 3 positions, one
    # causal layer (6 pairs): 7 matmuls x 2 x 6 x 4 x 2 heads
    flops, nbytes = sparse_counts.attention_kernel_cost(
        1, 2, 1, 4, 3, [6], bytes_per_el=2)
    assert flops == 7 * 2 * 6 * 4 * 2
    # a tensor of one head: 3 x 4 x 2 bytes = 24; q, o (twice), do, dq
    # and q again in the backward: 6 tensors of 2 heads; k, v twice each
    # and dk, dv: 6 of 1 head
    assert nbytes == 6 * 2 * 24 + 6 * 1 * 24


def test_grouped_product_cost_by_hand():
    # 10 landed rows through 3 tables of 4 x 2, 5 held tables sets
    flops, nbytes = sparse_counts.grouped_product_cost(10, 5, 4, 2, 3, 2)
    assert flops == 6 * 10 * 3 * 4 * 2
    weights = 5 * 3 * 4 * 2 * 2
    rows = 10 * 3 * (4 + 2) * 2
    assert nbytes == 3 * (weights + rows)
    # the cell: 98,304 landed a step (a quarter of 16,384 x 6 x 4 layers)
    flops, _ = sparse_counts.grouped_product_cost(98_304, 64, 2560, 768, 3)
    assert round(flops / 1e12, 2) == 3.48


def test_train_step_flops_by_hand():
    # 8 tokens, 100 dense parameters, 5 landed x 10 expert parameters,
    # 1 row of 2 heads of 4 over one layer of 6 pairs
    got = sparse_counts.train_step_flops(8, 100, 5, 10, 1, 2, 4, [6])
    assert got == 6 * 8 * 100 + 6 * 5 * 10 + 3 * 2 * 2 * 6 * 4 * 2
    # the cell by ISSUE 32's own arithmetic: 30.7 TFLOP a step
    dense = 4 * 21_135_360 + 38_016 * 2560
    pairs = [33_558_528] + [25_167_872] * 3
    total = sparse_counts.train_step_flops(
        16_384, dense, 98_304, 5_898_240, 2, 28, 128, pairs)
    assert 30.5e12 < total < 31.0e12


def test_time_anywhere_under_a_scope():
    step = "jit__micro_step(1)"
    ops = [
        # a window layer's kernel: attn_core innermost, attn_window around
        ["k.1", 100.0, 40.0,
         "jit(_micro_step)/jvp(attn_window)/attn_core/pallas_call",
         "custom-call"],
        # a copy the compiler put beside it, same scopes, another opcode
        ["c.1", 140.0, 5.0,
         "jit(_micro_step)/jvp(attn_window)/attn_core/copy", "copy"],
        ["k.2", 150.0, 30.0,
         "jit(_micro_step)/transpose(jvp(attn_global))/attn_core/pallas_call",
         "custom-call"],
        # `attn_window` as a primitive's own name is not a scope
        ["x.1", 180.0, 7.0, "jit(_micro_step)/mlp/attn_window", "fusion"],
        # outside any step program
        ["k.3", 900.0, 11.0,
         "jit(other)/jvp(attn_window)/attn_core/pallas_call", "custom-call"],
    ]
    view = {"devices": [{"name": "/device:TPU:0",
                         "modules": [[step, 90.0, 110.0],
                                     [step, 300.0, 100.0],
                                     ["jit_other(2)", 890.0, 30.0]],
                         "ops": ops}]}
    pattern = "micro_step|batch_step"
    # two executions of the step: per-execution means
    assert scope_within.within_ns(view, pattern, "attn_window") == 45.0 / 2
    assert scope_within.within_ns(
        view, pattern, "attn_window", "custom-call") == 40.0 / 2
    assert scope_within.within_ns(
        view, pattern, "attn_global", "custom-call") == 30.0 / 2
    assert scope_within.within_ns(view, pattern, "attn_core") == 75.0 / 2
    assert scope_within.within_ns(view, "no_such_program", "attn_core") \
        is None
