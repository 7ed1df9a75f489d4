"""Operations and bytes of a stack with masked attention and routed
experts, from shapes and LANDED counts alone, kept with the benchmark so
no later PR can move them. As in `core/flops.py`, what the program
chooses to compute again (the expert layer runs its forward pass again in
the backward pass; a recomputed layer) is never counted; what the
algorithm itself needs is. `benchmarks/tests/test_sparse_counts.py` holds
each to a hand-worked case.
"""


def mask_pairs(seq, window=None):
    """(query, key) pairs inside a causal mask of `seq` positions, where
    `window` also asks `i - j < window`: a global row of 8,192 has
    33,558,528, a window of 4,096 leaves 25,167,872."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def attention_kernel_cost(rows, q_heads, kv_heads, head_dim, seq,
                          pairs_by_layer, bytes_per_el=2):
    """(flops, bytes) one training step asks of the attention kernels on
    one device. Flash attention's own algorithm (`core/flops.py`): 7
    matmuls a (row, query head), each 2 * pairs * head_dim, with the
    pairs INSIDE each layer's mask. Bytes: the forward reads q, k, v and
    writes o; the backward reads q, k, v, o, do and writes dq, dk, dv;
    q, o, do, dq have `q_heads` heads, k, v, dk, dv `kv_heads`."""
    flops = sum(7.0 * 2.0 * pairs * head_dim * rows * q_heads
                for pairs in pairs_by_layer)
    tensor = rows * seq * head_dim * bytes_per_el
    per_layer = 6.0 * q_heads * tensor + 6.0 * kv_heads * tensor
    return flops, per_layer * len(pairs_by_layer)


def grouped_product_cost(landed, tables, hidden, ffn, table_sets,
                         bytes_per_el=2):
    """(flops, bytes) one training step asks of the experts' grouped
    products: `landed` assignments a step (summed over the layers)
    through 3 tables of hidden x ffn, forward and two backward products
    (2 flops each): 6 * landed * 3 * hidden * ffn. Bytes: each of the 3
    passes reads every held table once (`tables` = experts held x
    layers sets of 3) and moves each landed row's operands: a product
    reads its rows and writes its result, (hidden + ffn) elements a row
    a table."""
    flops = 6.0 * landed * table_sets * hidden * ffn
    weights = tables * table_sets * hidden * ffn * bytes_per_el
    rows = landed * table_sets * (hidden + ffn) * bytes_per_el
    return flops, 3.0 * (weights + rows)


def train_step_flops(tokens, dense_params, landed, expert_params, rows,
                     q_heads, head_dim, pairs_by_layer):
    """Operations the forward and backward passes of one step require:
    6 x tokens x the parameters every token uses (head slice included,
    embedding lookup not) + 6 x landed assignments x an expert's
    parameters + attention by the pairs inside each layer's mask,
    forward once and backward twice (2 matmuls of 2 * pairs * head_dim
    a pass)."""
    attention = sum(3.0 * 2.0 * 2.0 * pairs * head_dim * rows * q_heads
                    for pairs in pairs_by_layer)
    return (6.0 * tokens * dense_params + 6.0 * landed * expert_params
            + attention)
