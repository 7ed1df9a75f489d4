"""Operations and bytes of a served hybrid's own layers (gated
delta-rule attention, held experts), from shapes alone and kept with the
benchmark so no later PR can move them. Counted from the SEQUENTIAL form
of the recurrence, so that the count is the same whatever implements it
(a chunked scan does more arithmetic for the same result: that is its
business, not the count's).
"""


def delta_rule_token_flops(heads, key_dim, value_dim):
    """One token of one layer, by the equations: the decay of the state
    (1 a cell), k^T S (2), the rank-one update (2 and the step-scaled
    key), S^T q (2): 7 a cell of the state plus the vectors."""
    cells = key_dim * value_dim
    return heads * (7.0 * cells + key_dim + value_dim)


def delta_rule_decode_bytes(rows, heads, key_dim, value_dim, tail_bytes):
    """One layer's one-token update over `rows` rows of the state pool:
    every row's float32 state read once and written once, the layer's
    activations (q, k, the decay and the scaled key in, v in, o out:
    float32 vectors) and the convolution tail (`tail_bytes` a row) read
    and written."""
    state = 2.0 * rows * heads * key_dim * value_dim * 4
    vectors = rows * heads * (4 * key_dim + 2 * value_dim) * 4
    return state + vectors + 2.0 * rows * tail_bytes


def held_tables_bytes(held, hidden, ffn, bytes_per_el=2):
    """The three tables of every held expert of one layer, read once."""
    return 3.0 * held * hidden * ffn * bytes_per_el


def hybrid_decode_step_bytes(weight_bytes, active_slots, state_per_slot,
                             live_tokens, kv_per_token):
    """HBM bytes one decode step needs: every held weight once as it is
    held, the active slots' recurrent state read and written, the live
    keys and values once."""
    return (weight_bytes + 2.0 * active_slots * state_per_slot
            + live_tokens * kv_per_token)
