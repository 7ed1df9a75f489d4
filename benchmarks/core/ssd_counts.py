"""Operations and bytes of a served state-space hybrid's own layers
(Mamba-2 mixers), from shapes alone and kept with the benchmark so no
later PR can move them. Counted from the SEQUENTIAL form of the
recurrence, so that the count is the same whatever implements it (a
chunked scan does other arithmetic for the same result: that is its
business, not the count's). Each count is of the LEAST work.
"""


def ssd_token_flops(heads, head_dim, state_dim):
    """One token of one layer, by the equations: the decay of the state
    (1 a cell), dt x B^T added to it (2), S C (2): 5 a cell of the
    heads x head_dim x state_dim state, plus the vectors (dt x, D x and
    its sum a channel; dt A a head)."""
    return heads * (5.0 * head_dim * state_dim + 3 * head_dim + 1)


def ssd_decode_bytes(rows, heads, head_dim, state_dim, tail_bytes):
    """One layer's one-token update over `rows` rows of the state pool:
    every row's float32 state read once and written once, the layer's
    activations (x in, y out a head and channel; B and C a row; dt a
    head: float32 vectors) and the convolution tail (`tail_bytes` a row)
    read and written."""
    state = 2.0 * rows * heads * head_dim * state_dim * 4
    vectors = rows * (2 * heads * head_dim + 2 * state_dim + heads) * 4
    return state + vectors + 2.0 * rows * tail_bytes


def causal_scores_flops(heads, head_dim, tokens, rows):
    """The least a softmax layer's scores and weighted values can cost
    for `tokens` prompt tokens in `rows` prompts that each attend to
    themselves alone: 2 x heads x head_dim x n^2 a prompt of n (the
    causal half of q k^T and of p v), and the sum of squares is least
    where the prompts are equal."""
    return 2.0 * heads * head_dim * tokens * tokens / max(rows, 1)


def prefill_model_flops(tokens, rows, params_met_per_token, head_params,
                        softmax_layers, heads, head_dim, recurrent_layers,
                        ssd_heads, ssd_head_dim, ssd_state_dim):
    """The model's operations for one prefill of `tokens` real prompt
    tokens in at most `rows` prompts: twice the parameters a token's
    products meet on this chip, the head once (ONE last position at
    least), the attention layers' scores, the recurrence by its
    sequential form."""
    return (2.0 * tokens * params_met_per_token + 2.0 * head_params
            + softmax_layers * causal_scores_flops(heads, head_dim, tokens,
                                                   rows)
            + tokens * recurrent_layers * ssd_token_flops(
                ssd_heads, ssd_head_dim, ssd_state_dim))
