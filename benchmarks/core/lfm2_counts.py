"""Operations and bytes of a served hybrid of gated short convolutions
and rotary grouped-query attention whose expert layers hold EVERY
expert, from shapes and from the `serve/decode` and `serve/prefill`
spans' arguments alone, and kept
with the benchmark so no later PR can move them. Each count is of the
LEAST work, the same whatever implements it: a table is read once if any
row lands on its expert and not at all if none does, a landed assignment
costs its three products and nothing for a tile's slack, attention every
(query, key) pair inside the causal mask once.
"""


def short_conv_token_flops(hidden, width):
    """One token of one convolution layer beside its two products: the
    gate product B * X, `width` taps a channel (a multiply and an add
    each: 2 x width x hidden) and the gate C."""
    return (2.0 * width + 2.0) * hidden


def expert_half_least_s(tables_read, landed, hidden, ffn, bytes_per_s,
                        flops_per_s, bytes_per_el=2):
    """(least seconds, "B" or "FLOP": which bound) of the expert
    products of one decode step over all its expert layers:
    `tables_read` expert-layers' tables with at least one row (three of
    hidden x ffn each), `landed` assignments (a row in, a row out:
    hidden elements each) over the HBM's peak, against `landed`
    assignments' three products (2 x hidden x ffn each) over the MXU's."""
    moved = (3.0 * tables_read * hidden * ffn
             + 2.0 * landed * hidden) * bytes_per_el
    by_bytes = moved / bytes_per_s
    by_flops = 6.0 * hidden * ffn * landed / flops_per_s
    return (by_bytes, "B") if by_bytes >= by_flops else (by_flops, "FLOP")


def own_attention_flops(heads, head_dim, own_pairs):
    """Scores and weighted values of a prompt's queries over its OWN
    rows, the causal half: q k^T and p v are 2 x head_dim each a (query,
    key) pair a head; `own_pairs` is the sum over the rows of real
    tokens squared."""
    return 2.0 * heads * head_dim * own_pairs


def prefill_model_flops(real_tokens, rows, own_pairs, m):
    """The model's operations for ONE prefill dispatch of `rows` rows:
    twice the parameters a real token's products meet on this chip, the
    head once a row (ONE last position each at least), the convolutions'
    elementwise work, the attention layers' least scores. `m` is
    `families/lfm2.describe_served`'s dict."""
    return (2.0 * real_tokens * m["params_met_per_token"]
            + 2.0 * rows * m["head_params"]
            + real_tokens * m["conv_layers"] * short_conv_token_flops(
                m["hidden"], m["conv_width"])
            + m["attention_layers"] * own_attention_flops(
                m["heads"], m["head_dim"], own_pairs))


def decode_step_bytes(active_slots, live_tokens, m):
    """HBM bytes one decode step needs: every held weight once as it is
    held (the tied table once: the head reads it whole, the embedding a
    row a slot of it), the live tokens' keys and values once, the active
    slots' tails read and written."""
    return (m["weight_bytes"] + live_tokens * m["kv_bytes_per_token"]
            + 2.0 * active_slots * m["tail_bytes_per_slot"])
