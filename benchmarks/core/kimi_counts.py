"""Operations and bytes of a served hybrid of delta-rule and latent
attention layers whose prompts are prefilled in CHUNKS (a chunk starts
from the state its slot holds and reads the latent rows earlier chunks
wrote), from shapes and from the `serve/chunk` spans' arguments alone,
and kept with the benchmark so no later PR can move them. Each count is
of the LEAST work, the same whatever implements it: the recurrence by
its sequential form (`core/hybrid_counts.delta_rule_token_flops`), the
latent layers' scores and weighted values at the expanded widths, every
(query, key) pair once (a reader that expands the prefix again, or
carries the queries into the latent's space, does more arithmetic for
the same result: that is its business, not the count's).
"""

from core import hybrid_counts as hc
from core import mla_counts as mc


def prefix_attention_flops(heads, key_width, value_width, prefix_pairs):
    """A latent layer's scores and weighted values of a chunk's real
    queries over the rows BEFORE its start: q k^T (2 x key_width) and
    p v (2 x value_width) a (query, prefix row) pair a head;
    `prefix_pairs` is the sum over the rows of real tokens x start."""
    return 2.0 * heads * (key_width + value_width) * prefix_pairs


def own_attention_flops(heads, key_width, value_width, own_pairs):
    """... over the chunk's OWN rows, the causal half: `own_pairs` is
    the sum over the rows of real tokens squared."""
    return float(heads) * (key_width + value_width) * own_pairs


def scan_flops(real_tokens, layers, heads, key_dim, value_dim):
    """The delta-rule recurrence for `real_tokens` tokens of `layers`
    layers, by the sequential form."""
    return float(real_tokens) * layers * hc.delta_rule_token_flops(
        heads, key_dim, value_dim)


def chunk_model_flops(real_tokens, rows, prefix_pairs, own_pairs, m):
    """The model's operations for ONE chunk dispatch of `rows` rows:
    twice the parameters a real token's products meet on this chip, the
    head once a row (ONE last position each at least), the recurrence,
    the latent layers' attention over own rows and prefix. `m` is
    `families/kimi_linear.describe_served`'s dict."""
    attention = m["latent_layers"] * (
        prefix_attention_flops(m["heads"], m["key_width"],
                               m["value_width"], prefix_pairs)
        + own_attention_flops(m["heads"], m["key_width"], m["value_width"],
                              own_pairs))
    return (2.0 * real_tokens * m["params_met_per_token"]
            + 2.0 * rows * m["head_params"]
            + scan_flops(real_tokens, m["kda_layers"], m["kda_heads"],
                         m["kda_key_dim"], m["kda_value_dim"])
            + attention)


def state_decode_bytes(pool_rows, m):
    """The decode program's one-token state update over every row of
    the state pool, every delta-rule layer."""
    return m["kda_layers"] * hc.delta_rule_decode_bytes(
        pool_rows, m["kda_heads"], m["kda_key_dim"], m["kda_value_dim"],
        m["kda_tail_bytes_per_layer"])


def latent_decode_least_s(live_tokens, m, bytes_per_s, flops_per_s):
    """The absorbed reader over the latent layers (`core/mla_counts`)."""
    return mc.latent_decode_least_s(
        live_tokens, m["latent_layers"], m["heads"], m["latent_width"],
        m["shared_key_width"], bytes_per_s, flops_per_s)


def decode_step_bytes(active_slots, live_tokens, m):
    """HBM bytes one decode step needs: every held weight once as it is
    held, the active slots' recurrent state read and written, the live
    tokens' latent rows once."""
    return (m["weight_bytes"]
            + 2.0 * active_slots * m["state_bytes_per_slot"]
            + mc.latent_decode_bytes(live_tokens, m["latent_layers"],
                                     m["latent_width"],
                                     m["shared_key_width"]))
