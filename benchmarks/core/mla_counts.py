"""Operations and bytes of a served latent-attention model's own layers
(multi-head latent attention over ONE latent row a token a layer), from
shapes alone and kept with the benchmark so no later PR can move them.
Each count is of the LEAST work: the latent row at the lanes the
equations give it (a pool that pads the row reads more: that is its
business, not the count's), every product once.
"""


def latent_row_bytes(latent_width, rope_width, bytes_per_el=2):
    """One cached token of one layer: `[c | k_r]`."""
    return (latent_width + rope_width) * bytes_per_el


def latent_decode_bytes(live_tokens, layers, latent_width, rope_width,
                        bytes_per_el=2):
    """What one decode step's reader must read: every live token's row
    of every layer, ONCE (keys and values are the same bytes)."""
    return float(live_tokens) * layers * latent_row_bytes(
        latent_width, rope_width, bytes_per_el)


def latent_decode_flops(live_tokens, layers, heads, latent_width,
                        rope_width):
    """The absorbed reader's products for one decode step: a head's
    query against a row (2 a lane of `latent_width + rope_width`) and
    its probability against the row's latent (2 a lane of
    `latent_width`), every head, every live token, every layer."""
    return float(live_tokens) * layers * heads * 2.0 * (
        2 * latent_width + rope_width)


def latent_decode_least_s(live_tokens, layers, heads, latent_width,
                          rope_width, bytes_per_s, flops_per_s,
                          bytes_per_el=2):
    """The least time of the reader: the larger of its bytes over the
    HBM peak and its products over the MXU peak, and which bound it
    ("B" or "FLOP")."""
    by_bytes = latent_decode_bytes(live_tokens, layers, latent_width,
                                   rope_width, bytes_per_el) / bytes_per_s
    by_flops = latent_decode_flops(live_tokens, layers, heads,
                                   latent_width, rope_width) / flops_per_s
    return (by_bytes, "B") if by_bytes >= by_flops else (by_flops, "FLOP")


def expanded_scores_flops(heads, key_width, value_width, tokens, rows):
    """The least the EXPANDED scores and weighted values can cost for
    `tokens` prompt tokens in `rows` prompts that each attend to
    themselves alone: the causal half of q k^T (2 x key_width a pair)
    and of p v (2 x value_width), a head; the sum of squares is least
    where the prompts are equal."""
    return float(heads) * (key_width + value_width) * tokens * tokens \
        / max(rows, 1)


def prefill_model_flops(tokens, rows, params_met_per_token, head_params,
                        layers, heads, key_width, value_width):
    """The model's operations for one prefill of `tokens` real prompt
    tokens in at most `rows` prompts: twice the parameters a token's
    products meet on this chip (the latent's expansion W_kvb among
    them: it is a product a token), the head once (ONE last position at
    least), every layer's expanded scores."""
    return (2.0 * tokens * params_met_per_token + 2.0 * head_params
            + layers * expanded_scores_flops(heads, key_width, value_width,
                                             tokens, rows))


def decode_step_bytes(weight_bytes, live_tokens, layers, latent_width,
                      rope_width, bytes_per_el=2):
    """HBM bytes one decode step needs: every held weight once as it is
    held, every live token's latent rows once."""
    return weight_bytes + latent_decode_bytes(
        live_tokens, layers, latent_width, rope_width, bytes_per_el)
