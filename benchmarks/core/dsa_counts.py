"""Operations and bytes of a served decoder whose attention reads a
LEARNED SELECTION of its tokens (an indexer scores every token a query
may see against one small key a token, the best `topk` are attended),
from shapes and from the `serve/decode` and `serve/chunk` spans'
arguments alone, and kept with the benchmark so no later PR can move
them. Each count is of what the ALGORITHM needs, the same whatever
implements it: the indexer's products and key bytes a SCORED (query,
key) pair, the attention's products and row bytes a SELECTED pair. A
reader that attends every live key under a mask, sorts where a count
would do, or gathers a row twice does more for the same result: that is
its business, not the count's, so its share reads LOW, never over 100.
"""


def indexer_pair_flops(heads, dim):
    """One scored (query, key) pair of one layer: q_j . k over `dim` for
    each of `heads` heads (2 x heads x dim); the ReLU, the weights and
    the sum over heads are not counted."""
    return 2.0 * heads * dim


def indexer_key_bytes(dim, bytes_per_el=2):
    """One scored key of one layer, read once."""
    return float(dim * bytes_per_el)


def selected_pair_flops(heads, head_dim):
    """One selected (query, key) pair of one layer: q . k and p v over
    `head_dim` for each of `heads` query heads (4 x head_dim x heads)."""
    return 4.0 * head_dim * heads


def selected_row_bytes(kv_heads, head_dim, bytes_per_el=2):
    """One selected token's keys AND values of one layer, read once."""
    return 2.0 * kv_heads * head_dim * bytes_per_el


def indexer_flops(scored_pairs, m):
    """Every layer's indexer over `scored_pairs` (query, key) pairs (a
    span's `scored_tokens`: a query at position p scores p + 1 keys).
    `m` is `families/keye_vl2.describe_served`'s dict."""
    return m["layers"] * scored_pairs * indexer_pair_flops(
        m["indexer_heads"], m["indexer_dim"])


def indexer_bytes(scored_keys, m):
    """Every layer's indexer keys for a DECODE step that scores
    `scored_keys` keys (one query a row: a key is read for one pair)."""
    return m["layers"] * scored_keys * indexer_key_bytes(m["indexer_dim"])


def selected_flops(selected_pairs, m):
    """Every layer's attention over `selected_pairs` (query, selected
    token) pairs (a span's `selected_tokens`)."""
    return m["layers"] * selected_pairs * selected_pair_flops(
        m["heads"], m["head_dim"])


def selected_bytes(selected_rows, m):
    """Every layer's selected rows of a DECODE step (one query a row: a
    row is read for one pair)."""
    return m["layers"] * selected_rows * selected_row_bytes(
        m["kv_heads"], m["head_dim"])


def held_tables_bytes(m):
    """The three tables of every held expert of every layer, read
    once."""
    return m["expert_layers"] * 3.0 * m["experts_held"] * m["hidden"] \
        * m["ffn"] * 2


def decode_step_bytes(scored_keys, selected_rows, m):
    """HBM bytes one decode step needs: every held weight once as it is
    held, the scored tokens' indexer keys, the selected tokens' keys and
    values. NOT every live token's keys and values: the selection is the
    algorithm."""
    return (m["weight_bytes"] + indexer_bytes(scored_keys, m)
            + selected_bytes(selected_rows, m))


def chunk_model_flops(real_tokens, rows, scored_pairs, selected_pairs, m):
    """The model's operations for ONE chunk dispatch of `rows` rows:
    twice the parameters a real token's products meet on this chip, the
    head once a row, the indexer over the pairs it scored and the
    attention over the pairs it selected."""
    return (2.0 * real_tokens * m["params_met_per_token"]
            + 2.0 * rows * m["head_params"]
            + indexer_flops(scored_pairs, m)
            + selected_flops(selected_pairs, m))
