"""Operations and bytes from shapes alone, kept with the benchmark so no
later PR can move them. Recomputation the program chooses (remat, the
chunked loss head) is never counted; what an algorithm itself needs is.
"""


def gpt2_param_count(vocab, positions, hidden, layers, inter=None):
    """Every parameter of GPT-2 with a tied head: embeddings, per layer
    qkv + out + two MLP matrices with their biases and two layer norms,
    and the final layer norm."""
    inter = inter or 4 * hidden
    per_layer = (hidden * 3 * hidden + 3 * hidden      # qkv
                 + hidden * hidden + hidden            # out
                 + hidden * inter + inter              # fc
                 + inter * hidden + hidden             # proj
                 + 4 * hidden)                         # ln_1, ln_2
    return (vocab * hidden + positions * hidden + layers * per_layer
            + 2 * hidden)


def train_flops_per_token(n_params, layers, hidden, seq):
    """6N for the weight matmuls forward and backward, plus 12·L·h·s for
    the attention scores and values (forward 4·L·h·s, backward twice
    that) at full, not causal, width: the usual model-FLOP convention."""
    return 6.0 * n_params + 12.0 * layers * hidden * seq


def flash_attention_train_cost(batch, heads, seq, head_dim, layers,
                               causal=True, bytes_per_el=2):
    """(flops, bytes) one training step asks of the attention kernels on
    one device. Flash attention's own algorithm: forward QK^T and PV
    (2 matmuls), backward QK^T again, dV, dP, dQ, dK (5 matmuls), each
    2·S²·D per head, halved under a causal mask. Bytes: the forward reads
    q, k, v and writes o; the backward reads q, k, v, o, do and writes
    dq, dk, dv (row statistics are S/D of that and left out)."""
    per_matmul = 2.0 * batch * heads * seq * seq * head_dim
    flops = 7.0 * per_matmul * (0.5 if causal else 1.0) * layers
    tensor = batch * heads * seq * head_dim * bytes_per_el
    return flops, 12.0 * tensor * layers


def roofline_seconds(flops, nbytes, peak):
    """The least time the chip could take, and which peak bounds it."""
    t_f, t_b = flops / peak["flops_per_s"], nbytes / peak["bytes_per_s"]
    return (t_f, "flops") if t_f >= t_b else (t_b, "bytes")


def kv_bytes_per_token(layers, hidden, bytes_per_el=2):
    """Keys and values of one cached token over all layers."""
    return 2 * layers * hidden * bytes_per_el


def decode_step_bytes(n_params, live_tokens, layers, hidden,
                      weight_bytes_per_el=2, kv_bytes_per_el=2):
    """HBM bytes one decode step needs: every weight once, at the
    engine's compute width, plus the keys and values of every live
    token."""
    return (n_params * weight_bytes_per_el
            + live_tokens * kv_bytes_per_token(layers, hidden,
                                               kv_bytes_per_el))
