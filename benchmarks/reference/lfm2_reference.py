"""LFM2-24B-A2B as published
(https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json,
`model_type` `lfm2_moe`), the forward pass in plain `jax.numpy` and
float32: no kernel, no cache, no sort, no batching, matmuls at `highest`
precision, the convolution as three shifted products, attention a head
at a time, every held expert on every position weighed by the router.
Independent of `deepspeed_tpu/models/` and `deepspeed_tpu/ops/`; it
reads only the parameter tree's layout (`tok_emb`, `ln_f`, `h_<l>` with
`ln_1`, `ln_2`, `conv` {`w_in`, `taps`, `w_out`} or `attn` {`wq`, `wk`,
`wv`, `q_norm`, `k_norm`, `wo`}, and `mlp` {`w_gate`, `w_up`, `w_down`}
or `router`, `router_bias`, `experts` {the same three, a leading expert
axis}) and upcasts whatever dtype the tree is held in.

Layer l on the residual stream x (hidden 2,048; RMSNorm with a learned
weight, eps 1e-5; no bias but b_e), h = RMSNorm_1(x), kind from
`layer_types[l]`:

  "conv" -- gated short convolution:
    [B | C | X] = h W_in                             2,048 -> 3 x 2,048
    u = B * X
    v_t = w_0 u_{t-2} + w_1 u_{t-1} + w_2 u_t        (u zero before 0)
    x += (C * v) W_out                               2,048 -> 2,048
  "full_attention" -- 32 query heads, 8 key-value heads of 64:
    q, k, v = h W_q, h W_k, h W_v
    q, k = RMSNorm_64(q), RMSNorm_64(k) a head, THEN rotated at the
        token's position: the pair (i, i + 32) by pos * theta^(-i / 32)
    x += concat_heads(softmax_causal(q k^T / 8) v) W_o, a key-value head
        serving 4 query heads
  then, h2 = RMSNorm_2(x):
    l < 2:  x += W_down( SiLU(W_gate h2) * (W_up h2) )            11,776
    l >= 2: s = sigmoid(h2 W_r) over the 64 experts (float32); S4 the 4
            largest of s + b_e (b_e for the CHOICE only; ties to the
            lower index); w_e = s_e / (sum of s over S4 + 1e-6) * 1
            x += sum over e in S4 of w_e E_e(h2)                   1,536
            NO shared expert.

After the last layer: final RMSNorm, then the head: the embedding
table, tied.

The share. `cfg["experts_held"] = (first, count)`: the sum over e runs
over S4 INTERSECTED with the held experts, with w from all four; what
absent experts would add is left out, as the program's layer does (the
benchmark's configuration holds all 64: nothing is left out there).

In blocks, so that 2,048 positions at the published widths fit beside an
engine that holds 11.6 GB: attention a head at a time and a block of
`QUERY_BLOCK` queries at a time (`lax.map` twice), the dense layer
1,536 of its inner channels a loop turn and the held experts ONE
expert's three tables a loop turn (`lax.scan`: 38 MB of float32 tables
at a time, where a layer's 64 are 2.4 GB), the head a block of positions
at a time.

The reference at a LOWER precision or with a PLANTED FAULT, for the
cell's controls; None is the reference itself. `products` rounds both
operands of every product with a weight table to that dtype (the
arithmetic stays float32); `state_dtype` rounds the convolution's
products u (what a slot's tail holds); `round_to` the attention's
operands q, k, v (what the pages hold). `fault`: `"conv_tail"` (a
position convolves its own product alone: what a decode step that lost
its tail computes), `"gate_dropped"` (y = v W_out: the gate C left
out), `"qk_unnormed"` (no norm on queries and keys), `"rope_zero"`
(every token rotated at position 0: no rotation at all),
`"router_bias"` (the 4 largest of s, no b_e), `"router_weights"` (the
chosen scores as they are, not renormalised), `"routed_sum"` (the
routed experts' sum left out).
"""

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
FAULTS = ("conv_tail", "gate_dropped", "qk_unnormed", "rope_zero",
          "router_bias", "router_weights", "routed_sum")
QUERY_BLOCK = 512       # queries whose scores are held at once, a head
WEIGHT_EPS = 1e-6       # under the chosen scores' sum

_PRODUCTS = [None]      # the dtype products' operands are rounded to


def _rounded(x, dtype):
    """x at `dtype`'s precision, still float32 (`reduce_precision`: the
    compiler may keep the excess precision of a cast there and back)."""
    if dtype is None:
        return x
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, exponent_bits=info.nexp,
                                    mantissa_bits=info.nmant)


def _mm(a, b):
    b = b.astype(jnp.float32)
    if _PRODUCTS[0] is not None:
        a, b = _rounded(a, _PRODUCTS[0]), _rounded(b, _PRODUCTS[0])
    return jnp.matmul(a, b, precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps
                             ) * w.astype(jnp.float32)


def short_conv(cp, h, state_dtype=None, fault=None):
    """The gated short convolution on h (B, S, H): three shifted
    products of u = B * X, gated by C, then W_out."""
    S = h.shape[1]
    b, c, x = jnp.split(_mm(h, cp["w_in"]), 3, axis=-1)
    u = _rounded(b * x, state_dtype)
    taps = cp["taps"].astype(jnp.float32)
    width = taps.shape[0]
    padded = jnp.pad(u, ((0, 0), (width - 1, 0), (0, 0)))
    v = 0.0
    for j in range(width):
        if fault == "conv_tail" and j < width - 1:
            continue
        v = v + padded[:, j:j + S] * taps[j]
    return _mm(v if fault == "gate_dropped" else c * v, cp["w_out"])


def rotate(x, positions, theta):
    """x (B, S, heads, hd) rotated at `positions` (S,): the half-split
    pairing, the pair (i, i + hd / 2) by positions * theta^(-2 i / hd)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[None, :, None, None] * inv
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(ap, cfg, h, round_to=None, fault=None):
    B, S, _ = h.shape
    nh, nkv = cfg["num_heads"], cfg["num_kv_heads"]
    hd = cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    q = _mm(h, ap["wq"]).reshape(B, S, nh, hd)
    k = _mm(h, ap["wk"]).reshape(B, S, nkv, hd)
    v = _mm(h, ap["wv"]).reshape(B, S, nkv, hd)
    if fault != "qk_unnormed":
        q, k = _rms(q, ap["q_norm"], eps), _rms(k, ap["k_norm"], eps)
    at = jnp.zeros((S,), jnp.int32) if fault == "rope_zero" \
        else jnp.arange(S)
    q, k = rotate(q, at, cfg["rope_theta"]), rotate(k, at, cfg["rope_theta"])
    q, k, v = (_rounded(t, round_to) for t in (q, k, v))
    block = QUERY_BLOCK if S % QUERY_BLOCK == 0 else S
    keys = jnp.arange(S)
    scale = hd ** -0.5

    def head(x):
        qh, n = x                                           # (B, S, hd)
        kh, vh = k[:, :, n // (nh // nkv)], v[:, :, n // (nh // nkv)]

        def queries(y):
            qb, first = y                                   # (B, block, hd)
            seen = keys[None, :] <= (first + jnp.arange(block))[:, None]
            s = jnp.einsum("bqd,bkd->bqk", qb, kh,
                           precision=HIGHEST) * scale
            p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
            return jnp.einsum("bqk,bkd->bqd", p, vh, precision=HIGHEST)

        blocks = jnp.moveaxis(qh.reshape(B, S // block, block, hd), 1, 0)
        o = jax.lax.map(queries, (blocks, jnp.arange(0, S, block)))
        return jnp.moveaxis(o, 0, 1).reshape(B, S, hd)

    o = jax.lax.map(head, (jnp.moveaxis(q, 2, 0), jnp.arange(nh)))
    return _mm(jnp.moveaxis(o, 0, 2).reshape(B, S, nh * hd), ap["wo"])


def _glu(x, gate, up, down):
    return _mm(jax.nn.silu(_mm(x, gate)) * _mm(x, up), down)


def _dense(h2, mp, block):
    """The dense SwiGLU, `block` of its inner channels a loop turn."""
    hdim, f = mp["w_gate"].shape
    n = f // block if f % block == 0 else 1
    cols = lambda w: jnp.moveaxis(w.reshape(hdim, n, f // n), 1, 0)
    rows = mp["w_down"].reshape(n, f // n, hdim)

    def one(y, t):
        return y + _glu(h2, *t), None

    return jax.lax.scan(one, jnp.zeros_like(h2),
                        (cols(mp["w_gate"]), cols(mp["w_up"]), rows))[0]


def route(h2, w_router, bias, cfg, fault=None):
    """(weights (B, S, experts) float32, zero off the chosen; the
    chosen's indices)."""
    s = jax.nn.sigmoid(jnp.matmul(h2, w_router.astype(jnp.float32),
                                  precision=HIGHEST))
    ranked = s if fault == "router_bias" else s + bias.astype(jnp.float32)
    _, idx = jax.lax.top_k(ranked, cfg["experts_per_token"])
    top = jnp.take_along_axis(s, idx, axis=-1)
    w = top if fault == "router_weights" else top / (
        jnp.sum(top, -1, keepdims=True) + WEIGHT_EPS
    ) * cfg["routed_scaling_factor"]
    spread = jnp.sum(jax.nn.one_hot(idx, s.shape[-1], dtype=jnp.float32)
                     * w[..., None], axis=-2)
    return spread, idx


def experts(h2, weights, tables, held):
    """sum over the held experts e of weights[..., first + e] E_e(h2):
    every held expert on every position, one expert's tables a turn."""
    first, count = held
    mine = jax.lax.dynamic_slice_in_dim(weights, first, count, axis=-1)

    def one(y, e):
        t = jax.tree_util.tree_map(lambda a: a[e], tables)
        return y + mine[..., e, None] * _glu(
            h2, t["w_gate"], t["w_up"], t["w_down"]), None

    return jax.lax.scan(one, jnp.zeros_like(h2), jnp.arange(count))[0]


def _head(x, table):
    """x (B, S, H) against the table's rows, a block of positions a
    turn."""
    B, S, hdim = x.shape
    block = QUERY_BLOCK if S % QUERY_BLOCK == 0 else S
    out = jax.lax.map(lambda xb: _mm(xb, table.T),
                      jnp.moveaxis(x.reshape(B, S // block, block, hdim),
                                   1, 0))
    return jnp.moveaxis(out, 0, 1).reshape(B, S, -1)


def logits(params, ids, cfg, state_dtype=None, round_to=None,
           products=None, fault=None):
    """(B, S) tokens -> (B, S, rows) float32 logits. `cfg`:
    `families/lfm2.reference_config`'s dict."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"lfm2_reference: no planted fault {fault!r}")
    state_dtype, round_to, products = (
        None if d is None else jnp.dtype(d)
        for d in (state_dtype, round_to, products))
    _PRODUCTS[0] = products          # read as the forward is traced
    try:
        with jax.default_matmul_precision("highest"):
            eps = cfg["rms_norm_eps"]
            x = params["tok_emb"].astype(jnp.float32)[ids]
            for l, kind in enumerate(cfg["layer_types"]):
                lp = params[f"h_{l}"]
                h = _rms(x, lp["ln_1"]["w"], eps)
                if kind == "conv":
                    x = x + short_conv(lp["conv"], h, state_dtype, fault)
                else:
                    x = x + attention(lp["attn"], cfg, h, round_to, fault)
                h2 = _rms(x, lp["ln_2"]["w"], eps)
                if l < cfg["num_dense_layers"]:
                    x = x + _dense(h2, lp["mlp"],
                                   cfg["moe_intermediate_size"])
                elif fault != "routed_sum":
                    weights, _ = route(h2, lp["router"], lp["router_bias"],
                                       cfg, fault)
                    x = x + experts(h2, weights, lp["experts"],
                                    cfg["experts_held"])
            x = _rms(x, params["ln_f"]["w"], eps)
            return _head(x, params["tok_emb"])
    finally:
        _PRODUCTS[0] = None
