"""Kimi-Linear-48B-A3B as published
(https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/config.json),
the forward pass in plain `jax.numpy` and float32: no kernel, no cache,
no chunks, no absorption, no sort, matmuls at `highest` precision, the
recurrence a token a step, latent attention EXPANDED (every head's keys
and values formed from the latent). Independent of
`deepspeed_tpu/models/` and `deepspeed_tpu/ops/`; it reads only the
parameter tree's layout (`tok_emb`, `lm_head`, `ln_f`, `h_<l>` with
`ln_1`, `ln_2`, `kda` {`wq`, `wk`, `wv`, `conv`, `wf1`, `wf2`, `b_dt`,
`a_log`, `wb`, `wg1`, `wg2`, `norm`, `wo`} or `attn` {`wq`, `wkv_a`,
`kv_norm`, `wkv_b`, `wo`}, and `mlp` {`w_gate`, `w_up`, `w_down`} or
`router`, `router_bias`, `experts` {the same three, a leading expert
axis}, `shared`) and upcasts whatever dtype the tree is held in.

Layer l on the residual stream x (hidden 2,304; RMSNorm eps 1e-5; no
bias but b_dt and b_e), h = RMSNorm_1(x):

  l not in latent_layers -- gated delta-rule attention, 32 heads of 128:
    q = SiLU(conv4(h W_q)), k = SiLU(conv4(h W_k)), v = SiLU(conv4(h W_v))
        conv4: causal, depthwise, width 4: out[t] = sum_j c[j] in[t-3+j],
        zeros before position 0
    per head: q, k divided by sqrt(sum of squares + 1e-6); q times 128^-1/2
    g_t = -exp(A_h) * softplus(h W_f1 W_f2 + b_dt)      (a head AND channel)
    b_t = sigmoid(h W_b)                                 (a head)
    S_t = (I - b_t k_t k_t^T) Diag(exp g_t) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t                                      (S_0 = 0, 128 x 128)
    x  += W_o [ RMSNorm_head(o_t) * sigmoid(h W_g1 W_g2) ]
  l in latent_layers -- multi-head latent attention, 32 heads, NO
  rotation anywhere (`mla_use_nope`), no queries' rank:
    [q_n | q_r] = h W_q  a head                      2,304 -> 32 x (128 + 64)
    [c | k_r] = h W_kva                              2,304 -> 512 + 64
    c = RMSNorm(c)                                   (ONE k_r for all heads)
    [k_n | v] = c W_kvb  a head                      512 -> 32 x (128 + 128)
    s = (q_n . k_n + q_r . k_r) * 192^-1/2
    x += concat_heads(softmax_causal(s) v) W_o       4,096 -> 2,304
  then, h2 = RMSNorm_2(x):
    l = 0:  x += W_down( SiLU(W_gate h2) * (W_up h2) )            9,216
    l > 0:  s = sigmoid(h2 W_r) over the 256 experts (float32); S8 the 8
            largest of s + b_e (b_e for the CHOICE only; one group: no
            limit); w_e = 2.446 s_e / sum of s over S8
            x += sum over e in S8 of w_e E_e(h2) + E_shared(h2)   1,024

After the last layer: final RMSNorm, untied head.

The share. `cfg["experts_held"] = (first, count)`: the sum over e runs
over S8 INTERSECTED with the held experts, with w from all eight; what
the absent experts would add is left out, exactly as the program's
layer does. The shared expert is whole on every chip. The tables hold
the vocabulary rows of the slice.

In blocks, so that 18,432 positions at the published widths fit beside
an engine: attention a head at a time and a block of `QUERY_BLOCK`
queries at a time (`lax.map` twice), the dense layer and the held
experts one 1,024-wide block of tables a loop turn (`lax.scan`), the
recurrence one `lax.scan` over tokens, the head a block of positions at
a time.

The reference at a LOWER precision or with a PLANTED FAULT, for the
cell's controls; None is the reference itself. `products` rounds both
operands of every product with a weight table to that dtype (the
arithmetic stays float32); `state_dtype` rounds the recurrent state to
it after every token; `round_to` the recurrence's operands q, k, v, g,
b. `fault` with `chunk` (a chunk's tokens) is what a served CHUNK could
get wrong: `"chunk_state"` (the state emptied at every chunk boundary),
`"chunk_tail"` (the convolution reads zeros before every chunk
boundary), `"chunk_prefix"` (a query sees the keys of its own chunk
alone); and of the router: `"router_bias"` (the 8 largest of s, no
b_e), `"router_weights"` (the chosen scores as they are: neither
renormalised nor scaled), `"routed_sum"` (the routed experts' sum left
out).
"""

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
FAULTS = ("chunk_state", "chunk_tail", "chunk_prefix", "router_bias",
          "router_weights", "routed_sum")
QUERY_BLOCK = 2048      # queries whose scores are held at once, a head

_PRODUCTS = [None]      # the dtype products' operands are rounded to


def _rounded(x, dtype):
    """x at `dtype`'s precision, still float32 (`reduce_precision`: the
    compiler may keep the excess precision of a cast there and back)."""
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


def _conv4(x, taps, cut):
    """x (B, S, C), taps (W, C): out[t] = sum_j taps[j] x[t - (W-1) + j].
    `cut` (a planted fault) is a chunk's tokens: an input before the
    chunk of the output's position reads as zero."""
    width, s = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    t = jnp.arange(s)
    out = 0.0
    for j in range(width):
        term = padded[:, j:j + s] * taps[j].astype(jnp.float32)
        if cut:
            back = width - 1 - j
            term = jnp.where(((t - back) // cut == t // cut)[None, :, None],
                             term, 0.0)
        out = out + term
    return out


def _delta_rule(kp, cfg, h, state_dtype, round_to, fault, chunk):
    B, S, _ = h.shape
    nh, hd = cfg["kda_num_heads"], cfg["kda_head_dim"]
    heads = lambda t: t.reshape(B, S, nh, hd)
    cut = chunk if fault == "chunk_tail" else 0
    q, k, v = (heads(jax.nn.silu(_conv4(_mm(h, kp[w]), kp["conv"][i], cut)))
               for i, w in enumerate(("wq", "wk", "wv")))
    unit = lambda t: t / jnp.sqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)
    q, k = unit(q) / math.sqrt(hd), unit(k)
    g = -jnp.exp(kp["a_log"].astype(jnp.float32))[:, None] * heads(
        jax.nn.softplus(_mm(_mm(h, kp["wf1"]), kp["wf2"])
                        + kp["b_dt"].astype(jnp.float32)))
    b = jax.nn.sigmoid(_mm(h, kp["wb"]))                     # (B, S, nh)
    if round_to is not None:
        q, k, v, g, b = (_rounded(t, round_to) for t in (q, k, v, g, b))
    keep = (lambda s: s) if state_dtype is None else (
        lambda s: _rounded(s, state_dtype))
    # 0 where a planted fault empties the state before the token
    kept = jnp.ones((S,), jnp.float32)
    if fault == "chunk_state":
        kept = (jnp.arange(S) % chunk != 0).astype(jnp.float32)

    def token(s, x):
        q_t, k_t, v_t, g_t, b_t, kept_t = x                  # (B, nh, .)
        s = jnp.exp(g_t)[..., None] * s * kept_t
        s = s - b_t[..., None, None] * k_t[..., None] * jnp.einsum(
            "bhk,bhkv->bhv", k_t, s, precision=HIGHEST)[..., None, :]
        s = keep(s + b_t[..., None, None] * k_t[..., None]
                 * v_t[..., None, :])
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t, precision=HIGHEST)

    seq = lambda t: jnp.moveaxis(t, 1, 0)
    _, o = jax.lax.scan(token, jnp.zeros((B, nh, hd, hd), jnp.float32),
                        (seq(q), seq(k), seq(v), seq(g), seq(b), kept))
    o = _rms(jnp.moveaxis(o, 0, 1), kp["norm"], cfg["rms_norm_eps"])
    gate = jax.nn.sigmoid(_mm(_mm(h, kp["wg1"]), kp["wg2"]))
    return _mm(o.reshape(B, S, nh * hd) * gate, kp["wo"])


def _latent_attention(ap, cfg, h, fault, chunk):
    B, S, _ = h.shape
    nh, rkv = cfg["num_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    scale = (dn + dr) ** -0.5
    q = _mm(h, ap["wq"]).reshape(B, S, nh, dn + dr)
    kv = _mm(h, ap["wkv_a"])
    c, k_r = _rms(kv[..., :rkv], ap["kv_norm"], cfg["rms_norm_eps"]), \
        kv[..., rkv:]
    kvx = _mm(c, ap["wkv_b"]).reshape(B, S, nh, dn + dv)
    block = QUERY_BLOCK if S % QUERY_BLOCK == 0 else S
    keys = jnp.arange(S)

    def head(x):
        qh, kn, v = x                                       # (B, S, .)
        kh = jnp.concatenate([kn, k_r], -1)

        def queries(y):
            qb, first = y                                   # (B, block, .)
            at = first + jnp.arange(block)
            seen = keys[None, :] <= at[:, None]
            if fault == "chunk_prefix":
                seen = seen & (keys[None, :] // chunk == at[:, None] // chunk)
            s = jnp.einsum("bqd,bkd->bqk", qb, kh,
                           precision=HIGHEST) * scale
            p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
            return jnp.einsum("bqk,bkd->bqd", p, v, precision=HIGHEST)

        blocks = jnp.moveaxis(qh.reshape(B, S // block, block, dn + dr),
                              1, 0)
        o = jax.lax.map(queries, (blocks, jnp.arange(0, S, block)))
        return jnp.moveaxis(o, 0, 1).reshape(B, S, dv)

    by_head = lambda t: jnp.moveaxis(t, 2, 0)               # (nh, B, S, .)
    o = jax.lax.map(head, (by_head(q), by_head(kvx[..., :dn]),
                           by_head(kvx[..., dn:])))
    return _mm(jnp.moveaxis(o, 0, 2).reshape(B, S, nh * dv), ap["wo"])


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
    """(weights (B, S, experts) float32, zero off the eight chosen; the
    eight's indices)."""
    s = jax.nn.sigmoid(jnp.matmul(h2, w_router.astype(jnp.float32),
                                  precision=HIGHEST))
    ranked = s if fault == "router_bias" else s + bias.astype(jnp.float32)
    _, idx = jax.lax.top_k(ranked, cfg["experts_per_token"])
    top = jnp.take_along_axis(s, idx, axis=-1)
    w = top if fault == "router_weights" else top / jnp.sum(
        top, -1, keepdims=True) * cfg["routed_scaling_factor"]
    spread = jnp.sum(jax.nn.one_hot(idx, s.shape[-1], dtype=jnp.float32)
                     * w[..., None], axis=-2)
    return spread, idx


def experts(h2, weights, tables, held, shared=None):
    """sum over the held experts e of weights[..., first + e] E_e(h2),
    plus the shared expert where one is given."""
    first, count = held
    mine = jax.lax.dynamic_slice_in_dim(weights, first, count, axis=-1)

    def one(y, e):
        t = jax.tree_util.tree_map(lambda a: a[e], tables)
        return y + mine[..., e, None] * _glu(
            h2, t["w_gate"], t["w_up"], t["w_down"]), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h2), jnp.arange(count))
    if shared is not None:
        y = y + _glu(h2, shared["w_gate"], shared["w_up"], shared["w_down"])
    return y


def _head(x, table):
    """x (B, S, H) against the table's rows, a block of positions a
    turn (the table upcast once a turn)."""
    B, S, hdim = x.shape
    block = QUERY_BLOCK if S % QUERY_BLOCK == 0 else S
    out = jax.lax.map(lambda xb: _mm(xb, table.T),
                      jnp.moveaxis(x.reshape(B, S // block, block, hdim),
                                   1, 0))
    return jnp.moveaxis(out, 0, 1).reshape(B, S, -1)


def logits(params, ids, cfg, state_dtype=None, round_to=None,
           products=None, fault=None, chunk=None):
    """(B, S) tokens of the held slice -> (B, S, rows) float32 logits.
    `cfg`: `families/kimi_linear.reference_config`'s dict."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"kimi_linear_reference: no planted fault "
                         f"{fault!r}")
    if fault is not None and fault.startswith("chunk_") and not chunk:
        raise ValueError(f"the planted fault {fault!r} needs `chunk`")
    state_dtype, round_to, products = (
        None if d is None else jnp.dtype(d)
        for d in (state_dtype, round_to, products))
    _PRODUCTS[0] = products          # read as the forward is traced
    try:
        with jax.default_matmul_precision("highest"):
            eps = cfg["rms_norm_eps"]
            x = params["tok_emb"].astype(jnp.float32)[ids]
            for l in range(cfg["num_layers"]):
                lp = params[f"h_{l}"]
                h = _rms(x, lp["ln_1"]["w"], eps)
                if l in cfg["latent_layers"]:
                    x = x + _latent_attention(lp["attn"], cfg, h, fault,
                                              chunk)
                else:
                    x = x + _delta_rule(lp["kda"], cfg, h, state_dtype,
                                        round_to, fault, chunk)
                h2 = _rms(x, lp["ln_2"]["w"], eps)
                if l < cfg["first_k_dense"]:
                    x = x + _dense(h2, lp["mlp"],
                                   cfg["moe_intermediate_size"])
                else:
                    weights, _ = route(h2, lp["router"], lp["router_bias"],
                                       cfg, fault)
                    if fault == "routed_sum":
                        weights = jnp.zeros_like(weights)
                    x = x + experts(h2, weights, lp["experts"],
                                    cfg["experts_held"], lp["shared"])
            x = _rms(x, params["ln_f"]["w"], eps)
            return _head(x, params["lm_head"])
    finally:
        _PRODUCTS[0] = None
