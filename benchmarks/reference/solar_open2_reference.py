"""Solar-Open2-250B as published
(https://huggingface.co/upstage/Solar-Open2-250B/blob/main/config.json),
the forward pass in plain `jax.numpy` and float32: no kernel, no cache,
no chunks, no sort, matmuls at `highest` precision, the recurrence a
token a step. Independent of `deepspeed_tpu/models/solar_open2.py` and
`deepspeed_tpu/ops/kda.py`; it reads only the parameter tree's layout
(`tok_emb`, `lm_head`, `ln_f`, `h_<l>` with `ln_1`, `ln_2`, `router`,
`experts` {`w_gate`, `w_up`, `w_down`}, `shared` {the same, one expert}
and `attn` {`wq`, `wk`, `wv`, `wg`, `wo`} or `kda` {`wq`, `wk`, `wv`,
`conv`, `wf1`, `wf2`, `b_dt`, `a_log`, `wb`, `wg1`, `wg2`, `b_g`,
`norm`, `wo`}) and upcasts whatever dtype the tree is held in.

Layer l on the residual stream x (hidden 4,096; RMSNorm eps 1e-5; no
bias but b_dt and b_g), h = RMSNorm_1(x):

  l not in gqa_layers — gated delta-rule linear attention, 64 heads of
  128:
    q = SiLU(conv4(h W_q)), k = SiLU(conv4(h W_k)), v = SiLU(conv4(h W_v))
        conv4: causal, depthwise, width 4: out[t] = sum_j c[j] in[t-3+j],
        zeros before position 0
    per head: q, k divided by sqrt(sum of squares + 1e-6); q times 128^-1/2
    g_t = -exp(A_h) * softplus(h W_f1 W_f2 + b_dt)      (a head AND channel)
    b_t = 2 sigmoid(h W_b)                               (a head)
    S_t = (I - b_t k_t k_t^T) Diag(exp g_t) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t                                      (S_0 = 0, 128 x 128)
    x  += W_o [ RMSNorm_head(o_t) * sigmoid(h W_g1 W_g2 + b_g) ]
  l in gqa_layers — causal softmax attention with NO position, 64 query
  heads over 8 key-value heads of 128 (query head i reads key-value head
  i // 8), scores / sqrt(128):
    x  += W_o [ attn * sigmoid(h W_gate) ]
  then, h2 = RMSNorm_2(x):
    s = softmax(h2 W_r) over the 320 experts (float32); S8 its eight
        largest; w_e = s_e / sum of s over S8 (times routed_scaling_factor 1)
    x += sum over e in S8 of w_e E_e(h2) + E_shared(h2)
        E(x) = W_down( SiLU(W_gate x) * (W_up x) )

After the last layer: final RMSNorm, untied head.

The share. `cfg["experts_held"] = (first, count)`: the sum over e runs
over S8 INTERSECTED with the held experts, with w from all eight; what
the absent experts would add is left out and goes on to the next layer
so, exactly as the program's layer does. The shared expert is whole on
every chip. The tables hold the vocabulary rows of the slice.

Departures from the published model: none in the equations. Assumed
(the config keeps no key for them; `configs/solar-open2-250b.json`
`assumed`): SiLU; a softmax router with no correction bias; the softmax
layers' gate elementwise from a projection of h of width heads x
head_dim; the decay's parametrisation above; the 1e-6 under the unit
norm's root.

The held experts are ONE loop body (`lax.scan` over the expert index,
each table upcast in its turn, so that its float32 copies stay the size
of one expert) and the recurrence one `lax.scan` over tokens, so that
the program compiles in seconds.

`final_states` is the same forward read at another place: each
delta-rule layer's state S after a row's TRUE length (positions at or
past it leave the state as it is: g = 0, b = 0 there), which is what a
slot of the engine's state pool has to hold.

The reference at a LOWER precision, for the cell's controls; None is the
reference itself: `state_dtype` rounds the recurrent state to that dtype
after every token (what a pool held in it would keep), `round_to` rounds
the recurrence's operands q, k, v, g, b to it, `products` rounds both
operands of EVERY matrix product to it (the arithmetic stays float32).
"""

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


_PRODUCTS = [None]      # the dtype products' operands are rounded to


def _mm(a, b):
    b = b.astype(jnp.float32)
    if _PRODUCTS[0] is not None:
        a, b = _rounded(a, _PRODUCTS[0]), _rounded(b, _PRODUCTS[0])
    return jnp.matmul(a, b, precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps
                             ) * w.astype(jnp.float32)


def _rounded(x, dtype):
    """x at `dtype`'s precision, still float32. Not a pair of casts: the
    compiler may keep the excess precision of a cast there and back
    (it did on the chip: the control read the reference itself)."""
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, exponent_bits=info.nexp,
                                    mantissa_bits=info.nmant)


def _conv4(x, taps):
    """x (B, S, C), taps (W, C): out[t] = sum_j taps[j] x[t - (W-1) + j]."""
    width, s = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    return sum(padded[:, j:j + s] * taps[j].astype(jnp.float32)
               for j in range(width))


def _delta_rule(kp, cfg, h, live, state_dtype, round_to):
    """(what the layer adds to x, its state after the last position).
    `live` (B, S) bool or None: the state passes a position that is not
    live unchanged."""
    B, S, _ = h.shape
    nh, hd = cfg["kda_num_heads"], cfg["kda_head_dim"]
    heads = lambda t: t.reshape(B, S, nh, hd)
    q, k, v = (heads(jax.nn.silu(_conv4(_mm(h, kp[w]), kp["conv"][i])))
               for i, w in enumerate(("wq", "wk", "wv")))
    unit = lambda t: t / jnp.sqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)
    q, k = unit(q) / math.sqrt(hd), unit(k)
    g = -jnp.exp(kp["a_log"].astype(jnp.float32))[:, None] * heads(
        jax.nn.softplus(_mm(_mm(h, kp["wf1"]), kp["wf2"])
                        + kp["b_dt"].astype(jnp.float32)))
    b = 2.0 * jax.nn.sigmoid(_mm(h, kp["wb"]))               # (B, S, nh)
    if live is not None:
        g = jnp.where(live[..., None, None], g, 0.0)
        b = jnp.where(live[..., None], b, 0.0)
    if round_to is not None:
        q, k, v, g, b = (_rounded(t, round_to) for t in (q, k, v, g, b))
    keep = (lambda s: s) if state_dtype is None else (
        lambda s: _rounded(s, state_dtype))

    def token(s, x):
        q_t, k_t, v_t, g_t, b_t = x                          # (B, nh, .)
        s = jnp.exp(g_t)[..., None] * s
        s = s - b_t[..., None, None] * k_t[..., None] * jnp.einsum(
            "bhk,bhkv->bhv", k_t, s, precision=HIGHEST)[..., None, :]
        s = keep(s + b_t[..., None, None] * k_t[..., None]
                 * v_t[..., None, :])
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t, precision=HIGHEST)

    seq = lambda t: jnp.moveaxis(t, 1, 0)
    last, o = jax.lax.scan(token, jnp.zeros((B, nh, hd, hd), jnp.float32),
                           (seq(q), seq(k), seq(v), seq(g), seq(b)))
    o = _rms(jnp.moveaxis(o, 0, 1), kp["norm"], cfg["rms_norm_eps"])
    gate = jax.nn.sigmoid(_mm(_mm(h, kp["wg1"]), kp["wg2"])
                          + kp["b_g"].astype(jnp.float32))
    return _mm(o.reshape(B, S, nh * hd) * gate, kp["wo"]), last


def _softmax_attention(ap, cfg, h):
    B, S, _ = h.shape
    nh, nkv, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    q = _mm(h, ap["wq"]).reshape(B, S, nkv, nh // nkv, hd)
    k = _mm(h, ap["wk"]).reshape(B, S, nkv, hd)
    v = _mm(h, ap["wv"]).reshape(B, S, nkv, hd)
    s = jnp.einsum("bqngd,bknd->bngqk", q, k,
                   precision=HIGHEST) / math.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    ctx = jnp.einsum("bngqk,bknd->bqngd", jax.nn.softmax(s, -1), v,
                     precision=HIGHEST).reshape(B, S, nh * hd)
    return _mm(ctx * jax.nn.sigmoid(_mm(h, ap["wg"])), ap["wo"])


def _glu(x, gate, up, down):
    return _mm(jax.nn.silu(_mm(x, gate)) * _mm(x, up), down)


def route(h2, w_router, cfg):
    """(weights (B, S, experts) float32, zero off the eight chosen;
    the eight's indices)."""
    s = jax.nn.softmax(_mm(h2, w_router), axis=-1)
    top, idx = jax.lax.top_k(s, cfg["experts_per_token"])
    w = top / jnp.sum(top, -1, keepdims=True) * cfg["routed_scaling_factor"]
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


def _forward(params, ids, cfg, lengths, state_dtype, round_to, products):
    """(logits, [each delta-rule layer's last state])."""
    live = None if lengths is None else (
        jnp.arange(ids.shape[1])[None, :] < lengths[:, None])
    _PRODUCTS[0] = products          # read as the forward is traced
    try:
        with jax.default_matmul_precision("highest"):
            eps = cfg["rms_norm_eps"]
            x = params["tok_emb"].astype(jnp.float32)[ids]
            states = []
            for l in range(cfg["num_layers"]):
                lp = params[f"h_{l}"]
                h = _rms(x, lp["ln_1"]["w"], eps)
                if l in cfg["gqa_layers"]:
                    x = x + _softmax_attention(lp["attn"], cfg, h)
                else:
                    y, last = _delta_rule(lp["kda"], cfg, h, live,
                                          state_dtype, round_to)
                    x = x + y
                    states.append(last)
                h2 = _rms(x, lp["ln_2"]["w"], eps)
                weights, _ = route(h2, lp["router"], cfg)
                x = x + experts(h2, weights, lp["experts"],
                                cfg["experts_held"], lp["shared"])
            x = _rms(x, params["ln_f"]["w"], eps)
            return _mm(x, params["lm_head"].T), states
    finally:
        _PRODUCTS[0] = None


def logits(params, ids, cfg, state_dtype=None, round_to=None,
           products=None):
    """(B, S) tokens of the held slice -> (B, S, rows) float32 logits.
    `cfg`: `families/solar_open2.reference_config`'s dict."""
    return _forward(params, ids, cfg, None, state_dtype, round_to,
                    products)[0]


def final_states(params, ids, lengths, cfg, state_dtype=None,
                 round_to=None, products=None):
    """(B, S) tokens, (B,) true lengths -> (B, delta-rule layers, heads,
    dk, dv) float32: each layer's state after a row's true length."""
    return jnp.stack(_forward(params, ids, cfg, lengths, state_dtype,
                              round_to, products)[1], axis=1)
