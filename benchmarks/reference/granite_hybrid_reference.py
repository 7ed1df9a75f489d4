"""granite-4.0-h-small as published
(https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/main/config.json,
`model_type: granitemoehybrid`), the forward pass in plain `jax.numpy`
and float32: no kernel, no cache, no chunks, no sort, matmuls at
`highest` precision, the recurrence a token a step. Independent of
`deepspeed_tpu/models/granite_hybrid.py` and `deepspeed_tpu/ops/ssd.py`;
it reads only the parameter tree's layout (`tok_emb`, `ln_f`, `h_<l>`
with `ln_1`, `ln_2`, `router`, `experts` {`w_gate`, `w_up`, `w_down`},
`shared` {the same, one expert} and `attn` {`wq`, `wk`, `wv`, `wo`} or
`mamba` {`w_in`, `conv`, `conv_b`, `dt_bias`, `a_log`, `d`, `norm`,
`w_out`}) and upcasts whatever dtype the tree is held in.

x_0 = 12 E[ids]. Layer l on the residual stream x (hidden 4,096;
RMSNorm eps 1e-5), h = RMSNorm_1(x):

  layer_types[l] == "mamba" — a Mamba-2 mixer, 128 heads of 64, state
  width 128, one group:
    [z | xBC | dt] = h W_in            (4,096 -> 8,192 + 8,448 + 128)
    xBC = SiLU(conv4(xBC) + b_conv)    causal, depthwise, width 4:
        out[t] = sum_j c[j] in[t-3+j], zeros before position 0
    [x | B | C] = xBC                  (8,192 | 128 | 128)
    dt_t = softplus(dt_t + dt_bias_h); a_t = exp(dt_t A_h), A_h = -exp(A_log_h)
    per head: S_t = a_t S_{t-1} + dt_t x_t B_t^T      (S_0 = 0, 64 x 128)
              y_t = S_t C_t + D_h x_t
    y = RMSNorm(y * SiLU(z)) * w_norm  over all 8,192 channels
    x += 0.22 (y W_out)
  "attention" — causal softmax attention with NO position, 32 query
  heads over 8 key-value heads of 128 (query head i reads key-value head
  i // 4), scores x 0.0078125:
    x += 0.22 (attn W_o)
  then, h2 = RMSNorm_2(x):
    s = h2 W_r (72 logits, float32); S10 its ten largest; w = softmax of
        s over S10
    x += 0.22 (sum over e in S10 of w_e E_e(h2) + E_shared(h2))
        E(u) = W_down( SiLU(W_gate u) * (W_up u) )

After the last layer: final RMSNorm, logits = (x E^T) / 16 over the
TIED table.

The share. `cfg["experts_held"] = (first, count)`: the sum over e runs
over S10 INTERSECTED with the held experts, with w from all ten; what
the absent experts would add is left out and goes on to the next layer
so, exactly as the program's layer does. The shared expert is whole on
every chip. The table holds the vocabulary rows of the slice.

Departures from the published model: none in the equations. Assumed
(the config keeps no key for them; `configs/granite-4.0-h-small.json`
`assumed`): the Mamba-2 parametrisation above (projection order z, xBC,
dt; one convolution over x, B and C with bias; no clamp on dt; the gate
before a norm over the whole inner width), head width 4,096 / 32, the
shared expert always on and summed, softmax over the top ten's own
logits.

So that 4,480 positions fit beside a serving engine on one chip: the
held experts are ONE loop body (`lax.scan` over the expert index, each
table upcast in its turn), the recurrence one `lax.scan` over tokens,
attention one `lax.map` over blocks of queries (the scores of a block
against every key, never S x S), the head one loop over blocks of the
table's rows.

`final_states` is the same forward read at another place: each Mamba
layer's state S after a row's TRUE length (positions at or past it leave
the state as it is: dt = 0 there), which is what a slot of the engine's
state pool has to hold.

The reference at a LOWER precision, for the cell's controls; None is the
reference itself: `state_dtype` rounds the recurrent state to that dtype
after every token, `round_to` rounds the recurrence's operands x, B, C,
dt to it, `products` rounds both operands of EVERY matrix product to it
(the arithmetic stays float32).
"""

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
_QUERY_BLOCK = 256      # at most this many queries' scores held at once
_HEAD_BLOCKS = 8        # the table's rows, in this many blocks

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


def _conv4(x, taps, bias):
    """x (B, S, C), taps (W, C): out[t] = sum_j taps[j] x[t - (W-1) + j]
    + bias."""
    width, s = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    return sum(padded[:, j:j + s] * taps[j].astype(jnp.float32)
               for j in range(width)) + bias.astype(jnp.float32)


def _mamba(mp, cfg, h, live, state_dtype, round_to):
    """(what the mixer gives before the residual multiplier, its state
    after the last position). `live` (B, S) bool or None: the state
    passes a position that is not live unchanged."""
    B, S, _ = h.shape
    nh, hd, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    di = nh * hd
    proj = _mm(h, mp["w_in"])
    z, xbc, dt = (proj[..., :di], proj[..., di:di + di + 2 * n],
                  proj[..., di + di + 2 * n:])
    xbc = jax.nn.silu(_conv4(xbc, mp["conv"], mp["conv_b"]))
    x = xbc[..., :di].reshape(B, S, nh, hd)
    b, c = xbc[..., di:di + n], xbc[..., di + n:]
    dt = jax.nn.softplus(dt + mp["dt_bias"].astype(jnp.float32))
    if live is not None:
        dt = jnp.where(live[..., None], dt, 0.0)
    if round_to is not None:
        x, b, c, dt = (_rounded(t, round_to) for t in (x, b, c, dt))
    a_rate = -jnp.exp(mp["a_log"].astype(jnp.float32))          # (nh,)
    d = mp["d"].astype(jnp.float32)
    keep = (lambda s: s) if state_dtype is None else (
        lambda s: _rounded(s, state_dtype))

    def token(s, inp):
        x_t, b_t, c_t, dt_t = inp             # (B, nh, hd), (B, n), (B, nh)
        s = keep(jnp.exp(dt_t * a_rate)[..., None, None] * s
                 + (dt_t[..., None] * x_t)[..., None]
                 * b_t[:, None, None, :])
        y = jnp.einsum("bhpn,bn->bhp", s, c_t, precision=HIGHEST)
        return s, y + d[:, None] * x_t

    seq = lambda t: jnp.moveaxis(t, 1, 0)
    last, y = jax.lax.scan(token, jnp.zeros((B, nh, hd, n), jnp.float32),
                           (seq(x), seq(b), seq(c), seq(dt)))
    y = jnp.moveaxis(y, 0, 1).reshape(B, S, di) * jax.nn.silu(z)
    return _mm(_rms(y, mp["norm"], cfg["rms_norm_eps"]), mp["w_out"]), last


def _attention(ap, cfg, h):
    B, S, _ = h.shape
    nh, nkv, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    q = _mm(h, ap["wq"]).reshape(B, S, nkv, nh // nkv, hd)
    k = _mm(h, ap["wk"]).reshape(B, S, nkv, hd)
    v = _mm(h, ap["wv"]).reshape(B, S, nkv, hd)
    block = max(b for b in range(1, _QUERY_BLOCK + 1) if S % b == 0)
    at = jnp.arange(S)

    def some_queries(args):
        q_b, at_b = args                       # (B, block, nkv, g, hd)
        s = jnp.einsum("bqngd,bknd->bngqk", q_b, k, precision=HIGHEST
                       ) * cfg["attention_multiplier"]
        s = jnp.where(at[None, :] <= at_b[:, None], s, -jnp.inf)
        return jnp.einsum("bngqk,bknd->bqngd", jax.nn.softmax(s, -1), v,
                          precision=HIGHEST)

    ctx = jax.lax.map(some_queries, (
        jnp.moveaxis(q.reshape(B, S // block, block, nkv, nh // nkv, hd),
                     1, 0), at.reshape(S // block, block)))
    return _mm(jnp.moveaxis(ctx, 0, 1).reshape(B, S, nh * hd), ap["wo"])


def _glu(x, gate, up, down):
    return _mm(jax.nn.silu(_mm(x, gate)) * _mm(x, up), down)


def route(h2, w_router, cfg):
    """(weights (B, S, experts) float32, zero off the ten chosen; the
    ten's indices)."""
    s = _mm(h2, w_router)
    top, idx = jax.lax.top_k(s, cfg["experts_per_token"])
    w = jax.nn.softmax(top, axis=-1)
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


def _head(x, table, scaling):
    """x E^T / scaling, a block of the table's rows at a time, each
    written into its place in the one result (the float32 copy of a
    block, never of the table; no second copy of the logits)."""
    rows = table.shape[0]
    n = rows // _HEAD_BLOCKS if rows % _HEAD_BLOCKS == 0 else rows

    def block(i, out):
        t = jax.lax.dynamic_slice_in_dim(table, i * n, n)
        return jax.lax.dynamic_update_slice_in_dim(
            out, _mm(x, t.T) / scaling, i * n, axis=-1)

    return jax.lax.fori_loop(
        0, rows // n, block, jnp.zeros((*x.shape[:-1], rows), jnp.float32))


def _forward(params, ids, cfg, lengths, state_dtype, round_to, products):
    """(logits, [each Mamba layer's last state])."""
    live = None if lengths is None else (
        jnp.arange(ids.shape[1])[None, :] < lengths[:, None])
    _PRODUCTS[0] = products          # read as the forward is traced
    try:
        with jax.default_matmul_precision("highest"):
            eps, r = cfg["rms_norm_eps"], cfg["residual_multiplier"]
            x = cfg["embedding_multiplier"] * params["tok_emb"][ids].astype(
                jnp.float32)
            states = []
            for l in range(cfg["num_layers"]):
                lp = params[f"h_{l}"]
                h = _rms(x, lp["ln_1"]["w"], eps)
                if cfg["layer_types"][l] == "attention":
                    x = x + r * _attention(lp["attn"], cfg, h)
                else:
                    y, last = _mamba(lp["mamba"], cfg, h, live,
                                     state_dtype, round_to)
                    x = x + r * y
                    states.append(last)
                h2 = _rms(x, lp["ln_2"]["w"], eps)
                weights, _ = route(h2, lp["router"], cfg)
                x = x + r * experts(h2, weights, lp["experts"],
                                    cfg["experts_held"], lp["shared"])
            x = _rms(x, params["ln_f"]["w"], eps)
            return _head(x, params["tok_emb"], cfg["logits_scaling"]), states
    finally:
        _PRODUCTS[0] = None


def logits(params, ids, cfg, state_dtype=None, round_to=None,
           products=None):
    """(B, S) tokens of the held slice -> (B, S, rows) float32 logits.
    `cfg`: `families/granite_hybrid.reference_config`'s dict."""
    return _forward(params, ids, cfg, None, state_dtype, round_to,
                    products)[0]


def final_states(params, ids, lengths, cfg, state_dtype=None,
                 round_to=None, products=None):
    """(B, S) tokens, (B,) true lengths -> (B, Mamba layers, heads,
    d_head, d_state) float32: each layer's state after a row's true
    length."""
    return jnp.stack(_forward(params, ids, cfg, lengths, state_dtype,
                              round_to, products)[1], axis=1)
