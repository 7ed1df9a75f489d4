"""A.X-K1 as published
(https://huggingface.co/skt/A.X-K1/blob/main/config.json), the forward
pass in plain `jax.numpy` and float32: no kernel, no cache, no
absorption, no sort, matmuls at `highest` precision, attention EXPANDED
(every head's keys and values formed from the latent). Independent of
`deepspeed_tpu/models/axk1.py` and `deepspeed_tpu/ops/`; it reads only
the parameter tree's layout (`tok_emb`, `lm_head`, `ln_f`, `h_<l>` with
`ln_1`, `ln_2`, `attn` {`wq_a`, `q_norm`, `wq_b`, `wkv_a`, `kv_norm`,
`wkv_b`, `wo`} and `mlp` {`w_gate`, `w_up`, `w_down`} or `router`,
`experts` {the same three, a leading expert axis}, `shared`) and upcasts
whatever dtype the tree is held in.

Layer l on the residual stream x (hidden 7,168; RMSNorm eps 1e-6; no
bias), h = RMSNorm_1(x), 64 heads, t a token's position:

    c_q = RMSNorm(h W_qa)                            7,168 -> 1,536
    [q_n | q_r] = c_q W_qb  a head                   1,536 -> 64 x (128 + 64)
    [c | k_r] = h W_kva                              7,168 -> 512 + 64
    c = RMSNorm(c);  q_r = R_t q_r;  k_r = R_t k_r   (ONE k_r for all heads)
    [k_n | v] = c W_kvb  a head                      512 -> 64 x (128 + 128)
    s = (q_n . k_n + q_r . k_r) * 192^-1/2 * m^2,  m = 0.1 ln(32) + 1
    x += concat_heads(softmax_causal(s) v) W_o       8,192 -> 7,168
  R_t rotates pair i, (x[i], x[i + 32]), by t f_i:  f_i = theta^(-2i/64)
  blended with f_i / 32 by YaRN's linear ramp between the pairs whose
  wavelengths make 32 and 1 turns in 4,096 positions (`inv_freq`).
  then, h2 = RMSNorm_2(x):
    l = 0:  x += W_down( SiLU(W_gate h2) * (W_up h2) )           18,432
    l > 0:  p = sigmoid(h2 W_r) over the 192 experts (float32); 8 groups
            of 24; a group's score the sum of its two largest p; the 4
            best groups kept; S8 the 8 largest p among their 96 experts;
            w_e = 2.5 p_e / sum of p over S8
            x += sum over e in S8 of w_e E_e(h2) + E_shared(h2)   2,048

After the last layer: final RMSNorm, untied head.

The share. `cfg["experts_held"] = (first, count)`: the sum over e runs
over S8 INTERSECTED with the held experts, with w from all eight; what
the absent experts would add is left out and goes on to the next layer
so, exactly as the program's layer does. The shared expert is whole on
every chip. The tables hold the vocabulary rows of the slice.

Departures from the published model: none in the equations. Assumed
(`configs/ax-k1.json` `assumed`): the group-limited choice by top-2
sums, no correction bias, the rotary pair layout, no bias anywhere.

In blocks, so that 6,144 positions at the published widths fit beside an
engine: attention a head at a time (`lax.map`), the dense layer and the
held experts one 2,048-wide block of tables a loop turn (`lax.scan`,
each block upcast in its turn).

The reference at a LOWER precision or with a PLANTED FAULT, for the
cell's controls; None is the reference itself: `products` rounds both
operands of every product with a weight table to that dtype (the
arithmetic stays float32); `state_dtype` rounds the cached row `[c |
k_r]` (after the norm and the rotation) to it; `fault` is `"scale"`
(the scores without m^2), `"rotary_key"` (k_r left unrotated), or one of
the expert half's: `"group_limit"` (the 8 largest of all 192 scores, no
group left out), `"router_weights"` (the chosen scores as they are:
neither renormalised nor scaled) or `"routed_sum"` (the routed experts'
sum left out; the shared expert stays).
"""

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
FAULTS = ("scale", "rotary_key", "group_limit", "router_weights",
          "routed_sum")

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


def inv_freq(cfg):
    """(d_r / 2,) rotary frequencies, YaRN's blend."""
    d, theta = cfg["qk_rope_head_dim"], cfg["rope_theta"]
    plain = [theta ** (-2.0 * i / d) for i in range(d // 2)]

    def pair_with(turns):
        return d * math.log(cfg["rope_original_max"]
                            / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(pair_with(cfg["rope_beta_fast"])), 0)
    high = min(math.ceil(pair_with(cfg["rope_beta_slow"])), d - 1)
    out = []
    for i, f in enumerate(plain):
        ramp = min(max((i - low) / max(high - low, 1e-3), 0.0), 1.0)
        out.append(f / cfg["rope_factor"] * ramp + f * (1.0 - ramp))
    return jnp.asarray(out, jnp.float32)


def _rotate(x, freqs):
    """x (B, S, ..., d_r) at positions 0..S-1."""
    S, half = x.shape[1], x.shape[-1] // 2
    angle = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    angle = angle.reshape((1, S) + (1,) * (x.ndim - 3) + (half,))
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle),
                            a * jnp.sin(angle) + b * jnp.cos(angle)], -1)


def _latent_attention(ap, cfg, h, state_dtype, fault):
    B, S, _ = h.shape
    nh, rkv = cfg["num_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    eps, freqs = cfg["rms_norm_eps"], inv_freq(cfg)
    m = 0.1 * cfg["rope_mscale_all_dim"] * math.log(cfg["rope_factor"]) + 1
    scale = (dn + dr) ** -0.5 * (1.0 if fault == "scale" else m * m)
    q = _mm(_rms(_mm(h, ap["wq_a"]), ap["q_norm"], eps),
            ap["wq_b"]).reshape(B, S, nh, dn + dr)
    q_n, q_r = q[..., :dn], _rotate(q[..., dn:], freqs)
    kv = _mm(h, ap["wkv_a"])
    c = _rms(kv[..., :rkv], ap["kv_norm"], eps)
    k_r = kv[..., rkv:] if fault == "rotary_key" else _rotate(
        kv[..., rkv:], freqs)
    if state_dtype is not None:      # what a pool held in it would keep
        c, k_r = _rounded(c, state_dtype), _rounded(k_r, state_dtype)
    kvx = _mm(c, ap["wkv_b"]).reshape(B, S, nh, dn + dv)
    causal = jnp.tril(jnp.ones((S, S), bool))

    def head(x):
        qn, qr, kn, v = x                                  # (B, S, .)
        s = (jnp.einsum("bqd,bkd->bqk", qn, kn, precision=HIGHEST)
             + jnp.einsum("bqd,bkd->bqk", qr, k_r, precision=HIGHEST)
             ) * scale
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
        return jnp.einsum("bqk,bkd->bqd", p, v, precision=HIGHEST)

    by_head = lambda t: jnp.moveaxis(t, 2, 0)              # (nh, B, S, .)
    o = jax.lax.map(head, (by_head(q_n), by_head(q_r),
                           by_head(kvx[..., :dn]), by_head(kvx[..., dn:])))
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


def route(h2, w_router, cfg, fault=None):
    """(weights (B, S, experts) float32, zero off the eight chosen; the
    eight's indices; the groups kept (B, S, topk_group))."""
    p = jax.nn.sigmoid(jnp.matmul(h2, w_router.astype(jnp.float32),
                                  precision=HIGHEST))
    n_group, e = cfg["n_group"], p.shape[-1]
    groups = p.reshape(p.shape[:-1] + (n_group, e // n_group))
    score = jnp.sum(jax.lax.top_k(groups, 2)[0], -1)
    _, kept = jax.lax.top_k(score, cfg["topk_group"])
    is_kept = jnp.sum(jax.nn.one_hot(kept, n_group, dtype=jnp.float32),
                      -2) > 0
    allowed = p if fault == "group_limit" else jnp.where(
        is_kept[..., None], groups, -1.0).reshape(p.shape)
    top, idx = jax.lax.top_k(allowed, cfg["experts_per_token"])
    w = top if fault == "router_weights" else top / jnp.sum(
        top, -1, keepdims=True) * cfg["routed_scaling_factor"]
    spread = jnp.sum(jax.nn.one_hot(idx, e, dtype=jnp.float32)
                     * w[..., None], axis=-2)
    return spread, idx, kept


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


def logits(params, ids, cfg, state_dtype=None, products=None, fault=None):
    """(B, S) tokens of the held slice -> (B, S, rows) float32 logits.
    `cfg`: `families/axk1.reference_config`'s dict."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"axk1_reference: no planted fault {fault!r}")
    _PRODUCTS[0] = products          # read as the forward is traced
    try:
        with jax.default_matmul_precision("highest"):
            eps = cfg["rms_norm_eps"]
            x = params["tok_emb"].astype(jnp.float32)[ids]
            for l in range(cfg["num_layers"]):
                lp = params[f"h_{l}"]
                h = _rms(x, lp["ln_1"]["w"], eps)
                x = x + _latent_attention(lp["attn"], cfg, h, state_dtype,
                                          fault)
                h2 = _rms(x, lp["ln_2"]["w"], eps)
                if l < cfg["first_k_dense"]:
                    x = x + _dense(h2, lp["mlp"],
                                   cfg["moe_intermediate_size"])
                else:
                    weights, _, _ = route(h2, lp["router"], cfg, fault)
                    if fault == "routed_sum":
                        weights = jnp.zeros_like(weights)
                    x = x + experts(h2, weights, lp["experts"],
                                    cfg["experts_held"], lp["shared"])
            x = _rms(x, params["ln_f"]["w"], eps)
            return _mm(x, params["lm_head"].T)
    finally:
        _PRODUCTS[0] = None
