"""Keye-VL-2.0-30B-A3B's language trunk as published
(https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json,
`model_type` `KeyeVL2`), the forward pass in plain `jax.numpy` and
float32: ONE full forward, no kernel, no cache, no pages, no batching,
matmuls at `highest` precision, the indexer's scores and the choice a
block of queries at a time against EVERY position, attention a
key-value head at a time under the chosen set's mask, every held expert
on every position weighed by the router. Independent of
`deepspeed_tpu/models/` and `deepspeed_tpu/ops/`; it reads only the
parameter tree's layout (`tok_emb`, `lm_head`, `ln_f`, `h_<l>` with
`ln_1`, `ln_2`, `attn` {`wq`, `wk`, `wv`, `q_norm`, `k_norm`, `wo`},
`indexer` {`wq`, `wk`, `ww`, `k_norm` {`w`, `b`}}, `router`, `experts`
{`w_gate`, `w_up`, `w_down`, a leading expert axis}) and upcasts
whatever dtype the tree is held in.

Every layer on the residual stream x (hidden 2,048; RMSNorm with a
learned weight, eps 1e-6; no bias anywhere but the LayerNorm's), h =
RMSNorm_1(x):

  q, k, v = h W_q, h W_k, h W_v         32 query, 4 key-value heads of 128
  q, k = RMSNorm_128(q), RMSNorm_128(k) a head, THEN rotated: the pair
      (i, i + 64) by p_i * theta^(-i / 64), theta 10,000,000, where p_i
      is the position in the stream frequency i belongs to:
      `mrope_section` [16, 24, 24] gives the first 16 frequencies to the
      time stream, the next 24 to height, the last 24 to width. For text
      the three are the token's position.
  the indexer: qI = h W_qI (16 heads of 64), kI = LayerNorm_64(h W_kI)
      (ONE head; weight and bias, eps 1e-6), both rotated over all 64
      values (pairs (i, i + 32), theta as above) at the time stream;
      w = h W_w (16 values);
      I(t, s) = sum_j w[t, j] 16^-1/2 64^-1/2 ReLU(qI[t, j] . kI[s]),
      s <= t; S_t = the 2,048 positions of largest I(t, .) (every s <= t
      while t + 1 <= 2,048; ties to the lower position)
  x += concat_heads(softmax over s in S_t of (q_t . k_s / sqrt(128)) v_s)
      W_o, a key-value head serving 8 query heads, ONE set S_t for all
  h2 = RMSNorm_2(x); p = softmax(h2 W_r) over the 128 experts (float32);
      S8 the 8 largest (ties to the lower index); w_e = p_e / sum of p
      over S8; x += sum over e in S8 of w_e E_e(h2), E_e a SwiGLU of 768.
      No shared expert, no dense layer.

After the last layer: final RMSNorm, then the head (untied).

Departures from the published description, each stated in the
configuration file too: the vision tower is not here (it hands the
trunk embeddings; `streams` are the positions it would give); the
head-norm on q and k, the LayerNorm on kI, the 16^-1/2 64^-1/2 factors,
ReLU, the indexer's inputs (the layer's normed h) and its rotation are
ASSUMED (the config names the sizes, not these); `q_chunk_size` /
`kv_chunk_size` are read as the tile of the score computation and
change no result.

The share. `cfg["experts_held"] = (first, count)`: the sum over e runs
over S8 INTERSECTED with the held experts, with w from all eight; what
absent experts would add is left out, as the program's layer does.

In blocks, so that 69,632 positions fit beside an engine that holds its
pool: a layer first makes every position's keys, values and indexer key
(a block of positions a turn), then takes `QUERY_BLOCK` queries a turn
(`lax.map`): their projections, the scores and the choice against every
position, the four key-value heads one at a time, the output
projection, and the experts of the same block (ONE expert's three
tables a loop turn); the head ONE product. What is
whole at once is the stream, a layer's keys, values and indexer keys,
and the logits.

The reference at a LOWER precision or with a PLANTED FAULT, for the
cell's controls; None is the reference itself. `products` rounds both
operands of every product with a weight table to that dtype (the
arithmetic stays float32); `state_dtype` rounds the indexer's keys kI
(what the cache's third leaf holds); `round_to` the attention's
operands q, k, v (what the pages hold). `fault`: `"no_selection"`
(every position s <= t attended), `"newest"` (the newest 2,048
positions in place of the indexer's), `"indexer_weights"` (w left out:
every head weighs 1), `"indexer_relu"` (no ReLU), `"chunk_scores"` (a
query scores the positions of its own chunk of `chunk` alone: what a
chunk that never read the prefix's indexer keys computes),
`"stale_index"` (the `chunk` newest positions before a query, itself
among them, read a ZERO indexer key: what a decode that does not write
the third leaf leaves behind, `chunk` steps in), `"router_weights"` (the
chosen experts' p as they are, not divided by their sum).
"""

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
FAULTS = ("no_selection", "newest", "indexer_weights", "indexer_relu",
          "chunk_scores", "stale_index", "router_weights")
QUERY_BLOCK = 64        # queries whose scores are held at once
LAYER_NORM_EPS = 1e-6

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


def _layer_norm(x, p):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LAYER_NORM_EPS) \
        * p["w"].astype(jnp.float32) + p["b"].astype(jnp.float32)


def rotate(x, streams, theta, sections):
    """x (S, heads, hd) rotated in half-split pairs; `streams` (n, S)
    positions and `sections` how many consecutive frequencies each
    stream takes (they add up to hd / 2)."""
    half = x.shape[-1] // 2
    assert sum(sections) == half and len(sections) == streams.shape[0]
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    of = np.repeat(np.arange(len(sections)), sections)
    angle = streams.astype(jnp.float32).T[:, of][:, None, :] * inv
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def selection(scores, first, topk, fault=None, chunk=None):
    """Which positions each of a block's queries selects: `scores`
    (block, S) = I(t, .) of the queries at `first`, `first` + 1, ...;
    -> (block, S) bool. The `topk` largest among s <= t, every s <= t
    while there are no more than `topk`; ties to the lower position."""
    block, S = scores.shape
    at = first + jnp.arange(block)[:, None]
    s = jnp.arange(S)[None, :]
    seen = s <= at
    if fault == "no_selection":
        return seen
    if fault == "newest":
        return seen & (s > at - topk)
    if fault == "chunk_scores":
        seen = seen & (s >= at // chunk * chunk)
    scores = jnp.where(seen, scores, -jnp.inf)
    kth = jax.lax.top_k(scores, min(topk, S))[0][:, -1:]
    above, tie = scores > kth, scores == kth
    need = topk - jnp.sum(above, -1, keepdims=True)
    earlier = jnp.cumsum(tie, -1) - tie
    return seen & (above | (tie & (earlier < need)))


def _key_rows(lp, cfg, h, streams, state_dtype, round_to):
    """What every later query needs of a block of positions: its keys
    and values (rounded to `round_to`) and its indexer key (to
    `state_dtype`)."""
    ap, ip = lp["attn"], lp["indexer"]
    S = h.shape[0]
    nkv, hd, idim = cfg["num_kv_heads"], cfg["head_dim"], \
        cfg["indexer_head_dim"]
    theta = cfg["rope_theta"]
    k = _rms(_mm(h, ap["wk"]).reshape(S, nkv, hd), ap["k_norm"],
             cfg["rms_norm_eps"])
    k = rotate(k, streams, theta, cfg["mrope_section"])
    v = _mm(h, ap["wv"]).reshape(S, nkv, hd)
    ki = rotate(_layer_norm(_mm(h, ip["wk"]), ip["k_norm"])[:, None],
                streams[:1], theta, (idim // 2,))[:, 0]
    return (_rounded(k, round_to), _rounded(v, round_to),
            _rounded(ki, state_dtype))


def _attend(lp, cfg, h, streams, first, k, v, ki, round_to=None,
            fault=None, chunk=None):
    """A block of queries `h` (block, H) at positions `first`, `first`
    + 1, ... (their `streams` (3, block)) over EVERY position's `k`,
    `v` (S, kv_heads, hd) and `ki` (S, dim): (the mixer's output
    (block, H), the selection (block, S) bool)."""
    ap, ip = lp["attn"], lp["indexer"]
    block, S = h.shape[0], k.shape[0]
    nh, nkv, hd = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    ih, idim, topk = (cfg["indexer_num_heads"], cfg["indexer_head_dim"],
                      cfg["indexer_topk"])
    theta = cfg["rope_theta"]
    q = _rms(_mm(h, ap["wq"]).reshape(block, nh, hd), ap["q_norm"],
             cfg["rms_norm_eps"])
    q = _rounded(rotate(q, streams, theta, cfg["mrope_section"]), round_to)
    qi = rotate(_mm(h, ip["wq"]).reshape(block, ih, idim), streams[:1],
                theta, (idim // 2,))
    w = _mm(h, ip["ww"]) * (ih ** -0.5 * idim ** -0.5)
    if fault == "indexer_weights":
        w = jnp.full_like(w, ih ** -0.5 * idim ** -0.5)
    dots = jnp.einsum("qjd,sd->qjs", qi, ki, precision=HIGHEST)
    if fault == "stale_index":
        # a zero key scores zero, ReLU or none
        stale = jnp.arange(S)[None, :] > (
            first + jnp.arange(block))[:, None] - chunk
        dots = jnp.where(stale[:, None, :], 0.0, dots)
    if fault != "indexer_relu":
        dots = jax.nn.relu(dots)
    scores = jnp.sum(dots * w[..., None], axis=1)
    chosen = selection(jnp.where(scores == 0.0, 0.0, scores), first, topk,
                       fault, chunk)
    group = nh // nkv

    def kv_head(n):
        qg = jax.lax.dynamic_slice_in_dim(q, n * group, group, 1)
        s = jnp.einsum("qgd,sd->gqs", qg, k[:, n],
                       precision=HIGHEST) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(chosen[None], s, -jnp.inf), -1)
        return jnp.einsum("gqs,sd->qgd", p, v[:, n], precision=HIGHEST)

    o = jax.lax.map(kv_head, jnp.arange(nkv))           # (nkv, q, g, hd)
    return _mm(jnp.moveaxis(o, 0, 1).reshape(block, nh * hd), ap["wo"]), \
        chosen


def layer(lp, cfg, x, streams, state_dtype=None, round_to=None, fault=None,
          chunk=None, sets=None):
    """One layer on x (S, H), two passes over blocks of positions: every
    position's keys, values and indexer key; then, a block of
    `QUERY_BLOCK` queries a turn, the mixer AND the experts (both act a
    position once its selection is made). `sets` (a list or None)
    receives the layer's selection (S, S) bool."""
    S = x.shape[0]
    eps = cfg["rms_norm_eps"]
    block = QUERY_BLOCK if S % QUERY_BLOCK == 0 else S
    blocks = lambda t, axis=0: jnp.moveaxis(
        t.reshape(*t.shape[:axis], S // block, block, *t.shape[axis + 1:]),
        axis, 0)
    whole = lambda t: t.reshape(S, *t.shape[2:])
    k, v, ki = (whole(t) for t in jax.lax.map(
        lambda a: _key_rows(lp, cfg, _rms(a[0], lp["ln_1"]["w"], eps), a[1],
                            state_dtype, round_to),
        (blocks(x), blocks(streams, 1))))

    def queries(a):
        xb, sb, first = a
        y, chosen = _attend(lp, cfg, _rms(xb, lp["ln_1"]["w"], eps), sb,
                            first, k, v, ki, round_to, fault, chunk)
        xb = xb + y
        h2 = _rms(xb, lp["ln_2"]["w"], eps)
        weights, _ = route(h2, lp["router"], cfg, fault)
        xb = xb + experts(h2, weights, lp["experts"], cfg["experts_held"])
        return (xb, chosen) if sets is not None else xb

    out = jax.lax.map(queries, (blocks(x), blocks(streams, 1),
                                jnp.arange(0, S, block)))
    if sets is not None:
        out, chosen = out
        sets.append(chosen.reshape(S, S))
    return whole(out)


def _glu(x, gate, up, down):
    return _mm(jax.nn.silu(_mm(x, gate)) * _mm(x, up), down)


def route(h2, w_router, cfg, fault=None):
    """(weights (S, experts) float32, zero off the chosen; the chosen's
    indices)."""
    p = jax.nn.softmax(jnp.matmul(h2, w_router.astype(jnp.float32),
                                  precision=HIGHEST), axis=-1)
    top, idx = jax.lax.top_k(p, cfg["experts_per_token"])
    w = top if fault == "router_weights" \
        else top / jnp.sum(top, -1, keepdims=True)
    spread = jnp.sum(jax.nn.one_hot(idx, p.shape[-1], dtype=jnp.float32)
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
    """x (S, H) against the table's rows: ONE product, so that the
    logits are written once, where they are returned (a loop's stacked
    result is copied out of the loop: 5.3 GB more at 69,632
    positions)."""
    return _mm(x, table.T)


def logits(params, ids, cfg, state_dtype=None, round_to=None,
           products=None, fault=None, chunk=None, streams=None, sets=None):
    """(B, S) tokens -> (B, S, rows) float32 logits, a sequence at a
    time. `cfg`: `families/keye_vl2.reference_config`'s dict. `streams`
    (B, 3, S) int32: the three rotary position streams (None: text, each
    the token's position). `sets` (a list or None; an eager call of ONE
    sequence) receives each layer's selection (S, S) bool."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"keye_vl2_reference: no planted fault {fault!r}")
    if fault in ("chunk_scores", "stale_index") and not chunk:
        raise ValueError(f"keye_vl2_reference: fault {fault!r} needs "
                         f"`chunk`")
    state_dtype, round_to, products = (
        None if d is None else jnp.dtype(d)
        for d in (state_dtype, round_to, products))
    B, S = ids.shape
    if streams is None:
        streams = jnp.broadcast_to(jnp.arange(S), (B, 3, S))

    def one(ids, streams):
        x = params["tok_emb"].astype(jnp.float32)[ids]
        for l in range(cfg["num_layers"]):
            x = layer(params[f"h_{l}"], cfg, x, streams, state_dtype,
                      round_to, fault, chunk, sets)
        x = _rms(x, params["ln_f"]["w"], cfg["rms_norm_eps"])
        return _head(x, params["lm_head"])

    _PRODUCTS[0] = products          # read as the forward is traced
    try:
        with jax.default_matmul_precision("highest"):
            if B == 1:      # no copy of a long sequence's logits
                return one(ids[0], streams[0])[None]
            return jnp.stack([one(ids[b], streams[b]) for b in range(B)])
    finally:
        _PRODUCTS[0] = None
