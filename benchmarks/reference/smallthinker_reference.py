"""SmallThinker-21BA3B-Instruct as published
(https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/blob/main/config.json),
forward, next-token loss and (through `jax.grad`) gradients in plain
`jax.numpy` and float32: no kernel, no cache, no sort, matmuls at
`highest` precision. Independent of `deepspeed_tpu/models/smallthinker.py`;
it reads only the parameter tree's layout (`tok_emb`, `lm_head`, `ln_f`,
`h_<l>` with `ln_1`, `attn` {`wq`, `wk`, `wv`, `wo`}, `router`, `ln_2`,
`experts` {`w_gate`, `w_up`, `w_down`}).

Layer l on input x (hidden 2,560; no biases; RMSNorm eps 1e-6):

    h  = RMSNorm_1(x)
    r  = h W_r                      (2,560 x 64, float32)
    S6 = the six largest of r;  p = softmax(r[S6]) over those six only
         (`moe_primary_router_apply_softmax`; with it `norm_topk_prob`
         changes nothing). The router reads h, BEFORE attention.
    q = h W_q (28 heads x 128), k = h W_k, v = h W_v (4 heads x 128);
         query head i reads key-value head i // 7
    where rope_layout[l] = 1: rotary on all 128 dims of q and k,
         rotate-half pairing (dim d with d + 64), theta 1,500,000, no
         scaling; where 0: no position at all
    scores q k^T / sqrt(128); key j is seen by query i where j <= i and,
         where sliding_window_layout[l] = 1, also i - j < 4096
    x1 = x + softmax(scores) v W_o
    h2 = RMSNorm_2(x1)
    x2 = x1 + sum over e in S6 of
              p_e W_down,e (relu(W_gate,e h2) * (W_up,e h2))     (ReGLU)

Both layouts are [0, 1, 1, 1] x 13. After the last layer: final
RMSNorm, untied head.

The share. `cfg["experts_held"] = (first, count)`: the sum over e runs
over S6 INTERSECTED with the held experts, with p from the full softmax
over six; what the absent experts would add is left out and goes on to
the next layer so, exactly as the program's layer does. The tables hold
the vocabulary rows of the slice; ids, logits and loss are over it.

Departures from the published model: none in the equations. Assumed
(the config keeps no key for them): no bias anywhere; no auxiliary
router loss.

Attention is computed a block of queries at a time so that 8,192
positions fit beside a training engine's state; the blocks of queries,
the held experts and the head's chunks of positions are each ONE loop
body (`lax.map`, `lax.scan`), so that the program compiles in seconds. `round_to` rounds every
matmul operand to that dtype first (float32 accumulation): what the
reference gives at a lower precision, for setting a tolerance between
two readings; None is the reference itself. `fault` plants one of
`FAULTS`, an error a comparison has to catch; None is the reference
itself. `choice` (layers, B, S, 6) names the experts to use in place of
the reference's own six largest (p is still the softmax of ITS router
values at them): how a comparison follows a program through a near tie
between a sixth and a seventh router value.
"""

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
FAULTS = ("no_window", "rotary_on_every_layer", "p_over_all_experts",
          "router_reads_h2")


def _mm(a, b, round_to):
    if round_to is not None:
        a = a.astype(round_to).astype(jnp.float32)
        b = b.astype(round_to).astype(jnp.float32)
    return jnp.matmul(a, b, precision=HIGHEST)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rotary(x, theta):
    """x (B, heads, S, D): dim d is paired with d + D/2."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[2], dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def _attention(q, k, v, window, q_block, round_to):
    """q (B, H, S, D), k and v (B, Hkv, S, D) -> (B, H, S, D), a block
    of `q_block` queries at a time (one loop body, not S / q_block
    copies of it: the program stays small)."""
    B, H, S, D = q.shape
    assert S % q_block == 0, (S, q_block)
    group = H // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    kt = k.transpose(0, 1, 3, 2)
    j = jnp.arange(S)[None, :]

    def block(s0):
        i = s0 + jnp.arange(q_block)[:, None]
        seen = j <= i
        if window:
            seen &= i - j < window
        qb = jax.lax.dynamic_slice_in_dim(q, s0, q_block, axis=2)
        s = _mm(qb, kt, round_to) / math.sqrt(D)
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return _mm(p, v, round_to)

    out = jax.lax.map(block, jnp.arange(0, S, q_block))    # (n, B, H, qb, D)
    return out.transpose(1, 2, 0, 3, 4).reshape(B, H, S, D)


def _experts(h2, r, p6, idx6, ex, first, round_to):
    """The held experts' part of the expert sum: h2 (T, H). Every held
    expert on every token, weighted by the router's p where the expert
    is among the token's six and by 0 elsewhere."""
    def one(y, held):
        local, w_gate, w_up, w_down = held
        weight = jnp.sum(jnp.where(idx6 == first + local, p6, 0.0), -1)
        act = jax.nn.relu(_mm(h2, w_gate, round_to)) \
            * _mm(h2, w_up, round_to)
        return y + weight[:, None] * _mm(act, w_down, round_to), None

    count = ex["w_gate"].shape[0]
    y, _ = jax.lax.scan(one, jnp.zeros_like(h2),
                        (jnp.arange(count), ex["w_gate"], ex["w_up"],
                         ex["w_down"]))
    return y


def hidden(params, ids, cfg, q_block=1024, round_to=None, choice=None,
           fault=None):
    """(B, S) ids -> (final normed hidden states (B, S, H), router
    values (layers, B, S, num_experts), the experts used (layers, B, S,
    6))."""
    assert fault is None or fault in FAULTS, fault
    f32 = lambda t: jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), t)
    B, S = ids.shape
    H, Hkv, D = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    first = cfg["experts_held"][0]
    x = f32(params["tok_emb"])[ids]
    routers, used = [], []
    for l in range(cfg["num_layers"]):
        lp = f32(params[f"h_{l}"])
        h = _rms(x, lp["ln_1"]["w"], eps)
        r = jnp.matmul(h, lp["router"], precision=HIGHEST)
        heads = lambda t, n: t.reshape(B, S, n, D).transpose(0, 2, 1, 3)
        q = heads(_mm(h, lp["attn"]["wq"], round_to), H)
        k = heads(_mm(h, lp["attn"]["wk"], round_to), Hkv)
        v = heads(_mm(h, lp["attn"]["wv"], round_to), Hkv)
        if cfg["rope_layout"][l] or fault == "rotary_on_every_layer":
            q, k = _rotary(q, cfg["rope_theta"]), _rotary(k, cfg["rope_theta"])
        window = cfg["sliding_window_size"] \
            if cfg["sliding_window_layout"][l] and fault != "no_window" \
            else None
        ctx = _attention(q, k, v, window, q_block, round_to)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(B, S, H * D)
        x = x + _mm(ctx, lp["attn"]["wo"], round_to)
        h2 = _rms(x, lp["ln_2"]["w"], eps)
        if fault == "router_reads_h2":
            r = jnp.matmul(h2, lp["router"], precision=HIGHEST)
        if choice is None:
            top, idx6 = jax.lax.top_k(r, cfg["experts_per_token"])
        else:
            idx6 = choice[l]
            top = jnp.take_along_axis(r, idx6, axis=-1)
        p6 = jax.nn.softmax(top, axis=-1)
        if fault == "p_over_all_experts":
            p6 = jnp.take_along_axis(jax.nn.softmax(r, axis=-1), idx6, -1)
        routers.append(r)
        used.append(idx6)
        flat = lambda t: t.reshape(B * S, t.shape[-1])
        y = _experts(flat(h2), flat(r), flat(p6), flat(idx6),
                     lp["experts"], first, round_to)
        x = x + y.reshape(x.shape)
    x = _rms(x, f32(params["ln_f"])["w"], eps)
    return x, jnp.stack(routers), jnp.stack(used).astype(jnp.int32)


def _xent(x, head_t, targets, chunk, round_to):
    """Summed next-token cross entropy, the head a chunk of positions at
    a time."""
    B, S, H = x.shape
    assert S % chunk == 0, (S, chunk)

    def one(args):
        xs, ts = args
        logp = jax.nn.log_softmax(_mm(xs, head_t, round_to), axis=-1)
        return -jnp.take_along_axis(logp, ts[..., None], axis=-1).sum()

    split = lambda a: a.reshape(B, S // chunk, chunk, *a.shape[2:]
                                ).swapaxes(0, 1)
    return jax.lax.map(one, (split(x), split(targets))).sum()


def logits_at(params, ids, positions, cfg, q_block=1024, round_to=None):
    """float32 logits over the held rows at `positions` of every row:
    ((B, len(positions), rows), router values (layers, B, S, E))."""
    x, routers, _ = hidden(params, ids, cfg, q_block, round_to)
    head = params["lm_head"].astype(jnp.float32)
    return _mm(x[:, positions], head.T, round_to), routers


def next_token_loss(params, ids, cfg, q_block=1024, chunk=2048,
                    round_to=None):
    """Mean next-token cross entropy of (B, S + 1) tokens over the held
    rows."""
    x, _, _ = hidden(params, ids[:, :-1], cfg, q_block, round_to)
    head_t = params["lm_head"].astype(jnp.float32).T
    return _xent(x, head_t, ids[:, 1:], chunk, round_to) / ids[:, 1:].size


def loss_logits_routers(params, ids, positions, cfg, q_block=1024,
                        chunk=2048, round_to=None, choice=None, fault=None):
    """One forward pass of (B, S + 1) tokens for the readings a
    comparison needs: (mean next-token loss, logits at `positions`
    (B, n, rows), router values there (layers, B, n, E), the experts
    used at every position (layers, B, S, 6))."""
    x, routers, used = hidden(params, ids[:, :-1], cfg, q_block, round_to,
                              choice, fault)
    head_t = params["lm_head"].astype(jnp.float32).T
    loss = _xent(x, head_t, ids[:, 1:], chunk, round_to) / ids[:, 1:].size
    return loss, _mm(x[:, positions], head_t, round_to), \
        routers[:, :, positions], used
