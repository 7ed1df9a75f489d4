"""Solar-Open2-style hybrid decoder, SERVED: gated delta-rule linear
attention ("KDA") among gated softmax layers in one trunk, every layer's
second half routed SwiGLU experts with a shared expert beside them
(upstage/Solar-Open2-250B, config.json; docs/solar_open2.md has the
equations and what the config leaves open).

Layer ``l`` on the residual stream ``x`` (``h = RMSNorm(x)``):

- ``l`` in ``gqa_layers``: causal softmax attention with no position,
  ``num_heads`` query heads over ``num_kv_heads`` key-value heads, and
  an elementwise output gate: ``x += W_o [attn * sigmoid(h W_gate)]``.
  Keys and values live in the page pool (``inference/kv_cache.py``).
- else: ``q, k, v = SiLU(conv4(h W))``, q and k to unit norm a head,
  log-decay ``g = -exp(A) softplus(h W_f1 W_f2 + b_dt)`` a head and
  channel, step ``b = 2 sigmoid(h W_b)``, the recurrence of
  ``ops/kda.py`` on a float32 state, ``x += W_o [RMSNorm_head(o) *
  sigmoid(h W_g1 W_g2 + b_g)]``. The state and the convolution's last
  three inputs live in the per-slot state pool, one row a slot.
- then ``x += sum_top8 w_e E_e(h2) + E_shared(h2)`` on ``h2 =
  RMSNorm(x)``: router in float32, softmax, the eight largest, their
  weights renormalised to sum 1.

The config carries the chip's SHARE of a layer as
``models/smallthinker.py`` does: ``experts_held`` and ``vocab_held``.

Two programs. PREFILL (more than one token a row): every row starts at
position 0 with an empty state (the family is served without prefix
cache or chunked prefill: ``inference/engine.py`` refuses them), the
softmax layers attend the prompt's own keys and values through
``flash_attention`` and write them to the pages, the delta-rule layers
run ``kda_chunk_scan`` to each row's TRUE length and write the final
state and tail WHOLE at the row's slot. DECODE (one token a row, the
rows the slot table): the Pallas paged reader, ``kda_decode_update`` on
every row's state in place, the held experts on every row.
"""

from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.served_trunk import (ServedFamily, _mm,
                                               paged_pair_mixer,
                                               served_forward,
                                               whole_leaf_specs)
from deepspeed_tpu.ops.attention.flash import flash_attention
from deepspeed_tpu.ops.attention.page_pool import (gqa_stripe_attention,
                                                   paged_attend,
                                                   write_paged_layer)
from deepspeed_tpu.ops.functional import rms_norm
from deepspeed_tpu.ops.kda import kda_chunk_scan, kda_decode_update
from deepspeed_tpu.ops.moe import route_top_k
from deepspeed_tpu.profiling.spans import scope

# caps of the grouped products' tile at these experts' widths (4,096 x
# 1,280): cut to whole divisors, (128, 1024, 640) up and (128, 1280,
# 512) down. 128 rows: a prefill bucket of T tokens lands T / 40 rows
# on a held expert (6 to 100), and a tile a group touches is worked
# whole
_EXPERT_TILE = (128, 1280, 640)


class SolarOpen2Config(NamedTuple):
    vocab_size: int = 196608
    hidden_size: int = 4096
    num_layers: int = 48
    num_heads: int = 64
    num_kv_heads: int = 8
    head_dim: int = 128
    gqa_layers: Tuple[int, ...] = tuple(range(0, 48, 4))
    kda_num_heads: int = 64
    kda_head_dim: int = 128
    kda_conv_width: int = 4
    kda_gate_rank: int = 128          # the low-rank decay and gate
    moe_intermediate_size: int = 1280
    num_experts: int = 320
    experts_per_token: int = 8
    routed_scaling_factor: float = 1.0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 1048576
    initializer_range: float = 0.02
    # the chip's share: (first, count); count 0 => all of them
    experts_held: Tuple[int, int] = (0, 0)
    vocab_held: Tuple[int, int] = (0, 0)

    @property
    def held(self):
        first, count = self.experts_held
        return (first, count or self.num_experts)

    @property
    def vocab_rows(self):
        return self.vocab_held[1] or self.vocab_size

    @property
    def kv_heads(self):               # what inference/kv_cache.py reads
        return self.num_kv_heads

    @property
    def softmax_layers(self):
        return tuple(l for l in range(self.num_layers)
                     if l in self.gqa_layers)

    @property
    def recurrent_layers(self):
        return tuple(l for l in range(self.num_layers)
                     if l not in self.gqa_layers)

    @property
    def kv_cache_layers(self):
        """Layers with keys and values in the page pool."""
        return len(self.softmax_layers)

    @property
    def expert_counters(self):
        """What the serving engine's ``serve/decode`` span reports of
        the routed experts: (assignments a row that decodes offers the
        router over the layers, experts held here). The decode program
        returns the layers' counters (``with_counts``) to go with them.
        """
        return (self.experts_per_token * self.num_layers, self.held[1])

    @property
    def kda_beta_scale(self):
        """The step is ``2 sigmoid``: an eigenvalue of the transition
        may be negative (a second family's is ``sigmoid`` alone)."""
        return 2.0

    @property
    def state_geometry(self):
        """What a slot holds whatever its length, for
        ``kv_cache.state_pool_spec_for``: (recurrent layers, heads, key
        width, value width, tail positions, tail channels)."""
        width = self.kda_num_heads * self.kda_head_dim
        return (len(self.recurrent_layers), self.kda_num_heads,
                self.kda_head_dim, self.kda_head_dim,
                self.kda_conv_width - 1, 3 * width)


def init_solar_open2_params(config: SolarOpen2Config, key,
                            dtype=jnp.bfloat16) -> Dict[str, Any]:
    """The tree, matrices HELD in ``dtype`` (bfloat16: 3.3B parameters in
    float32 would not leave room for the state pool), the router, the
    decay's ``a_log`` and ``b_dt`` and the norms in float32:
    ``tok_emb``, ``lm_head`` (rows held, H), ``ln_f``, ``h_<l>`` with
    ``ln_1``, ``ln_2``, ``router`` (H, experts), ``experts`` {w_gate,
    w_up: (held, H, F), w_down: (held, F, H)}, ``shared`` {w_gate, w_up:
    (H, F), w_down: (F, H)} and ``attn`` {wq, wk, wv, wg, wo} or ``kda``
    {wq, wk, wv, conv (3, width, W), wf1, wf2, b_dt, a_log, wb, wg1, wg2,
    b_g, norm, wo}. The decay starts as the family's public
    initialisers do: ``exp(a_log)`` uniform in [1, 16], ``softplus(b_dt)``
    log-uniform in [0.001, 0.1]."""
    h, hd = config.hidden_size, config.head_dim
    nq, nkv = config.num_heads * hd, config.num_kv_heads * hd
    kh, kd = config.kda_num_heads, config.kda_head_dim
    kw, rank, cw = kh * kd, config.kda_gate_rank, config.kda_conv_width
    f, held, rows = (config.moe_intermediate_size, config.held[1],
                     config.vocab_rows)
    std = config.initializer_range
    out_std = std / np.sqrt(2.0 * config.num_layers)

    def normal(k, shape, s, dt=dtype):
        return (jax.random.normal(k, shape, jnp.float32) * s).astype(dt)

    ones = lambda n: jnp.ones((n,), jnp.float32)
    keys = jax.random.split(key, 2 + config.num_layers)
    params: Dict[str, Any] = {
        "tok_emb": normal(keys[0], (rows, h), std),
        "lm_head": normal(keys[1], (rows, h), std),
        "ln_f": {"w": ones(h)},
    }
    for l in range(config.num_layers):
        k = jax.random.split(keys[2 + l], 24)
        lp = {
            "ln_1": {"w": ones(h)}, "ln_2": {"w": ones(h)},
            "router": normal(k[0], (h, config.num_experts), std,
                             jnp.float32),
            "experts": {"w_gate": normal(k[1], (held, h, f), std),
                        "w_up": normal(k[2], (held, h, f), std),
                        "w_down": normal(k[3], (held, f, h), out_std)},
            "shared": {"w_gate": normal(k[4], (h, f), std),
                       "w_up": normal(k[5], (h, f), std),
                       "w_down": normal(k[6], (f, h), out_std)},
        }
        if l in config.gqa_layers:
            lp["attn"] = {"wq": normal(k[7], (h, nq), std),
                          "wk": normal(k[8], (h, nkv), std),
                          "wv": normal(k[9], (h, nkv), std),
                          "wg": normal(k[10], (h, nq), std),
                          "wo": normal(k[11], (nq, h), out_std)}
        else:
            dt = jnp.exp(jax.random.uniform(
                k[12], (kw,), jnp.float32, np.log(1e-3), np.log(1e-1)))
            lp["kda"] = {
                "wq": normal(k[13], (h, kw), std),
                "wk": normal(k[14], (h, kw), std),
                "wv": normal(k[15], (h, kw), std),
                "conv": normal(k[16], (3, cw, kw), cw ** -0.5),
                "wf1": normal(k[17], (h, rank), std),
                "wf2": normal(k[18], (rank, kw), std),
                # softplus(b_dt) = dt
                "b_dt": dt + jnp.log(-jnp.expm1(-dt)),
                "a_log": jnp.log(jax.random.uniform(
                    k[19], (kh,), jnp.float32, 1.0, 16.0)),
                "wb": normal(k[20], (h, kh), std),
                "wg1": normal(k[21], (h, rank), std),
                "wg2": normal(k[22], (rank, kw), std),
                "b_g": jnp.zeros((kw,), jnp.float32),
                "norm": ones(kd),
                "wo": normal(k[23], (kw, h), out_std)}
        params[f"h_{l}"] = lp
    return params


def solar_open2_param_specs(config: SolarOpen2Config):
    """Every leaf whole (``served_trunk.whole_leaf_specs``)."""
    return whole_leaf_specs(init_solar_open2_params, config)


def _softmax_mixer(ap, config, h, dtype, cache):
    """Gated softmax attention of one layer on ``h`` (B, S, H). ``cache``
    None (no pages: the plain forward) or ``served_trunk._Pages``;
    returns (y, the pools)."""
    B, S, _ = h.shape
    H, hkv, hd = config.num_heads, config.num_kv_heads, config.head_dim
    with scope("attn_proj"):
        heads = lambda t, n: t.astype(dtype).reshape(
            B, S, n, hd).transpose(0, 2, 1, 3)
        q = heads(_mm(h, ap["wq"], dtype), H)
        k = heads(_mm(h, ap["wk"], dtype), hkv)
        v = heads(_mm(h, ap["wv"], dtype), hkv)
    pools = None
    if cache is not None and S == 1:
        box = []
        ctx = paged_attend(q, k, v, cache.pools, cache.layer, cache.tables,
                           cache.positions, cache.index, box, cache.reader,
                           gqa_stripe_attention)
        pools = box[0]
    else:
        if cache is not None:
            pools = write_paged_layer(cache.pools, cache.layer, k, v,
                                      cache.index)
        # every row starts at position 0: its own keys and values are
        # all it may see
        with scope("attn_core"):
            ctx = flash_attention(q, k, v, causal=True)
    with scope("attn_gate"):
        gate = jax.nn.sigmoid(_mm(h, ap["wg"], dtype))
        ctx = ctx.transpose(0, 2, 1, 3).reshape(B, S, H * hd)
        ctx = (ctx.astype(jnp.float32) * gate).astype(dtype)
    with scope("attn_proj"):
        return _mm(ctx, ap["wo"], dtype), pools


def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def kda_mixer(lp, h, call, cache, n):
    """Gated delta-rule attention of one layer on ``h`` (B, S, H), a
    mixer of ``models/served_trunk.py``: the ``n``-th recurrent layer
    over the tree's ``state`` and ``tails`` (``cache`` None, the plain
    forward: an empty state, nothing kept), a served prefill to
    ``call.lengths`` and into rows ``call.slots``. Shared with
    ``models/kimi_linear.py``; what differs is read from the config (the
    step's range, ``kda_beta_scale``) and the tree (a gate bias ``b_g``
    or none). Under ``call.carry`` (a family served in chunks) a prefill
    row whose ``call.positions`` is past 0 starts from its slot's state
    and tail, and a decode leaves a row that is not ``call.active`` as
    it is."""
    kp, config, dtype, lengths = (lp["kda"], call.config, call.dtype,
                                  call.lengths)
    B, S, _ = h.shape
    nh, hd, cw = (config.kda_num_heads, config.kda_head_dim,
                  config.kda_conv_width)
    decode = cache is not None and S == 1
    with scope("kda_proj"):
        raw = jnp.concatenate([_mm(h, kp[w], dtype).astype(dtype)
                               for w in ("wq", "wk", "wv")], axis=-1)
        carried = None
        if decode:
            window = jnp.concatenate([cache.tails[n], raw], axis=1)
            tail = window[:, 1:]
            if call.carry:
                # a slot in the middle of its prefill keeps its tail
                tail = jnp.where(call.active[:, None, None], tail,
                                 cache.tails[n])
        else:
            window = jnp.pad(raw, ((0, 0), (cw - 1, 0), (0, 0)))
            if cache is not None and call.carry:
                # a later chunk: the three inputs before its first, and
                # the state its predecessor left (zeros at position 0)
                carried = call.positions > 0
                window = jnp.concatenate([jnp.where(
                    carried[:, None, None], cache.tails[n, call.slots],
                    0).astype(raw.dtype), raw], axis=1)
            if cache is not None:
                # the last inputs before each row's TRUE length (zeros
                # before position 0)
                tail = jax.vmap(lambda w, m: jax.lax.dynamic_slice_in_dim(
                    w, m, cw - 1))(window, lengths)
        # conv[c, j] weighs the input j - (cw - 1) positions back
        taps = jnp.concatenate(list(kp["conv"].astype(jnp.float32)), -1)
        mixed = sum(window[:, j:j + S].astype(jnp.float32) * taps[j]
                    for j in range(cw))
        q, k, v = (t.reshape(B, S, nh, hd) for t in jnp.split(
            jax.nn.silu(mixed), 3, axis=-1))
        q, k = _unit(q) * hd ** -0.5, _unit(k)
        decay = _mm(_mm(h, kp["wf1"], dtype), kp["wf2"], dtype) + kp["b_dt"]
        g = -jnp.exp(kp["a_log"])[:, None] * jax.nn.softplus(
            decay).reshape(B, S, nh, hd)
        beta = jax.nn.sigmoid(_mm(h, kp["wb"], dtype))
        if config.kda_beta_scale != 1.0:
            beta = config.kda_beta_scale * beta
        gate = _mm(_mm(h, kp["wg1"], dtype), kp["wg2"], dtype)
        gate = jax.nn.sigmoid(gate + kp["b_g"] if "b_g" in kp else gate)
    if decode:
        if call.carry:
            # g = 0, b = 0: the identity on an inactive row's state
            g = jnp.where(call.active[:, None, None, None], g, 0.0)
            beta = jnp.where(call.active[:, None, None], beta, 0.0)
        with scope("kda_state"):
            o, state = kda_decode_update(
                cache.state, n, q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                beta[:, 0])
            o = o[:, None]
            tails = cache.tails.at[n].set(tail)
    else:
        with scope("kda_scan"):
            start = jnp.zeros((B, nh, hd, hd), jnp.float32)
            if carried is not None:
                start = jnp.where(carried[:, None, None, None],
                                  cache.state[n, call.slots], start)
            o, last = kda_chunk_scan(q, k, v, g, beta, start, lengths)
        if cache is not None:
            assert call.slots is not None, \
                "a served prefill needs each row's slot"
            with scope("kda_state"):
                state = cache.state.at[n, call.slots].set(last)
                tails = cache.tails.at[n, call.slots].set(tail)
    if cache is not None:
        cache = cache._replace(state=state, tails=tails)
    with scope("kda_proj"):
        o = rms_norm(o, kp["norm"], config.rms_norm_eps).reshape(B, S, -1)
        return _mm((o * gate).astype(dtype), kp["wo"], dtype), cache


def _family(config: SolarOpen2Config) -> ServedFamily:
    def route(flat, router):
        # softmax over all the experts, the eight largest, renormalised
        # to sum 1: the softmax over those eight's own scores
        idx, p, _ = route_top_k(flat, router, config.experts_per_token)
        return idx, p * config.routed_scaling_factor, None

    return ServedFamily(
        layers=tuple(("softmax" if l in config.gqa_layers else "kda",
                      "experts") for l in range(config.num_layers)),
        mixers={"softmax": paged_pair_mixer(_softmax_mixer),
                "kda": kda_mixer},
        route=route, expert_tile=_EXPERT_TILE)


def solar_open2_forward(params, config: SolarOpen2Config, input_ids,
                        dtype=jnp.bfloat16, kv_cache=None,
                        cache_position=None, block_tables=None,
                        paged_attn_kernel: str = "gather", lengths=None,
                        slots=None, active=None, with_counts=False):
    """Logits over the held rows of the vocabulary.

    Plain (``kv_cache=None``): (B, S) ids -> (B, S, rows) float32, every
    row from position 0 and an empty state.

    Serving: ``kv_cache`` a ``kv_cache.PagedStateCache`` — ``keys`` and
    ``values``, the page pools of the softmax layers, ``(softmax layers,
    pages, page_size, kv_heads * head_dim)``, and ``state`` and
    ``tails``, the per-slot pools of the delta-rule layers,
    ``(recurrent layers, slots + 1, heads, dk, dv)`` float32 and
    ``(recurrent layers, slots + 1, 3, 3 * heads * dk)`` — with
    ``block_tables`` and ``cache_position`` as the other families take
    them. PREFILL (S > 1) also takes ``lengths`` (B,) and ``slots``
    (B,), each row's true length and its row of the state pools (a pad
    row names the scratch row), and returns logits at each row's LAST
    true position only, (B, 1, rows). DECODE (S == 1) runs row i
    against row i of the state pools. Returns (logits, the cache); with
    ``with_counts`` also (layers, 2) int32: in DECODE each layer's
    assignments landed on held experts and its fullest held expert's,
    counted over the ``active`` (B,) rows; in PREFILL the rows each
    layer's expert turns worked and the rows static turns would have."""
    return served_forward(_family(config), params, config, input_ids, dtype,
                          kv_cache, cache_position, block_tables,
                          paged_attn_kernel, lengths, slots, active,
                          with_counts)


def solar_open2_param_count(config: SolarOpen2Config):
    """(a delta-rule mixer, a softmax mixer, router + shared expert +
    two norms, an expert, embedding + head + final norm)."""
    h, hd = config.hidden_size, config.head_dim
    nq, nkv = config.num_heads * hd, config.num_kv_heads * hd
    kw, rank = (config.kda_num_heads * config.kda_head_dim,
                config.kda_gate_rank)
    kda = (4 * h * kw + 3 * config.kda_conv_width * kw
           + 2 * (h * rank + rank * kw) + 2 * kw + h * config.kda_num_heads
           + config.kda_num_heads + config.kda_head_dim)
    soft = 3 * h * nq + 2 * h * nkv
    expert = 3 * h * config.moe_intermediate_size
    return (kda, soft, h * config.num_experts + expert + 2 * h, expert,
            2 * config.vocab_rows * h + h)
