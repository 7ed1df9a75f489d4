"""LFM2-MoE-style hybrid decoder, SERVED: gated short convolutions among
grouped-query softmax attention layers whose queries and keys are normed
a head and then rotated, leading dense SwiGLU layers, then layers of
routed SwiGLU experts chosen by a sigmoid router under a per-expert
correction bias, and NO shared expert (LiquidAI/LFM2-24B-A2B,
config.json ``model_type`` ``lfm2_moe``; docs/lfm2.md has the equations
and what the config leaves open).

Layer ``l`` on the residual stream ``x`` (``h = RMSNorm(x)``, kind from
``layer_types[l]``):

- ``"conv"``: ``[B | C | X] = h W_in``; ``u = B * X``; ``v_t = sum_j
  w_j u_{t-2+j}`` (a causal depthwise convolution of ``conv_L_cache``
  positions, ``u`` zero before the sequence starts); ``x += (C * v)
  W_out``. No activation inside. What a slot keeps between calls is the
  last ``conv_L_cache - 1`` values of the PRODUCT ``u``: its row of the
  cache tree's ``tails``.
- ``"full_attention"``: ``q, k, v = h W_q, h W_k, h W_v``; ``q`` and
  ``k`` through an RMSNorm a head (one learned weight set a layer each),
  THEN rotated at the token's position (half-split pairs, the whole
  head, ``rope_theta``); causal softmax in float32 of ``q k^T
  head_dim^-1/2``, a key-value head serving ``num_heads / num_kv_heads``
  query heads; ``x += ctx W_o``. The cached row of a token is ``k``
  after norm and rotation, and ``v``, in the tree's page pools.
- then, on ``h2 = RMSNorm(x)``: layers below ``num_dense_layers`` a
  dense SwiGLU, the rest ``x += sum_top w_e E_e(h2)`` with ``s =
  sigmoid(h2 W_r)``, the ``experts_per_token`` largest of ``s + b_e``,
  ``w_e = s_e / (sum of the chosen s + 1e-6) * routed_scaling_factor``.

After the last layer an RMSNorm, then the head: the embedding table,
tied. The cache tree is ``inference/kv_cache.PagedTailCache``: ``keys``
and ``values`` (attention layers, pages, page_size, kv_heads x
head_dim) and ``tails`` (convolution layers, slots + 1, positions,
hidden): no recurrent state. The config carries what the chip HOLDS as
the other served families' do (``experts_held``, ``vocab_held``).
"""

from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.served_trunk import (ServedFamily, _mm,
                                               paged_pair_mixer,
                                               served_forward,
                                               whole_leaf_specs)
from deepspeed_tpu.ops.attention.page_pool import (gqa_stripe_attention,
                                                   own_keys_attention,
                                                   paged_attend,
                                                   write_paged_layer)
from deepspeed_tpu.ops.functional import rms_norm
from deepspeed_tpu.ops.moe import route_group_limited
from deepspeed_tpu.profiling.spans import scope

# caps of a PREFILL bucket's grouped products' tile at these experts'
# widths (2,048 x 1,536), cut to whole divisors: (128, 2048, 768) up and
# (128, 1536, 512) down. 128 rows: a bucket of T tokens lands T / 16
# rows on an expert (16 to 256), every table is read whatever lands, and
# a tile a group touches is worked whole
_EXPERT_TILE = (128, 2048, 768)
# under the sum of the chosen scores (the family's public gate)
_WEIGHT_EPS = 1e-6


class LFM2Config(NamedTuple):
    vocab_size: int = 65536
    hidden_size: int = 2048
    num_layers: int = 40
    layer_types: Tuple[str, ...] = tuple(
        "full_attention" if l % 4 == 2 else "conv" for l in range(40))
    num_heads: int = 32
    num_kv_heads: int = 8
    conv_L_cache: int = 3
    intermediate_size: int = 11776
    moe_intermediate_size: int = 1536
    num_dense_layers: int = 2
    num_experts: int = 64
    experts_per_token: int = 4
    routed_scaling_factor: float = 1.0
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1000000.0
    max_position_embeddings: int = 128000
    initializer_range: float = 0.02
    # SEEDED trees only (a benchmark's choices, stated in its
    # configuration file: docs/lfm2.md Seeding): a ROUTED expert's
    # w_down against the other branches', the spread of the router's
    # correction bias, and how far a head's norm weights on queries and
    # keys lie from one (log-uniform in [1 / spread, spread]; 1: ones);
    # trained weights carry their own
    routed_init_gain: float = 1.0
    router_bias_std: float = 0.0
    qk_norm_spread: float = 1.0
    # what the chip holds: (first, count); count 0 => all of them
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
    def head_dim(self):
        return self.hidden_size // self.num_heads

    @property
    def kv_heads(self):               # what inference/kv_cache.py reads
        return self.num_kv_heads

    @property
    def kinds(self):
        """The mixer kind of each layer that is RUN: the first
        ``num_layers`` entries of the published ``layer_types``."""
        kinds = tuple(self.layer_types[:self.num_layers])
        if len(kinds) != self.num_layers or \
                not set(kinds) <= {"conv", "full_attention"}:
            raise ValueError(f"layer_types has to name {self.num_layers} "
                             f"layers 'conv' or 'full_attention', got "
                             f"{kinds}")
        return kinds

    @property
    def attention_layers(self):
        return tuple(l for l, k in enumerate(self.kinds)
                     if k == "full_attention")

    @property
    def conv_layers(self):
        return tuple(l for l, k in enumerate(self.kinds) if k == "conv")

    @property
    def expert_layers(self):
        return tuple(range(self.num_dense_layers, self.num_layers))

    @property
    def kv_cache_layers(self):
        """Layers with keys and values in the page pool."""
        return len(self.attention_layers)

    @property
    def tail_geometry(self):
        """What a slot holds whatever its length, for
        ``kv_cache.state_pool_spec_for``: (convolution layers, tail
        positions, tail channels), and no recurrent state."""
        return (len(self.conv_layers), self.conv_L_cache - 1,
                self.hidden_size)

    @property
    def expert_counters(self):
        """As ``SolarOpen2Config.expert_counters``."""
        return (self.experts_per_token * len(self.expert_layers),
                self.held[1])


def init_lfm2_params(config: LFM2Config, key,
                     dtype=jnp.bfloat16) -> Dict[str, Any]:
    """The tree, matrices HELD in ``dtype``, the router, its bias, the
    convolution's taps and the norms in float32: ``tok_emb`` (rows held,
    H; the head too), ``ln_f``, ``h_<l>`` with ``ln_1``, ``ln_2``,
    ``conv`` {w_in (H, 3H): B | C | X, taps (L, H), w_out (H, H)} or
    ``attn`` {wq, wk, wv, q_norm, k_norm (head_dim,), wo}, and ``mlp``
    {w_gate, w_up, w_down} below ``num_dense_layers``, else ``router``
    (H, experts), ``router_bias`` (experts,), ``experts`` {(held, H, F)
    x 2, (held, F, H)}. NO ``shared`` leaf: the family has no shared
    expert. Normal with ``initializer_range`` for a matrix that reads a
    normed input, that over sqrt(2 x layers) for one that writes to the
    stream (a routed expert's times ``routed_init_gain``), the taps with
    L^-1/2, the bias ``+- router_bias_std`` with a seeded sign, a head's
    norm weights on q and k ``qk_norm_spread ** uniform(-1, 1)``."""
    h, nq = config.hidden_size, config.num_heads * config.head_dim
    nkv, hd = config.num_kv_heads * config.head_dim, config.head_dim
    f, held, rows = (config.moe_intermediate_size, config.held[1],
                     config.vocab_rows)
    std = config.initializer_range
    out_std = std / np.sqrt(2.0 * config.num_layers)

    def normal(k, shape, s, dt=dtype):
        return (jax.random.normal(k, shape, jnp.float32) * s).astype(dt)

    ones = lambda n: jnp.ones((n,), jnp.float32)
    # a norm weight a channel of a head, away from one: a seeded q or k
    # is near unit rms before its norm, so ones would make the norm
    # (and its absence) near the identity
    head_norm = lambda k: config.qk_norm_spread ** jax.random.uniform(
        k, (hd,), jnp.float32, -1.0, 1.0)
    keys = jax.random.split(key, 1 + config.num_layers)
    params: Dict[str, Any] = {"tok_emb": normal(keys[0], (rows, h), std),
                              "ln_f": {"w": ones(h)}}
    for l, kind in enumerate(config.kinds):
        k = jax.random.split(keys[1 + l], 12)
        lp = {"ln_1": {"w": ones(h)}, "ln_2": {"w": ones(h)}}
        if kind == "conv":
            cw = config.conv_L_cache
            lp["conv"] = {"w_in": normal(k[0], (h, 3 * h), std),
                          "taps": normal(k[1], (cw, h), cw ** -0.5,
                                         jnp.float32),
                          "w_out": normal(k[2], (h, h), out_std)}
        else:
            lp["attn"] = {"wq": normal(k[3], (h, nq), std),
                          "wk": normal(k[4], (h, nkv), std),
                          "wv": normal(k[5], (h, nkv), std),
                          "q_norm": head_norm(k[10]),
                          "k_norm": head_norm(k[11]),
                          "wo": normal(k[6], (nq, h), out_std)}
        if l < config.num_dense_layers:
            fd = config.intermediate_size
            lp["mlp"] = {"w_gate": normal(k[7], (h, fd), std),
                         "w_up": normal(k[8], (h, fd), std),
                         "w_down": normal(k[9], (fd, h), out_std)}
        else:
            lp["router"] = normal(k[7], (h, config.num_experts), std,
                                  jnp.float32)
            # +- router_bias_std, the sign seeded: every expert's bias
            # as far from zero as every other's (a normal's few
            # outliers fill a few experts and say little)
            lp["router_bias"] = config.router_bias_std * jnp.sign(
                jax.random.normal(k[8], (config.num_experts,), jnp.float32))
            ek = jax.random.split(k[9], 3)
            lp["experts"] = {"w_gate": normal(ek[0], (held, h, f), std),
                             "w_up": normal(ek[1], (held, h, f), std),
                             "w_down": normal(ek[2], (held, f, h),
                                              out_std
                                              * config.routed_init_gain)}
        params[f"h_{l}"] = lp
    return params


def lfm2_param_specs(config: LFM2Config):
    """Every leaf whole (``served_trunk.whole_leaf_specs``)."""
    return whole_leaf_specs(init_lfm2_params, config)


def rotate_half_split(x, positions, theta: float):
    """``x`` (B, heads, S, hd) float32 rotated at ``positions`` (B, S):
    the pair (i, i + hd / 2) turns by ``positions * theta^(-2 i / hd)``
    (the half-split pairing of the family's public code, over the whole
    head)."""
    half = x.shape[-1] // 2
    inv = theta ** (-np.arange(half, dtype=np.float32) / half)
    angle = positions.astype(jnp.float32)[:, None, :, None] * inv
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _softmax_mixer(ap, config, h, dtype, cache, token_positions):
    """Attention of one layer on ``h`` (B, S, H). ``cache`` None (no
    pages: the plain forward) or ``served_trunk._Pages``;
    ``token_positions`` (B, S), the trunk's; returns (y, the pools)."""
    B, S, _ = h.shape
    H, hkv, hd = config.num_heads, config.num_kv_heads, config.head_dim
    with scope("attn_proj"):
        heads = lambda t, n: t.reshape(B, S, n, hd).transpose(0, 2, 1, 3)
        q = heads(_mm(h, ap["wq"], dtype), H)
        k = heads(_mm(h, ap["wk"], dtype), hkv)
        v = heads(_mm(h, ap["wv"], dtype), hkv).astype(dtype)
    with scope("attn_norm_rope"):
        # a head's norm, THEN its rotation, both float32
        eps, theta = config.rms_norm_eps, config.rope_theta
        q = rotate_half_split(rms_norm(q, ap["q_norm"], eps),
                              token_positions, theta).astype(dtype)
        k = rotate_half_split(rms_norm(k, ap["k_norm"], eps),
                              token_positions, theta).astype(dtype)
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
            ctx = own_keys_attention(q, k, v, jnp.zeros((B,), jnp.int32),
                                     gqa_stripe_attention)
    with scope("attn_proj"):
        ctx = ctx.transpose(0, 2, 1, 3).reshape(B, S, H * hd)
        return _mm(ctx, ap["wo"], dtype), pools


def conv_mixer(lp, h, call, cache, n):
    """The gated short convolution of one layer on ``h`` (B, S, H), a
    mixer of ``models/served_trunk.py``: the ``n``-th convolution layer
    over the tree's ``tails`` (``cache`` None, the plain forward:
    nothing kept). A served prefill starts every row from zeros and
    leaves, in row ``call.slots`` of the tails, the products ``u``
    before each row's TRUE length (a padded position reaches no tail); a
    decode reads row i's tail, and rewrites it for the rows that are
    ``call.active`` alone."""
    cp, config, dtype = lp["conv"], call.config, call.dtype
    B, S, H = h.shape
    cw = config.conv_L_cache
    with scope("conv_proj"):
        gates = _mm(h, cp["w_in"], dtype)
    with scope("conv_core"):
        b, c, x = jnp.split(gates, 3, axis=-1)
        # the PRODUCT is what is convolved and what is kept; float32
        # where it is made, the tail's type where it is kept
        u = b * x
        if cache is not None and S == 1:
            old = cache.tails[n]
            window = jnp.concatenate([old.astype(jnp.float32), u], axis=1)
            tail = jnp.where(call.active[:, None, None],
                             window[:, 1:].astype(old.dtype), old)
            tails = cache.tails.at[n].set(tail)
        else:
            window = jnp.pad(u, ((0, 0), (cw - 1, 0), (0, 0)))
            if cache is not None:
                assert call.slots is not None, \
                    "a served prefill needs each row's slot"
                # the last products before each row's TRUE length
                # (zeros before position 0)
                tail = jax.vmap(lambda w, m: jax.lax.dynamic_slice_in_dim(
                    w, m, cw - 1))(window, call.lengths)
                tails = cache.tails.at[n, call.slots].set(
                    tail.astype(cache.tails.dtype))
        # taps[j] weighs the product cw - 1 - j positions back
        v = sum(window[:, j:j + S] * cp["taps"][j] for j in range(cw))
        gated = (c * v).astype(dtype)
    if cache is not None:
        cache = cache._replace(tails=tails)
    with scope("conv_proj"):
        return _mm(gated, cp["w_out"], dtype), cache


def _family(config: LFM2Config) -> ServedFamily:
    def route(flat, router, bias):
        # ONE group: nothing is left out before the choice; the weights
        # are the chosen scores over their sum + 1e-6
        idx, _, scores, _ = route_group_limited(
            flat, router, config.experts_per_token, 1, 1, bias=bias)
        s = jnp.take_along_axis(scores, idx, axis=-1)
        w = s / (jnp.sum(s, axis=-1, keepdims=True) + _WEIGHT_EPS)
        if config.routed_scaling_factor != 1.0:
            w = w * config.routed_scaling_factor
        return idx, w, None

    return ServedFamily(
        layers=tuple(("attn" if kind == "full_attention" else "conv",
                      "dense" if l < config.num_dense_layers else "experts")
                     for l, kind in enumerate(config.kinds)),
        mixers={"attn": paged_pair_mixer(_softmax_mixer, positions=True),
                "conv": conv_mixer},
        route=route, expert_tile=_EXPERT_TILE, head="tok_emb",
        token_positions=True)


def lfm2_forward(params, config: LFM2Config, input_ids,
                 dtype=jnp.bfloat16, kv_cache=None, cache_position=None,
                 block_tables=None, paged_attn_kernel: str = "gather",
                 lengths=None, slots=None, active=None, with_counts=False):
    """Logits over the held rows of the vocabulary.

    Plain (``kv_cache=None``): (B, S) ids -> (B, S, rows) float32, every
    row from position 0 and an empty tail.

    Serving: ``kv_cache`` a ``kv_cache.PagedTailCache`` with
    ``block_tables`` and ``cache_position`` as the other families take
    them. PREFILL (S > 1) also takes ``lengths`` (B,) and ``slots``
    (B,), each row's true length and its row of the tails (a pad row
    names the scratch row); every row starts at position 0; returns
    logits at each row's LAST true position only, (B, 1, rows). DECODE
    (S == 1) takes ``active`` (B,) bool and runs row i against row i of
    the tails, leaving an inactive row's as it is. Returns (logits, the
    cache); with ``with_counts`` also the expert layers' int32 counters
    as ``solar_open2_forward`` does."""
    if kv_cache is not None and input_ids.shape[1] == 1 and active is None:
        raise ValueError("a served decode of this family needs `active`: "
                         "an inactive slot must keep its tail")
    return served_forward(_family(config), params, config, input_ids, dtype,
                          kv_cache, cache_position, block_tables,
                          paged_attn_kernel, lengths, slots, active,
                          with_counts)


def lfm2_param_count(config: LFM2Config):
    """(a convolution mixer, an attention mixer, the dense feed-forward,
    router + its bias, an expert, the table + final norm + the layers'
    two norms each)."""
    h, hd = config.hidden_size, config.head_dim
    nq, nkv = config.num_heads * hd, config.num_kv_heads * hd
    conv = h * 3 * h + config.conv_L_cache * h + h * h
    attn = 2 * h * nq + 2 * h * nkv + 2 * hd
    return (conv, attn, 3 * h * config.intermediate_size,
            h * config.num_experts + config.num_experts,
            3 * h * config.moe_intermediate_size,
            config.vocab_rows * h + h + 2 * h * config.num_layers)
