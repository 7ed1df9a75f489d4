"""A.X-K1-style decoder, SERVED: multi-head latent attention (MLA) over
ONE latent page pool, a leading dense SwiGLU layer, then layers of
routed SwiGLU experts chosen by a sigmoid router limited to the best
groups, with a shared expert beside them (skt/A.X-K1, config.json;
docs/axk1.md has the equations and what the config leaves open).

Layer ``l`` on the residual stream ``x`` (``h = RMSNorm(x)``), H heads,
``d_n`` / ``d_r`` the key's part without and with position, ``d_v`` the
value's width:

- queries: ``c_q = RMSNorm(h W_qa)``, ``[q_n | q_r] = c_q W_qb`` a
  head, ``q_r = RoPE(q_r)``;
- latent: ``[c | k_r] = h W_kva``, ``c = RMSNorm(c)``, ``k_r =
  RoPE(k_r)``: ONE rotary key slice for all heads. **The cache row of a
  token is ``[c | k_r]``**, after the norm and the rotation, in the
  engine's compute dtype (``inference/kv_cache.LatentPoolSpec``);
- EXPANDED (a prompt bucket): ``[k_n | v] = c W_kvb`` a head, scores
  ``(q_n . k_n + q_r . k_r) * scale``, causal softmax in float32,
  ``o = P v``;
- ABSORBED (decode; the same function, reassociated): with ``W_uk``,
  ``W_uv`` the two halves of ``W_kvb`` by head, ``q_c = q_n W_uk^T``,
  scores ``(q_c . c + q_r . k_r) * scale``, ``ctx = P c``, ``o = ctx
  W_uv``: neither K nor V of a cached token is ever formed, and the
  pool's row is read once for both products
  (``ops/attention/paged.latent_decode_attention``);
- ``x += concat_heads(o) W_o``; then the feed-forward on ``h2 =
  RMSNorm(x)``: layers below ``first_k_dense`` a dense SwiGLU, the rest
  ``x += sum_top8 w_e E_e(h2) + E_shared(h2)`` with
  ``ops/moe.route_group_limited``.

``scale = (d_n + d_r)^-1/2 * m^2``, ``m = 0.1 * mscale_all_dim *
ln(factor) + 1`` (YaRN); the rotary frequencies are YaRN's blend
(:func:`yarn_inv_freq`).

The config carries the chip's SHARE of a layer as
``models/solar_open2.py`` does: ``experts_held`` and ``vocab_held``.

Two programs. PREFILL (more than one token a row): every row starts at
position 0 (the family is served without prefix cache or chunked
prefill: ``inference/engine.py`` refuses them), attention is EXPANDED
over the prompt's own latent rows through ``own_keys_attention`` and the
rows are written to the pages. DECODE (one token a row): ABSORBED,
through the Pallas latent reader. With ``paged_attn_kernel="gather"``
both run the STRIPE reader instead: each row's latent rows gathered back
from the pool and expanded, whatever the row's start: the reader of
everything else, and the numerics oracle.
"""

import functools
from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.served_trunk import (ServedFamily, _mm,
                                               served_forward,
                                               whole_leaf_specs)
from deepspeed_tpu.ops.attention.flash import NEG_INF
from deepspeed_tpu.ops.attention.page_pool import (causal_cache_mask,
                                                   gather_paged_kv,
                                                   own_keys_attention,
                                                   prefix_block_rows,
                                                   prefix_own_attention,
                                                   write_paged_kv_cache)
from deepspeed_tpu.ops.attention.paged import latent_decode_attention
from deepspeed_tpu.ops.functional import rms_norm
from deepspeed_tpu.ops.moe import route_group_limited
from deepspeed_tpu.profiling.spans import scope

# caps of the grouped products' tile at these experts' widths (7,168 x
# 2,048), cut to whole divisors: 128 rows (a prefill bucket of T tokens
# lands T / 24 rows on a held expert, and a tile a group touches is
# worked whole)
_EXPERT_TILE = (128, 1024, 1024)


class AXK1Config(NamedTuple):
    vocab_size: int = 163840
    hidden_size: int = 7168
    num_layers: int = 61
    num_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    first_k_dense: int = 1
    num_experts: int = 192
    experts_per_token: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_factor: float = 32.0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    rope_original_max: int = 4096
    max_position_embeddings: int = 131072
    initializer_range: float = 0.02
    # a ROUTED expert's w_down against the other branches' when the tree
    # is SEEDED (a benchmark's choice, stated in its configuration file:
    # docs/axk1.md Seeding); trained weights carry their own
    routed_init_gain: float = 1.0
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
    def expert_layers(self):
        return tuple(range(self.first_k_dense, self.num_layers))

    @property
    def kv_cache_layers(self):
        """Layers with a row in the latent pool: all of them."""
        return self.num_layers

    @property
    def latent_geometry(self):
        """What ``inference/kv_cache.paged_spec_for`` builds the latent
        pool from: (the latent's width, the rotary key's)."""
        return (self.kv_lora_rank, self.qk_rope_head_dim)

    @property
    def expert_counters(self):
        """As ``SolarOpen2Config.expert_counters``: (assignments a row
        that decodes offers the router over the expert layers, experts
        held here). The decode program's counters are (landed, fullest,
        rows whose kept groups include a group held here)."""
        return (self.experts_per_token * len(self.expert_layers),
                self.held[1])

    @property
    def rotary(self):
        """``q_r`` and ``k_r`` are rotated by their positions (a second
        family of these mixers has no rotation anywhere)."""
        return True

    @property
    def sm_scale(self):
        m = 0.1 * self.rope_mscale_all_dim * np.log(self.rope_factor) + 1.0
        return float((self.qk_nope_head_dim + self.qk_rope_head_dim)
                     ** -0.5 * m * m)


def yarn_inv_freq(config: AXK1Config):
    """(d_r / 2,) float32 rotary frequencies: pair ``i`` turns by
    ``theta^(-2i/d_r)`` a position where its wavelength makes more than
    ``beta_fast`` turns in the original context, by that over ``factor``
    where it makes fewer than ``beta_slow``, and by a linear blend of
    the two between (YaRN). The cos/sin factor ``mscale /
    mscale_all_dim`` is 1 for this model and not applied."""
    d = config.qk_rope_head_dim
    extra = config.rope_theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)

    def pair_of(turns):
        return d * np.log(config.rope_original_max / (turns * 2 * np.pi)) \
            / (2 * np.log(config.rope_theta))

    low = max(np.floor(pair_of(config.rope_beta_fast)), 0)
    high = min(np.ceil(pair_of(config.rope_beta_slow)), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (extra / config.rope_factor * ramp
            + extra * (1 - ramp)).astype(np.float32)


def _rope(x, positions, inv_freq):
    """Rotate ``x`` (B, S, ..., d_r) by its positions (B, S), float32;
    the pair layout is (x[..., :d/2], x[..., d/2:])."""
    angle = positions[..., None].astype(jnp.float32) * inv_freq
    angle = angle.reshape(angle.shape[:2] + (1,) * (x.ndim - 3)
                          + angle.shape[-1:])
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def init_axk1_params(config: AXK1Config, key,
                     dtype=jnp.bfloat16) -> Dict[str, Any]:
    """The tree, matrices HELD in ``dtype``, the router and the norms in
    float32: ``tok_emb``, ``lm_head`` (rows held, H), ``ln_f``,
    ``h_<l>`` with ``ln_1``, ``ln_2``, ``attn`` {wq_a (H, r_q), q_norm,
    wq_b (r_q, heads x (d_n + d_r)), wkv_a (H, r_kv + d_r), kv_norm,
    wkv_b (r_kv, heads x (d_n + d_v)), wo (heads x d_v, H)} and ``mlp``
    {w_gate, w_up: (H, F), w_down: (F, H)} below ``first_k_dense``, else
    ``router`` (H, experts), ``experts`` {w_gate, w_up: (held, H, F),
    w_down: (held, F, H)}, ``shared`` {the same, one expert}.

    Seeding (docs/axk1.md): a matrix that reads a normed input normal
    with ``initializer_range`` (scores then spread by about 1.5: q 0.78
    and k_n 0.45 a lane, k_r 1.7), one that writes to the residual
    stream with it over sqrt(2 x layers), a routed expert's with
    ``config.routed_init_gain`` of that."""
    h, nh = config.hidden_size, config.num_heads
    rq, rkv = config.q_lora_rank, config.kv_lora_rank
    dn, dr, dv = (config.qk_nope_head_dim, config.qk_rope_head_dim,
                  config.v_head_dim)
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
        k = jax.random.split(keys[2 + l], 12)
        lp = {
            "ln_1": {"w": ones(h)}, "ln_2": {"w": ones(h)},
            "attn": {
                "wq_a": normal(k[0], (h, rq), std),
                "q_norm": ones(rq),
                "wq_b": normal(k[1], (rq, nh * (dn + dr)), std),
                "wkv_a": normal(k[2], (h, rkv + dr), std),
                "kv_norm": ones(rkv),
                "wkv_b": normal(k[3], (rkv, nh * (dn + dv)), std),
                "wo": normal(k[4], (nh * dv, h), out_std)},
        }
        if l < config.first_k_dense:
            fd = config.intermediate_size
            lp["mlp"] = {"w_gate": normal(k[5], (h, fd), std),
                         "w_up": normal(k[6], (h, fd), std),
                         "w_down": normal(k[7], (fd, h), out_std)}
        else:
            lp["router"] = normal(k[5], (h, config.num_experts), std,
                                  jnp.float32)
            ek = jax.random.split(k[6], 3)
            lp["experts"] = {"w_gate": normal(ek[0], (held, h, f), std),
                             "w_up": normal(ek[1], (held, h, f), std),
                             "w_down": normal(ek[2], (held, f, h),
                                              out_std
                                              * config.routed_init_gain)}
            lp["shared"] = {"w_gate": normal(k[7], (h, f), std),
                            "w_up": normal(k[8], (h, f), std),
                            "w_down": normal(k[9], (f, h), out_std)}
        params[f"h_{l}"] = lp
    return params


def axk1_param_specs(config: AXK1Config):
    """Every leaf whole (``served_trunk.whole_leaf_specs``)."""
    return whole_leaf_specs(init_axk1_params, config)


@functools.lru_cache(maxsize=None)
def _stripe_attention(sm_scale: float):
    """The family's ``stripe_attention`` at its score scale (one
    function a scale: ``own_keys_attention`` keys its trace on it):
    causal attention of ``q`` (B, H, S, d_n + d_r) over expanded keys
    (B, H, L, d_n + d_r) and values (B, H, L, >= d_v) under the shared
    ``causal_cache_mask``, in float32."""
    def attend(q, k, v, cache_position):
        with scope("attn_core"):
            scores = jnp.einsum("bhsd,bhld->bhsl", q.astype(jnp.float32),
                                k.astype(jnp.float32)) * sm_scale
            mask = causal_cache_mask(cache_position, q.shape[2], k.shape[2])
            probs = jax.nn.softmax(jnp.where(mask, scores, NEG_INF), -1)
            return jnp.einsum("bhsl,bhld->bhsd", probs,
                              v.astype(jnp.float32)).astype(q.dtype)
    return attend


def latent_mixer(lp, h, call, cache, n):
    """Latent attention of one layer on ``h`` (B, S, H) whose tokens sit
    at ``call.token_positions`` (B, S), a mixer of
    ``models/served_trunk.py``: layer ``n`` of the tree's latent pool,
    its FIRST leaf (``cache`` None: no pages, the plain forward). Shared
    with ``models/kimi_linear.py``; what differs is read from the config:
    a queries' rank or none (``q_lora_rank`` 0: ``wq`` alone, no norm),
    rotation or none (``rotary``), the scores' scale. Under
    ``call.carry`` (a family served in chunks) a prefill row whose
    ``call.positions`` is past 0 also attends the latent rows earlier
    chunks wrote, read back through its block table a block at a time
    (``page_pool.prefix_own_attention``)."""
    ap, config, dtype, positions = (lp["attn"], call.config, call.dtype,
                                    call.token_positions)
    B, S, _ = h.shape
    nh, rkv = config.num_heads, config.kv_lora_rank
    dn, dr, dv = (config.qk_nope_head_dim, config.qk_rope_head_dim,
                  config.v_head_dim)
    eps = config.rms_norm_eps
    if config.rotary:
        inv_freq = jnp.asarray(yarn_inv_freq(config))
        turned = lambda x: _rope(x, positions, inv_freq)
    else:
        turned = lambda x: x
    with scope("mla_q"):
        if config.q_lora_rank:
            c_q = rms_norm(_mm(h, ap["wq_a"], dtype), ap["q_norm"], eps)
            q = _mm(c_q, ap["wq_b"], dtype)
        else:
            q = _mm(h, ap["wq"], dtype)
        q = q.reshape(B, S, nh, dn + dr)
        q_n = q[..., :dn].astype(dtype)
        q_r = turned(q[..., dn:]).astype(dtype)
    with scope("mla_latent"):
        kv = _mm(h, ap["wkv_a"], dtype)
        # the cache row: after the norm and the rotation, as it is held
        row = jnp.concatenate(
            [rms_norm(kv[..., :rkv], ap["kv_norm"], eps),
             turned(kv[..., rkv:])], -1).astype(dtype)
    w_kvb = ap["wkv_b"].astype(dtype).reshape(rkv, nh, dn + dv)
    if cache is not None:
        # the pool's row is ONE head as wide as its lanes, the tail zeros
        pool = cache[0]
        held = jnp.pad(row, ((0, 0), (0, 0),
                             (0, pool.shape[-1] - rkv - dr)))
        pool = write_paged_kv_cache(pool, n, held[:, None], call.index)
        # a tree of the pool alone, or one that names it beside others
        cache = cache._replace(pool=pool) if hasattr(cache, "_replace") \
            else (pool,)

    def keys_values(rows):
        """Latent ``rows`` (B, L, >= r_kv + d_r) expanded to every
        head's keys (B, heads, L, d_n + d_r), and the expansion itself,
        whose lanes past d_n are the values (.., d_v)."""
        L = rows.shape[1]
        kvx = jnp.einsum("blc,chd->bhld", rows[..., :rkv].astype(dtype),
                         w_kvb, preferred_element_type=jnp.float32
                         ).astype(dtype)
        k = jnp.concatenate(
            [kvx[..., :dn], jnp.broadcast_to(
                rows[:, None, :, rkv:rkv + dr].astype(dtype),
                (B, nh, L, dr))], -1)
        return k, kvx

    # the flash kernel has one width for keys and values: the values
    # ride zero-padded to the keys' (docs/axk1.md: the padding is half
    # again the P V products of the prefill reader)
    wide = lambda v: jnp.pad(v, ((0, 0),) * 3 + ((0, dn + dr - dv),))

    def expanded(rows, cache_position, own):
        """Attention over latent ``rows`` expanded."""
        with scope("mla_expand"):
            k, kvx = keys_values(rows)
            qx = jnp.concatenate([q_n, q_r], -1).transpose(0, 2, 1, 3)
            v = kvx[..., dn:]
        stripe = _stripe_attention(config.sm_scale)
        if not own:
            return stripe(qx, k, v, cache_position)
        if not call.carry:
            return own_keys_attention(qx, k, wide(v), cache_position,
                                      stripe,
                                      sm_scale=config.sm_scale)[..., :dv]
        # a chunk: its own rows and the prefix earlier chunks wrote,
        # whole pages of the block table a loop turn
        ps = pool.shape[2]
        per = prefix_block_rows(call.tables.shape[1] * ps) // ps
        tables = jnp.pad(call.tables,
                         ((0, 0), (0, -call.tables.shape[1] % per)))

        def prefix_block(j, block_rows):
            pages = jax.lax.dynamic_slice_in_dim(tables, j * per, per, 1)
            k_j, kvx_j = keys_values(
                pool[n, pages].reshape(B, block_rows, pool.shape[-1]))
            return k_j, wide(kvx_j[..., dn:])

        return prefix_own_attention(
            qx, k, wide(v), cache_position, prefix_block,
            tables.shape[1] * ps, sm_scale=config.sm_scale,
            prefix_scope="mla_prefix")[..., :dv]

    if cache is not None and S == 1 and call.reader == "pallas":
        with scope("mla_absorb"):
            q_c = jnp.einsum("bhd,chd->bhc", q_n[:, 0], w_kvb[..., :dn],
                             preferred_element_type=jnp.float32)
            q_abs = jnp.concatenate(
                [q_c.astype(dtype), q_r[:, 0]], -1)
            q_abs = jnp.pad(q_abs, ((0, 0), (0, 0),
                                    (0, pool.shape[-1] - rkv - dr)))
        with scope("attn_core"):
            ctx = latent_decode_attention(
                q_abs, pool, call.tables, call.positions,
                config.sm_scale, rkv, layer=n)
        with scope("mla_absorb"):
            o = jnp.einsum("bhc,chd->bhd", ctx.astype(dtype),
                           w_kvb[..., dn:],
                           preferred_element_type=jnp.float32)[:, :, None]
    elif cache is not None and call.reader != "pallas":
        rows = gather_paged_kv(pool, n, call.tables, 1)[:, 0]
        o = expanded(rows, call.positions, own=False)
    else:
        # its own rows (rounded to the dtype the pool holds them in) are
        # all a row that starts at position 0 may see; a later chunk's
        # start is its prefix's length
        o = expanded(row, call.positions if call.carry
                     else jnp.zeros((B,), jnp.int32), own=True)
    with scope("mla_out"):
        o = o.transpose(0, 2, 1, 3).reshape(B, S, nh * dv)
        return _mm(o, ap["wo"], dtype), cache


def _family(config: AXK1Config) -> ServedFamily:
    def route(flat, router):
        idx, p, _, kept = route_group_limited(
            flat, router, config.experts_per_token, config.n_group,
            config.topk_group, config.routed_scaling_factor)
        return idx, p, kept

    def rows_kept_here(kept, active):
        """Rows whose kept groups include a group with an expert held
        here: the third DECODE counter."""
        per = config.num_experts // config.n_group
        first, count = config.held
        mine = (kept >= first // per) & (kept <= (first + count - 1) // per)
        here = jnp.any(mine, axis=-1)
        if active is not None:
            here = here & active
        return here

    return ServedFamily(
        layers=tuple(("latent", "dense" if l < config.first_k_dense
                      else "experts") for l in range(config.num_layers)),
        mixers={"latent": latent_mixer}, route=route,
        expert_tile=_EXPERT_TILE, decode_rows=rows_kept_here,
        token_positions=True)


def axk1_forward(params, config: AXK1Config, input_ids, dtype=jnp.bfloat16,
                 kv_cache=None, cache_position=None, block_tables=None,
                 paged_attn_kernel: str = "gather", lengths=None,
                 slots=None, active=None, with_counts=False):
    """Logits over the held rows of the vocabulary.

    Plain (``kv_cache=None``): (B, S) ids -> (B, S, rows) float32, every
    row from position 0, attention EXPANDED.

    Serving: ``kv_cache`` the 1-tuple of the latent pool ``(layers,
    pages, page_size, row lanes)`` with ``block_tables`` and
    ``cache_position`` as the other families take them. PREFILL (S > 1)
    also takes ``lengths`` (B,), each row's true length (``slots`` is
    taken and not read: the family keeps nothing a slot), and returns
    logits at each row's LAST true position only, (B, 1, rows); with
    ``paged_attn_kernel="pallas"`` every row must start at position 0.
    DECODE (S == 1) is absorbed through the Pallas reader, or the stripe
    reader under ``"gather"``. Returns (logits, the cache); with
    ``with_counts`` also (expert layers, 3 in decode and 2 in prefill)
    int32 (``served_trunk._expert_half``): DECODE (landed, fullest,
    active rows whose kept groups include a group with an expert held
    here), PREFILL (rows the turns worked, rows static turns would
    have)."""
    return served_forward(_family(config), params, config, input_ids, dtype,
                          kv_cache, cache_position, block_tables,
                          paged_attn_kernel, lengths, slots, active,
                          with_counts)


def axk1_param_count(config: AXK1Config):
    """(a latent attention mixer with its two inner norms and the
    layer's two, the dense feed-forward, router + shared expert, an
    expert, embedding + head + final norm)."""
    h, nh = config.hidden_size, config.num_heads
    rq, rkv = config.q_lora_rank, config.kv_lora_rank
    dn, dr, dv = (config.qk_nope_head_dim, config.qk_rope_head_dim,
                  config.v_head_dim)
    mixer = (h * rq + rq * nh * (dn + dr) + h * (rkv + dr)
             + rkv * nh * (dn + dv) + nh * dv * h + rq + rkv + 2 * h)
    expert = 3 * h * config.moe_intermediate_size
    return (mixer, 3 * h * config.intermediate_size,
            h * config.num_experts + expert, expert,
            2 * config.vocab_rows * h + h)
