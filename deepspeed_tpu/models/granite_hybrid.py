"""Granite-4.0-H-style hybrid decoder, SERVED: Mamba-2 state-space
mixers with one softmax layer in ten among them, every layer's second
half routed SwiGLU experts with a shared expert beside them, a
muP-scaled trunk over a TIED table (ibm-granite/granite-4.0-h-small,
config.json; docs/granite_hybrid.md has the equations and what the
config leaves open).

``x_0 = embedding_multiplier * E[ids]``. Layer ``l`` on the residual
stream ``x`` (``h = RMSNorm(x)``, ``r = residual_multiplier``):

- ``layer_types[l] == "mamba"``: ``[z | xBC | dt] = h W_in``, ``xBC =
  SiLU(conv4(xBC) + b)`` (ONE causal depthwise convolution over x, B and
  C together), ``dt = softplus(dt + dt_bias)``, the recurrence of
  ``ops/ssd.py`` on a float32 state with ``A = -exp(A_log)`` a head and
  the skip ``D``, ``x += r W_out [RMSNorm(y * SiLU(z)) w]`` (the gate
  BEFORE a norm over the whole inner width). The state and the
  convolution's last three inputs live in the per-slot state pool, one
  row a slot (``inference/kv_cache.py``).
- ``"attention"``: causal softmax attention with NO position,
  ``num_heads`` query heads over ``num_kv_heads`` key-value heads,
  scores times the published ``attention_multiplier`` (1/128 at a head
  of 128, not 128^-1/2), no gate: ``x += r W_o attn``. Keys and values
  live in the page pool.
- then ``x += r (sum_top10 w_e E_e(h2) + E_shared(h2))`` on ``h2 =
  RMSNorm(x)``: router logits in float32, the ten largest, softmax over
  THOSE.

After the last layer the final RMSNorm and ``logits = x E^T /
logits_scaling`` over the rows of the table held here.

The config carries the chip's SHARE of a layer as
``models/solar_open2.py`` does: ``experts_held`` and ``vocab_held``.

Two programs, as that family's. PREFILL (more than one token a row):
every row starts at position 0 with an empty state (served without
prefix cache or chunked prefill: ``inference/engine.py`` refuses them),
the attention layer attends the prompt's own keys and values
(``page_pool.own_keys_attention``) and writes them to the pages, the
Mamba layers run ``ssd_chunk_scan`` to each row's TRUE length and write
the final state and tail WHOLE at the row's slot. DECODE (one token a
row, the rows the slot table): the Pallas paged reader,
``ssd_decode_update`` on every row's state in place, the held experts
on every row.
"""

import functools
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
from deepspeed_tpu.ops.moe import route_top_k
from deepspeed_tpu.ops.ssd import ssd_chunk_scan, ssd_decode_update
from deepspeed_tpu.profiling.spans import scope

# caps of the grouped products' tile at these experts' widths (4,096 x
# 768): cut to whole divisors, (256, 1024, 768) up and (256, 768, 512)
# down. 256 rows: a prefill bucket of T tokens lands 10 T / 72 rows on
# a held expert (142 to 1,138), and a tile a group touches is worked
# whole
_EXPERT_TILE = (256, 1024, 768)


class GraniteHybridConfig(NamedTuple):
    vocab_size: int = 100352
    hidden_size: int = 4096
    num_layers: int = 40
    layer_types: Tuple[str, ...] = tuple(
        "attention" if l % 10 == 5 else "mamba" for l in range(40))
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 128
    attention_multiplier: float = 0.0078125
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    logits_scaling: float = 16.0
    mamba_n_heads: int = 128
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    intermediate_size: int = 768           # ONE routed expert's width
    shared_intermediate_size: int = 1536
    num_experts: int = 72
    experts_per_token: int = 10
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 131072
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
    def kinds(self):
        """The mixer kind of each layer that is RUN: the first
        ``num_layers`` entries of the published ``layer_types``."""
        kinds = tuple(self.layer_types[:self.num_layers])
        if len(kinds) != self.num_layers or \
                not set(kinds) <= {"mamba", "attention"}:
            raise ValueError(f"layer_types has to name {self.num_layers} "
                             f"layers 'mamba' or 'attention', got {kinds}")
        return kinds

    @property
    def softmax_layers(self):
        return tuple(l for l, k in enumerate(self.kinds) if k == "attention")

    @property
    def recurrent_layers(self):
        return tuple(l for l, k in enumerate(self.kinds) if k == "mamba")

    @property
    def kv_cache_layers(self):
        """Layers with keys and values in the page pool."""
        return len(self.softmax_layers)

    @property
    def d_inner(self):
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_channels(self):
        """x, B and C side by side (one group of B and C)."""
        return self.d_inner + 2 * self.mamba_d_state

    @property
    def expert_counters(self):
        """(assignments a row that decodes offers the router over the
        layers, experts held here): ``SolarOpen2Config.expert_counters``.
        """
        return (self.experts_per_token * self.num_layers, self.held[1])

    @property
    def state_geometry(self):
        """What a slot holds whatever its length, for
        ``kv_cache.state_pool_spec_for``: (recurrent layers, heads, the
        state's two widths, tail positions, tail channels). The state is
        ``S = sum dt x B^T`` as the equations write it, (d_head,
        d_state) = 64 x 128 with the 128 on the lanes: the pool's
        ``key_dim`` is x's width and its ``value_dim`` B and C's. The
        tail is the ONE convolution's, over x, B and C."""
        return (len(self.recurrent_layers), self.mamba_n_heads,
                self.mamba_d_head, self.mamba_d_state,
                self.mamba_d_conv - 1, self.conv_channels)


# standard deviations of the seeded weights (docs/granite_hybrid.md): the
# tied table's gives logits an rms of sqrt(H) x 0.25 / 16 = 1.0 at H
# 4,096; what reads a normed input 0.02, but wq and wk 0.08 (scores x
# 1/128 then spread by about 2) and the router 0.04 (its logits by about
# 2.5: steeper, the softmax multiplies every rounding before it by the
# logits' spread: my chip runs, PR 41); what WRITES to the residual
# stream _OUT_GAIN / sqrt(fan in), so that ten layers' branches x 0.22
# stand some forty times over the 12 x E[id] they started from and a
# TIED head does not simply read the last token back; but a ROUTED
# expert _ROUTED_GAIN / sqrt(fan in), a quarter of that. Top-10 routing
# is a step: bfloat16 products upstream swap the tenth and eleventh
# expert of some 3-20% of the (token, layer) pairs, and a swap moves
# the layer's output by the tenth's weight (0.01-0.03 as a rule, 0.07
# where the ten logits lie flat) times an expert's whole output. At
# layer 0, where the stream is still small (rms 29 before the expert
# half, 39 after), experts as loud as the rest let ONE such swap move the
# stream 7% and the logits 0.19 rms (a served token 0.65 under the
# reference's pick: PERF.md section 6, PR 41); a quarter as loud it
# moves them under 2%
_TABLE_STD, _IN_STD, _QK_STD, _ROUTER_STD, _OUT_GAIN, _ROUTED_GAIN = (
    0.25, 0.02, 0.08, 0.04, 128.0, 32.0)


def init_granite_hybrid_params(config: GraniteHybridConfig, key,
                               dtype=jnp.bfloat16) -> Dict[str, Any]:
    """The tree, matrices HELD in ``dtype``, the router, ``a_log``,
    ``dt_bias``, ``d`` and the norms in float32: ``tok_emb`` (rows held,
    H; the head is the same table), ``ln_f``, ``h_<l>`` with ``ln_1``,
    ``ln_2``, ``router`` (H, experts), ``experts`` {w_gate, w_up: (held,
    H, F), w_down: (held, F, H)}, ``shared`` {w_gate, w_up: (H, Fs),
    w_down: (Fs, H)} and ``attn`` {wq, wk, wv, wo} or ``mamba`` {w_in
    (H, [z | xBC | dt]), conv (width, channels), conv_b, dt_bias, a_log,
    d, norm, w_out}. The decay starts as the family's public initialiser
    does: ``exp(a_log)`` uniform in [1, 16], ``softplus(dt_bias)``
    log-uniform in [0.001, 0.1]."""
    h, hd = config.hidden_size, config.head_dim
    nq, nkv = config.num_heads * hd, config.num_kv_heads * hd
    mh, di, cc = config.mamba_n_heads, config.d_inner, config.conv_channels
    cw = config.mamba_d_conv
    f, fs = config.intermediate_size, config.shared_intermediate_size
    held = config.held[1]

    def normal(k, shape, s, dt=dtype):
        return (jax.random.normal(k, shape, jnp.float32) * s).astype(dt)

    out = lambda fan_in, gain=_OUT_GAIN: gain / np.sqrt(fan_in)
    ones = lambda n: jnp.ones((n,), jnp.float32)
    keys = jax.random.split(key, 1 + config.num_layers)
    params: Dict[str, Any] = {
        "tok_emb": normal(keys[0], (config.vocab_rows, h), _TABLE_STD),
        "ln_f": {"w": ones(h)},
    }
    for l, kind in enumerate(config.kinds):
        k = jax.random.split(keys[1 + l], 17)
        lp = {
            "ln_1": {"w": ones(h)}, "ln_2": {"w": ones(h)},
            "router": normal(k[0], (h, config.num_experts), _ROUTER_STD,
                             jnp.float32),
            "experts": {"w_gate": normal(k[1], (held, h, f), _IN_STD),
                        "w_up": normal(k[2], (held, h, f), _IN_STD),
                        "w_down": normal(k[3], (held, f, h),
                                         out(f, _ROUTED_GAIN))},
            "shared": {"w_gate": normal(k[4], (h, fs), _IN_STD),
                       "w_up": normal(k[5], (h, fs), _IN_STD),
                       "w_down": normal(k[6], (fs, h), out(fs))},
        }
        if kind == "attention":
            lp["attn"] = {"wq": normal(k[7], (h, nq), _QK_STD),
                          "wk": normal(k[8], (h, nkv), _QK_STD),
                          "wv": normal(k[9], (h, nkv), _IN_STD),
                          "wo": normal(k[10], (nq, h), out(nq))}
        else:
            dt = jnp.exp(jax.random.uniform(
                k[11], (mh,), jnp.float32, np.log(1e-3), np.log(1e-1)))
            lp["mamba"] = {
                "w_in": normal(k[12], (h, di + cc + mh), _IN_STD),
                "conv": normal(k[13], (cw, cc), cw ** -0.5),
                "conv_b": normal(k[14], (cc,), 0.1, jnp.float32),
                # softplus(dt_bias) = dt
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "a_log": jnp.log(jax.random.uniform(
                    k[15], (mh,), jnp.float32, 1.0, 16.0)),
                "d": ones(mh), "norm": ones(di),
                "w_out": normal(k[16], (di, h), out(di))}
        params[f"h_{l}"] = lp
    return params


def granite_hybrid_param_specs(config: GraniteHybridConfig):
    """Every leaf whole (``served_trunk.whole_leaf_specs``)."""
    return whole_leaf_specs(init_granite_hybrid_params, config)


@functools.lru_cache(maxsize=None)
def _stripe_attention_at(sm_scale: float):
    """``page_pool.gqa_stripe_attention`` at the published score
    scale: the numerics oracle behind ``paged_attend``'s gather reader
    and a small call's own keys. ONE function a scale (``_own_keys`` is
    jitted with it as a static argument)."""
    return functools.partial(gqa_stripe_attention, sm_scale=sm_scale)


def _softmax_mixer(ap, config, h, dtype, cache):
    """NoPE attention of one layer on ``h`` (B, S, H). ``cache`` None
    (no pages: the plain forward) or ``served_trunk._Pages``; returns
    (y, the pools)."""
    B, S, _ = h.shape
    H, hkv, hd = config.num_heads, config.num_kv_heads, config.head_dim
    scale = float(config.attention_multiplier)
    stripe = _stripe_attention_at(scale)
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
                           stripe, sm_scale=scale)
        pools = box[0]
    else:
        if cache is not None:
            pools = write_paged_layer(cache.pools, cache.layer, k, v,
                                      cache.index)
        # every row starts at position 0: its own keys and values are
        # all it may see
        with scope("attn_core"):
            ctx = own_keys_attention(q, k, v, jnp.zeros((B,), jnp.int32),
                                     stripe, sm_scale=scale)
    with scope("attn_proj"):
        ctx = ctx.transpose(0, 2, 1, 3).reshape(B, S, H * hd)
        return _mm(ctx, ap["wo"], dtype), pools


def _mamba_mixer(lp, h, call, cache, n):
    """The Mamba-2 mixer of one layer on ``h`` (B, S, H), a mixer of
    ``models/served_trunk.py``: the ``n``-th recurrent layer over the
    tree's ``state`` and ``tails`` (``cache`` None, the plain forward:
    an empty state, nothing kept), a served prefill to ``call.lengths``
    and into rows ``call.slots``."""
    mp, config, dtype, lengths = (lp["mamba"], call.config, call.dtype,
                                  call.lengths)
    B, S, _ = h.shape
    nh, hd, ds = (config.mamba_n_heads, config.mamba_d_head,
                  config.mamba_d_state)
    di, cw = config.d_inner, config.mamba_d_conv
    decode = cache is not None and S == 1
    with scope("ssd_proj"):
        proj = _mm(h, mp["w_in"], dtype)
        z = proj[..., :di]
        raw = proj[..., di:di + config.conv_channels].astype(dtype)
        dt = jax.nn.softplus(proj[..., di + config.conv_channels:]
                             + mp["dt_bias"])                 # (B, S, nh)
        if decode:
            window = jnp.concatenate([cache.tails[n], raw], axis=1)
            tail = window[:, 1:]
        else:
            window = jnp.pad(raw, ((0, 0), (cw - 1, 0), (0, 0)))
            if cache is not None:
                # the last inputs before each row's TRUE length (zeros
                # before position 0)
                tail = jax.vmap(lambda w, m: jax.lax.dynamic_slice_in_dim(
                    w, m, cw - 1))(window, lengths)
        # conv[j] weighs the input (cw - 1) - j positions back
        taps = mp["conv"].astype(jnp.float32)
        mixed = jax.nn.silu(sum(
            window[:, j:j + S].astype(jnp.float32) * taps[j]
            for j in range(cw)) + mp["conv_b"])
        x = mixed[..., :di].reshape(B, S, nh, hd)
        bm, cm = mixed[..., di:di + ds], mixed[..., di + ds:]
        a, d = -jnp.exp(mp["a_log"]), mp["d"]
    if decode:
        with scope("ssd_state"):
            y, state = ssd_decode_update(cache.state, n, x[:, 0], dt[:, 0],
                                         a, bm[:, 0], cm[:, 0], d)
            y = y[:, None]
            tails = cache.tails.at[n].set(tail)
    else:
        with scope("ssd_scan"):
            y, last = ssd_chunk_scan(
                x, dt, a, bm, cm, d, jnp.zeros((B, nh, hd, ds), jnp.float32),
                lengths, chunk=config.mamba_chunk_size)
        if cache is not None:
            assert call.slots is not None, \
                "a served prefill needs each row's slot"
            with scope("ssd_state"):
                state = cache.state.at[n, call.slots].set(last)
                tails = cache.tails.at[n, call.slots].set(tail)
    if cache is not None:
        cache = cache._replace(state=state, tails=tails)
    with scope("ssd_proj"):
        # the gate BEFORE the norm, the norm over the whole inner width
        y = rms_norm(y.reshape(B, S, di) * jax.nn.silu(z), mp["norm"],
                     config.rms_norm_eps)
        return _mm(y, mp["w_out"], dtype), cache


def _family(config: GraniteHybridConfig) -> ServedFamily:
    def route(flat, router):
        # the ten largest logits, softmax over those ten
        idx, p, _ = route_top_k(flat, router, config.experts_per_token)
        return idx, p, None

    return ServedFamily(
        layers=tuple((kind, "experts") for kind in config.kinds),
        mixers={"attention": paged_pair_mixer(_softmax_mixer),
                "mamba": _mamba_mixer},
        route=route, expert_tile=_EXPERT_TILE,
        embedding_multiplier=config.embedding_multiplier,
        residual_multiplier=config.residual_multiplier,
        logits_scaling=config.logits_scaling, head="tok_emb")


def granite_hybrid_forward(params, config: GraniteHybridConfig, input_ids,
                           dtype=jnp.bfloat16, kv_cache=None,
                           cache_position=None, block_tables=None,
                           paged_attn_kernel: str = "gather", lengths=None,
                           slots=None, active=None, with_counts=False):
    """Logits over the held rows of the tied table, with the arguments
    and returns of ``models/solar_open2.solar_open2_forward``: plain
    (``kv_cache=None``) (B, S) ids -> (B, S, rows) float32; serving with
    a ``kv_cache.PagedStateCache`` whose ``state`` is ``(mamba layers,
    slots + 1, heads, d_head, d_state)`` float32 and ``tails`` ``(mamba
    layers, slots + 1, 3, d_inner + 2 d_state)``, PREFILL (S > 1, with
    ``lengths`` and ``slots``) returning the logits at each row's last
    true position, (B, 1, rows), DECODE (S == 1) running row i against
    row i of the state pools. Returns (logits, the cache); with
    ``with_counts`` also (layers, 2) int32: over the ``active`` rows in
    decode, the expert turns' rows (worked, static) in prefill."""
    return served_forward(_family(config), params, config, input_ids, dtype,
                          kv_cache, cache_position, block_tables,
                          paged_attn_kernel, lengths, slots, active,
                          with_counts)


def granite_hybrid_param_count(config: GraniteHybridConfig):
    """(a Mamba mixer, an attention mixer, router + shared expert + two
    norms, an expert, the tied table + final norm)."""
    h, hd = config.hidden_size, config.head_dim
    nq, nkv = config.num_heads * hd, config.num_kv_heads * hd
    di, cc, mh = config.d_inner, config.conv_channels, config.mamba_n_heads
    mamba = (h * (di + cc + mh) + (config.mamba_d_conv + 1) * cc + 3 * mh
             + di + di * h)
    soft = 2 * h * nq + 2 * h * nkv
    return (mamba, soft,
            h * config.num_experts + 3 * h * config.shared_intermediate_size
            + 2 * h, 3 * h * config.intermediate_size,
            config.vocab_rows * h + h)
