"""Kimi-Linear-style hybrid decoder, SERVED: gated delta-rule linear
attention ("KDA") among multi-head latent attention (MLA) layers with NO
rotation anywhere, a leading dense SwiGLU layer, then layers of routed
SwiGLU experts chosen by a sigmoid router under a per-expert correction
bias, with a shared expert beside them
(moonshotai/Kimi-Linear-48B-A3B-Instruct, config.json;
docs/kimi_linear.md has the equations and what the config leaves open).

Layer ``l`` on the residual stream ``x`` (``h = RMSNorm(x)``):

- ``l`` in ``latent_layers``: ``models/axk1.py``'s ``latent_mixer``
  with what this config states: no queries' rank (``q = h W_q``, no
  norm), ``[c | k_r] = h W_kva``, the cache row ``[RMSNorm(c) | k_r]``
  with ``k_r`` UNROTATED and shared by the heads, scores ``(q_n . k_n +
  q_r . k_r) * (d_n + d_r)^-1/2``; expanded in prefill, absorbed in
  decode, over the tree's ONE latent page pool.
- else: ``models/solar_open2.py``'s ``kda_mixer`` with what this config
  states: the step ``b = sigmoid(h W_b)`` (no negative eigenvalue) and
  no bias on the output gate; the state and the convolution's last
  three inputs live in the per-slot state pool.
- then, on ``h2 = RMSNorm(x)``: layers below ``first_k_dense`` a dense
  SwiGLU, the rest ``x += sum_top8 w_e E_e(h2) + E_shared(h2)`` with
  ``s = sigmoid(h2 W_r)``, the eight largest of ``s + b_e`` (one group:
  no limit), ``w_e = s_e / sum of the eight s * routed_scaling_factor``
  (``ops/moe.route_group_limited`` with its ``bias``).

The cache tree is ``inference/kv_cache.LatentStateCache``: ``pool``
(latent layers, pages, page_size, row lanes), ``state`` and ``tails``
(recurrent layers, slots + 1, ...). The config carries the chip's SHARE
of a layer as ``models/solar_open2.py`` does: ``experts_held`` and
``vocab_held``.

Two programs, and the family is served in CHUNKS
(``inference.chunked_prefill``): a PREFILL row (more than one token) may
start at any ``cache_position``: a delta-rule layer then starts from the
state and tail its slot holds (zeros at position 0) and leaves both at
the row's TRUE end, a latent layer attends its own rows causally and the
prefix earlier chunks wrote, read back through the block table a block
at a time (``ops/attention/page_pool.prefix_own_attention``). DECODE
(one token a row, the rows the slot table) leaves the state and tail of
a row that does not decode, a slot mid-prefill among them, as they are.
"""

from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.axk1 import latent_mixer
from deepspeed_tpu.models.served_trunk import (ServedFamily, served_forward,
                                               whole_leaf_specs)
from deepspeed_tpu.models.solar_open2 import kda_mixer
from deepspeed_tpu.ops.moe import route_group_limited

# caps of the grouped products' tile at these experts' widths (2,304 x
# 1,024), cut to whole divisors: 128 rows (a chunk of 2,048 tokens lands
# 64-128 rows on a held expert, and a tile a group touches is worked
# whole)
_EXPERT_TILE = (128, 1152, 1024)


class KimiLinearConfig(NamedTuple):
    vocab_size: int = 163840
    hidden_size: int = 2304
    num_layers: int = 27
    # latent attention at these layers (0-indexed), delta-rule elsewhere
    latent_layers: Tuple[int, ...] = (3, 7, 11, 15, 19, 23, 26)
    num_heads: int = 32
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    kda_num_heads: int = 32
    kda_head_dim: int = 128
    kda_conv_width: int = 4
    kda_gate_rank: int = 128          # the low-rank decay and gate
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    first_k_dense: int = 1
    num_experts: int = 256
    experts_per_token: int = 8
    routed_scaling_factor: float = 2.446
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 1048576
    initializer_range: float = 0.02
    # SEEDED trees only (a benchmark's choices, stated in its
    # configuration file: docs/kimi_linear.md Seeding): a ROUTED
    # expert's w_down against the other branches', and the spread of the
    # router's correction bias; trained weights carry their own
    routed_init_gain: float = 1.0
    router_bias_std: float = 0.0
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
    def recurrent_layers(self):
        return tuple(l for l in range(self.num_layers)
                     if l not in self.latent_layers)

    @property
    def expert_layers(self):
        return tuple(range(self.first_k_dense, self.num_layers))

    @property
    def kv_cache_layers(self):
        """Layers with a row in the latent pool."""
        return sum(l < self.num_layers for l in self.latent_layers)

    @property
    def latent_geometry(self):
        """As ``AXK1Config.latent_geometry``: (the latent's width, the
        shared key slice's)."""
        return (self.kv_lora_rank, self.qk_rope_head_dim)

    @property
    def state_geometry(self):
        """As ``SolarOpen2Config.state_geometry``."""
        width = self.kda_num_heads * self.kda_head_dim
        return (len(self.recurrent_layers), self.kda_num_heads,
                self.kda_head_dim, self.kda_head_dim,
                self.kda_conv_width - 1, 3 * width)

    @property
    def expert_counters(self):
        """As ``SolarOpen2Config.expert_counters``."""
        return (self.experts_per_token * len(self.expert_layers),
                self.held[1])

    # what the two shared mixers read of a config (module docstring)
    q_lora_rank = property(lambda self: 0)
    rotary = property(lambda self: False)
    kda_beta_scale = property(lambda self: 1.0)

    @property
    def sm_scale(self):
        return float((self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5)

    # the mixers follow a chunk (``served_trunk._Call.carry``), so
    # ``inference/engine.py`` does not refuse chunked prefill
    serves_chunked_prefill = property(lambda self: True)


def init_kimi_linear_params(config: KimiLinearConfig, key,
                            dtype=jnp.bfloat16) -> Dict[str, Any]:
    """The tree, matrices HELD in ``dtype``, the router, its bias, the
    decay's ``a_log`` and ``b_dt`` and the norms in float32:
    ``tok_emb``, ``lm_head`` (rows held, H), ``ln_f``, ``h_<l>`` with
    ``ln_1``, ``ln_2``, ``kda`` {wq, wk, wv, conv (3, width, W), wf1,
    wf2, b_dt, a_log, wb, wg1, wg2, norm, wo} or ``attn`` {wq (H, heads
    x (d_n + d_r)), wkv_a (H, r_kv + d_r), kv_norm, wkv_b (r_kv, heads x
    (d_n + d_v)), wo}, and ``mlp`` {w_gate, w_up, w_down} below
    ``first_k_dense``, else ``router`` (H, experts), ``router_bias``
    (experts,), ``experts`` {(held, H, F) x 2, (held, F, H)}, ``shared``.
    Seeded as the two families these mixers come from seed theirs; the
    bias normal with ``config.router_bias_std``."""
    h, nh = config.hidden_size, config.num_heads
    rkv = config.kv_lora_rank
    dn, dr, dv = (config.qk_nope_head_dim, config.qk_rope_head_dim,
                  config.v_head_dim)
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
        lp = {"ln_1": {"w": ones(h)}, "ln_2": {"w": ones(h)}}
        if l in config.latent_layers:
            lp["attn"] = {
                "wq": normal(k[0], (h, nh * (dn + dr)), std),
                "wkv_a": normal(k[1], (h, rkv + dr), std),
                "kv_norm": ones(rkv),
                "wkv_b": normal(k[2], (rkv, nh * (dn + dv)), std),
                "wo": normal(k[3], (nh * dv, h), out_std)}
        else:
            dt = jnp.exp(jax.random.uniform(
                k[4], (kw,), jnp.float32, np.log(1e-3), np.log(1e-1)))
            lp["kda"] = {
                "wq": normal(k[5], (h, kw), std),
                "wk": normal(k[6], (h, kw), std),
                "wv": normal(k[7], (h, kw), std),
                "conv": normal(k[8], (3, cw, kw), cw ** -0.5),
                "wf1": normal(k[9], (h, rank), std),
                "wf2": normal(k[10], (rank, kw), std),
                # softplus(b_dt) = dt
                "b_dt": dt + jnp.log(-jnp.expm1(-dt)),
                "a_log": jnp.log(jax.random.uniform(
                    k[11], (kh,), jnp.float32, 1.0, 16.0)),
                "wb": normal(k[12], (h, kh), std),
                "wg1": normal(k[13], (h, rank), std),
                "wg2": normal(k[14], (rank, kw), std),
                "norm": ones(kd),
                "wo": normal(k[15], (kw, h), out_std)}
        if l < config.first_k_dense:
            fd = config.intermediate_size
            lp["mlp"] = {"w_gate": normal(k[16], (h, fd), std),
                         "w_up": normal(k[17], (h, fd), std),
                         "w_down": normal(k[18], (fd, h), out_std)}
        else:
            lp["router"] = normal(k[16], (h, config.num_experts), std,
                                  jnp.float32)
            lp["router_bias"] = normal(k[17], (config.num_experts,),
                                       config.router_bias_std, jnp.float32)
            ek = jax.random.split(k[18], 3)
            lp["experts"] = {"w_gate": normal(ek[0], (held, h, f), std),
                             "w_up": normal(ek[1], (held, h, f), std),
                             "w_down": normal(ek[2], (held, f, h),
                                              out_std
                                              * config.routed_init_gain)}
            lp["shared"] = {"w_gate": normal(k[19], (h, f), std),
                            "w_up": normal(k[20], (h, f), std),
                            "w_down": normal(k[21], (f, h), out_std)}
        params[f"h_{l}"] = lp
    return params


def kimi_linear_param_specs(config: KimiLinearConfig):
    """Every leaf whole (``served_trunk.whole_leaf_specs``)."""
    return whole_leaf_specs(init_kimi_linear_params, config)


def _family(config: KimiLinearConfig) -> ServedFamily:
    def route(flat, router, bias):
        # ONE group: nothing is left out before the choice
        idx, p, _, _ = route_group_limited(
            flat, router, config.experts_per_token, 1, 1,
            config.routed_scaling_factor, bias=bias)
        return idx, p, None

    return ServedFamily(
        layers=tuple(("latent" if l in config.latent_layers else "kda",
                      "dense" if l < config.first_k_dense else "experts")
                     for l in range(config.num_layers)),
        mixers={"latent": latent_mixer, "kda": kda_mixer},
        route=route, expert_tile=_EXPERT_TILE,
        chunked=config.serves_chunked_prefill)


def kimi_linear_forward(params, config: KimiLinearConfig, input_ids,
                        dtype=jnp.bfloat16, kv_cache=None,
                        cache_position=None, block_tables=None,
                        paged_attn_kernel: str = "gather", lengths=None,
                        slots=None, active=None, with_counts=False):
    """Logits over the held rows of the vocabulary.

    Plain (``kv_cache=None``): (B, S) ids -> (B, S, rows) float32, every
    row from position 0 and an empty state.

    Serving: ``kv_cache`` a ``kv_cache.LatentStateCache`` with
    ``block_tables`` and ``cache_position`` as the other families take
    them. PREFILL (S > 1) also takes ``lengths`` (B,) and ``slots``
    (B,), each row's true length and its row of the state pools (a pad
    row names the scratch row); a row may start at any
    ``cache_position`` (a later chunk of its prompt; with
    ``paged_attn_kernel="gather"`` the stripe reader takes the prefix
    instead of the reader in blocks); returns logits at each row's LAST
    true position only, (B, 1, rows). DECODE (S == 1) takes ``active``
    (B,) bool and runs row i against row i of the state pools, leaving
    an inactive row's state and tail as they are. Returns (logits, the
    cache); with ``with_counts`` also (expert layers, 2) int32 as
    ``solar_open2_forward`` does."""
    if kv_cache is not None and input_ids.shape[1] == 1 and active is None:
        raise ValueError("a served decode of this family needs `active`: "
                         "a slot mid-prefill must keep its state")
    return served_forward(_family(config), params, config, input_ids, dtype,
                          kv_cache, cache_position, block_tables,
                          paged_attn_kernel, lengths, slots, active,
                          with_counts)


def kimi_linear_param_count(config: KimiLinearConfig):
    """(a delta-rule mixer, a latent mixer, the dense feed-forward,
    router + its bias + shared expert, an expert, embedding + head +
    final norm + the layers' two norms each)."""
    h, nh = config.hidden_size, config.num_heads
    rkv = config.kv_lora_rank
    dn, dr, dv = (config.qk_nope_head_dim, config.qk_rope_head_dim,
                  config.v_head_dim)
    kh, kd = config.kda_num_heads, config.kda_head_dim
    kw, rank = kh * kd, config.kda_gate_rank
    kda = (4 * h * kw + 3 * config.kda_conv_width * kw
           + 2 * (h * rank + rank * kw) + kw + kh + h * kh + kd)
    latent = (h * nh * (dn + dr) + h * (rkv + dr) + rkv
              + rkv * nh * (dn + dv) + nh * dv * h)
    expert = 3 * h * config.moe_intermediate_size
    return (kda, latent, 3 * h * config.intermediate_size,
            h * config.num_experts + config.num_experts + expert, expert,
            2 * config.vocab_rows * h + h + 2 * h * config.num_layers)
