"""SmallThinker-style sparse decoder: one stack whose layers differ in
mask and in position, with dropless routed experts routed BEFORE
attention (PowerInfer/SmallThinker-21BA3B-Instruct, config.json).

Layer ``l`` on input ``x`` (docs/smallthinker.md has the equations):

- ``h = RMSNorm_1(x)``;
- router, before attention: ``r = h W_r`` in float32, the six largest,
  ``p = softmax`` over those six only;
- attention on ``h``: 28 query heads over 4 key-value heads of 128
  (query head i reads key-value head i // 7), causal; where
  ``rope_layout[l]`` rotary on q and k (rotate-half), else no position
  at all; where ``sliding_window_layout[l]`` only the last
  ``sliding_window_size`` keys. ``x1 = x + attn W_o``;
- experts on ``h2 = RMSNorm_2(x1)``, routed by ``r`` from ``h``:
  ``x2 = x1 + sum_e p_e W_down,e(relu(W_gate,e h2) * (W_up,e h2))``.

No biases, an untied head. The config carries the chip's SHARE of a
layer: ``experts_held = (first, count)`` of the ``num_experts`` the
router scores (the layer computes its own experts' part of the sum and
leaves the rest out: ops/moe.py) and ``vocab_held = (first, count)``
rows of the vocabulary (ids, logits and loss are over the slice). With
all experts and all rows it is the whole model.

One attention kernel (``flash_attention``: the causal and the banded
causal BlockMask of ops/attention/masked_flash.py), one expert path.
"""

from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.gpt2 import cast_weight, tied_xent_chunked
from deepspeed_tpu.models.llama import apply_rope, rope_cos_sin
from deepspeed_tpu.ops.attention.flash import flash_attention
from deepspeed_tpu.ops.functional import rms_norm
from deepspeed_tpu.ops.moe import dropless_experts, route_top_k
from deepspeed_tpu.profiling.spans import scope


class SmallThinkerConfig(NamedTuple):
    vocab_size: int = 151936
    hidden_size: int = 2560
    num_layers: int = 52
    num_heads: int = 28
    num_kv_heads: int = 4
    head_dim: int = 128
    moe_ffn_hidden_size: int = 768
    num_experts: int = 64
    experts_per_token: int = 6
    rope_layout: Tuple[int, ...] = (0, 1, 1, 1) * 13
    sliding_window_layout: Tuple[int, ...] = (0, 1, 1, 1) * 13
    sliding_window_size: int = 4096
    rope_theta: float = 1500000.0
    rms_norm_eps: float = 1e-6
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


def init_smallthinker_params(config: SmallThinkerConfig,
                             key) -> Dict[str, Any]:
    """float32 tree: ``tok_emb``, ``lm_head`` (rows held, H), ``ln_f``,
    ``h_<l>`` with ``ln_1``, ``attn`` {wq, wk, wv, wo}, ``router``
    (H, num_experts), ``ln_2``, ``experts`` {w_gate, w_up: (held, H, F),
    w_down: (held, F, H)}."""
    h, hd = config.hidden_size, config.head_dim
    nq, nkv = config.num_heads * hd, config.num_kv_heads * hd
    f, held, rows = (config.moe_ffn_hidden_size, config.held[1],
                     config.vocab_rows)
    std = config.initializer_range
    out_std = std / np.sqrt(2.0 * config.num_layers)
    normal = lambda k, shape, s: jax.random.normal(k, shape,
                                                   jnp.float32) * s
    keys = jax.random.split(key, 2 + config.num_layers)
    params: Dict[str, Any] = {
        "tok_emb": normal(keys[0], (rows, h), std),
        "lm_head": normal(keys[1], (rows, h), std),
        "ln_f": {"w": jnp.ones((h,), jnp.float32)},
    }
    for l in range(config.num_layers):
        k = jax.random.split(keys[2 + l], 8)
        params[f"h_{l}"] = {
            "ln_1": {"w": jnp.ones((h,), jnp.float32)},
            "attn": {"wq": normal(k[0], (h, nq), std),
                     "wk": normal(k[1], (h, nkv), std),
                     "wv": normal(k[2], (h, nkv), std),
                     "wo": normal(k[3], (nq, h), out_std)},
            "router": normal(k[4], (h, config.num_experts), std),
            "ln_2": {"w": jnp.ones((h,), jnp.float32)},
            "experts": {"w_gate": normal(k[5], (held, h, f), std),
                        "w_up": normal(k[6], (held, h, f), std),
                        "w_down": normal(k[7], (held, f, h), out_std)},
        }
    return params


def _norm(x, p, eps):
    with scope("ln"):
        return rms_norm(x, p["w"], eps)


def _attention_half(lp, config: SmallThinkerConfig, layer: int, x, dtype):
    """x -> (x + attention, the router's choice (idx, p) made from the
    same normed input)."""
    B, S, _ = x.shape
    H, hkv, hd = config.num_heads, config.num_kv_heads, config.head_dim
    h = _norm(x, lp["ln_1"], config.rms_norm_eps)
    with scope("moe_route"):
        idx, p, _ = route_top_k(h.reshape(B * S, -1), lp["router"],
                                config.experts_per_token)
    ap = lp["attn"]
    with scope("attn_proj"):
        q = (h @ cast_weight(ap["wq"], dtype)).reshape(B, S, H, hd)
        k = (h @ cast_weight(ap["wk"], dtype)).reshape(B, S, hkv, hd)
        v = (h @ cast_weight(ap["wv"], dtype)).reshape(B, S, hkv, hd)
        q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        if config.rope_layout[layer]:
            cos, sin = rope_cos_sin(S, hd, config.rope_theta)
            q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    windowed = bool(config.sliding_window_layout[layer])
    with scope("attn_window" if windowed else "attn_global"):
        with scope("attn_core"):
            ctx = flash_attention(
                q, k, v, causal=True,
                window=config.sliding_window_size if windowed else None)
    with scope("attn_proj"):
        ctx = ctx.transpose(0, 2, 1, 3).reshape(B, S, H * hd)
        x = x + ctx @ cast_weight(ap["wo"], dtype)
    return x, (idx, p)


def _expert_half(lp, config: SmallThinkerConfig, x, idx, p, dtype):
    """x1 -> (x1 + the held experts' part, assignments landed on each
    held expert)."""
    B, S, hdim = x.shape
    h2 = _norm(x, lp["ln_2"], config.rms_norm_eps)
    experts = {name: cast_weight(table, dtype)
               for name, table in lp["experts"].items()}
    y, counts = dropless_experts(
        h2.reshape(B * S, hdim), idx, p, experts, config.held,
        config.num_experts, jax.nn.relu)
    with scope("moe_dispatch"):
        return x + y.reshape(B, S, hdim).astype(x.dtype), counts


def smallthinker_trunk(params, config: SmallThinkerConfig, input_ids,
                       dtype=jnp.bfloat16):
    """(B, S) ids of the held slice -> (final hidden states (B, S, H)
    after ln_f, {"moe_counts": (layers, held) int32 assignments landed,
    "moe_choice": (layers, B*S, k) int32 experts chosen})."""
    with scope("embed"):
        x = params["tok_emb"][input_ids].astype(dtype)
    counts, choices = [], []
    for l in range(config.num_layers):
        lp = params[f"h_{l}"]
        x, (idx, p) = _attention_half(lp, config, l, x, dtype)
        x, c = _expert_half(lp, config, x, idx, p, dtype)
        counts.append(c)
        choices.append(idx)
    x = _norm(x, params["ln_f"], config.rms_norm_eps)
    return x, {"moe_counts": jnp.stack(counts),
               "moe_choice": jnp.stack(choices)}


def smallthinker_logits(params, config: SmallThinkerConfig, input_ids,
                        positions, dtype=jnp.bfloat16):
    """float32 logits over the held rows at ``positions`` of every row
    of ``input_ids``: (B, len(positions), rows), and the trunk's facts."""
    x, facts = smallthinker_trunk(params, config, input_ids, dtype)
    with scope("lm_head"):
        logits = jax.lax.dot_general(
            x[:, positions], cast_weight(params["lm_head"], dtype),
            (((2,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    return logits, facts


def smallthinker_loss_fn(config: SmallThinkerConfig, dtype=jnp.bfloat16):
    """Engine-contract loss: batch = {'input_ids': (B, S+1) int32 of the
    held slice} -> (next-token cross entropy over the held rows,
    {"moe_counts": (layers, held) int32}). The function casts each
    weight where it is used (``owns_cast``): the router's stay float32."""
    def loss_fn(params, batch):
        ids = batch["input_ids"]
        x, facts = smallthinker_trunk(params, config, ids[:, :-1], dtype)
        loss = tied_xent_chunked(x, params["lm_head"], ids[:, 1:], dtype)
        return loss, {"moe_counts": facts["moe_counts"]}
    loss_fn.owns_cast = True
    return loss_fn


def smallthinker_param_count(config: SmallThinkerConfig):
    """(parameters outside the experts and the two tables a layer, an
    expert's parameters, the two tables' parameters)."""
    h, hd = config.hidden_size, config.head_dim
    layer = (h * config.num_heads * hd * 2 + h * config.num_kv_heads * hd * 2
             + h * config.num_experts + 2 * h)
    expert = 3 * h * config.moe_ffn_hidden_size
    return layer, expert, 2 * config.vocab_rows * h + h
