"""Llama-style decoder family: RoPE + RMSNorm + SwiGLU + GQA.

A beyond-reference model family (the reference snapshot predates this
architecture) demonstrating the framework on the modern decoder recipe:
rotary position embeddings (no learned positions), RMSNorm pre-norm,
SwiGLU MLP, and grouped-query attention served NATIVELY by the Pallas
flash kernels (ops/attention/flash.py — kv_heads < heads share K/V rows
via block index maps / DMA row select; K/V never expand to the full head
count). First-class Megatron-style tensor-parallel PartitionSpecs and
the stacked ``scan_layers`` layout ship like the GPT-2/BERT families'.
"""

from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.models.gpt2 import (cast_weight, embedding_rows,
                                       layer_params, make_token_sampler,
                                       run_decode_scan, tied_logits,
                                       tied_xent_chunked, write_kv_cache)
from deepspeed_tpu.ops.attention.flash import flash_attention
from deepspeed_tpu.ops.attention.page_pool import (gqa_stripe_attention,
                                                   paged_attend,
                                                   paged_write_index)
from deepspeed_tpu.ops.functional import rms_norm


class LlamaConfig(NamedTuple):
    vocab_size: int = 32000
    hidden_size: int = 512
    num_layers: int = 8
    num_heads: int = 8
    num_kv_heads: int = 0          # 0 => num_heads (MHA); 1 = MQA
    intermediate_size: int = 0     # 0 => the llama 8/3 * hidden, 128-aligned
    max_position_embeddings: int = 2048
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    scan_layers: bool = False

    @property
    def kv_heads(self):
        return self.num_kv_heads or self.num_heads

    @property
    def head_dim(self):
        return self.hidden_size // self.num_heads

    @property
    def inter(self):
        if self.intermediate_size:
            return self.intermediate_size
        raw = int(self.hidden_size * 8 / 3)
        return (raw + 127) // 128 * 128


def init_llama_params(config: LlamaConfig, key) -> Dict[str, Any]:
    h, hd = config.hidden_size, config.head_dim
    hkv, inter = config.kv_heads, config.inter
    rng = config.initializer_range
    out_rng = rng / np.sqrt(2.0 * config.num_layers)
    keys = jax.random.split(key, 2 + 7 * config.num_layers)
    params: Dict[str, Any] = {
        "tok_emb": jax.random.normal(keys[0], (config.vocab_size, h),
                                     jnp.float32) * rng,
        "ln_f": {"w": jnp.ones((h,), jnp.float32)},
        # untied output head, stored (V, H) like a tied embedding so the
        # chunked fused head (gpt2.tied_xent_chunked) applies unchanged
        "lm_head": jax.random.normal(keys[1], (config.vocab_size, h),
                                     jnp.float32) * rng,
    }
    layers = []
    for i in range(config.num_layers):
        k = keys[2 + 7 * i: 9 + 7 * i]
        layers.append({
            "ln_1": {"w": jnp.ones((h,), jnp.float32)},
            "attn": {
                "wq": jax.random.normal(k[0], (h, h), jnp.float32) * rng,
                "wk": jax.random.normal(k[1], (h, hkv * hd),
                                       jnp.float32) * rng,
                "wv": jax.random.normal(k[2], (h, hkv * hd),
                                       jnp.float32) * rng,
                "wo": jax.random.normal(k[3], (h, h), jnp.float32) * out_rng,
            },
            "ln_2": {"w": jnp.ones((h,), jnp.float32)},
            "mlp": {
                "w_gate": jax.random.normal(k[4], (h, inter),
                                            jnp.float32) * rng,
                "w_up": jax.random.normal(k[5], (h, inter),
                                          jnp.float32) * rng,
                "w_down": jax.random.normal(k[6], (inter, h),
                                            jnp.float32) * out_rng,
            },
        })
    if config.scan_layers:
        params["h"] = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *layers)
    else:
        for i, lp in enumerate(layers):
            params[f"h_{i}"] = lp
    return params


def llama_param_specs(config: LlamaConfig) -> Dict[str, Any]:
    """Megatron column/row TP over the ``model`` axis: wq/wk/wv/gate/up
    column-parallel (output dim = heads — shard cleanly when num_heads
    and kv_heads divide the axis), wo/down row-parallel; embeddings and
    head vocab-sharded."""
    layer = {
        "ln_1": {"w": P()},
        "attn": {"wq": P(None, "model"), "wk": P(None, "model"),
                 "wv": P(None, "model"), "wo": P("model", None)},
        "ln_2": {"w": P()},
        "mlp": {"w_gate": P(None, "model"), "w_up": P(None, "model"),
                "w_down": P("model", None)},
    }
    specs: Dict[str, Any] = {
        "tok_emb": P("model", None),
        "ln_f": {"w": P()},
        "lm_head": P("model", None),
    }
    if config.scan_layers:
        specs["h"] = jax.tree_util.tree_map(
            lambda p: P(None, *p), layer,
            is_leaf=lambda x: isinstance(x, P))
    else:
        for i in range(config.num_layers):
            specs[f"h_{i}"] = layer
    return specs


def rope_cos_sin(seq_len: int, head_dim: int, theta: float,
                 dtype=jnp.float32):
    """(S, hd/2) cos/sin tables for rotary embedding."""
    inv = 1.0 / (theta ** (np.arange(0, head_dim, 2,
                                     dtype=np.float32) / head_dim))
    t = jnp.arange(seq_len, dtype=jnp.float32)
    freqs = jnp.outer(t, jnp.asarray(inv))           # (S, hd/2)
    return jnp.cos(freqs).astype(dtype), jnp.sin(freqs).astype(dtype)


def apply_rope(x, cos, sin):
    """Rotate (B, H, S, hd) by per-position angles.

    ``cos``/``sin`` are either the shared (S, hd/2) tables (training —
    every row sees positions 0..S-1) or per-row (B, S, hd/2) gathers
    (KV-cache serving — continuous-batching slots sit at different
    absolute positions). Pair layout is (x[..., :hd/2], x[..., hd/2:])
    — the "rotate_half" convention; consistent across q and k so
    relative phases match.
    """
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    if cos.ndim == 3:            # (B, S, hd/2): broadcast over heads only
        c = cos[:, None].astype(x.dtype)
        s = sin[:, None].astype(x.dtype)
    else:                        # (S, hd/2): broadcast over batch + heads
        c = cos[None, None].astype(x.dtype)
        s = sin[None, None].astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


def llama_block(block_params, config: LlamaConfig, x, cos, sin, dtype,
                attention_fn=None):
    """``attention_fn(q, k, v) -> ctx`` optionally replaces causal GQA
    flash attention (q post-RoPE (B, H, S, hd); k/v (B, kv_heads, S,
    hd), k post-RoPE) — the KV-cache decode hook."""
    B, S, h = x.shape
    H, hkv, hd = config.num_heads, config.kv_heads, config.head_dim

    a_in = rms_norm(x, block_params["ln_1"]["w"], config.rms_norm_eps)
    ap = block_params["attn"]
    q = (a_in @ cast_weight(ap["wq"], dtype)).reshape(B, S, H, hd)
    k = (a_in @ cast_weight(ap["wk"], dtype)).reshape(B, S, hkv, hd)
    v = (a_in @ cast_weight(ap["wv"], dtype)).reshape(B, S, hkv, hd)
    q = apply_rope(q.transpose(0, 2, 1, 3), cos, sin)
    k = apply_rope(k.transpose(0, 2, 1, 3), cos, sin)
    v = v.transpose(0, 2, 1, 3)
    if attention_fn is not None:
        ctx = attention_fn(q, k, v)
    else:
        ctx = flash_attention(q, k, v, causal=True)  # native GQA
    ctx = ctx.transpose(0, 2, 1, 3).reshape(B, S, h)
    x = x + ctx @ cast_weight(ap["wo"], dtype)

    m_in = rms_norm(x, block_params["ln_2"]["w"], config.rms_norm_eps)
    mp = block_params["mlp"]
    gate = jax.nn.silu(m_in @ cast_weight(mp["w_gate"], dtype))
    up = m_in @ cast_weight(mp["w_up"], dtype)
    return x + (gate * up) @ cast_weight(mp["w_down"], dtype)


def _llama_trunk(params, config: LlamaConfig, input_ids,
                 dtype=jnp.bfloat16, remat: bool = False):
    B, S = input_ids.shape
    assert S <= config.max_position_embeddings, (
        "sequence length exceeds max_position_embeddings — RoPE would "
        "silently extrapolate", S, config.max_position_embeddings)
    x = embedding_rows(params["tok_emb"], input_ids, dtype)
    cos, sin = rope_cos_sin(S, config.head_dim, config.rope_theta)

    block = llama_block
    if remat:
        block = jax.checkpoint(llama_block, static_argnums=(1, 5, 6))

    if config.scan_layers:
        def body(x, lp):
            return block(lp, config, x, cos, sin, dtype, None), None
        x, _ = jax.lax.scan(body, x, params["h"])
    else:
        for i in range(config.num_layers):
            x = block(params[f"h_{i}"], config, x, cos, sin, dtype, None)
    return rms_norm(x, params["ln_f"]["w"], config.rms_norm_eps)


def _gqa_offset_cache_attention(kcache, vcache, cache_position, out_box):
    """attention_fn for the cached llama forward (prefill-into-cache and
    decode alike): write this call's post-RoPE K/V into the hkv-head
    cache at each row's own offset, attend group-wise over all cache
    slots <= each query's absolute position (the shared
    ``causal_cache_mask``). The cache stays kv_heads-sized — GQA's
    serving payoff. Updated caches return through ``out_box``."""

    def attn(q, k, v):
        kc = write_kv_cache(kcache, k, cache_position)
        vc = write_kv_cache(vcache, v, cache_position)
        out_box.append((kc, vc))
        return gqa_stripe_attention(q, kc, vc, cache_position)
    return attn


def _gqa_paged_cache_attention(pools, layer: int, block_table,
                               cache_position, index, out_box,
                               attn_kernel: str = "gather"):
    """Paged attention_fn for layer ``layer`` of the cached llama
    forward: ``page_pool.paged_attend`` over the kv_heads-sized stacked
    pool tree with this call's post-RoPE K/V. Single-query calls with
    ``attn_kernel="pallas"`` run the fused paged-decode kernel, which
    serves GQA natively — the q_heads/kv_heads query rows of each group
    share their kv head's page stream inside the kernel, so no head
    replication ever materializes; otherwise the gathered stripe is
    attended group-wise (:func:`gqa_stripe_attention`)."""

    def attn(q, k, v):
        return paged_attend(q, k, v, pools, layer, block_table,
                            cache_position, index, out_box, attn_kernel,
                            gqa_stripe_attention)
    return attn


def _llama_trunk_cached(params, config: LlamaConfig, input_ids, kv_cache,
                        cache_position, dtype, block_tables=None,
                        paged_attn_kernel: str = "gather"):
    """Cache-carrying trunk (see gpt2._gpt2_trunk_cached): one code path
    for prefill-into-cache and decode, through the SAME llama_block as
    training. RoPE angles are gathered per row at each token's absolute
    position. Returns (hidden states after ln_f, updated kv_cache).
    ``block_tables`` switches to the paged pool pair (each
    (layers, num_pages, page_size, kv_heads * hd), carried through the
    layers and written in place); an int8-quantized pool arrives as the
    4-tuple ``(kc, vc, kscale, vscale)``; ``paged_attn_kernel`` picks
    the fused Pallas decode kernel or the gather oracle for seq-1
    queries."""
    B, S = input_ids.shape
    paged = block_tables is not None
    if paged:
        page_size = kv_cache[0].shape[2]
        max_len = block_tables.shape[1] * page_size
        index = paged_write_index(block_tables, cache_position, S,
                                  page_size)
    else:
        max_len = kv_cache[0].shape[3]
    pos = cache_position[:, None] + jnp.arange(S)[None, :]
    cos_full, sin_full = rope_cos_sin(max_len, config.head_dim,
                                      config.rope_theta)
    cos_b, sin_b = cos_full[pos], sin_full[pos]        # (B, S, hd/2)
    x = embedding_rows(params["tok_emb"], input_ids, dtype)
    if paged:
        for i in range(config.num_layers):
            box = []
            attn = _gqa_paged_cache_attention(
                kv_cache, i, block_tables, cache_position, index, box,
                attn_kernel=paged_attn_kernel)
            x = llama_block(layer_params(params, config, i), config, x,
                            cos_b, sin_b, dtype, attention_fn=attn)
            kv_cache = box[0]
    else:
        new_caches = []
        for i in range(config.num_layers):
            box = []
            attn = _gqa_offset_cache_attention(
                kv_cache[0][i], kv_cache[1][i], cache_position, box)
            x = llama_block(layer_params(params, config, i), config, x,
                            cos_b, sin_b, dtype, attention_fn=attn)
            new_caches.append(box[0])
        kv_cache = tuple(jnp.stack(leaf) for leaf in zip(*new_caches))
    return rms_norm(x, params["ln_f"]["w"], config.rms_norm_eps), kv_cache


def llama_forward(params, config: LlamaConfig, input_ids,
                  dtype=jnp.bfloat16, remat: bool = False,
                  kv_cache=None, cache_position=None, block_tables=None,
                  paged_attn_kernel: str = "gather"):
    """Logits (B, S, vocab).

    KV-cache mode (serving): with ``kv_cache=(kc, vc)`` (each
    ``(layers, B, kv_heads, max_len, hd)``) and ``cache_position``
    ((B,) int32), writes this call's K/V at each row's offset and
    returns ``(logits, updated_cache)`` — same contract as
    :func:`deepspeed_tpu.models.gpt2.gpt2_forward`, including the
    paged-pool interpretation under ``block_tables`` and the
    ``paged_attn_kernel`` fused-decode switch. Training call signature
    unchanged."""
    if kv_cache is not None:
        if cache_position is None:
            cache_position = jnp.zeros((input_ids.shape[0],), jnp.int32)
        x, cache = _llama_trunk_cached(params, config, input_ids,
                                       kv_cache, cache_position, dtype,
                                       block_tables=block_tables,
                                       paged_attn_kernel=paged_attn_kernel)
        return tied_logits(x, params["lm_head"], dtype), cache
    x = _llama_trunk(params, config, input_ids, dtype=dtype, remat=remat)
    return tied_logits(x, params["lm_head"], dtype)


def _gqa_cached_attention(kcache, vcache, pos, out_box):
    """Single-position decode hook (llama_generate's scan): every row
    writes/attends at the same scalar ``pos`` — the offset-cache GQA
    attention with a broadcast position vector (one copy of the cache
    attention math; the cache stays kv_heads-sized)."""
    B = kcache.shape[0]
    return _gqa_offset_cache_attention(
        kcache, vcache, jnp.full((B,), pos, jnp.int32), out_box)


def llama_generate(params, config: LlamaConfig, prompt_ids,
                   max_new_tokens, rng=None, temperature: float = 1.0,
                   top_k: int = 0, dtype=jnp.bfloat16):
    """Autoregressive sampling with a kv_heads-sized KV cache (GQA's
    inference payoff: cache memory is kv_heads/heads of the MHA cache).
    Same contract as :func:`deepspeed_tpu.models.gpt2.gpt2_generate`;
    decode is one ``lax.scan``."""
    B, Pl = prompt_ids.shape
    if max_new_tokens <= 0:
        return prompt_ids
    L = Pl + max_new_tokens
    assert L <= config.max_position_embeddings, (
        L, config.max_position_embeddings)
    hkv, hd = config.kv_heads, config.head_dim
    nl = config.num_layers
    greedy = rng is None or temperature == 0.0
    sample = make_token_sampler(config.vocab_size, temperature, top_k,
                                greedy)
    cos_full, sin_full = rope_cos_sin(L, hd, config.rope_theta)

    # ---- prefill: full forward over the prompt, capturing post-RoPE K/V
    x = params["tok_emb"][prompt_ids].astype(dtype)
    kc = jnp.zeros((nl, B, hkv, L, hd), dtype)
    vc = jnp.zeros((nl, B, hkv, L, hd), dtype)
    captured = {}

    def capture_attn(i):
        def attn(q, k, v):
            captured[i] = (k, v)
            return flash_attention(q, k, v, causal=True)
        return attn

    cos_p, sin_p = cos_full[:Pl], sin_full[:Pl]
    for i in range(nl):
        x = llama_block(layer_params(params, config, i), config, x,
                        cos_p, sin_p, dtype, attention_fn=capture_attn(i))
        k, v = captured.pop(i)
        kc = kc.at[i, :, :, :Pl].set(k.astype(dtype))
        vc = vc.at[i, :, :, :Pl].set(v.astype(dtype))
    x = rms_norm(x, params["ln_f"]["w"], config.rms_norm_eps)
    last_logits = tied_logits(x[:, -1:], params["lm_head"], dtype)[:, 0]

    if rng is None:
        rng = jax.random.PRNGKey(0)
    first_tok = sample(last_logits, jax.random.fold_in(rng, 0))

    def step_logits(tok, t, caches):
        kc, vc = caches
        pos = Pl + t                      # position of `tok` in the stream
        x = params["tok_emb"][tok[:, None]].astype(dtype)
        cos_t = jax.lax.dynamic_slice_in_dim(cos_full, pos, 1, 0)
        sin_t = jax.lax.dynamic_slice_in_dim(sin_full, pos, 1, 0)
        new_kc, new_vc = [], []
        for i in range(nl):
            box = []
            x = llama_block(layer_params(params, config, i), config, x,
                            cos_t, sin_t, dtype,
                            attention_fn=_gqa_cached_attention(
                                kc[i], vc[i], pos, box))
            ki, vi = box[0]
            new_kc.append(ki)
            new_vc.append(vi)
        x = rms_norm(x, params["ln_f"]["w"], config.rms_norm_eps)
        logits = tied_logits(x, params["lm_head"], dtype)[:, 0]
        return logits, (jnp.stack(new_kc), jnp.stack(new_vc))

    gen = run_decode_scan(step_logits, sample, first_tok, (kc, vc),
                          max_new_tokens, rng)
    return jnp.concatenate([prompt_ids, gen], axis=1)


def llama_loss_fn(config: LlamaConfig, dtype=jnp.bfloat16,
                  remat: bool = False, deterministic: bool = True):
    """Engine-contract loss: batch = {'input_ids': (B, S+1) int32} —
    next-token cross entropy via the chunked fused head. The family has
    no dropout (llama recipe), so ``deterministic`` is accepted for
    engine-contract parity and ignored."""

    def loss_fn(params, batch, rng):
        del rng
        ids = batch["input_ids"]
        inputs, targets = ids[:, :-1], ids[:, 1:]
        x = _llama_trunk(params, config, inputs, dtype=dtype, remat=remat)
        return tied_xent_chunked(x, params["lm_head"], targets, dtype)
    return loss_fn
