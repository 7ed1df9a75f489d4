"""GPT-2 model family — the flagship workload.

Recreates the Megatron-GPT2 workload the reference trained through
DeepSpeedExamples (BASELINE.md: GPT-2 345M + ZeRO-2, GPT-2 1.5B 3D-parallel)
as a native model of this framework: causal flash attention, bf16 compute,
and first-class tensor-parallel PartitionSpecs (Megatron column/row sharding
over the ``model`` mesh axis — what the reference delegated to the client's
mpu, SURVEY §2.3).
"""

from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.ops.attention.flash import NEG_INF, flash_attention
from deepspeed_tpu.ops.attention.page_pool import (causal_cache_mask,
                                                   paged_attend,
                                                   paged_write_index)
from deepspeed_tpu.profiling.spans import scope


class GPT2Config(NamedTuple):
    vocab_size: int = 50257
    max_position_embeddings: int = 1024
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 0      # 0 => 4*hidden
    embd_dropout: float = 0.1
    attn_dropout: float = 0.1
    resid_dropout: float = 0.1
    initializer_range: float = 0.02
    layer_norm_eps: float = 1e-5
    # Stack the (homogeneous) blocks into leading-dim-L params and run
    # the trunk as one lax.scan: the block compiles ONCE instead of
    # num_layers times (BERT-large/GPT-2 first-compile drops ~20x; the
    # standard JAX LLM layout, cf. T5X/MaxText). Numerics are identical
    # to the unrolled trunk (same per-layer init keys); only the
    # per-layer dropout streams differ. Dense family only.
    scan_layers: bool = False

    @property
    def inter(self):
        return self.intermediate_size or 4 * self.hidden_size


# canonical sizes (Megatron/GPT-2 papers)
GPT2_SMALL = GPT2Config()                                          # 124M
GPT2_MEDIUM = GPT2Config(hidden_size=1024, num_layers=24,
                         num_heads=16)                             # 345M
GPT2_LARGE = GPT2Config(hidden_size=1280, num_layers=36,
                        num_heads=20)                              # 774M
GPT2_XL = GPT2Config(hidden_size=1600, num_layers=48,
                     num_heads=25)                                 # 1.5B


def init_gpt2_params(config: GPT2Config, key) -> Dict[str, Any]:
    h, inter = config.hidden_size, config.inter
    rng = config.initializer_range
    out_rng = rng / np.sqrt(2.0 * config.num_layers)
    keys = jax.random.split(key, 2 + 4 * config.num_layers)
    params: Dict[str, Any] = {
        "wte": jax.random.normal(keys[0], (config.vocab_size, h),
                                 jnp.float32) * rng,
        "wpe": jax.random.normal(keys[1], (config.max_position_embeddings, h),
                                 jnp.float32) * rng,
        "ln_f": {"w": jnp.ones((h,), jnp.float32),
                 "b": jnp.zeros((h,), jnp.float32)},
    }
    layers = []
    for i in range(config.num_layers):
        k = keys[2 + 4 * i: 6 + 4 * i]
        layers.append({
            "ln_1": {"w": jnp.ones((h,), jnp.float32),
                     "b": jnp.zeros((h,), jnp.float32)},
            "attn": {
                "qkvw": jax.random.normal(k[0], (h, 3 * h), jnp.float32) * rng,
                "qkvb": jnp.zeros((3 * h,), jnp.float32),
                "ow": jax.random.normal(k[1], (h, h), jnp.float32) * out_rng,
                "ob": jnp.zeros((h,), jnp.float32),
            },
            "ln_2": {"w": jnp.ones((h,), jnp.float32),
                     "b": jnp.zeros((h,), jnp.float32)},
            "mlp": {
                "fc_w": jax.random.normal(k[2], (h, inter), jnp.float32) * rng,
                "fc_b": jnp.zeros((inter,), jnp.float32),
                "proj_w": jax.random.normal(k[3], (inter, h),
                                            jnp.float32) * out_rng,
                "proj_b": jnp.zeros((h,), jnp.float32),
            },
        })
    if config.scan_layers:
        params["h"] = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *layers)
    else:
        for i, lp in enumerate(layers):
            params[f"h_{i}"] = lp
    return params


def layer_params(params, config: GPT2Config, i: int):
    """Block i's param pytree under either layout (``h_{i}`` keys, or the
    ``scan_layers`` stacked ``h``)."""
    if config.scan_layers:
        return jax.tree_util.tree_map(lambda a: a[i], params["h"])
    return params[f"h_{i}"]


def gpt2_param_specs(config: GPT2Config) -> Dict[str, Any]:
    """Megatron-style tensor-parallel shardings over the 'model' axis:
    column-parallel qkv/fc (shard output dim), row-parallel proj/ow (shard
    input dim); embeddings sharded over vocab."""
    layer = {
        "ln_1": {"w": P(), "b": P()},
        "attn": {"qkvw": P(None, "model"), "qkvb": P("model"),
                 "ow": P("model", None), "ob": P()},
        "ln_2": {"w": P(), "b": P()},
        "mlp": {"fc_w": P(None, "model"), "fc_b": P("model"),
                "proj_w": P("model", None), "proj_b": P()},
    }
    specs: Dict[str, Any] = {
        "wte": P("model", None),
        "wpe": P(),
        "ln_f": {"w": P(), "b": P()},
    }
    if config.scan_layers:
        # stacked layout: same shardings with an unsharded leading L dim
        specs["h"] = jax.tree_util.tree_map(
            lambda p: P(None, *p), layer,
            is_leaf=lambda x: isinstance(x, P))
    else:
        for i in range(config.num_layers):
            specs[f"h_{i}"] = layer
    return specs


from deepspeed_tpu.ops.functional import dropout as _dropout
from deepspeed_tpu.ops.functional import layer_norm as _ln_wb


def _layer_norm(x, p, eps):
    with scope("ln"):
        return _ln_wb(x, p["w"], p["b"], eps)


def cast_weight(leaf, dtype):
    """Weight at its use site: int8-resident leaves (serving under
    ``inference.quantize_weights: "int8"`` — runtime/quantized_params)
    dequantize per block RIGHT HERE, inside the compiled program, so
    the resident HBM copy stays int8; dense leaves just cast. The
    isinstance test is trace-time — training trees never carry
    QuantizedParam leaves, so the training path compiles unchanged."""
    from deepspeed_tpu.runtime.quantized_params import (QuantizedParam,
                                                        dequantize_param)
    with scope("weight_cast"):
        if isinstance(leaf, QuantizedParam):
            return dequantize_param(leaf, dtype)
        return leaf.astype(dtype)


def embedding_rows(leaf, ids, dtype):
    """Embedding-table row gather for dense or int8-resident tables:
    quantized tables gather the int8 rows AND their per-block scales,
    dequantizing only the gathered rows — the full-vocab table is never
    materialized at the model dtype."""
    from deepspeed_tpu.runtime.quantized_params import QuantizedParam
    if isinstance(leaf, QuantizedParam):
        q = leaf.q[ids]
        s = jnp.repeat(leaf.scale[ids], leaf.block, axis=-1)
        return (q.astype(jnp.float32) * s[..., :q.shape[-1]]
                ).astype(dtype)
    return leaf[ids].astype(dtype)


def _embed(wte, wpe, ids, dtype):
    """Token + position embedding (shared by flat and pipelined forms)."""
    from deepspeed_tpu.runtime.quantized_params import QuantizedParam
    pos = jnp.arange(ids.shape[1])[None, :]
    with scope("embed"):
        if isinstance(wte, QuantizedParam) or \
                isinstance(wpe, QuantizedParam):
            return (embedding_rows(wte, ids, jnp.float32)
                    + embedding_rows(wpe, pos, jnp.float32)).astype(dtype)
        return (wte[ids] + wpe[pos]).astype(dtype)


def tied_logits(x, wte, dtype):
    """LM head tied to the embedding: bf16 operands, fp32 accumulation —
    keeps the vocab GEMM on the MXU's fast path while the downstream
    softmax stays fp32."""
    with scope("lm_head"):
        return jax.lax.dot_general(
            x.astype(dtype), cast_weight(wte, dtype),
            (((2,), (1,)), ((), ())), preferred_element_type=jnp.float32)


def tied_xent_chunked(x, wte, targets, dtype, chunk_tokens: int = 2048,
                       mean: bool = True, weights=None):
    """Fused tied-LM-head + next-token cross entropy, chunked over tokens.

    The naive path materializes fp32 logits (B·S, V) plus a log_softmax
    copy — multi-GB of HBM traffic at V≈50k that makes the step
    bandwidth-bound (and *worse* at larger batch). Here the head GEMM +
    logsumexp run per token-chunk under ``jax.checkpoint``: peak extra
    memory is one (chunk, V) fp32 tile and the backward recomputes it —
    ~10% more MXU flops for a large cut in HBM traffic. The scan carries
    only the scalar loss.
    """
    with scope("loss_head"):
        return _tied_xent_scan(x, wte, targets, dtype, chunk_tokens, mean,
                               weights)


def _tied_xent_scan(x, wte, targets, dtype, chunk_tokens, mean, weights):
    B, S, H = x.shape
    n = B * S
    xf = x.reshape(n, H)
    tf = targets.reshape(n)
    c = min(chunk_tokens, n)
    # pad to a multiple of c (weight-masked) rather than shrinking the
    # chunk — a prime n would otherwise degrade to c=1 and a scan of
    # thousands of single-token GEMMs. ``weights``: optional per-token
    # loss weights (e.g. 0 for positions past a ragged sequence end)
    pad = (-n) % c
    wf = (jnp.ones((n,), jnp.float32) if weights is None
          else weights.reshape(n).astype(jnp.float32))
    if pad:
        xf = jnp.concatenate([xf, jnp.zeros((pad, H), xf.dtype)])
        tf = jnp.concatenate([tf, jnp.zeros((pad,), tf.dtype)])
        wf = jnp.concatenate([wf, jnp.zeros((pad,), jnp.float32)])
    m = (n + pad) // c
    wte_d = wte.astype(dtype)

    def body(xs_c, ts_c, ws_c):
        logits = jax.lax.dot_general(
            xs_c.astype(dtype), wte_d, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # (c, V) fp32
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, ts_c[:, None], axis=-1)[:, 0]
        return ((lse - picked) * ws_c).sum()

    body = jax.checkpoint(body)

    def scan_body(acc, inp):
        xs_c, ts_c, ws_c = inp
        return acc + body(xs_c, ts_c, ws_c), None

    total, _ = jax.lax.scan(
        scan_body, jnp.zeros((), jnp.float32),
        (xf.reshape(m, c, H), tf.reshape(m, c), wf.reshape(m, c)))
    return total / n if mean else total


def gpt2_block(block_params, config: GPT2Config, x, rng, deterministic,
               dtype, attention_fn=None, mlp_fn=None):
    """One pre-LN transformer block. ``attention_fn(q, k, v, rate, rng)``
    optionally replaces causal flash attention (e.g. ring attention for
    sequence parallelism). ``mlp_fn(mlp_params, m_in) -> (m_out, aux)``
    optionally replaces the dense MLP (e.g. a MoE FFN) — the block then
    returns ``(x, aux)`` instead of ``x``."""
    B, S, h = x.shape
    heads = config.num_heads
    hd = h // heads
    if rng is not None:
        r1, r2 = jax.random.split(rng)
    else:
        r1 = r2 = None

    # attention (pre-LN)
    a_in = _layer_norm(x, block_params["ln_1"], config.layer_norm_eps)
    ap = block_params["attn"]
    with scope("attn_proj"):
        qkv = a_in @ cast_weight(ap["qkvw"], dtype) + cast_weight(
            ap["qkvb"], dtype)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(B, S, heads, hd).transpose(0, 2, 1, 3)
        k = k.reshape(B, S, heads, hd).transpose(0, 2, 1, 3)
        v = v.reshape(B, S, heads, hd).transpose(0, 2, 1, 3)
    drop = (config.attn_dropout
            if not deterministic and rng is not None else 0.0)
    if drop > 0.0:
        r1, r_attn = jax.random.split(r1)
    else:
        r_attn = None
    # the cached attention_fns open their own scopes inside this one
    # (kv_write, kv_gather, attn_cached): innermost wins
    with scope("attn_core"):
        if attention_fn is not None:
            ctx = attention_fn(q, k, v, drop, r_attn)
        elif drop > 0.0:
            # attention dropout runs inside the Pallas kernel
            # (counter-based hash mask regenerated in fwd and bwd — no
            # (S, S) mask in HBM)
            ctx = flash_attention(q, k, v, causal=True, dropout_rate=drop,
                                  dropout_rng=r_attn)
        else:
            ctx = flash_attention(q, k, v, causal=True)
    with scope("attn_proj"):
        ctx = ctx.transpose(0, 2, 1, 3).reshape(B, S, h)
        attn_out = ctx @ cast_weight(ap["ow"], dtype) + cast_weight(
            ap["ob"], dtype)
    x = x + _dropout(attn_out, config.resid_dropout, r1, deterministic)

    # mlp
    m_in = _layer_norm(x, block_params["ln_2"], config.layer_norm_eps)
    mp = block_params["mlp"]
    if mlp_fn is not None:
        with scope("mlp"):
            m_out, aux = mlp_fn(mp, m_in)
        x = x + _dropout(m_out.astype(dtype), config.resid_dropout, r2,
                         deterministic)
        return x, aux
    with scope("mlp"):
        hmid = m_in @ cast_weight(mp["fc_w"], dtype) + cast_weight(
            mp["fc_b"], dtype)
        hmid = jax.nn.gelu(hmid, approximate=True)
        m_out = hmid @ cast_weight(mp["proj_w"], dtype) + cast_weight(
            mp["proj_b"], dtype)
    x = x + _dropout(m_out, config.resid_dropout, r2, deterministic)
    return x


def _gpt2_trunk(params, config: GPT2Config, input_ids, rng=None,
                deterministic: bool = True, dtype=jnp.bfloat16,
                remat: bool = False, mlp_fns=None):
    """Final hidden states (B, S, H) after ln_f (no LM head).

    ``mlp_fns``: optional {layer_index: mlp_fn} replacing that block's
    dense MLP (e.g. MoE); when given, returns ``(x, aux_loss_total)``."""
    x = _embed(params["wte"], params["wpe"], input_ids, dtype)
    if rng is not None:
        rng, r_emb = jax.random.split(rng)
        x = _dropout(x, config.embd_dropout, r_emb, deterministic)

    block = gpt2_block
    if remat:
        # attention_fn/mlp_fn are callables -> static under checkpoint
        block = jax.checkpoint(gpt2_block,
                               static_argnums=(1, 4, 5, 6, 7))
    aux_total = jnp.zeros((), jnp.float32)
    if config.scan_layers:
        assert mlp_fns is None, \
            "scan_layers supports the homogeneous dense family only"
        # one compiled block, scanned over the stacked layer params
        if rng is not None:
            layer_rngs = jax.random.split(rng, config.num_layers)

            def body(x, inp):
                lp, r = inp
                return block(lp, config, x, r, deterministic,
                             dtype, None, None), None
            x, _ = jax.lax.scan(body, x, (params["h"], layer_rngs))
        else:
            def body(x, lp):
                return block(lp, config, x, None, deterministic,
                             dtype, None, None), None
            x, _ = jax.lax.scan(body, x, params["h"])
    else:
        for i in range(config.num_layers):
            if rng is not None:
                rng, r = jax.random.split(rng)
            else:
                r = None
            mlp_fn = None if mlp_fns is None else mlp_fns.get(i)
            if mlp_fn is not None:
                x, aux = block(params[f"h_{i}"], config, x, r, deterministic,
                               dtype, None, mlp_fn)
                aux_total = aux_total + aux
            else:
                x = block(params[f"h_{i}"], config, x, r, deterministic,
                          dtype, None, None)

    x = _layer_norm(x, params["ln_f"], config.layer_norm_eps)
    if mlp_fns is not None:
        return x, aux_total
    return x


def _gpt2_trunk_cached(params, config: GPT2Config, input_ids, kv_cache,
                       cache_position, dtype, block_tables=None,
                       paged_attn_kernel: str = "gather"):
    """Cache-carrying trunk: run ``input_ids`` (B, S) through the SAME
    gpt2_block as training with attention over the provided KV cache
    (``kv_cache = (kc, vc)``, each (layers, B, heads, max_len, hd)),
    writing this call's K/V at each row's ``cache_position`` offset.
    Returns (final hidden states after ln_f, updated kv_cache). Serves
    prefill (S = padded prompt, cache_position = 0) and decode (S = 1,
    per-slot positions) with one code path — no second copy of the
    block math to drift.

    With ``block_tables`` ((B, pages_per_seq) int32) the cache is the
    PAGED pool pair (each (layers, num_pages, page_size, heads * hd):
    one token a row, heads major within it) and attention runs the
    paged path (:func:`_paged_cache_attention`) — same block, same
    mask; ``paged_attn_kernel`` picks the fused Pallas decode kernel
    ("pallas") or the gather oracle ("gather") for seq-1 queries. An
    int8-quantized pool arrives as the 4-tuple
    ``(kc, vc, kscale, vscale)`` (scale pools
    (layers, num_pages, page_size, heads * nb) fp32) — writes quantize
    per token row, reads dequantize at the attention site."""
    B, S = input_ids.shape
    pos = cache_position[:, None] + jnp.arange(S)[None, :]
    with scope("embed"):
        x = (embedding_rows(params["wte"], input_ids, jnp.float32)
             + embedding_rows(params["wpe"], pos, jnp.float32)).astype(dtype)
    if block_tables is not None:
        # the stacked pool is carried through the layers and written in
        # place at [layer, page, offset]: no layer is sliced out of it
        # and nothing is stacked back (ISSUE 28)
        index = paged_write_index(block_tables, cache_position, S,
                                  kv_cache[0].shape[2])
        for i in range(config.num_layers):
            box = []
            attn = _paged_cache_attention(
                kv_cache, i, block_tables, cache_position, index, box,
                attn_kernel=paged_attn_kernel)
            x = gpt2_block(layer_params(params, config, i), config, x,
                           None, True, dtype, attention_fn=attn)
            kv_cache = box[0]
    else:
        new_caches = []
        for i in range(config.num_layers):
            box = []
            with scope("kv_write"):    # the layer's slice of the cache
                kc, vc = (leaf[i] for leaf in kv_cache)
            attn = _offset_cache_attention(kc, vc, cache_position, box)
            x = gpt2_block(layer_params(params, config, i), config, x,
                           None, True, dtype, attention_fn=attn)
            new_caches.append(box[0])
        with scope("kv_write"):        # the layers stacked back
            kv_cache = tuple(jnp.stack(leaf) for leaf in zip(*new_caches))
    return _layer_norm(x, params["ln_f"], config.layer_norm_eps), kv_cache


def gpt2_forward(params, config: GPT2Config, input_ids, rng=None,
                 deterministic: bool = True, dtype=jnp.bfloat16,
                 remat: bool = False, kv_cache=None, cache_position=None,
                 block_tables=None, paged_attn_kernel: str = "gather"):
    """Logits (B, S, vocab). Embedding output layer is tied to wte.

    KV-cache mode (serving): with ``kv_cache=(kc, vc)`` (each
    ``(layers, B, heads, max_len, hd)``) and ``cache_position`` ((B,)
    int32 — tokens already in each row's cache), the forward writes this
    call's K/V into the cache at each row's offset, attends with
    :func:`causal_cache_mask`, and returns ``(logits, updated_cache)``
    instead of bare logits. ``block_tables`` ((B, pages_per_seq) int32)
    switches the cache interpretation to the paged pool pair (each
    ``(layers, num_pages, page_size, heads * hd)``);
    ``paged_attn_kernel="pallas"`` routes seq-1 queries through the
    fused Pallas paged-decode kernel instead of the stripe gather. The
    training call signature is unchanged (the serving arguments all
    default off)."""
    if kv_cache is not None:
        if cache_position is None:
            cache_position = jnp.zeros((input_ids.shape[0],), jnp.int32)
        x, cache = _gpt2_trunk_cached(params, config, input_ids, kv_cache,
                                      cache_position, dtype,
                                      block_tables=block_tables,
                                      paged_attn_kernel=paged_attn_kernel)
        return tied_logits(x, params["wte"], dtype), cache
    x = _gpt2_trunk(params, config, input_ids, rng=rng,
                    deterministic=deterministic, dtype=dtype, remat=remat)
    return tied_logits(x, params["wte"], dtype)


def gpt2_loss_fn(config: GPT2Config, dtype=jnp.bfloat16, remat: bool = False,
                 deterministic: bool = False):
    """Engine-contract loss: batch = {'input_ids': (B, S+1) int32} —
    next-token cross entropy on shifted ids."""
    def loss_fn(params, batch, rng):
        ids = batch["input_ids"]
        inputs, targets = ids[:, :-1], ids[:, 1:]
        # run the trunk, then the fused chunked head+loss (skips the full
        # (B,S,V) fp32 logits materialization of gpt2_forward)
        x = _gpt2_trunk(params, config, inputs, rng=rng,
                        deterministic=deterministic, dtype=dtype,
                        remat=remat)
        return tied_xent_chunked(x, params["wte"], targets, dtype)
    return loss_fn


# --------------------------------------------------------------------- #
# generation (KV-cache decode) — beyond-reference extension: the v0.3.0
# snapshot is training-only; sampling here is the natural flip side of
# the GPT-2 family. TPU-first shape discipline: the cache is a static
# (B, heads, max_len, hd) buffer per layer, prefill is ONE full forward
# (flash attention) that also writes the cache, and decode is a
# lax.scan over positions — a single compiled step per token, no
# Python-loop retracing, no dynamic shapes. Both phases run the SAME
# gpt2_block as training, with the attention swapped via its
# attention_fn hook (prefill captures K/V; decode attends to the cache)
# — no second copy of the block math to drift.
# --------------------------------------------------------------------- #
def make_token_sampler(vocab_size: int, temperature: float, top_k: int,
                       greedy: bool):
    """Shared decode-step sampler (gpt2_generate / llama_generate): greedy
    argmax, or temperature + optional top-k filtering + categorical. One
    home so sampling semantics cannot drift between model families."""
    eff_k = min(top_k, vocab_size)

    def sample(logits, key):
        if greedy:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        t = logits / jnp.maximum(temperature, 1e-6)
        if eff_k > 0:
            kth = jax.lax.top_k(t, eff_k)[0][:, -1][:, None]
            t = jnp.where(t < kth, NEG_INF, t)
        return jax.random.categorical(key, t, axis=-1).astype(jnp.int32)
    return sample


def run_decode_scan(step_logits, sample, first_tok, caches,
                    max_new_tokens, rng):
    """Shared decode loop (gpt2_generate / llama_generate): one
    ``lax.scan`` over ``step_logits(tok, t, caches) -> (logits, caches)``.
    Owns the carry shape, the max_new_tokens-1 step count (`first_tok`
    was already sampled from the prefill logits), and the
    ``[toks.T | last]`` assembly — one home so the off-by-one contract
    cannot drift between model families. Returns (B, max_new_tokens)."""
    def step(carry, t):
        tok, caches = carry
        logits, caches = step_logits(tok, t, caches)
        nxt = sample(logits, jax.random.fold_in(rng, t + 1))
        return (nxt, caches), tok

    (last, _), toks = jax.lax.scan(
        step, (first_tok, caches), jnp.arange(max_new_tokens - 1))
    return jnp.concatenate([toks.T, last[:, None]], axis=1)


def write_kv_cache(cache, new, cache_position):
    """Write ``new`` (B, heads, S, hd) into ``cache`` (B, heads, max_len,
    hd) starting at per-row position ``cache_position`` (B,) — a
    ``lax.dynamic_update_slice`` vmapped over the batch so every serving
    slot advances at its own offset (continuous batching: slots are at
    different sequence lengths)."""
    with scope("kv_write"):
        return jax.vmap(
            lambda c, u, s: jax.lax.dynamic_update_slice(c, u, (0, s, 0))
        )(cache, new.astype(cache.dtype), cache_position)


def _paged_cache_attention(pools, layer: int, block_table, cache_position,
                           index, out_box, attn_kernel: str = "gather"):
    """attention_fn for layer ``layer`` of the paged cached forward:
    :func:`paged_attend` with GPT-2's stripe math under the shared
    ``causal_cache_mask``."""
    def attn(q, k, v, rate, rng):
        del rate, rng                  # cached forward is deterministic
        return paged_attend(q, k, v, pools, layer, block_table,
                            cache_position, index, out_box, attn_kernel,
                            _stripe_attention)
    return attn


# rows of one sublane tile: with fewer query rows the TPU's compiler does
# not keep a dot a matmul (see _stripe_attention)
_MXU_QUERY_ROWS = 8


def _stripe_attention(q, kc, vc, cache_position):
    """Scores, offset-causal mask, softmax and context of ``q`` over a
    whole key/value stripe (B, heads, kv_len, hd); mask and softmax in
    float32, both dots accumulating in float32.

    The operands are chosen from what the inputs show, no option. A
    query of ``_MXU_QUERY_ROWS`` rows or more (every prefill and chunk
    program) and any float32 stripe (a float32 cache, an int8 pool
    after ``dequantize_pool``) contract float32 operands. Fewer rows
    over a narrower stripe (decode over a bf16 cache) XLA:TPU rewrites
    from a dot into a VPU multiply-and-reduce; the v5e's VPU has no
    bf16, so the whole stripe was first written out in float32, and
    that copy cost more than the attention (ISSUE 25). There the query
    is zero-PADDED to the tile's rows, the stripe goes to the MXU in
    the dtype it arrives in, and the padded rows are dropped after the
    context. Zero rows, not copies of the one row: the installed
    compiler keeps either as a matmul, but a dot of a broadcast is a
    broadcast of the one-row dot, an identity a simplifier may come to
    use; ``tests/unit/test_tpu_compile.py`` pins that no float32 stripe
    comes back. Products of two bf16 values are exact in float32, so
    these scores differ from the float32 operands' only by the order
    of the sum; the probabilities stay float32 and the context dot runs
    at ``Precision.HIGHEST`` so that they are not rounded to the
    stripe's dtype (as fast on the chip as casting them)."""
    with scope("attn_cached"):
        rows, hd, out_dtype = q.shape[2], q.shape[-1], q.dtype
        narrow = rows < _MXU_QUERY_ROWS and kc.dtype != jnp.float32
        if narrow:
            q = jnp.pad(q.astype(kc.dtype),
                        ((0, 0), (0, 0), (0, _MXU_QUERY_ROWS - rows), (0, 0)))
        else:
            q, kc = q.astype(jnp.float32), kc.astype(jnp.float32)
        scores = jnp.einsum("bhqd,bhld->bhql", q, kc,
                            preferred_element_type=jnp.float32) / np.sqrt(hd)
        mask = causal_cache_mask(cache_position, q.shape[2], kc.shape[2])
        scores = jnp.where(mask, scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        ctx = jnp.einsum(
            "bhql,bhld->bhqd", probs, vc.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST if narrow else None)
        return ctx[:, :, :rows].astype(out_dtype)


def _offset_cache_attention(kcache, vcache, cache_position, out_box):
    """attention_fn for the cached forward (prefill-into-cache and
    decode alike): write this call's K/V into the cache at each row's
    own offset, attend every query to all cache slots <= its absolute
    position (``causal_cache_mask``). Updated caches return through
    ``out_box`` (gpt2_block's hook only returns the context)."""
    def attn(q, k, v, rate, rng):
        del rate, rng                      # cached forward is deterministic
        kc = write_kv_cache(kcache, k, cache_position)
        vc = write_kv_cache(vcache, v, cache_position)
        out_box.append((kc, vc))
        return _stripe_attention(q, kc, vc, cache_position)
    return attn


def _cached_attention(kcache, vcache, pos, out_box):
    """Single-position decode hook (gpt2_generate's scan): every row
    writes/attends at the same scalar ``pos`` — the offset-cache
    attention with a broadcast position vector."""
    B = kcache.shape[0]
    return _offset_cache_attention(
        kcache, vcache, jnp.full((B,), pos, jnp.int32), out_box)


def gpt2_generate(params, config: GPT2Config, prompt_ids, max_new_tokens,
                  rng=None, temperature: float = 1.0, top_k: int = 0,
                  dtype=jnp.bfloat16):
    """Autoregressive sampling with a KV cache.

    prompt_ids: (B, P) int32. Returns (B, P + max_new_tokens) int32.
    temperature=0 (or rng=None) decodes greedily; top_k > 0 restricts
    sampling to the k most likely tokens. Dense GPT-2 family only (MoE
    params are rejected). The whole decode loop is one ``lax.scan`` —
    compile once, generate any prompt of length P.
    """
    B, P = prompt_ids.shape
    if max_new_tokens <= 0:
        return prompt_ids
    L = P + max_new_tokens
    assert L <= config.max_position_embeddings, (
        L, config.max_position_embeddings)
    if config.scan_layers:
        # stacked layout is structurally dense (init_gpt2_moe_params
        # rejects it); one key check, no per-layer slicing
        if "fc_w" not in params["h"]["mlp"]:
            raise ValueError("gpt2_generate supports the dense GPT-2 "
                             "family only")
    else:
        for i in range(config.num_layers):
            if "fc_w" not in params[f"h_{i}"]["mlp"]:
                raise ValueError(
                    "gpt2_generate supports the dense GPT-2 family only; "
                    f"block h_{i} carries MoE expert params")
    heads = config.num_heads
    hd = config.hidden_size // heads
    nl = config.num_layers
    greedy = rng is None or temperature == 0.0
    sample = make_token_sampler(config.vocab_size, temperature, top_k,
                                greedy)

    # ---- prefill: one full forward over the prompt through gpt2_block,
    # the attention hook capturing each layer's K/V into the cache
    x = _embed(params["wte"], params["wpe"], prompt_ids, dtype)
    kc = jnp.zeros((nl, B, heads, L, hd), dtype)
    vc = jnp.zeros((nl, B, heads, L, hd), dtype)
    captured = {}

    def capture_attn(i):
        def attn(q, k, v, rate, rng_):
            del rate, rng_
            captured[i] = (k, v)
            return flash_attention(q, k, v, causal=True)
        return attn

    for i in range(nl):
        x = gpt2_block(layer_params(params, config, i), config, x, None,
                       True, dtype, attention_fn=capture_attn(i))
        k, v = captured.pop(i)
        kc = kc.at[i, :, :, :P].set(k.astype(dtype))
        vc = vc.at[i, :, :, :P].set(v.astype(dtype))
    x = _layer_norm(x, params["ln_f"], config.layer_norm_eps)
    last_logits = tied_logits(x[:, -1:], params["wte"], dtype)[:, 0]

    if rng is None:
        rng = jax.random.PRNGKey(0)
    first_tok = sample(last_logits, jax.random.fold_in(rng, 0))

    def step_logits(tok, t, caches):
        kc, vc = caches
        pos = P + t                       # position of `tok` in the stream
        with scope("embed"):
            x = (params["wte"][tok[:, None]]
                 + params["wpe"][pos][None, None]).astype(dtype)
        new_kc, new_vc = [], []
        for i in range(nl):
            box = []
            x = gpt2_block(layer_params(params, config, i), config, x,
                           None, True, dtype,
                           attention_fn=_cached_attention(kc[i], vc[i],
                                                          pos, box))
            ki, vi = box[0]
            new_kc.append(ki)
            new_vc.append(vi)
        x = _layer_norm(x, params["ln_f"], config.layer_norm_eps)
        logits = tied_logits(x, params["wte"], dtype)[:, 0]
        return logits, (jnp.stack(new_kc), jnp.stack(new_vc))

    gen = run_decode_scan(step_logits, sample, first_tok, (kc, vc),
                          max_new_tokens, rng)
    return jnp.concatenate([prompt_ids, gen], axis=1)


def _is_moe_block(i: int, moe_every: int) -> bool:
    # blocks moe_every-1, 2*moe_every-1, ... — moe_every=1 means every
    # block; the single predicate keeps init and loss_fn in lockstep
    return i % moe_every == moe_every - 1


def init_gpt2_moe_params(config: GPT2Config, moe_config, key,
                         moe_every: int = 2):
    """GPT-2 params with the dense MLP of every ``moe_every``-th block
    (blocks moe_every-1, 2*moe_every-1, ...) replaced by a MoE expert
    bank; ``moe_every=1`` converts every block."""
    from deepspeed_tpu.ops.moe import init_moe_params
    assert not config.scan_layers, \
        "MoE blocks are heterogeneous; use the h_{i} layout"
    params = init_gpt2_params(config, key)
    for i in range(config.num_layers):
        if _is_moe_block(i, moe_every):
            key, km = jax.random.split(key)
            params[f"h_{i}"]["mlp"] = init_moe_params(moe_config, km)
    return params


def gpt2_moe_param_specs(config: GPT2Config, moe_every: int = 2):
    """PartitionSpecs for the MoE GPT-2: dense blocks keep the Megatron
    column/row TP specs; MoE blocks shard their expert banks over the
    ``expert`` mesh axis (true expert parallelism — each device OWNS
    E/ep experts' weights and optimizer state, it does not just
    constrain activations). Router stays replicated (tiny, every token
    needs it)."""
    specs = gpt2_param_specs(config)
    moe_mlp = {
        "router": P(),
        "wi": P("expert", None, None),
        "wo": P("expert", None, None),
    }
    for i in range(config.num_layers):
        if _is_moe_block(i, moe_every):
            specs[f"h_{i}"] = dict(specs[f"h_{i}"], mlp=moe_mlp)
    return specs


def gpt2_moe_loss_fn(config: GPT2Config, moe_config, mesh=None,
                     moe_every: int = 2, dtype=jnp.bfloat16,
                     remat: bool = False, deterministic: bool = False):
    """Engine-contract loss for a MoE GPT-2: next-token cross entropy plus
    the routers' load-balance/z aux losses. Blocks selected by
    ``_is_moe_block`` (moe_every=1 -> every block) carry a MoE FFN
    (params from :func:`init_gpt2_moe_params`); experts shard over the
    ``expert`` mesh axis when ``mesh`` has one.

    Beyond-reference extension (no MoE in the v0.3.0 snapshot): the
    sparse-FFN scaling axis on the same engine contract as the dense
    family."""
    from deepspeed_tpu.ops.moe import moe_layer

    expert_axis = ("expert" if mesh is not None
                   and "expert" in mesh.axis_names else None)

    def mlp_fn(mp, m_in):
        return moe_layer(mp, moe_config, m_in, expert_axis=expert_axis,
                         mesh=mesh, dtype=dtype)

    mlp_fns = {i: mlp_fn for i in range(config.num_layers)
               if _is_moe_block(i, moe_every)}

    def loss_fn(params, batch, rng):
        ids = batch["input_ids"]
        inputs, targets = ids[:, :-1], ids[:, 1:]
        x, aux_total = _gpt2_trunk(params, config, inputs, rng=rng,
                                   deterministic=deterministic,
                                   dtype=dtype, remat=remat,
                                   mlp_fns=mlp_fns)
        return (tied_xent_chunked(x, params["wte"], targets, dtype)
                + aux_total)
    return loss_fn


def gpt2_sp_loss_fn(config: GPT2Config, mesh, dtype=jnp.bfloat16,
                    remat: bool = False, deterministic: bool = False,
                    zigzag: bool = False):
    """Sequence-parallel (context-parallel) GPT-2 loss over the ``seq``
    mesh axis — long-context training beyond one chip's activation
    memory (a TPU-native extension past the reference's block-sparse
    answer; SURVEY §5 long-context).

    Every activation tensor lives sharded (B, S/P, H) on its sequence
    shard: embeddings, LN, and MLP are token-local; attention crosses
    shards through :func:`deepspeed_tpu.ops.attention.ring.ring_attention`
    (K/V rotating over ICI); the chunked tied-head loss sums per-shard
    and psums in fp32. Engine-contract: batch = {'input_ids': (B, S+1)}
    with S divisible by the seq-axis size; batch rows shard over 'data'
    if present.
    """
    from deepspeed_tpu.ops.attention.ring import ring_attention
    from deepspeed_tpu.parallel.mesh import axis_size
    if "seq" not in mesh.axis_names:
        raise ValueError("gpt2_sp_loss_fn requires a 'seq' mesh axis")
    assert not config.scan_layers, \
        "gpt2_sp_loss_fn uses the h_{i} layout (set scan_layers=False)"
    Pn = axis_size(mesh, "seq")
    manual = frozenset(a for a in ("seq", "data") if a in mesh.axis_names)

    def attention_fn(q, k, v, rate, rng):
        return ring_attention(q, k, v, axis_name="seq", causal=True,
                              dropout_rate=rate, dropout_rng=rng,
                              zigzag=zigzag)

    block = gpt2_block
    if remat:
        block = jax.checkpoint(gpt2_block, static_argnums=(1, 4, 5, 6))

    def per_device(params, batch, rng):
        idx = jax.lax.axis_index("seq")
        ids = batch["input_ids"]                   # (B_l, S+1) replicated
        S = ids.shape[1] - 1
        assert S % Pn == 0, (S, Pn)
        sl = S // Pn
        if zigzag:
            # load-balanced causal layout: this shard owns global chunks
            # (idx, 2P-1-idx) of 2P (ring.zigzag_layout_indices); all
            # token-local math is position-gathered, so only the window
            # selection changes
            lc = sl // 2
            starts = (idx * lc, (2 * Pn - 1 - idx) * lc)
            wins = [jax.lax.dynamic_slice_in_dim(ids, st, lc + 1, axis=1)
                    for st in starts]
            inputs = jnp.concatenate([w[:, :-1] for w in wins], axis=1)
            targets = jnp.concatenate([w[:, 1:] for w in wins], axis=1)
            pos_emb = jnp.concatenate(
                [jax.lax.dynamic_slice_in_dim(params["wpe"], st, lc,
                                              axis=0) for st in starts],
                axis=0)
        else:
            # this shard's token window [idx*sl, idx*sl+sl] (+1 targets)
            win = jax.lax.dynamic_slice_in_dim(ids, idx * sl, sl + 1,
                                               axis=1)
            inputs, targets = win[:, :-1], win[:, 1:]
            pos_emb = jax.lax.dynamic_slice_in_dim(params["wpe"],
                                                   idx * sl, sl, axis=0)
        with scope("embed"):
            x = (params["wte"][inputs] + pos_emb[None]).astype(dtype)
        if rng is not None and not deterministic:
            rng = jax.random.fold_in(rng, 0)
            rng, r_emb = jax.random.split(rng)
            # per-shard stream for the token-local dropouts
            x = _dropout(x, config.embd_dropout,
                         jax.random.fold_in(r_emb, idx), deterministic)
        for i in range(config.num_layers):
            if rng is not None and not deterministic:
                rng, r = jax.random.split(rng)
                r = jax.random.fold_in(r, idx)
            else:
                r = None
            x = block(params[f"h_{i}"], config, x, r, deterministic, dtype,
                      attention_fn)
        x = _layer_norm(x, params["ln_f"], config.layer_norm_eps)
        local = tied_xent_chunked(x, params["wte"], targets, dtype,
                                   mean=False)
        # fp32 psums only (bf16 psum trips the XLA partitioner when auto
        # axes share the mesh — see runtime/pipe/spmd._psum_act)
        total = jax.lax.psum(local.astype(jnp.float32), "seq")
        if "data" in manual:
            total = jax.lax.pmean(total, "data")
        B = ids.shape[0]
        return total / (B * S)

    PS = P
    def loss_fn(params, batch, rng):
        param_specs = jax.tree_util.tree_map(lambda _: PS(), params)
        batch_specs = jax.tree_util.tree_map(
            lambda _: PS("data") if "data" in manual else PS(), batch)
        return jax.shard_map(
            per_device, mesh=mesh,
            in_specs=(param_specs, batch_specs, PS()),
            out_specs=PS(), axis_names=manual,
            check_vma=False)(params, batch, rng)

    # fp32 master params flow in directly; every weight is cast at its use
    # site, so the shard_map-transposed gradient psums stay fp32 (the
    # engine skips its up-front cast — same policy as ZeRO stage 3)
    loss_fn.owns_cast = True
    return loss_fn


def count_params(params) -> int:
    return sum(int(np.prod(p.shape))
               for p in jax.tree_util.tree_leaves(params))


def gpt2_pipeline_spec(config: GPT2Config, num_stages: int,
                       dtype=None, deterministic: bool = True):
    """GPT-2 as a PipelineSpec for the compiled SPMD pipeline
    (runtime/pipe/spmd.py) — the 3D-parallel (pipe × data × model)
    flagship workload (BASELINE.md: GPT-2 1.5B 3D-parallel; reference ran
    it via PipelineModule + Megatron mpu).

    - pre: token+position embedding (stage-0 slot, wte/wpe replicated over
      'pipe', vocab-sharded over 'model');
    - stages: ``num_layers/num_stages`` blocks each, params stacked
      ``(S, L/S, ...)``, applied via ``lax.scan`` over the layer dim;
    - post: final LN + logits tied to wte (TiedLayerSpec semantics) +
      next-token cross entropy.

    Micro-batch contract: ``{"input_ids": (mb, seq+1) int32}``.

    ``dtype=None`` (default) inherits the engine's configured compute dtype
    — the pipeline loss fn casts params inside the mapped program
    (spmd.py ``compute_dtype``), and these fns read the dtype off the cast
    param leaves, so an fp16 config really computes fp16.
    """
    from deepspeed_tpu.runtime.pipe.spmd import PipelineSpec

    assert not config.scan_layers, \
        "the pipeline spec stage-stacks layers itself (scan_layers=False)"
    L = config.num_layers
    # uneven partitions supported: stages hold ceil(L/S) slots, short
    # stages pad with zero blocks masked out in stage_apply (data-masked,
    # never branched — reference parameters-balanced partitions,
    # module.py:348, composed with the SPMD uniformity invariant)
    lps = -(-L // num_stages)  # ceil
    stage_counts = [min(lps, max(0, L - s * lps))
                    for s in range(num_stages)]
    if min(stage_counts) <= 0:
        raise ValueError(f"num_layers {L} too few for {num_stages} stages "
                         f"(an entire stage would be empty)")
    even_stages = (L % num_stages == 0)

    def init(key):
        full = init_gpt2_params(config, key)
        per_stage = []
        zero_block = jax.tree_util.tree_map(jnp.zeros_like, full["h_0"])
        for s in range(num_stages):
            blocks = [full[f"h_{s * lps + j}"]
                      for j in range(stage_counts[s])]
            blocks += [zero_block] * (lps - stage_counts[s])
            per_stage.append(jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *blocks))
        stages = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *per_stage)
        return {"pre": {"wte": full["wte"], "wpe": full["wpe"]},
                "stages": stages,
                "post": {"ln_f": full["ln_f"]}}

    def _dtype_of(leaf):
        return dtype if dtype is not None else leaf.dtype

    def pre_apply(pre_p, micro, rng):
        ids = micro["input_ids"][:, :-1]
        x = _embed(pre_p["wte"], pre_p["wpe"], ids, _dtype_of(pre_p["wte"]))
        if not deterministic and rng is not None:
            x = _dropout(x, config.embd_dropout, rng, deterministic)
        return x

    counts_arr = jnp.asarray(stage_counts, jnp.int32)

    def stage_apply(st_p, act, rng):
        # st_p leaves: (lps, ...) — scan the layer dim; padded slots of an
        # uneven partition pass x through via where (uniform execution)
        cnt = None if even_stages else \
            counts_arr[jax.lax.axis_index("pipe")]
        def body(x, inp):
            j, lp = inp
            r = jax.random.fold_in(rng, j) if rng is not None else None
            y = gpt2_block(lp, config, x, r, deterministic, _dtype_of(act))
            if cnt is not None:
                y = jnp.where(j < cnt, y, x)
            return y, None
        out, _ = jax.lax.scan(body, act, (jnp.arange(lps), st_p))
        return out

    def post_apply(post_p, pre_p, act, micro):
        # fused chunked head+xent: never materializes the (mb, S, V) fp32
        # logits (the same head the non-pipelined loss uses; the naive
        # full-logits path is exactly what it exists to avoid)
        targets = micro["input_ids"][:, 1:]
        x = _layer_norm(act, post_p["ln_f"], config.layer_norm_eps)
        return tied_xent_chunked(x, pre_p["wte"], targets, _dtype_of(act))

    def post_shard_apply(post_p, pre_p, act_slice, micro, start):
        # sequence-chunk of the head for the cooperative pipeline head
        # (spmd.py): positions [start, start+len) of the micro-batch;
        # per-token xent decomposes, so a SUM over the slice is exact.
        # Targets come via static shift + one-hot block select — a traced
        # `start` dynamic_slice here trips the XLA partitioner under auto
        # mesh axes (see spmd.seq_chunk_select). Ragged sequences
        # (seq %% S != 0): the executor pads the exit activation to
        # S*ceil(seq/S); targets pad with zeros and the pad positions are
        # weight-masked out of the loss.
        from deepspeed_tpu.runtime.pipe.spmd import seq_chunk_select
        length = act_slice.shape[1]
        shifted = micro["input_ids"][:, 1:]            # (mb, seq) next-token
        seq = shifted.shape[1]
        S = -(-seq // length)
        weights = None
        if S * length != seq:
            shifted = jnp.pad(shifted,
                              ((0, 0), (0, S * length - seq)))
            j = jax.lax.iota(jnp.int32, length)
            weights = jnp.broadcast_to(
                (start + j < seq)[None, :].astype(jnp.float32),
                act_slice.shape[:2])
        targets = seq_chunk_select(shifted, start // length, S, axis=1)
        x = _layer_norm(act_slice, post_p["ln_f"], config.layer_norm_eps)
        return tied_xent_chunked(x, pre_p["wte"], targets,
                                  _dtype_of(act_slice), mean=False,
                                  weights=weights)

    block_specs = gpt2_param_specs(config)["h_0"]
    # stacked stage leaves carry (lps, ...) — shift TP specs right one dim
    stage_specs = jax.tree_util.tree_map(
        lambda s: P(None, *tuple(s)), block_specs,
        is_leaf=lambda x: isinstance(x, P))

    return PipelineSpec(
        init=init, pre_apply=pre_apply, stage_apply=stage_apply,
        post_apply=post_apply, num_stages=num_stages,
        pre_specs={"wte": P("model", None), "wpe": P()},
        stage_specs=stage_specs,
        post_specs={"ln_f": {"w": P(), "b": P()}},
        post_shard_apply=post_shard_apply)
