"""Keye-VL-2.0-style sparse-attention decoder, SERVED: grouped-query
attention whose queries and keys are normed a head and rotated, read
over a LEARNED SELECTION of the tokens a query may see, then routed
SwiGLU experts behind a softmax router with no shared expert
(Kwai-Keye/Keye-VL-2.0-30B-A3B, config.json ``model_type`` ``KeyeVL2``;
docs/keye_vl2.md has the equations and what the config leaves open).
The language trunk only: the vision tower hands it embeddings and is
not here.

Every layer, on the residual stream ``x`` (``h = RMSNorm(x)``):

- ``q, k, v = h W_q, h W_k, h W_v``; ``q`` and ``k`` through an RMSNorm
  a head, THEN rotated (half-split pairs, ``rope_theta``; the 64
  frequencies split by ``mrope_section`` over three position streams,
  time, height and width, which coincide for text:
  :func:`rotate_mrope`).
- the INDEXER: ``qI = h W_qI`` (``indexer_num_heads`` of
  ``indexer_head_dim``), ``kI = LayerNorm(h W_kI)`` (ONE head), both
  rotated over their whole width at the time stream, ``w = h W_w`` (a
  value a head); ``I(t, s) = sum_j w[t, j] heads^-1/2 dim^-1/2
  ReLU(qI[t, j] . kI[s])`` for ``s <= t``; ``S_t`` = the
  ``indexer_topk`` positions of largest ``I(t, .)`` (every position
  while ``t + 1 <= indexer_topk``; ties to the lower position). One
  set a token a layer, shared by the heads.
- ``o_t = softmax over s in S_t of (q_t . k_s head_dim^-1/2) v_s``, a
  key-value head serving ``num_heads / num_kv_heads`` query heads;
  ``x += o W_o``.
- then, on ``h2 = RMSNorm(x)``: ``x += sum_top w_e E_e(h2)`` with ``p =
  softmax(h2 W_r)`` in float32, the ``experts_per_token`` largest,
  their weights divided by their sum (``ops/moe.route_top_k``).

After the last layer an RMSNorm, then the head (untied). The cache tree
is ``inference/kv_cache.IndexedPairCache``: ``keys`` and ``values``
(layers, pages, page_size, kv_heads x head_dim) and ``index_keys``
(layers, pages, page_size / 2, 2 x indexer_head_dim): ``kI`` after norm
and rotation, ONE a token a layer, two tokens a pool row. The config carries the chip's SHARE of
a layer as the other served families' do (``experts_held``,
``vocab_held``).

Two programs, and the family is served in CHUNKS
(``inference.chunked_prefill``): a PREFILL row may start at any
``cache_position``: its queries score and attend the rows earlier
chunks left in the pools, read back through the block table a block at
a time, and its own (``ops/attention/indexed.chunk_attention``).
DECODE scores every live indexer key of a row, selects on the device
and reads the chosen rows by (page, offset)
(``indexed.decode_attention``). A context of at most ``indexer_topk``
selects everything by the same code.
"""

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.lfm2 import rotate_half_split
from deepspeed_tpu.models.served_trunk import (ServedFamily, _mm,
                                               served_forward,
                                               whole_leaf_specs)
from deepspeed_tpu.ops.attention import indexed
from deepspeed_tpu.ops.attention.page_pool import (prefix_block_rows,
                                                   write_paged_kv_cache)
from deepspeed_tpu.ops.functional import rms_norm
from deepspeed_tpu.ops.moe import route_top_k
from deepspeed_tpu.profiling.spans import scope

# caps of a chunk's grouped products' tile at these experts' widths
# (2,048 x 768), cut to whole divisors: 128 rows (a chunk of 2,048
# tokens lands 128 rows on a held expert in the mean, and a tile a group
# touches is worked whole)
_EXPERT_TILE = (128, 2048, 768)
_LAYER_NORM_EPS = 1e-6


class KeyeVL2Config(NamedTuple):
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_layers: int = 48
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    moe_intermediate_size: int = 768
    num_experts: int = 128
    experts_per_token: int = 8
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000000.0
    mrope_section: Tuple[int, ...] = (16, 24, 24)
    indexer_num_heads: int = 16
    indexer_head_dim: int = 64
    indexer_topk: int = 2048
    max_position_embeddings: int = 262144
    initializer_range: float = 0.02
    # SEEDED trees only (a benchmark's choice, stated in its
    # configuration file: docs/keye_vl2.md Seeding): the spread of the
    # embedding table against ``initializer_range`` (a token's own
    # embedding is most of a trained model's early stream; where it is a
    # small part, one key in or out of a selection moves the next
    # layer's scores and the selections of a bfloat16 engine drift from
    # a float32 one's, layer on layer); trained weights carry their own
    embed_init_gain: float = 1.0
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
    def kv_cache_layers(self):
        return self.num_layers

    @property
    def indexer_geometry(self):
        """What a token holds beside its keys and values, for
        ``kv_cache.paged_spec_for``: (the indexer key's lanes, the
        positions a query selects)."""
        return (self.indexer_head_dim, self.indexer_topk)

    @property
    def expert_counters(self):
        """As ``SolarOpen2Config.expert_counters``."""
        return (self.experts_per_token * self.num_layers, self.held[1])

    # the mixer follows a chunk (``served_trunk._Call.carry``), so
    # ``inference/engine.py`` does not refuse chunked prefill
    serves_chunked_prefill = property(lambda self: True)


def init_keye_vl2_params(config: KeyeVL2Config, key,
                         dtype=jnp.bfloat16) -> Dict[str, Any]:
    """The tree, matrices HELD in ``dtype``, the router and the norms in
    float32: ``tok_emb``, ``lm_head`` (rows held, H), ``ln_f``,
    ``h_<l>`` with ``ln_1``, ``ln_2``, ``attn`` {wq, wk, wv, q_norm,
    k_norm (head_dim,), wo}, ``indexer`` {wq (H, heads x dim), wk (H,
    dim), ww (H, heads), k_norm {w, b} (dim,)}, ``router`` (H, experts),
    ``experts`` {(held, H, F) x 2, (held, F, H)}. NO ``shared`` leaf.
    Normal with ``initializer_range`` for a matrix that reads a normed
    input (the embedding table times ``embed_init_gain``), that over
    sqrt(2 x layers) for one that writes to the stream; every norm's
    weights ones."""
    h, hd = config.hidden_size, config.head_dim
    nq, nkv = config.num_heads * hd, config.num_kv_heads * hd
    ih, idim = config.indexer_num_heads, config.indexer_head_dim
    f, held, rows = (config.moe_intermediate_size, config.held[1],
                     config.vocab_rows)
    std = config.initializer_range
    out_std = std / np.sqrt(2.0 * config.num_layers)

    def normal(k, shape, s, dt=dtype):
        return (jax.random.normal(k, shape, jnp.float32) * s).astype(dt)

    ones = lambda n: jnp.ones((n,), jnp.float32)
    keys = jax.random.split(key, 2 + config.num_layers)
    params: Dict[str, Any] = {
        "tok_emb": normal(keys[0], (rows, h), std * config.embed_init_gain),
        "lm_head": normal(keys[1], (rows, h), std),
        "ln_f": {"w": ones(h)}}
    for l in range(config.num_layers):
        k = jax.random.split(keys[2 + l], 11)
        ek = jax.random.split(k[10], 3)
        params[f"h_{l}"] = {
            "ln_1": {"w": ones(h)}, "ln_2": {"w": ones(h)},
            "attn": {"wq": normal(k[0], (h, nq), std),
                     "wk": normal(k[1], (h, nkv), std),
                     "wv": normal(k[2], (h, nkv), std),
                     "q_norm": ones(hd), "k_norm": ones(hd),
                     "wo": normal(k[5], (nq, h), out_std)},
            "indexer": {"wq": normal(k[6], (h, ih * idim), std),
                        "wk": normal(k[7], (h, idim), std),
                        "ww": normal(k[8], (h, ih), std),
                        "k_norm": {"w": ones(idim),
                                   "b": jnp.zeros((idim,), jnp.float32)}},
            "router": normal(k[9], (h, config.num_experts), std,
                             jnp.float32),
            "experts": {"w_gate": normal(ek[0], (held, h, f), std),
                        "w_up": normal(ek[1], (held, h, f), std),
                        "w_down": normal(ek[2], (held, f, h), out_std)}}
    return params


def keye_vl2_param_specs(config: KeyeVL2Config):
    """Every leaf whole (``served_trunk.whole_leaf_specs``)."""
    return whole_leaf_specs(init_keye_vl2_params, config)


def rotate_mrope(x, streams, theta: float, sections):
    """``x`` (B, heads, S, hd) float32 rotated in half-split pairs, the
    pair (i, i + hd / 2) by ``p_i * theta^(-2 i / hd)`` where ``p_i`` is
    the position in the stream frequency ``i`` belongs to: ``streams``
    (3, B, S) positions (time, height, width) and ``sections`` how many
    consecutive frequencies each takes (they add up to hd / 2). With
    three equal streams this IS ``models/lfm2.rotate_half_split``, bit
    for bit."""
    half = x.shape[-1] // 2
    if sum(sections) != half or len(sections) != streams.shape[0]:
        raise ValueError(f"mrope_section {tuple(sections)} has to split "
                         f"{half} frequencies over {streams.shape[0]} "
                         f"position streams")
    inv = theta ** (-np.arange(half, dtype=np.float32) / half)
    of = np.repeat(np.arange(len(sections)), sections)        # (half,)
    # (B, S, half): each frequency's own stream
    position = jnp.moveaxis(streams.astype(jnp.float32), 0, -1)[..., of]
    angle = position[:, None] * inv
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer_norm(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + _LAYER_NORM_EPS) * p["w"] + p["b"]


def _sparse_mixer(config: KeyeVL2Config, streams, probe):
    """The family's one mixer (``served_trunk.ServedFamily.mixers``).
    ``streams`` (3, B, S): the plain forward's position streams (None:
    the token positions, thrice); ``probe`` (a list or None): receives
    each layer's selection in an eager plain forward."""
    H, hkv, hd = config.num_heads, config.num_kv_heads, config.head_dim
    ih, idim, topk = (config.indexer_num_heads, config.indexer_head_dim,
                      config.indexer_topk)
    eps, theta = config.rms_norm_eps, config.rope_theta
    sm_scale = hd ** -0.5
    w_scale = ih ** -0.5 * idim ** -0.5

    def mixer(lp, h, call, cache, n):
        ap, ip, dtype = lp["attn"], lp["indexer"], call.dtype
        B, S, _ = h.shape
        positions = call.token_positions
        three = streams if streams is not None else jnp.broadcast_to(
            positions, (3,) + positions.shape)
        heads = lambda t, m, d: t.reshape(B, S, m, d).transpose(0, 2, 1, 3)
        with scope("attn_proj"):
            q = heads(_mm(h, ap["wq"], dtype), H, hd)
            k = heads(_mm(h, ap["wk"], dtype), hkv, hd)
            v = heads(_mm(h, ap["wv"], dtype), hkv, hd).astype(dtype)
        with scope("attn_norm_rope"):
            # a head's norm, THEN its rotation, both float32
            turn = lambda t, w: rotate_mrope(
                rms_norm(t, w, eps), three, theta,
                config.mrope_section).astype(dtype)
            q, k = turn(q, ap["q_norm"]), turn(k, ap["k_norm"])
        with scope("indexer"):
            # the indexer rotates over its whole width at the time
            # stream; its key is held as the pool holds it
            qi = rotate_half_split(
                heads(_mm(h, ip["wq"], dtype), ih, idim), three[0],
                theta).transpose(0, 2, 1, 3).astype(dtype)
            ki = rotate_half_split(
                _layer_norm(_mm(h, ip["wk"], dtype), ip["k_norm"])[:, None],
                three[0], theta).astype(dtype)          # (B, 1, S, dim)
            wi = _mm(h, ip["ww"], dtype) * w_scale      # (B, S, ih) f32
        if cache is not None:
            # the three leaves share the index
            pools = write_paged_kv_cache((cache.keys, cache.values), n,
                                         (k, v), call.index)
            ipool = indexed.write_index_keys(cache.index_keys, n, ki,
                                             call.index)
            cache = cache._replace(keys=pools[0], values=pools[1],
                                   index_keys=ipool)
        if cache is not None and S == 1:
            ctx = indexed.decode_attention(
                q[:, :, 0], pools, ipool, n, call.tables, call.positions,
                qi[:, 0], wi[:, 0], topk, sm_scale, probe)[:, :, None]
        else:
            if cache is None:
                start = jnp.zeros((B,), jnp.int32)
                table_tokens = S
                none = lambda *shape: jnp.zeros(shape, dtype)
                prefix_keys = lambda j, rows: none(B, rows, idim)
                prefix_pair = lambda j, rows: (none(B, hkv, rows, hd),) * 2
            else:
                # a chunk: its own rows and the prefix earlier chunks
                # wrote, whole pages of the block table a loop turn
                start = call.positions
                ps = pools[0].shape[2]
                per = prefix_block_rows(call.tables.shape[1] * ps) // ps
                tables = jnp.pad(
                    call.tables, ((0, 0), (0, -call.tables.shape[1] % per)))
                table_tokens = tables.shape[1] * ps
                block = lambda j: jax.lax.dynamic_slice_in_dim(
                    tables, j * per, per, 1)
                prefix_keys = lambda j, rows: ipool[n, block(j)].reshape(
                    B, rows, idim)
                rows_of = lambda pool, pages, rows: pool[n, pages].reshape(
                    B, rows, hkv, hd).transpose(0, 2, 1, 3)
                prefix_pair = lambda j, rows: (
                    rows_of(pools[0], block(j), rows),
                    rows_of(pools[1], block(j), rows))
            ctx = indexed.chunk_attention(
                q, k, v, qi, wi, ki[:, 0], start, prefix_keys, prefix_pair,
                table_tokens, topk, sm_scale, probe)
        with scope("attn_proj"):
            ctx = ctx.transpose(0, 2, 1, 3).reshape(B, S, H * hd)
            return _mm(ctx, ap["wo"], dtype), cache

    return mixer


def _family(config: KeyeVL2Config, streams=None, probe=None) -> ServedFamily:
    def route(flat, router):
        idx, p, _ = route_top_k(flat, router, config.experts_per_token)
        return idx, p, None

    return ServedFamily(
        layers=(("sparse", "experts"),) * config.num_layers,
        mixers={"sparse": _sparse_mixer(config, streams, probe)},
        route=route, expert_tile=_EXPERT_TILE, token_positions=True,
        chunked=config.serves_chunked_prefill)


def keye_vl2_forward(params, config: KeyeVL2Config, input_ids,
                     dtype=jnp.bfloat16, kv_cache=None, cache_position=None,
                     block_tables=None, paged_attn_kernel: str = "gather",
                     lengths=None, slots=None, active=None,
                     with_counts=False, position_streams=None,
                     selected: Optional[list] = None):
    """Logits over the held rows of the vocabulary.

    Plain (``kv_cache=None``): (B, S) ids -> (B, S, rows) float32, every
    row from position 0. ``position_streams`` (3, B, S) int32 are the
    three rotary streams of a sequence with image positions (None:
    text, all three the token's position). ``selected`` (a list, an
    EAGER call only) receives each layer's selection mask (B, S, S).

    Serving (text): ``kv_cache`` a ``kv_cache.IndexedPairCache`` with
    ``block_tables`` and ``cache_position`` as the other families take
    them. PREFILL (S > 1) also takes ``lengths`` (B,), each row's true
    length (``slots`` is taken and read by nothing: the family keeps
    nothing a slot); a row may start at any ``cache_position`` (a later
    chunk of its prompt); returns logits at each row's LAST true
    position only, (B, 1, rows). DECODE (S == 1) takes ``active`` (B,)
    bool, the rows the experts' counters count. ``paged_attn_kernel``
    is taken and read by nothing: the family has ONE reader a call
    shape. Returns (logits, the cache); with ``with_counts`` also the
    expert layers' int32 counters as ``solar_open2_forward`` does."""
    if kv_cache is not None and position_streams is not None:
        raise ValueError("image positions are not served: the scheduler "
                         "carries one position a token")
    return served_forward(
        _family(config, position_streams, selected), params, config,
        input_ids, dtype, kv_cache, cache_position, block_tables,
        paged_attn_kernel, lengths, slots, active, with_counts)


def keye_vl2_param_count(config: KeyeVL2Config):
    """(attention with its two head norms, the indexer, the router, an
    expert, embedding + head + final norm + the layers' two norms
    each)."""
    h, hd = config.hidden_size, config.head_dim
    nq, nkv = config.num_heads * hd, config.num_kv_heads * hd
    ih, idim = config.indexer_num_heads, config.indexer_head_dim
    return (2 * h * nq + 2 * h * nkv + 2 * hd,
            h * ih * idim + h * idim + h * ih + 2 * idim,
            h * config.num_experts,
            3 * h * config.moe_intermediate_size,
            2 * config.vocab_rows * h + h + 2 * h * config.num_layers)
