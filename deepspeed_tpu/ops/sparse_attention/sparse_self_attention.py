"""Sparse self-attention modules on top of the unified Pallas kernel.

Parity targets (reference):
- SparseSelfAttention            deepspeed/ops/sparse_attention/sparse_self_attention.py:13
- BertSparseSelfAttention        deepspeed/ops/sparse_attention/bert_sparse_self_attention.py:9
- SparseAttentionUtils           deepspeed/ops/sparse_attention/sparse_attention_utils.py:13

Where the reference caches three Triton ops per sequence length
(sparse_self_attention.py:44 get_ops), ``block_sparse_attention`` caches
one ``BlockMask`` per layout for the ONE mask-parameterized flash kernel
(``ops/attention/masked_flash.py`` — the same kernel dense training
attention compiles); layout construction itself is cached here per seq
len.

Modules follow the repo's functional convention: configs are plain
objects, parameters are pytrees created by ``init_*_params``, forward
passes are pure functions.
"""

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.ops.sparse_attention.blocksparse import (
    block_sparse_attention)
from deepspeed_tpu.ops.sparse_attention.sparsity_config import (
    FixedSparsityConfig, SparsityConfig)


class SparseSelfAttention:
    """Applies block-sparse attention with a SparsityConfig-driven layout.

    forward(query, key, value, rpe=None, key_padding_mask=None,
    attn_mask=None) with q/k/v of shape (B, H, S, D), key_padding_mask
    (B, S), attn_mask (S, S) — mirroring sparse_self_attention.py:84-142
    (including scaling = head_dim ** -0.5 and the add/mul mask modes).
    """

    _layout_cache: Dict[Any, np.ndarray] = {}

    def __init__(self, sparsity_config: Optional[SparsityConfig] = None,
                 key_padding_mask_mode: str = "add",
                 attn_mask_mode: str = "mul"):
        self.sparsity_config = sparsity_config or FixedSparsityConfig(
            num_heads=4)
        self.key_padding_mask_mode = key_padding_mask_mode
        self.attn_mask_mode = attn_mask_mode

    def get_layout(self, seq_len: int) -> np.ndarray:
        key = self.sparsity_config.layout_cache_key() + (seq_len,)
        if key not in SparseSelfAttention._layout_cache:
            SparseSelfAttention._layout_cache[key] = \
                self.sparsity_config.make_layout(seq_len)
        return SparseSelfAttention._layout_cache[key]

    def __call__(self, query, key, value, rpe=None, key_padding_mask=None,
                 attn_mask=None, **kw):
        B, H, S, D = query.shape
        if query.shape != key.shape or key.shape != value.shape:
            raise NotImplementedError(
                "only self-attention (q/k/v same shape) is supported")
        layout = self.get_layout(S)
        return block_sparse_attention(
            query, key, value, layout,
            sm_scale=float(D) ** -0.5,
            key_padding_mask=key_padding_mask,
            key_padding_mask_mode=self.key_padding_mask_mode,
            attn_mask=attn_mask, attn_mask_mode=self.attn_mask_mode,
            rpe=rpe, **kw)

    forward = __call__


def init_bert_sparse_self_attention_params(hidden_size: int, key,
                                           initializer_range: float = 0.02
                                           ) -> Dict[str, Any]:
    """Q/K/V projection parameters for BertSparseSelfAttention
    (bert_sparse_self_attention.py:40-42's three nn.Linear layers)."""
    ks = jax.random.split(key, 3)
    def lin(k):
        return {"w": jax.random.normal(k, (hidden_size, hidden_size),
                                       jnp.float32) * initializer_range,
                "b": jnp.zeros((hidden_size,), jnp.float32)}
    return {"query": lin(ks[0]), "key": lin(ks[1]), "value": lin(ks[2])}


class BertSparseSelfAttention:
    """BERT-style self-attention block with a sparse core
    (bert_sparse_self_attention.py:9). ``config`` needs hidden_size and
    num_attention_heads (our BertConfig uses hidden_size/num_heads; both
    spellings accepted)."""

    def __init__(self, config,
                 sparsity_config: Optional[SparsityConfig] = None):
        hidden = config.hidden_size
        heads = getattr(config, "num_attention_heads",
                        getattr(config, "num_heads", None))
        if heads is None:
            raise ValueError(
                "config must define num_attention_heads (or num_heads)")
        if hidden % heads != 0:
            raise ValueError(
                f"hidden size {hidden} not a multiple of heads {heads}")
        self.num_attention_heads = heads
        self.attention_head_size = hidden // heads
        self.hidden_size = hidden
        self.sparse_self_attention = SparseSelfAttention(
            sparsity_config or FixedSparsityConfig(num_heads=heads))

    def init_params(self, key, initializer_range: float = 0.02):
        return init_bert_sparse_self_attention_params(
            self.hidden_size, key, initializer_range)

    def _split_heads(self, x):
        B, S, _ = x.shape
        return x.reshape(B, S, self.num_attention_heads,
                         self.attention_head_size).transpose(0, 2, 1, 3)

    def __call__(self, params, hidden_states, attention_mask=None):
        """hidden_states (B, S, H_total); attention_mask (B, S) with 1=keep
        (applied as key padding). Returns (B, S, H_total)."""
        dtype = hidden_states.dtype
        def proj(p):
            return hidden_states @ p["w"].astype(dtype) + \
                p["b"].astype(dtype)
        q = self._split_heads(proj(params["query"]))
        k = self._split_heads(proj(params["key"]))
        v = self._split_heads(proj(params["value"]))
        ctx = self.sparse_self_attention(
            q, k, v, key_padding_mask=attention_mask)
        B, H, S, D = ctx.shape
        return ctx.transpose(0, 2, 1, 3).reshape(B, S, H * D)


class SparseAttentionUtils:
    """Helpers to adapt models/inputs to block-sparse attention
    (sparse_attention_utils.py:13) — re-targeted at this repo's functional
    param pytrees instead of torch module surgery."""

    @staticmethod
    def extend_position_embedding(params: Dict[str, Any],
                                  max_position: int) -> Dict[str, Any]:
        """Tile an existing position-embedding table up to max_position
        (sparse_attention_utils.py:19's weight-copy loop, functionally).
        Expects params['pos_emb'] of shape (P, H)."""
        pos = params["pos_emb"]
        original, h = pos.shape
        if max_position <= original:
            raise ValueError(
                f"max_position {max_position} must exceed current table "
                f"size {original}")
        reps = -(-max_position // original)
        new = jnp.tile(pos, (reps, 1))[:max_position]
        out = dict(params)
        out["pos_emb"] = new
        return out

    @staticmethod
    def update_tokenizer_model_max_length(tokenizer, max_position: int):
        """Bump a (HF-style) tokenizer's max length to the extended
        position-table size (sparse_attention_utils.py:68)."""
        tokenizer.model_max_length = max_position
        if hasattr(tokenizer, "init_kwargs"):
            tokenizer.init_kwargs["model_max_length"] = max_position
        return tokenizer

    @staticmethod
    def replace_model_self_attention_with_sparse_self_attention(
            params, config, max_position: Optional[int] = None,
            sparsity_config=None):
        """Functional analogue of the reference's module surgery
        (sparse_attention_utils.py:85): returns ``(params, config,
        encoder_fn)`` where ``encoder_fn(params, input_ids, ...)`` runs the
        BERT encoder with block-sparse core attention, reusing the dense
        QKV/output projection weights unchanged. Optionally extends the
        position table to ``max_position`` first."""
        from deepspeed_tpu.models.bert import bert_encoder
        if sparsity_config is None:
            sparsity_config = FixedSparsityConfig(
                num_heads=getattr(config, "num_heads", 4))
        if max_position is not None and \
                max_position > config.max_position_embeddings:
            params = SparseAttentionUtils.extend_position_embedding(
                params, max_position)
            config = config._replace(max_position_embeddings=max_position)
        cfg = config

        def encoder_fn(params, input_ids, **kw):
            return bert_encoder(params, cfg, input_ids,
                                sparsity_config=sparsity_config, **kw)

        return params, config, encoder_fn

    # reference-name alias (sparse_attention_utils.py:123 operates on one
    # layer; with a pluggable attention_fn the per-layer and whole-model
    # operations coincide)
    replace_self_attention_layer_with_sparse_self_attention_layer = \
        replace_model_self_attention_with_sparse_self_attention

    @staticmethod
    def pad_to_block_size(block_size: int, input_ids, pad_token_id: int,
                          attention_mask=None, token_type_ids=None,
                          position_ids=None, labels=None,
                          label_pad: int = -100):
        """Right-pad sequence inputs so seq_len % block_size == 0
        (sparse_attention_utils.py:151). Returns (pad_len, padded tensors
        with None passed through)."""
        B, S = input_ids.shape
        pad_len = (-S) % block_size
        if pad_len == 0:
            return 0, input_ids, attention_mask, token_type_ids, \
                position_ids, labels

        def pad(x, value):
            if x is None:
                return None
            return jnp.pad(x, ((0, 0), (0, pad_len)), constant_values=value)

        input_ids = pad(input_ids, pad_token_id)
        attention_mask = pad(attention_mask, 0)
        token_type_ids = pad(token_type_ids, 0)
        labels = pad(labels, label_pad)
        if position_ids is not None:
            position_ids = jnp.pad(position_ids, ((0, 0), (0, pad_len)),
                                   mode="edge")
        return pad_len, input_ids, attention_mask, token_type_ids, \
            position_ids, labels

    @staticmethod
    def unpad_sequence_output(pad_len: int, sequence_output):
        """Strip pad_to_block_size padding from the model output
        (sparse_attention_utils.py:210)."""
        if pad_len == 0:
            return sequence_output
        return sequence_output[:, :-pad_len]
