"""Sparse attention tests — mirrors the reference's
tests/unit/test_sparse_attention.py (sparse ops vs dense masked torch)
with our Pallas kernel checked against the dense-masked jnp oracle, plus
layout-structure assertions for every sparsity config."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.sparse_attention import (
    BertSparseSelfAttention, BigBirdSparsityConfig,
    BSLongformerSparsityConfig, DenseSparsityConfig, FixedSparsityConfig,
    SparseAttentionUtils, SparseSelfAttention, SparsityConfig,
    VariableSparsityConfig, block_sparse_attention,
    block_sparse_attention_reference, build_col_luts, build_row_luts,
    layout_additive_mask, sparsity_config_from_dict)


# --------------------------------------------------------------------- #
# layout structure
# --------------------------------------------------------------------- #
def test_dense_layout():
    layout = DenseSparsityConfig(num_heads=2, block=16).make_layout(64)
    assert layout.shape == (2, 4, 4)
    assert (layout == 1).all()


def test_seq_len_divisibility():
    with pytest.raises(ValueError):
        FixedSparsityConfig(num_heads=2, block=16).make_layout(65)


def test_fixed_layout_local_windows():
    cfg = FixedSparsityConfig(num_heads=2, block=16, num_local_blocks=2,
                              num_global_blocks=1)
    layout = cfg.make_layout(128)   # 8 blocks
    # local: 2x2 diagonal windows all present
    for w in range(4):
        assert (layout[0, 2 * w:2 * w + 2, 2 * w:2 * w + 2] == 1).all()
    # global: last block of each window (indices 1,3,5,7) fully attended
    for g in (1, 3, 5, 7):
        assert (layout[0, :, g] == 1).all()
    # heads share the layout by default
    assert (layout[0] == layout[1]).all()


def test_fixed_layout_unidirectional():
    cfg = FixedSparsityConfig(num_heads=1, block=16, num_local_blocks=4,
                              attention="unidirectional")
    layout = cfg.make_layout(128)
    nb = layout.shape[1]
    upper = np.triu(np.ones((nb, nb), dtype=bool), k=1)
    assert (layout[0][upper] == 0).all()


def test_fixed_different_patterns_per_head():
    cfg = FixedSparsityConfig(num_heads=4, block=16, num_local_blocks=4,
                              num_global_blocks=1,
                              different_layout_per_head=True,
                              num_different_global_patterns=4)
    layout = cfg.make_layout(128)
    # head h uses global column slot (num_local - 1 - h) within each window
    for h in range(4):
        g = 3 - h
        assert (layout[h, :, g] == 1).all()


def test_fixed_validation_errors():
    with pytest.raises(ValueError):
        FixedSparsityConfig(num_heads=2, num_local_blocks=4,
                            num_global_blocks=3)
    with pytest.raises(ValueError):
        FixedSparsityConfig(num_heads=2, attention="unidirectional",
                            horizontal_global_attention=True)
    with pytest.raises(ValueError):
        FixedSparsityConfig(num_heads=2, num_different_global_patterns=2)


def test_variable_layout():
    cfg = VariableSparsityConfig(num_heads=2, block=16, num_random_blocks=1,
                                 local_window_blocks=[1, 2],
                                 global_block_indices=[0])
    layout = cfg.make_layout(128)
    assert (layout[0, :, 0] == 1).all()          # global column 0
    assert layout[0, 0, 0] == 1                  # first local window
    # each row has at least its random block
    assert (layout[0].sum(axis=-1) >= 1).all()
    # deterministic under the seed
    layout2 = cfg.make_layout(128)
    assert (layout == layout2).all()


def test_bigbird_layout():
    cfg = BigBirdSparsityConfig(num_heads=2, block=16, num_random_blocks=1,
                                num_sliding_window_blocks=3,
                                num_global_blocks=1)
    layout = cfg.make_layout(128)
    nb = layout.shape[1]
    assert (layout[0, 0, :] == 1).all()          # global row
    assert (layout[0, :, 0] == 1).all()          # global column
    for r in range(1, nb - 1):                   # sliding window
        assert layout[0, r, r - 1] and layout[0, r, r] and layout[0, r, r + 1]


def test_bslongformer_layout():
    cfg = BSLongformerSparsityConfig(num_heads=2, block=16,
                                     num_sliding_window_blocks=3,
                                     global_block_indices=[0, 2])
    layout = cfg.make_layout(128)
    for g in (0, 2):
        assert (layout[0, g, :] == 1).all()
        assert (layout[0, :, g] == 1).all()


def test_luts_roundtrip():
    cfg = BigBirdSparsityConfig(num_heads=2, block=16)
    layout = cfg.make_layout(128)
    lut, cnt = build_row_luts(layout)
    H, nq, _ = layout.shape
    rebuilt = np.zeros_like(layout)
    for h in range(H):
        for r in range(nq):
            rebuilt[h, r, lut[h, r, :cnt[h, r]]] = 1
    assert (rebuilt == layout).all()
    clut, ccnt = build_col_luts(layout)
    assert (ccnt == layout.sum(axis=1)).all()


# --------------------------------------------------------------------- #
# kernel numerics vs dense oracle
# --------------------------------------------------------------------- #
def _dense_guarded_attention(q, k, v, add_mask, sm_scale):
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale + add_mask
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def _rand_qkv(B, H, S, D, seed=0, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(B, H, S, D), dtype) * 0.3
    return mk(), mk(), mk()


@pytest.mark.parametrize("cfg_factory", [
    lambda H: FixedSparsityConfig(num_heads=H, block=16, num_local_blocks=2,
                                  num_global_blocks=1),
    lambda H: BigBirdSparsityConfig(num_heads=H, block=16,
                                    num_random_blocks=1,
                                    num_sliding_window_blocks=3,
                                    num_global_blocks=1),
    lambda H: BSLongformerSparsityConfig(num_heads=H, block=16,
                                         num_sliding_window_blocks=3),
    lambda H: DenseSparsityConfig(num_heads=H, block=16),
])
def test_kernel_matches_dense_oracle(cfg_factory):
    B, H, S, D = 2, 2, 128, 32
    cfg = cfg_factory(H)
    layout = cfg.make_layout(S)
    q, k, v = _rand_qkv(B, H, S, D)
    sm_scale = D ** -0.5
    out = block_sparse_attention(q, k, v, layout, sm_scale=sm_scale)
    expected = _dense_guarded_attention(
        q, k, v, jnp.asarray(layout_additive_mask(layout, cfg.block))[None],
        sm_scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               atol=2e-5, rtol=2e-5)


def test_kernel_matches_reference_impl():
    B, H, S, D = 1, 2, 64, 16
    cfg = FixedSparsityConfig(num_heads=H, block=16, num_local_blocks=2)
    layout = cfg.make_layout(S)
    q, k, v = _rand_qkv(B, H, S, D, seed=3)
    out = block_sparse_attention(q, k, v, layout)
    ref = block_sparse_attention_reference(q, k, v, layout)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_kernel_key_padding_mask_add():
    B, H, S, D = 2, 2, 64, 16
    cfg = FixedSparsityConfig(num_heads=H, block=16, num_local_blocks=2)
    layout = cfg.make_layout(S)
    q, k, v = _rand_qkv(B, H, S, D, seed=1)
    kpm = np.zeros((B, S), np.float32)
    kpm[:, 40:] = -1e9                              # additive padding mask
    out = block_sparse_attention(q, k, v, layout,
                                 key_padding_mask=jnp.asarray(kpm),
                                 key_padding_mask_mode="add")
    ref = block_sparse_attention_reference(
        q, k, v, layout, key_padding_mask=jnp.asarray(kpm),
        key_padding_mask_mode="add")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("mode", ["add", "mul"])
@pytest.mark.parametrize("block", [32, 128])
def test_user_attn_mask_forward_and_gradient(block, mode):
    """A user ``attn_mask`` has no kernel channel: the call runs the
    differentiable dense reference, at every block size alike. Checked
    against a dense computation written here, so that the reference is
    not compared with itself."""
    B, H, D = 1, 2, 16
    S = 8 * block
    cfg = BigBirdSparsityConfig(num_heads=H, block=block)
    layout = cfg.make_layout(S)
    q, k, v = _rand_qkv(B, H, S, D, seed=2)
    sm_scale = D ** -0.5
    if mode == "mul":
        am = np.tril(np.ones((S, S), np.float32))   # causal keep-mask
        am_add = np.where(am == 0, -1e30, 0.0).astype(np.float32)
    else:
        am = np.random.RandomState(6).randn(S, S).astype(np.float32)
        am_add = am
    dense_mask = jnp.asarray(layout_additive_mask(layout, block))[None] \
        + jnp.asarray(am_add)[None, None]

    def attn(q, k, v):
        return block_sparse_attention(q, k, v, layout, sm_scale=sm_scale,
                                      attn_mask=jnp.asarray(am),
                                      attn_mask_mode=mode)

    def dense(q, k, v):
        return _dense_guarded_attention(q, k, v, dense_mask, sm_scale)

    np.testing.assert_allclose(np.asarray(attn(q, k, v)),
                               np.asarray(dense(q, k, v)),
                               atol=2e-5, rtol=2e-5)
    gk = jax.grad(lambda *a: jnp.sum(attn(*a) ** 2), (0, 1, 2))(q, k, v)
    gd = jax.grad(lambda *a: jnp.sum(dense(*a) ** 2), (0, 1, 2))(q, k, v)
    for a_, b_, name in zip(gk, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(a_), np.asarray(b_),
                                   atol=5e-5, rtol=5e-4,
                                   err_msg=f"d{name}")


@pytest.mark.slow
def test_kernel_gradients_match_oracle():
    B, H, S, D = 1, 2, 64, 16
    cfg = FixedSparsityConfig(num_heads=H, block=16, num_local_blocks=2)
    layout = cfg.make_layout(S)
    q, k, v = _rand_qkv(B, H, S, D, seed=4)
    mask = jnp.asarray(layout_additive_mask(layout, cfg.block))[None]
    sm_scale = D ** -0.5

    def loss_kernel(q, k, v):
        return jnp.sum(block_sparse_attention(q, k, v, layout,
                                              sm_scale=sm_scale) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(_dense_guarded_attention(q, k, v, mask,
                                                sm_scale) ** 2)

    gk = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gk, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5,
                                   rtol=5e-4, err_msg=f"d{name}")


def test_kernel_gradients_with_masks():
    B, H, S, D = 1, 2, 64, 16
    cfg = BSLongformerSparsityConfig(num_heads=H, block=16)
    layout = cfg.make_layout(S)
    q, k, v = _rand_qkv(B, H, S, D, seed=5)
    kpm = np.zeros((B, S), np.float32)
    kpm[:, 48:] = -1e9
    kpm = jnp.asarray(kpm)

    def loss_kernel(q, k, v):
        out = block_sparse_attention(q, k, v, layout, key_padding_mask=kpm,
                                     key_padding_mask_mode="add")
        return jnp.sum(out ** 2)

    def loss_ref(q, k, v):
        out = block_sparse_attention_reference(
            q, k, v, layout, key_padding_mask=kpm,
            key_padding_mask_mode="add")
        return jnp.sum(out ** 2)

    gk = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gk, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5,
                                   rtol=5e-4, err_msg=f"d{name}")


def test_rows_without_an_active_block_are_zero():
    """A query-block row whose layout names no key block (and no
    key-padding mask in the call, so the kernel gets no mask operand at
    all) writes zeros, forward, and takes no gradient — the oracle's
    convention for a row with nothing to attend to."""
    B, H, S, D = 1, 2, 128, 16
    layout = FixedSparsityConfig(num_heads=H, block=32,
                                 num_local_blocks=2).make_layout(S)
    layout[:, 2, :] = 0                      # rows 64..95 see nothing
    q, k, v = _rand_qkv(B, H, S, D, seed=8)
    out = block_sparse_attention(q, k, v, layout)
    ref = block_sparse_attention_reference(q, k, v, layout)
    assert float(jnp.abs(out[:, :, 64:96]).max()) == 0.0
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    dq = jax.grad(lambda q: jnp.sum(
        block_sparse_attention(q, k, v, layout) ** 2))(q)
    assert float(jnp.abs(dq[:, :, 64:96]).max()) == 0.0


def test_kernel_bf16():
    B, H, S, D = 1, 2, 64, 16
    cfg = FixedSparsityConfig(num_heads=H, block=16, num_local_blocks=2)
    layout = cfg.make_layout(S)
    q, k, v = _rand_qkv(B, H, S, D, seed=6, dtype=jnp.bfloat16)
    out = block_sparse_attention(q, k, v, layout)
    ref = block_sparse_attention_reference(q, k, v, layout)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=3e-2)


# --------------------------------------------------------------------- #
# modules + utils
# --------------------------------------------------------------------- #
def test_sparse_self_attention_module():
    B, H, S, D = 2, 4, 64, 16
    attn = SparseSelfAttention(FixedSparsityConfig(num_heads=H, block=16,
                                                   num_local_blocks=2))
    q, k, v = _rand_qkv(B, H, S, D, seed=7)
    out = attn(q, k, v)
    assert out.shape == (B, H, S, D)
    ref = block_sparse_attention_reference(q, k, v, attn.get_layout(S),
                                           sm_scale=D ** -0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)
    # layout cache hit
    assert attn.get_layout(S) is attn.get_layout(S)


def test_sparse_self_attention_forwards_a_user_mask():
    """``SparseSelfAttention`` only forwards ``attn_mask`` (no model or
    engine path of the repo passes one): the module's mode reaches the
    call, which runs the dense reference."""
    B, H, S, D = 1, 2, 64, 16
    cfg = BSLongformerSparsityConfig(num_heads=H, block=16)
    q, k, v = _rand_qkv(B, H, S, D, seed=9)
    am = jnp.asarray(np.tril(np.ones((S, S), np.float32)))
    out = SparseSelfAttention(cfg, attn_mask_mode="mul")(q, k, v,
                                                         attn_mask=am)
    ref = block_sparse_attention_reference(
        q, k, v, cfg.make_layout(S), sm_scale=D ** -0.5, attn_mask=am,
        attn_mask_mode="mul")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_bert_sparse_self_attention():
    from deepspeed_tpu.models.bert import BertConfig
    cfg = BertConfig(hidden_size=64, num_heads=4)
    layer = BertSparseSelfAttention(
        cfg, FixedSparsityConfig(num_heads=4, block=16, num_local_blocks=2))
    params = layer.init_params(jax.random.PRNGKey(0))
    x = jnp.asarray(np.random.RandomState(0).randn(2, 64, 64), jnp.float32)
    mask = jnp.ones((2, 64), jnp.float32).at[:, 50:].set(0.0)
    # mul-mode key padding via 'add' of -inf needs additive form; the module
    # defaults to 'add' mode, so feed additive values
    out = layer(params, x, attention_mask=(mask - 1.0) * 1e9)
    assert out.shape == (2, 64, 64)
    assert np.isfinite(np.asarray(out, np.float32)).all()


def test_pad_unpad_roundtrip():
    ids = jnp.asarray(np.arange(2 * 50).reshape(2, 50), jnp.int32)
    mask = jnp.ones((2, 50), jnp.int32)
    labels = jnp.zeros((2, 50), jnp.int32)
    pad_len, pids, pmask, ptt, ppos, plab = \
        SparseAttentionUtils.pad_to_block_size(
            16, ids, pad_token_id=0, attention_mask=mask, labels=labels)
    assert pad_len == 14 and pids.shape == (2, 64)
    assert int(pmask[0, 50:].sum()) == 0
    assert (np.asarray(plab[:, 50:]) == -100).all()
    out = SparseAttentionUtils.unpad_sequence_output(
        pad_len, jnp.zeros((2, 64, 8)))
    assert out.shape == (2, 50, 8)
    # no-op when already aligned
    pad_len, pids, *_ = SparseAttentionUtils.pad_to_block_size(
        16, jnp.zeros((1, 32), jnp.int32), 0)
    assert pad_len == 0 and pids.shape == (1, 32)


def test_extend_position_embedding():
    params = {"pos_emb": jnp.asarray(
        np.random.RandomState(0).randn(128, 8), jnp.float32)}
    out = SparseAttentionUtils.extend_position_embedding(params, 300)
    assert out["pos_emb"].shape == (300, 8)
    np.testing.assert_allclose(np.asarray(out["pos_emb"][:128]),
                               np.asarray(params["pos_emb"]))
    np.testing.assert_allclose(np.asarray(out["pos_emb"][128:256]),
                               np.asarray(params["pos_emb"]))


@pytest.mark.slow
def test_replace_model_self_attention_surgery():
    """Model surgery (reference sparse_attention_utils.py:85): swap the BERT
    encoder's core attention for block-sparse, reusing dense weights; with a
    dense sparsity layout the output must match the dense encoder."""
    from deepspeed_tpu.models.bert import BertConfig, init_bert_params
    from deepspeed_tpu.ops.sparse_attention import DenseSparsityConfig

    cfg = BertConfig(vocab_size=128, hidden_size=32, num_layers=2,
                     num_heads=2, intermediate_size=64,
                     max_position_embeddings=64,
                     hidden_dropout=0.0, attn_dropout=0.0)
    params = init_bert_params(cfg, jax.random.PRNGKey(0))
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 128, (2, 64)),
                      jnp.int32)

    from deepspeed_tpu.models.bert import bert_encoder
    dense_out = bert_encoder(params, cfg, ids, dtype=jnp.float32)

    sp, scfg, encoder_fn = \
        SparseAttentionUtils.replace_model_self_attention_with_sparse_self_attention(
            params, cfg,
            sparsity_config=DenseSparsityConfig(num_heads=2, block=16))
    sparse_out = encoder_fn(sp, input_ids=ids, dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(sparse_out, np.float32),
                               np.asarray(dense_out, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_surgery_extends_positions_and_runs_sparse():
    from deepspeed_tpu.models.bert import BertConfig, init_bert_params
    from deepspeed_tpu.ops.sparse_attention import FixedSparsityConfig

    cfg = BertConfig(vocab_size=128, hidden_size=32, num_layers=1,
                     num_heads=2, intermediate_size=64,
                     max_position_embeddings=64,
                     hidden_dropout=0.0, attn_dropout=0.0)
    params = init_bert_params(cfg, jax.random.PRNGKey(0))
    sp, scfg, encoder_fn = \
        SparseAttentionUtils.replace_model_self_attention_with_sparse_self_attention(
            params, cfg, max_position=256,
            sparsity_config=FixedSparsityConfig(num_heads=2, block=16,
                                                num_local_blocks=4))
    assert scfg.max_position_embeddings == 256
    assert sp["pos_emb"].shape[0] == 256
    # 4x the original max length now runs (the 10x-longer-sequences claim
    # mechanism, BASELINE.md sparse attention row)
    ids = jnp.asarray(np.random.RandomState(1).randint(0, 128, (1, 256)),
                      jnp.int32)
    out = encoder_fn(sp, input_ids=ids, dtype=jnp.float32)
    assert out.shape == (1, 256, 32)
    assert np.isfinite(np.asarray(out, np.float32)).all()


def test_update_tokenizer_model_max_length():
    class Tok:
        model_max_length = 512
        init_kwargs = {}
    tok = SparseAttentionUtils.update_tokenizer_model_max_length(Tok(), 2048)
    assert tok.model_max_length == 2048
    assert tok.init_kwargs["model_max_length"] == 2048


def test_surgery_respects_key_padding():
    """Padding tokens must not leak into sparse attention (mul-mode mask):
    output at kept positions matches dense masked encoder."""
    from deepspeed_tpu.models.bert import (BertConfig, bert_encoder,
                                           init_bert_params)
    from deepspeed_tpu.ops.sparse_attention import DenseSparsityConfig

    cfg = BertConfig(vocab_size=128, hidden_size=32, num_layers=1,
                     num_heads=2, intermediate_size=64,
                     max_position_embeddings=64,
                     hidden_dropout=0.0, attn_dropout=0.0)
    params = init_bert_params(cfg, jax.random.PRNGKey(0))
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 128, (2, 64)),
                      jnp.int32)
    mask = jnp.ones((2, 64), jnp.int32).at[:, 40:].set(0)

    dense = bert_encoder(params, cfg, ids, attention_mask=mask,
                         dtype=jnp.float32)
    sparse = bert_encoder(params, cfg, ids, attention_mask=mask,
                          dtype=jnp.float32,
                          sparsity_config=DenseSparsityConfig(num_heads=2,
                                                              block=16))
    np.testing.assert_allclose(np.asarray(sparse[:, :40], np.float32),
                               np.asarray(dense[:, :40], np.float32),
                               rtol=2e-2, atol=2e-2)



# --------------------------------------------------------------------- #
# composable MatMul / Softmax ops (reference matmul.py:595, softmax.py:207)
# --------------------------------------------------------------------- #
class TestComposableSparseOps:

    def _setup(self, B=2, H=2, S=64, D=16, blk=16, seed=0):
        from deepspeed_tpu.ops.sparse_attention import MatMul, Softmax
        cfg = BSLongformerSparsityConfig(num_heads=H, block=blk,
                                         num_sliding_window_blocks=3)
        layout = cfg.make_layout(S)
        q, k, v = _rand_qkv(B, H, S, D, seed=seed)
        return MatMul, Softmax, layout, q, k, v, blk

    def test_sdd_softmax_dsd_pipeline_matches_reference(self):
        """The reference's own composition (sparse_self_attention.py:125:
        sdd_nt -> sparse softmax -> dsd_nn) must reproduce the fused
        oracle."""
        MatMul, Softmax, layout, q, k, v, blk = self._setup()
        D = q.shape[-1]
        sdd = MatMul(layout, blk, "sdd", trans_a=False, trans_b=True)
        dsd = MatMul(layout, blk, "dsd")
        sm = Softmax(layout, blk)
        scores = sdd(q, k)                       # (B, nnz, blk, blk)
        assert scores.shape[1] == int(layout.sum())
        probs = sm(scores, scale=float(D) ** -0.5)
        out = dsd(probs, v)
        ref = block_sparse_attention_reference(q, k, v, layout)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_softmax_masks_match_reference(self):
        MatMul, Softmax, layout, q, k, v, blk = self._setup(seed=3)
        B, H, S, D = q.shape
        sdd = MatMul(layout, blk, "sdd", trans_b=True)
        dsd = MatMul(layout, blk, "dsd")
        sm = Softmax(layout, blk)
        mrng = np.random.RandomState(5)
        kpm = (mrng.rand(B, S) > 0.25).astype(np.float32)   # mul-mode
        am = (mrng.rand(S, S) > 0.2).astype(np.float32)
        probs = sm(sdd(q, k), scale=float(D) ** -0.5,
                   key_padding_mask=jnp.asarray(kpm),
                   key_padding_mask_mode="mul",
                   attn_mask=jnp.asarray(am), attn_mask_mode="mul")
        out = dsd(probs, v)
        ref = block_sparse_attention_reference(
            q, k, v, layout, key_padding_mask=jnp.asarray(kpm),
            key_padding_mask_mode="mul", attn_mask=jnp.asarray(am),
            attn_mask_mode="mul")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_dds_matches_dense_masked(self):
        """dense x sparse: out == a @ (dense-masked b)."""
        from deepspeed_tpu.ops.sparse_attention import MatMul
        B, H, S, blk = 1, 2, 64, 16
        cfg = FixedSparsityConfig(num_heads=H, block=blk,
                                  num_local_blocks=2)
        layout = cfg.make_layout(S)
        rng = np.random.RandomState(7)
        a = jnp.asarray(rng.randn(B, H, 24, S), jnp.float32)
        dense_b = jnp.asarray(rng.randn(B, H, S, S), jnp.float32)
        # compress dense_b to the layout's nonzero blocks
        sdd_id = MatMul(layout, blk, "sdd")   # identity trick not needed:
        hs, rs, cs = np.nonzero(layout)
        bb = np.asarray(dense_b).reshape(B, H, S // blk, blk,
                                         S // blk, blk)
        b_sparse = jnp.asarray(
            bb.transpose(0, 1, 2, 4, 3, 5)[:, hs, rs, cs])
        out = MatMul(layout, blk, "dds")(a, b_sparse)
        mask = np.kron(np.asarray(layout, np.float32),
                       np.ones((blk, blk), np.float32))  # (H, S, S)
        ref = jnp.einsum("bhqk,bhkn->bhqn", a,
                         dense_b * jnp.asarray(mask)[None])
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-4, rtol=2e-4)

    @pytest.mark.slow
    def test_sparse_ops_differentiable(self):
        MatMul, Softmax, layout, q, k, v, blk = self._setup(seed=9)
        D = q.shape[-1]
        sdd = MatMul(layout, blk, "sdd", trans_b=True)
        dsd = MatMul(layout, blk, "dsd")
        sm = Softmax(layout, blk)

        def loss(q, k, v):
            return jnp.sum(dsd(sm(sdd(q, k), scale=float(D) ** -0.5), v)
                           ** 2)

        def ref_loss(q, k, v):
            return jnp.sum(block_sparse_attention_reference(
                q, k, v, layout) ** 2)

        g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
        for a_, b_, name in zip(g, gr, "qkv"):
            np.testing.assert_allclose(np.asarray(a_), np.asarray(b_),
                                       atol=5e-4, rtol=1e-3,
                                       err_msg=f"d{name}")


# --------------------------------------------------------------------- #
# JSON sub-config -> SparsityConfig (runtime/config.py get_sparse_attention
# produces the dict; the reference left this glue to its examples repo)
# --------------------------------------------------------------------- #
class TestSparsityConfigFromDict:

    def test_every_mode_builds_and_roundtrips_layout(self):
        from deepspeed_tpu.runtime.config import get_sparse_attention
        configs = [
            ({"mode": "dense"}, DenseSparsityConfig),
            ({"mode": "fixed", "block": 16, "num_local_blocks": 4,
              "num_global_blocks": 1,
              "different_layout_per_head": True,
              "num_different_global_patterns": 4},
             FixedSparsityConfig),
            ({"mode": "variable", "block": 16,
              "local_window_blocks": [2, 2],
              "global_block_indices": [0]}, VariableSparsityConfig),
            ({"mode": "bigbird", "block": 16, "num_random_blocks": 1,
              "num_sliding_window_blocks": 3}, BigBirdSparsityConfig),
            ({"mode": "bslongformer", "block": 16,
              "num_sliding_window_blocks": 3}, BSLongformerSparsityConfig),
        ]
        for raw, klass in configs:
            parsed = get_sparse_attention({"sparse_attention": raw})
            sc = sparsity_config_from_dict(parsed, num_heads=4)
            assert isinstance(sc, klass), (raw, type(sc))
            layout = sc.make_layout(256)
            assert layout.shape == (4, 256 // sc.block, 256 // sc.block)
            assert layout.sum() > 0

    def test_parsed_defaults_match_class_defaults(self):
        # a bare {"mode": "fixed"} through the config parser must build
        # the same layout as FixedSparsityConfig() defaults (block 16 is
        # the JSON schema default, reference constants.py:32)
        from deepspeed_tpu.runtime.config import get_sparse_attention
        parsed = get_sparse_attention({"sparse_attention": {"mode": "fixed"}})
        sc = sparsity_config_from_dict(parsed, num_heads=2)
        ref = FixedSparsityConfig(num_heads=2, block=16)
        np.testing.assert_array_equal(sc.make_layout(128), ref.make_layout(128))

    def test_none_passthrough_and_bad_mode(self):
        assert sparsity_config_from_dict(None, num_heads=2) is None
        with pytest.raises(ValueError, match="not in"):
            sparsity_config_from_dict({"mode": "nope"}, num_heads=2)
