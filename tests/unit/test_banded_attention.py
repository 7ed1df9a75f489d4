"""Banded (Longformer-class) layouts through ``block_sparse_attention``.

Structure detection (``masked_flash.detect_banded``) and numerical
parity of the masked kernel against the dense-masked oracle
(blocksparse.block_sparse_attention_reference), across walk-tile
sizes, global/band geometries, causal clip, and key-padding masks.
Reference behavior being matched: block-level mask semantics of the
Triton sparse kernels (deepspeed/ops/sparse_attention/trsrc/
softmax_fwd.tr:100-119) for BSLongformer-class layouts
(sparsity_config.py:544).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.attention.masked_flash import (
    BlockMask, detect_banded, masked_flash_attention)
from deepspeed_tpu.ops.sparse_attention import blocksparse as bs
from deepspeed_tpu.ops.sparse_attention.sparsity_config import (
    BigBirdSparsityConfig, BSLongformerSparsityConfig,
    FixedSparsityConfig)


def make_banded_layout(H, n, g_r, g_c, w, causal):
    idx = np.arange(n)
    rb, cb = idx[:, None], idx[None, :]
    pred = (rb < g_r) | (cb < g_c) | (np.abs(rb - cb) <= w)
    if causal:
        pred = pred & (cb <= rb)
    return np.broadcast_to(pred.astype(np.int32), (H, n, n)).copy()


def _longformer(S, block=32, **kw):
    return BSLongformerSparsityConfig(num_heads=2, block=block,
                                      **kw).make_layout(S)


# --------------------------------------------------------------------- #
# detection
# --------------------------------------------------------------------- #
def test_detect_bslongformer_default():
    cfg = BSLongformerSparsityConfig(num_heads=4, block=64,
                                     num_sliding_window_blocks=3)
    p = detect_banded(cfg.make_layout(1024))
    assert p is not None
    assert (p.g_r, p.g_c, p.w, p.causal) == (1, 1, 1, False)


def test_detect_reproduces_layout_exactly():
    """Whatever parameters detection returns, their predicate must
    reproduce the layout bit-for-bit (equivalent representations are
    fine; different layouts are not)."""
    for (g_r, g_c, w, causal) in [(1, 1, 1, False), (2, 2, 2, True),
                                  (0, 0, 1, False), (2, 0, 1, False),
                                  (0, 2, 1, True), (1, 1, 0, True)]:
        L = make_banded_layout(2, 16, g_r, g_c, w, causal)
        p = detect_banded(L)
        assert p is not None, (g_r, g_c, w, causal)
        L2 = make_banded_layout(2, 16, p.g_r, p.g_c, p.w, p.causal)
        assert (L2 == L).all(), (g_r, g_c, w, causal, p)


def test_detect_declines_non_banded():
    # random blocks (BigBird) are not expressible as prefix+band
    bb = BigBirdSparsityConfig(num_heads=2, block=32).make_layout(512)
    assert detect_banded(bb) is None
    # per-head-different layouts
    L = make_banded_layout(2, 8, 1, 1, 1, False)
    L[1, 3, 7] = 1
    assert detect_banded(L) is None
    # fully dense should go to flash, not the banded walk
    assert detect_banded(np.ones((2, 8, 8), np.int32)) is None
    # non-prefix global column
    L = make_banded_layout(1, 8, 0, 0, 1, False)
    L[0, :, 5] = 1
    assert detect_banded(L) is None


def test_detect_declines_pure_global():
    """Global rows/cols with NO band: the w=-1 empty-band case must
    decline (a collapsed w=0 would add diagonal blocks the layout does
    not have — code-review r4 finding #1)."""
    n = 8
    idx = np.arange(n)
    rb, cb = idx[:, None], idx[None, :]
    for g_r, g_c in [(2, 0), (0, 2), (2, 2)]:
        L = np.broadcast_to(((rb < g_r) | (cb < g_c)).astype(np.int32),
                            (2, n, n)).copy()
        p = detect_banded(L)
        if p is not None:       # only legal if predicate reproduces bits
            L2 = make_banded_layout(2, n, p.g_r, p.g_c, p.w, p.causal)
            assert (L2 == L).all(), (g_r, g_c, p)
        # dispatcher must stay correct either way
        o = bs.block_sparse_attention(
            *[jax.random.normal(jax.random.PRNGKey(i), (1, 2, 256, 16))
              for i in range(3)], L)
        o_ref = bs.block_sparse_attention_reference(
            *[jax.random.normal(jax.random.PRNGKey(i), (1, 2, 256, 16))
              for i in range(3)], L)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                                   atol=5e-5, rtol=5e-5)


def test_longformer_layout_resolves_to_one_cached_band_mask():
    """The public entry turns a head-uniform banded layout into ONE
    mask head whose walk is coarsened, and builds it once per layout."""
    L = _longformer(512)
    mask = bs._layout_block_mask(L, 32)
    assert mask.heads == 1 and mask.band is not None
    assert mask.block > 32 and mask.fine_block == 32
    assert bs._layout_block_mask(L.copy(), 32) is mask


# --------------------------------------------------------------------- #
# numerical parity vs the dense-masked oracle
# --------------------------------------------------------------------- #
def _parity(L, fb, S, walk_block=None, kpm_mode=None, dtype=jnp.float32,
            seed=0):
    """Forward and gradients against the oracle. ``walk_block`` None is
    the public entry (the cost model picks the walk tile); a number
    forces that tile (0 = the layout's fine block)."""
    key = jax.random.PRNGKey(seed)
    B, H, D = 2, L.shape[0], 16
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (B, H, S, D),
                                 dtype) for i in range(3))
    kpm = None
    if kpm_mode == "add":
        kpm = (jax.random.normal(jax.random.fold_in(key, 7), (B, S))
               * 2).astype(jnp.float32)
    elif kpm_mode == "mul":
        kpm = (jax.random.uniform(jax.random.fold_in(key, 8), (B, S))
               > 0.2).astype(jnp.float32)
    kw = dict(key_padding_mask=kpm,
              key_padding_mask_mode=kpm_mode or "add")
    if walk_block is None:
        def attn(q, k, v):
            return bs.block_sparse_attention(q, k, v, L, **kw)
    else:
        assert kpm is None
        mask = BlockMask.from_layout(L, fb, walk_block=walk_block)
        assert mask.block == (walk_block or fb)

        def attn(q, k, v):
            return masked_flash_attention(q, k, v, mask)

    def ref(q, k, v):
        return bs.block_sparse_attention_reference(q, k, v, L, **kw)

    tol = 5e-5 if dtype == jnp.float32 else 6e-2
    np.testing.assert_allclose(np.asarray(attn(q, k, v), np.float32),
                               np.asarray(ref(q, k, v), np.float32),
                               atol=tol, rtol=tol)

    def loss(fn):
        return lambda q, k, v: jnp.sum(
            fn(q, k, v).astype(jnp.float32) ** 2)

    g = jax.grad(loss(attn), (0, 1, 2))(q, k, v)
    gr = jax.grad(loss(ref), (0, 1, 2))(q, k, v)
    gtol = tol * 40
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=gtol, rtol=gtol)


GEOMETRIES = [(1, 1, 1, False), (2, 2, 2, True), (0, 0, 1, False),
              (0, 0, 2, True), (3, 3, 1, False), (2, 0, 1, False),
              (0, 2, 1, True), (1, 1, 0, True)]

# id -> (layout, fine block, S, _parity keywords)
CASES = {
    # the walk-tile size must never change results — the fine block and
    # tiles larger than it (multi-block tiles with partial cells)
    **{f"walk{wb}": (lambda: _longformer(256), 32, 256,
                     dict(walk_block=wb)) for wb in (0, 64, 128)},
    # global rows only / cols only / band only / causal clip / diag-only
    # band, incl. multi-tile global prefixes
    **{f"geometry-{g_r}-{g_c}-{w}-{'causal' if c else 'full'}":
       (lambda g=(g_r, g_c, w, c): make_banded_layout(2, 16, *g), 32, 512,
        {}) for g_r, g_c, w, c in GEOMETRIES},
    "kpm-add": (lambda: _longformer(256), 32, 256, dict(kpm_mode="add")),
    "kpm-mul": (lambda: _longformer(256), 32, 256, dict(kpm_mode="mul")),
    "bf16": (lambda: _longformer(512, 64, num_sliding_window_blocks=5),
             64, 512, dict(dtype=jnp.bfloat16)),
}


@pytest.mark.parametrize("case", list(CASES))
def test_banded_parity(case):
    make_layout, fb, S, kw = CASES[case]
    L = make_layout()
    assert detect_banded(L) is not None
    _parity(L, fb, S, **kw)


def test_fixed_config_band_detection_consistency():
    """FixedSparsityConfig layouts are block-local, not banded — if
    detection ever matches one, the predicate must reproduce the bits
    (guards against over-eager detection); either way the public entry
    matches the oracle."""
    cfg = FixedSparsityConfig(num_heads=2, block=32, num_local_blocks=4)
    L = cfg.make_layout(512)
    p = detect_banded(L)
    if p is not None:
        L2 = make_banded_layout(L.shape[0], L.shape[1], p.g_r, p.g_c,
                                p.w, p.causal)
        assert (L2 == L).all()
    else:
        assert bs._layout_block_mask(L, 32).band is None
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (1, 2, 512, 16))
               for i in range(3))
    np.testing.assert_allclose(
        np.asarray(bs.block_sparse_attention(q, k, v, L)),
        np.asarray(bs.block_sparse_attention_reference(q, k, v, L)),
        atol=5e-5, rtol=5e-5)


def test_zero_coverage_rows_zero_output():
    """A fully-masked key set (mul-mode kpm dropping every key) must
    yield zero output rows, matching the oracle's convention."""
    L = _longformer(256)
    key = jax.random.PRNGKey(4)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i),
                                 (1, 2, 256, 16), jnp.float32)
               for i in range(3))
    kpm = np.zeros((1, 256), np.float32)        # mul-mode: drop all keys
    o = bs.block_sparse_attention(q, k, v, L, key_padding_mask=kpm,
                                  key_padding_mask_mode="mul")
    assert float(jnp.abs(o).max()) == 0.0
