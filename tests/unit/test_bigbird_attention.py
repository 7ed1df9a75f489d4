"""Non-banded sparse layouts through ``block_sparse_attention``.

BigBird random blocks, per-head layouts, a causal band with a random
lower-triangle residue and VariableSparsityConfig's chunked windows are
what ``detect_banded`` declines: the masked kernel walks them at the
layout's fine block. Reference capability being matched:
BigBirdSparsityConfig layouts (deepspeed/ops/sparse_attention/
sparsity_config.py:421). Numerics are pinned against the dense-masked
oracle, forward and backward.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.attention.masked_flash import detect_banded
from deepspeed_tpu.ops.sparse_attention import blocksparse as bs
from deepspeed_tpu.ops.sparse_attention.sparsity_config import (
    BigBirdSparsityConfig, VariableSparsityConfig)

S = 512


def _rand_qkv(B, H, S, D, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return [jax.random.normal(k, (B, H, S, D), dtype) for k in ks]


def _bigbird(per_head=False, seed=0, **kw):
    kw = dict(dict(num_random_blocks=1, num_sliding_window_blocks=3,
                   num_global_blocks=1), **kw)
    return BigBirdSparsityConfig(
        num_heads=2, block=32, different_layout_per_head=per_head,
        seed=seed, **kw).make_layout(S)


def _causal_band_with_residue():
    """Causal band + random lower-triangle residue, per head."""
    n = 16
    idx = np.arange(n)
    rb, cb = idx[:, None], idx[None, :]
    pred = ((rb < 1) | (cb < 1) | (np.abs(rb - cb) <= 1)) & (cb <= rb)
    L = np.broadcast_to(pred, (2, n, n)).copy()
    rng = np.random.default_rng(11)
    for h in range(2):
        for r in range(4, n):
            L[h, r, rng.integers(1, r - 1)] = True
    return L.astype(np.int32)


def _variable_windows():
    """Block-diagonal CHUNKS, not a sliding band."""
    return VariableSparsityConfig(
        num_heads=2, block=32, num_random_blocks=1,
        local_window_blocks=[3], global_block_indices=[0]).make_layout(S)


def _tail_padding():
    kpm = np.zeros((1, S), np.float32)
    kpm[:, 480:] = -1e9
    return dict(key_padding_mask=jnp.asarray(kpm),
                key_padding_mask_mode="add")


# id -> (layout, qkv seed, dtype, tolerance, extra call arguments)
CASES = {
    "bigbird": (_bigbird, 0, jnp.float32, 5e-6, {}),
    "per_head_random": (lambda: _bigbird(per_head=True, seed=3), 0,
                        jnp.float32, 5e-6, {}),
    "more_random": (lambda: _bigbird(seed=7, num_random_blocks=2,
                                     num_sliding_window_blocks=5,
                                     num_global_blocks=2), 5,
                    jnp.float32, 5e-6, {}),
    "causal_residual": (_causal_band_with_residue, 2, jnp.float32, 5e-6,
                        {}),
    "variable_windows": (_variable_windows, 4, jnp.float32, 5e-6, {}),
    "key_padding": (_bigbird, 0, jnp.float32, 5e-6, _tail_padding()),
    "bf16": (_bigbird, 6, jnp.bfloat16, 3e-2, {}),
}


@pytest.mark.parametrize("case", list(CASES))
def test_matches_dense_reference_fwd_bwd(case):
    make_layout, seed, dtype, atol, kw = CASES[case]
    L = make_layout()
    # none of these is global-prefix + band: they walk the fine block
    assert detect_banded(L) is None
    assert bs._layout_block_mask(L, 32).block == 32
    q, k, v = _rand_qkv(1, 2, S, 16, seed=seed, dtype=dtype)

    def loss(fn):
        return lambda q, k, v: jnp.sum(
            fn(q, k, v, L, **kw).astype(jnp.float32) ** 2)

    o = bs.block_sparse_attention(q, k, v, L, **kw)
    o_ref = bs.block_sparse_attention_reference(q, k, v, L, **kw)
    assert o.dtype == dtype
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32),
                               atol=atol, rtol=atol)
    g = jax.grad(loss(bs.block_sparse_attention), (0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss(bs.block_sparse_attention_reference),
                     (0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g, g_ref):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=atol * 20, rtol=atol * 20, err_msg=f"d{name}")
