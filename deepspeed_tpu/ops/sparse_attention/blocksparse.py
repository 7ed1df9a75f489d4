"""Block-sparse attention — the public entry over the one masked kernel.

TPU re-design of the reference's Triton block-sparse stack
(deepspeed/ops/sparse_attention: matmul.py:18 SDD/DSD `_sparse_matmul`,
softmax.py:17 `_sparse_softmax`, trsrc/{matmul.tr,softmax_fwd.tr,
softmax_bwd.tr}). The reference decomposes sparse attention into three
kernels (SDD scores → sparse softmax → DSD context) with materialized
block-sparse score storage. Here a layout becomes a static
:class:`~deepspeed_tpu.ops.attention.masked_flash.BlockMask` and runs the
ONE flash-style kernel that dense and causal training attention also
compile (``ops/attention/masked_flash.py``): each program walks only its
row's active key/value tiles and never materializes scores —
O(S * active_blocks) compute with O(S) memory.

Layouts come from sparsity_config.py as static numpy (H, nb, nb) 0/1
tensors. That kernel carries a key-padding mask but no channel for a
user ``attn_mask``; a call that passes one (or an ``rpe``) runs the
differentiable dense reference below, which is also the oracle every
parity test compares against.

Mask semantics (parity with trsrc/softmax_fwd.tr:100-119): scores are
scaled, then rpe added, then key-padding mask and attention mask applied —
'add' mode adds the mask values; 'mul' mode maps zero entries to -inf and
nonzero to 0 (a hard keep/drop mask).
"""

from typing import Optional

import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.ops.attention.masked_flash import (BlockMask,
                                                      masked_flash_attention)
from deepspeed_tpu.utils.logging import log_once

NEG_INF = -1e30
# scores below this are "structurally masked": several -1e30 mask terms may
# stack, so the threshold sits well above any sum of them but far below any
# finite score
VALID_THRESH = -1e28


# --------------------------------------------------------------------- #
# layout utilities
# --------------------------------------------------------------------- #
def build_row_luts(layout: np.ndarray):
    """Per-(head, query-block) list of active key-block indices.

    Returns (lut, cnt): lut (H, nq, A) int32 padded with 0, cnt (H, nq)
    int32; A = max active blocks over all rows (>= 1)."""
    H, nq, _ = layout.shape
    cnt = layout.sum(axis=-1).astype(np.int32)
    A = max(int(cnt.max()) if cnt.size else 0, 1)
    lut = np.zeros((H, nq, A), dtype=np.int32)
    for h in range(H):
        for r in range(nq):
            idx = np.nonzero(layout[h, r])[0]
            lut[h, r, :len(idx)] = idx
    return lut, cnt


def build_col_luts(layout: np.ndarray):
    """Column-wise LUTs (which query blocks touch each key block) — drives
    the dk/dv backward pass."""
    return build_row_luts(np.ascontiguousarray(layout.transpose(0, 2, 1)))


def layout_additive_mask(layout: np.ndarray, block: int) -> np.ndarray:
    """Expand a block layout to a dense (H, S, S) additive mask (0 keep /
    NEG_INF drop) — the oracle path."""
    dense = np.kron(layout, np.ones((block, block), dtype=np.int32))
    return np.where(dense != 0, 0.0, NEG_INF).astype(np.float32)


def _to_additive(mask, mode):
    mask = mask.astype(jnp.float32)
    if mode == "add":
        return mask
    if mode == "mul":
        return jnp.where(mask == 0, NEG_INF, 0.0)
    raise ValueError(f"mask mode must be 'add' or 'mul', got {mode!r}")


# --------------------------------------------------------------------- #
# oracle / fallback implementation
# --------------------------------------------------------------------- #
def block_sparse_attention_reference(q, k, v, layout, sm_scale=None,
                                     key_padding_mask=None,
                                     key_padding_mask_mode="add",
                                     attn_mask=None, attn_mask_mode="mul",
                                     rpe=None):
    """Dense-masked jnp attention equivalent to the block-sparse kernel.

    q, k, v: (B, H, S, D). layout: numpy (H, nb, nb). Rows with no valid
    key (structurally or via masks) produce zero output, matching the
    kernel (the reference Triton softmax yields 0/0 there; we define it)."""
    B, H, S, D = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(D)
    block = S // layout.shape[1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    if rpe is not None:
        s = s + rpe.astype(jnp.float32)
    if key_padding_mask is not None:
        kpm = _to_additive(key_padding_mask, key_padding_mask_mode)
        s = s + kpm[:, None, None, :]
    if attn_mask is not None:
        am = _to_additive(attn_mask, attn_mask_mode)
        s = s + am[None, None, :, :]
    s = s + jnp.asarray(layout_additive_mask(layout, block))[None]
    m = jnp.max(s, axis=-1, keepdims=True)
    m_safe = jnp.where(m <= VALID_THRESH, 0.0, m)
    p = jnp.where(s > VALID_THRESH, jnp.exp(s - m_safe), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    p = p / jnp.where(l == 0.0, 1.0, l)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


# --------------------------------------------------------------------- #
# layout -> BlockMask (cached: the CSR/CSC walk metadata is numpy work)
# --------------------------------------------------------------------- #
_MASK_CACHE = {}


def _layout_block_mask(layout: np.ndarray, block: int) -> BlockMask:
    """The layout as the masked kernel's :class:`BlockMask`: head-uniform
    layouts collapse; banded layouts coarsen to MXU-sized walk tiles with
    the fine structure in register predicates."""
    key = (layout.shape, layout.tobytes(), block)
    if key not in _MASK_CACHE:
        _MASK_CACHE[key] = BlockMask.from_layout(layout, block)
    return _MASK_CACHE[key]


# --------------------------------------------------------------------- #
# public API
# --------------------------------------------------------------------- #
def block_sparse_attention(q, k, v, layout, sm_scale: Optional[float] = None,
                           key_padding_mask=None,
                           key_padding_mask_mode: str = "add",
                           attn_mask=None, attn_mask_mode: str = "mul",
                           rpe=None, interpret: Optional[bool] = None,
                           force_reference: bool = False):
    """Fused block-sparse attention.

    q, k, v: (B, H, S, D); layout: numpy int (H, nb, nb) from a
    SparsityConfig (block size = S // nb). key_padding_mask: (B, S);
    attn_mask: (S, S); modes per the reference's sparse softmax ('add' adds
    values, 'mul' drops zero entries). rpe (dense additive (B, H, S, S))
    and attn_mask route through the jnp oracle — dense operands defeat
    sparse storage anyway.
    """
    _, H, S, _ = q.shape
    layout = np.asarray(layout)
    assert layout.ndim == 3 and layout.shape[0] == H, \
        f"layout heads {layout.shape} vs q heads {H}"
    assert S % layout.shape[1] == 0, (S, layout.shape)
    block = S // layout.shape[1]
    if attn_mask is not None and not force_reference and rpe is None:
        log_once(("attn-mask-reference", layout.shape, block),
                 "block_sparse_attention: a user attention mask runs the "
                 "O(S^2) dense reference (differentiable) — the masked "
                 "kernel carries no user-mask channel.")
    if force_reference or rpe is not None or attn_mask is not None:
        return block_sparse_attention_reference(
            q, k, v, layout, sm_scale=sm_scale,
            key_padding_mask=key_padding_mask,
            key_padding_mask_mode=key_padding_mask_mode,
            attn_mask=attn_mask, attn_mask_mode=attn_mask_mode, rpe=rpe)

    kpm = None if key_padding_mask is None else \
        _to_additive(key_padding_mask, key_padding_mask_mode)
    return masked_flash_attention(q, k, v, _layout_block_mask(layout, block),
                                  key_mask=kpm, sm_scale=sm_scale,
                                  interpret=interpret)
