"""Standalone block-sparse MatMul / Softmax ops.

API parity with the reference's composable sparse ops
(deepspeed/ops/sparse_attention/matmul.py:595 MatMul,
softmax.py:207 Softmax): users building their OWN sparse kernels
compose ``sdd`` (dense x dense -> sparse), softmax-on-sparse, and
``dsd``/``dds`` (sparse x dense / dense x sparse -> dense) directly,
with the same compressed block format — a (batch, nnz, block, block)
tensor whose block order is the layout's nonzero order (head-major,
then block-row, then block-col; np.nonzero order).

Implementation is layout-driven jnp gather/einsum/scatter: the MXU
executes the per-block GEMMs batched over the nonzero list and XLA
fuses the rest. (The fused attention path — SparseSelfAttention — uses
the Pallas kernel in ops/attention/masked_flash.py instead; these classes
exist for composability parity, differentiable by construction.)

Softmax normalizes each query row over the row's nonzero blocks only
(structural zeros excluded exactly), with the reference's mask
semantics: ``rpe`` (same compressed shape as x, added), key-padding
mask (B, S), attention mask (S, S), each in 'add' (values added) or
'mul' (zeros drop entries) mode.
"""

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30


def _nonzeros(layout: np.ndarray):
    hs, rs, cs = np.nonzero(np.asarray(layout))
    return (hs.astype(np.int32), rs.astype(np.int32), cs.astype(np.int32))


class MatMul:
    """Block-sparse matmul (reference matmul.py:595): one of
    - 'sdd': dense x dense -> sparse (compressed (B, nnz, blk, blk))
    - 'dsd': sparse x dense -> dense
    - 'dds': dense x sparse -> dense
    ``trans_a``/``trans_b`` transpose the last two dims of the
    corresponding operand first (e.g. sdd + trans_b=True is the
    attention Q @ K^T)."""

    def __init__(self, layout, block: int, mode: str,
                 trans_a: bool = False, trans_b: bool = False,
                 bench: bool = False):
        if mode not in ("sdd", "dsd", "dds"):
            raise NotImplementedError(
                f"Supported modes are: sdd, dsd, dds; got {mode}")
        self.layout = np.asarray(layout)
        self.block = int(block)
        self.mode = mode
        self.trans_a = trans_a
        self.trans_b = trans_b
        self.bench = bench                       # accepted for parity
        self.spdims = self.layout.shape
        self.hs, self.rs, self.cs = _nonzeros(self.layout)
        self.nnz = len(self.hs)

    def _dense_blocks(self, x, block_idx, seq_axis_blocks):
        """Gather (B, nnz, blk, D) row/col blocks out of a dense
        (B, H, S, D) operand: head hs[n], seq block ``block_idx[n]``."""
        B, H, S, D = x.shape
        blk = self.block
        xb = x.reshape(B, H, S // blk, blk, D)
        return xb[:, self.hs, block_idx]          # (B, nnz, blk, D)

    def __call__(self, a, b):
        blk = self.block
        if self.mode == "sdd":
            if self.trans_a:
                a = jnp.swapaxes(a, -1, -2)
            if self.trans_b:
                b = jnp.swapaxes(b, -1, -2)
            # a: (B, H, Sq, K), b: (B, H, K, Sk) -> blocks of a @ b
            a_blocks = self._dense_blocks(a, self.rs, None)  # (B,nnz,blk,K)
            bT = jnp.swapaxes(b, -1, -2)                     # (B, H, Sk, K)
            b_blocks = self._dense_blocks(bT, self.cs, None)  # (B,nnz,blk,K)
            return jnp.einsum("bnik,bnjk->bnij", a_blocks, b_blocks)
        if self.mode == "dsd":
            # a: sparse (B, nnz, blk, blk), b: dense (B, H, Sk, D)
            if self.trans_a:
                a = jnp.swapaxes(a, -1, -2)
                rs, cs = self.cs, self.rs
                out_blocks = self.spdims[2]
            else:
                rs, cs = self.rs, self.cs
                out_blocks = self.spdims[1]
            if self.trans_b:
                b = jnp.swapaxes(b, -1, -2)
            B, H, Sk, D = b.shape
            b_blocks = self._dense_blocks(b, cs, None)        # (B,nnz,blk,D)
            contrib = jnp.einsum("bnij,bnjd->bnid", a, b_blocks)
            # scatter-add into (B, H, out_blocks, blk, D) rows
            out = jnp.zeros((B, self.spdims[0], out_blocks, blk, D),
                            contrib.dtype)
            out = out.at[:, self.hs, rs].add(contrib)
            return out.reshape(B, self.spdims[0], out_blocks * blk, D)
        # dds: a dense (B, H, Sq, K) x b sparse -> dense (B, H, Sq, Sk)
        if self.trans_a:
            a = jnp.swapaxes(a, -1, -2)
        if self.trans_b:
            b = jnp.swapaxes(b, -1, -2)
            rs, cs = self.cs, self.rs
            out_blocks = self.spdims[1]
        else:
            rs, cs = self.rs, self.cs
            out_blocks = self.spdims[2]
        B, H, Sq, K = a.shape
        # a's K dim is blocked by the sparse operand's row blocks
        ab = a.reshape(B, H, Sq, K // blk, blk)
        a_blocks = ab[:, self.hs, :, rs.astype(np.int64)]
        # advanced-index quirk: result is (nnz, B, Sq, blk) — move axes
        a_blocks = jnp.moveaxis(a_blocks, 0, 1)               # (B,nnz,Sq,blk)
        contrib = jnp.einsum("bnqj,bnjk->bnqk", a_blocks, b)
        out = jnp.zeros((B, self.spdims[0], Sq, out_blocks, blk),
                        contrib.dtype)
        out = out.at[:, self.hs, :, cs].add(
            jnp.moveaxis(contrib, 1, 0))
        return out.reshape(B, self.spdims[0], Sq, out_blocks * blk)


def _to_additive(mask, mode):
    mask = mask.astype(jnp.float32)
    if mode == "mul":
        return jnp.where(mask == 0, NEG_INF, 0.0)
    return mask


class Softmax:
    """Block-sparse softmax (reference softmax.py:207): normalizes each
    query row over the row's nonzero blocks; structural zeros never
    contribute. Masks as in the reference: rpe (compressed, added),
    key_padding_mask (B, S), attn_mask (S, S), each 'add'/'mul'."""

    def __init__(self, layout, block: int, bench: bool = False):
        self.layout = np.asarray(layout)
        self.block = int(block)
        self.bench = bench
        self.spdims = self.layout.shape
        self.num_blocks = int(self.layout.sum())
        self.hs, self.rs, self.cs = _nonzeros(self.layout)
        # group the nonzeros by (head, block-row) and pad to max degree
        H, nq, _ = self.spdims
        groups = [[] for _ in range(H * nq)]
        for n, (h, r) in enumerate(zip(self.hs, self.rs)):
            groups[h * nq + r].append(n)
        self.maxdeg = max((len(g) for g in groups), default=1) or 1
        lut = np.zeros((H * nq, self.maxdeg), np.int32)
        valid = np.zeros((H * nq, self.maxdeg), bool)
        for g, ns in enumerate(groups):
            lut[g, :len(ns)] = ns
            valid[g, :len(ns)] = True
        self.lut, self.valid = lut, valid
        # inverse: block n -> (group, slot)
        self.g_of_n = (self.hs.astype(np.int64) * nq
                       + self.rs.astype(np.int64))
        slot = np.zeros(len(self.hs), np.int32)
        for g, ns in enumerate(groups):
            for i, n in enumerate(ns):
                slot[n] = i
        self.slot_of_n = slot

    def __call__(self, x, scale=1.0, rpe=None, key_padding_mask=None,
                 attn_mask=None, key_padding_mask_mode: str = "add",
                 attn_mask_mode: str = "add"):
        blk = self.block
        B = x.shape[0]
        s = x.astype(jnp.float32) * scale
        if rpe is not None:
            s = s + rpe.astype(jnp.float32)
        if attn_mask is not None:
            am = _to_additive(jnp.asarray(attn_mask), attn_mask_mode)
            amb = am.reshape(self.spdims[1], blk, self.spdims[2], blk
                             ).transpose(0, 2, 1, 3)
            s = s + amb[self.rs, self.cs][None]
        if key_padding_mask is not None:
            kpm = _to_additive(jnp.asarray(key_padding_mask),
                               key_padding_mask_mode)    # (B, S)
            kpmb = kpm.reshape(B, self.spdims[2], blk)
            s = s + kpmb[:, self.cs][:, :, None, :]
        # gather each (head, block-row) group: (B, G, maxdeg, blk, blk)
        sg = s[:, self.lut]
        sg = jnp.where(self.valid[None, :, :, None, None], sg, NEG_INF)
        # softmax jointly over (maxdeg, blk_k) per query row
        Bn, G, Dg, _, _ = sg.shape
        flat = jnp.swapaxes(sg, 2, 3).reshape(Bn, G, blk, Dg * blk)
        m = jnp.max(flat, axis=-1, keepdims=True)
        # all-masked rows normalize to exact zeros, like the kernels
        e = jnp.where(flat > NEG_INF / 2, jnp.exp(flat - m), 0.0)
        denom = jnp.sum(e, axis=-1, keepdims=True)
        p = e / jnp.where(denom == 0.0, 1.0, denom)
        pg = jnp.swapaxes(p.reshape(Bn, G, blk, Dg, blk), 2, 3)
        out = pg[:, self.g_of_n, self.slot_of_n]
        return out.astype(x.dtype)
