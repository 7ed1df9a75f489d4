"""Pallas block-sparse attention — TPU-native long-sequence kernel.

TPU re-design of the reference's Triton block-sparse stack
(deepspeed/ops/sparse_attention: matmul.py:18 SDD/DSD `_sparse_matmul`,
softmax.py:17 `_sparse_softmax`, trsrc/{matmul.tr,softmax_fwd.tr,
softmax_bwd.tr}). The reference decomposes sparse attention into three
kernels (SDD scores → sparse softmax → DSD context) with materialized
block-sparse score storage. On TPU we fuse all three into ONE
flash-attention-style kernel driven by per-row look-up tables: each
program owns a (query-block, head) tile, streams only the *active*
key/value blocks named by its LUT through VMEM, and never materializes
scores — O(S * active_blocks) compute with O(S) memory, which beats the
reference's sparse-storage scheme on both HBM traffic and fusion.

Layouts come from sparsity_config.py as static numpy (H, nb, nb) 0/1
tensors; LUTs are delivered to the kernel via scalar prefetch (SMEM), the
canonical Mosaic pattern for block-sparse grids.

Mask semantics (parity with trsrc/softmax_fwd.tr:100-119): scores are
scaled, then rpe added, then key-padding mask and attention mask applied —
'add' mode adds the mask values; 'mul' mode maps zero entries to -inf and
nonzero to 0 (a hard keep/drop mask).
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from deepspeed_tpu.ops.attention.flash import _use_pallas

try:
    from jax.experimental.pallas import tpu as pltpu
except ImportError:  # pragma: no cover
    pltpu = None

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


def _block_kpm(kpm, block):
    """(B, S) -> (B, nk, 1, block): the key-block index becomes a leading
    (untiled) dimension so the kernel can gather it with a LUT value —
    dynamic offsets on the lane dimension would need 128-alignment proofs
    Mosaic can't make for arbitrary block sizes."""
    B, S = kpm.shape
    return kpm.reshape(B, S // block, 1, block)


def _block_am(am, block):
    """(S, S) -> (nq, nk, block, block) with the same leading-dim gather
    rationale as _block_kpm."""
    S = am.shape[0]
    nb = S // block
    return am.reshape(nb, block, nb, block).transpose(0, 2, 1, 3)


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
# pallas kernels
# --------------------------------------------------------------------- #
# Grid-iterated ("splash") design: the grid's second axis walks the
# *nonzero blocks themselves* — one grid step per active (head, q-block,
# k-block) triple, nothing per empty block. Scalar-prefetch index maps
# translate the triple id through LUTs to pick which Q/K/V/mask tiles
# Mosaic DMAs, so every load is an aligned BlockSpec copy the pipeline
# double-buffers. Online-softmax state lives in VMEM scratch, reset on a
# triple flagged row-first and flushed to the output block on row-last
# (Pallas holds the output tile in VMEM until its index changes, and
# triples are sorted row-major so the index is constant within a row).
# Rows with no active block get one dummy triple (valid=0) so their output
# still gets written (as zeros).


def build_triples(layout: np.ndarray):
    """Flatten a (H, nr, nc) layout into row-major nonzero triples.

    Returns int32 arrays (trow, tcol, tfirst, tlast, tvalid), each (T,):
    trow = h * nr + r, tcol = c, tfirst/tlast mark row boundaries, and
    empty rows contribute a single valid=0 dummy so every output block is
    produced."""
    H, nr, _ = layout.shape
    trow, tcol, tfirst, tlast, tvalid = [], [], [], [], []
    for h in range(H):
        for r in range(nr):
            idx = np.nonzero(layout[h, r])[0]
            valid = 1
            if len(idx) == 0:
                idx, valid = np.array([0]), 0
            n = len(idx)
            trow.extend([h * nr + r] * n)
            tcol.extend(int(c) for c in idx)
            tfirst.extend([1] + [0] * (n - 1))
            tlast.extend([0] * (n - 1) + [1])
            tvalid.extend([valid] * n)
    return tuple(np.asarray(x, np.int32)
                 for x in (trow, tcol, tfirst, tlast, tvalid))


def _bs_fwd_kernel(trow_ref, tcol_ref, tfirst_ref, tlast_ref, tvalid_ref,
                   q_ref, k_ref, v_ref, kpm_ref, am_ref, o_ref, lse_ref,
                   m_scr, l_scr, acc_scr, *, sm_scale):
    t = pl.program_id(1)

    @pl.when(tfirst_ref[t] == 1)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # MXU fast path: bf16 operands / fp32 accumulation (fp32 converts
    # both halve the MXU rate and bloat VMEM); scale applies to the
    # fp32 scores post-dot
    q = q_ref[0]                                         # (block, D)
    k = k_ref[0]
    v = v_ref[0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s * sm_scale
    s += kpm_ref[0, 0, 0, :][None, :]
    if am_ref is not None:
        s += am_ref[0, 0]
    s = jnp.where(tvalid_ref[t] == 1, s, NEG_INF)
    m = m_scr[:, 0]
    l = l_scr[:, 0]
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    # exact-zero probability for structurally masked entries; rows with no
    # valid entry keep l == 0 and fall out as zero output
    p = jnp.where(s > VALID_THRESH, jnp.exp(s - m_new[:, None]), 0.0)
    alpha = jnp.exp(m - m_new)
    m_scr[:, 0] = m_new
    l_scr[:, 0] = l * alpha + jnp.sum(p, axis=-1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(tlast_ref[t] == 1)
    def _finalize():
        l = l_scr[:, 0]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l_safe[:, None]).astype(o_ref.dtype)
        lse_ref[0, :, 0] = m_scr[:, 0] + jnp.log(l_safe)


def _bs_dq_kernel(trow_ref, tcol_ref, tfirst_ref, tlast_ref, tvalid_ref,
                  q_ref, k_ref, v_ref, kpm_ref, am_ref, do_ref, lse_ref,
                  delta_ref, dq_ref, dq_scr, *, sm_scale):
    t = pl.program_id(1)

    @pl.when(tfirst_ref[t] == 1)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    q = q_ref[0]
    k = k_ref[0]
    v = v_ref[0]
    do = do_ref[0]
    lse = lse_ref[0, :, 0]
    delta = delta_ref[0, :, 0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s * sm_scale
    s += kpm_ref[0, 0, 0, :][None, :]
    if am_ref is not None:
        s += am_ref[0, 0]
    s = jnp.where(tvalid_ref[t] == 1, s, NEG_INF)
    p = jnp.where(s > VALID_THRESH, jnp.exp(s - lse[:, None]), 0.0)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta[:, None])
    dq_scr[...] += jax.lax.dot_general(
        ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(tlast_ref[t] == 1)
    def _finalize():
        dq_ref[0] = (dq_scr[...] * sm_scale).astype(dq_ref.dtype)


def _bs_dkv_kernel(crow_ref, ccol_ref, cfirst_ref, clast_ref, cvalid_ref,
                   q_ref, k_ref, v_ref, kpm_ref, am_ref, do_ref, lse_ref,
                   delta_ref, dk_ref, dv_ref, dk_scr, dv_scr, *, sm_scale):
    t = pl.program_id(1)

    @pl.when(cfirst_ref[t] == 1)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    k = k_ref[0]                                         # (block, D)
    v = v_ref[0]
    q = q_ref[0]
    do = do_ref[0]
    lse = lse_ref[0, :, 0]
    delta = delta_ref[0, :, 0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s * sm_scale
    s += kpm_ref[0, 0, 0, :][None, :]
    if am_ref is not None:
        s += am_ref[0, 0]
    s = jnp.where(cvalid_ref[t] == 1, s, NEG_INF)
    p = jnp.where(s > VALID_THRESH, jnp.exp(s - lse[:, None]), 0.0)
    dv_scr[...] += jax.lax.dot_general(
        p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta[:, None])
    dk_scr[...] += jax.lax.dot_general(
        ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(clast_ref[t] == 1)
    def _finalize():
        # dk carries sm_scale (scores were scaled post-dot)
        dk_ref[0] = (dk_scr[...] * sm_scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _drop_am(kernel, n_before):
    """Adapter for the no-attn-mask variant: inserts am_ref=None at the
    right positional slot (after `n_before` refs)."""
    def wrapped(*refs, **kw):
        return kernel(*refs[:n_before], None, *refs[n_before:], **kw)
    return wrapped


# --------------------------------------------------------------------- #
# builder: layout -> differentiable fused function (cached)
# --------------------------------------------------------------------- #
_FN_CACHE = {}

# unified mask-parameterized flash kernel (ops/attention/masked_flash.py,
# PR 11): the DEFAULT for every layout without a user attention mask —
# dense, causal, banded and BigBird are BlockMask choices of ONE kernel.
# Flip off to reach the legacy dispatch below (banded / hybrid / v2 /
# coarse), kept as numerics oracles and A/B baselines.
USE_MASKED_FLASH = True

# row-run DMA kernels (blocksparse_v2.py) for the no-attn-mask path
# within the LEGACY dispatch; flip off to fall back to the per-triple v1
# kernels. DEPRECATED AS A DISPATCH TARGET: the v1 one-program-per-
# nonzero-block grid loses to dense flash on launch overhead (~10k
# sequential launches at a 128-block Longformer S=8192 layout), so the
# automatic dispatch NEVER selects it anymore — an unstreamable block
# size now routes to the unified masked kernel instead. v1 stays
# importable/buildable (set USE_SPLASH_V2 = False explicitly) as a test
# oracle only.
USE_SPLASH_V2 = True

# banded fast path (banded.py): layouts that match the global-prefix +
# sliding-window predicate (BSLongformer-class) skip all CSR/DMA-stream
# machinery — masks are computed from iota block arithmetic in registers
USE_BANDED = True

# hybrid banded+residual path (hybrid.py): mostly-banded layouts with a
# small non-banded residue (BigBird random blocks) run the banded
# kernels on the banded sub-pattern and the v2 walk on the residue,
# merged by per-part log-sum-exp (flash-decoding style)
USE_HYBRID = True

# layout coarsening (blocksparse_v2.build_coarse_index): walk coarse
# tiles, express fine structure as streamed NEG_INF mask tiles. Auto by
# cost model; _FORCE_COARSE_BLOCK: None = auto, 0 = off, N = force N.
USE_COARSE = True
_FORCE_COARSE_BLOCK = None
_COARSE_TILE_BUDGET = 256 * 2 ** 20   # bytes of unique (CB, CB) tiles


def _iter_cost_us(blk):
    """Empirical per-inner-iteration cost (v5e, 2026-07-31 ladder): a
    ~2us fixed floor (DMA latency + loop/VPU epilogue) plus ~22us of
    MXU+VPU work at a 512-wide tile, linear in tile width below that.
    Only RATIOS matter — this picks between walking many fine tiles and
    fewer coarse tiles with masked lanes."""
    return 2.0 + 22.0 * (blk / 512.0)


def _pick_coarse_block(layout: np.ndarray, block: int, has_am: bool):
    """Choose a coarse walk-tile size (or None): coarsening must beat the
    fine walk's modeled cost by >10% and keep the unique mask tiles under
    the HBM budget. Fine blocks that v2 cannot stream (block % 128 != 0)
    are costed at the v1 per-triple launch overhead (~30us/block), which
    coarsening almost always beats."""
    if not USE_COARSE:
        return None
    if _FORCE_COARSE_BLOCK is not None:
        cb = _FORCE_COARSE_BLOCK
        if not cb:
            return None
        H, nq, nk = layout.shape
        assert cb > block and cb % block == 0 and cb % 128 == 0 and \
            (nq * block) % cb == 0 and (nk * block) % cb == 0, (
                f"_FORCE_COARSE_BLOCK={cb} incompatible with block={block}, "
                f"S=({nq * block},{nk * block})")
        return cb
    from deepspeed_tpu.ops.sparse_attention.blocksparse_v2 import (
        build_coarse_index)
    H, nq, nk = layout.shape
    nnz_f = int(np.count_nonzero(layout))
    fine_cost = nnz_f * (_iter_cost_us(block) if block % 128 == 0
                         else 30.0)
    best = None
    for cb in (512, 256):
        if cb <= block or cb % block or (nq * block) % cb or \
                (nk * block) % cb:
            continue
        # count_only passes here + the winner's full build in
        # build_v2_impls re-hash the (f, f) patterns up to 3x per fn-cache
        # miss — a few thousand tiny tobytes() calls, negligible next to
        # the kernel compile the miss is about to pay
        nnz_c, n_unique = build_coarse_index(layout, block, cb,
                                             per_coord=has_am,
                                             count_only=True)
        if n_unique * cb * cb * 4 > _COARSE_TILE_BUDGET:
            continue
        cost = nnz_c * _iter_cost_us(cb)
        if cost < fine_cost * 0.9 and (best is None or cost < best[0]):
            best = (cost, cb)
    return best[1] if best else None


def planned_kernel(layout, block, has_am=False, interpret=False) -> str:
    """Which kernel family _sparse_attention_fn would build for this
    layout — diagnostic/bench reporting only: 'masked[-coarse<N>]'
    (unified kernel, the default) | 'banded' | 'hybrid' | 'v2-coarse<N>'
    | 'v2' | 'masked-fallback' | 'v1' (explicit USE_SPLASH_V2=False
    only — retired as an automatic dispatch target)."""
    layout = np.asarray(layout)
    if USE_MASKED_FLASH and not has_am:
        from deepspeed_tpu.ops.attention.masked_flash import BlockMask
        bm = BlockMask.from_layout(layout, block)
        return (f"masked-coarse{bm.block}" if bm.block != block
                else "masked")
    if USE_BANDED and not has_am:
        from deepspeed_tpu.ops.sparse_attention import banded as _b
        if _b.plan(layout, block, interpret) is not None:
            return "banded"
        if USE_HYBRID and USE_SPLASH_V2:
            from deepspeed_tpu.ops.sparse_attention import hybrid as _h
            if _h.plan_hybrid(layout, block, interpret) is not None:
                return "hybrid"
    coarse = (_pick_coarse_block(layout, block, has_am)
              if USE_SPLASH_V2 else None)
    if USE_SPLASH_V2 and (interpret or block % 128 == 0
                          or coarse is not None):
        return f"v2-coarse{coarse}" if coarse else "v2"
    if USE_SPLASH_V2:
        # the v1-retirement route: plain layouts land on the unified
        # kernel; a user attn mask lands on the differentiable dense
        # reference (_build_masked_fn has_am) — report what actually
        # runs, O(S^2) included
        return "reference-fallback" if has_am else "masked-fallback"
    return "v1"


def _build_masked_fn(layout: np.ndarray, block: int, sm_scale: float,
                     interpret: bool, has_am: bool = False):
    """The unified masked-kernel implementation with the legacy impl
    signature ``f(q, k, v, kpm[, am])`` (kpm pre-blocked additive
    ``(B, nk, 1, block)``). The layout becomes a :class:`BlockMask`
    (head-uniform layouts collapse; banded layouts coarsen to MXU-sized
    walk tiles with the fine structure in register predicates).

    ``has_am``: the unified kernel carries no streamed user-mask
    channel, so a pre-blocked attention mask falls back to the
    DIFFERENTIABLE dense reference — only reachable from the
    v1-retirement branch (unstreamable block + user mask), never for
    the plain layout path."""
    from deepspeed_tpu.ops.attention.masked_flash import (
        BlockMask, masked_flash_attention)
    if has_am:
        from deepspeed_tpu.utils.logging import log_once
        log_once(("masked-am-reference", layout.shape, block),
                 "block_sparse_attention: user attention mask with an "
                 "unstreamable block size — using the O(S^2) dense "
                 "reference (differentiable) instead of the retired v1 "
                 "kernels.")

        def fref(q, k, v, kpm, am):
            B, _, S, _ = q.shape
            am_flat = am.transpose(0, 2, 1, 3).reshape(S, S)
            return block_sparse_attention_reference(
                q, k, v, layout, sm_scale=sm_scale,
                key_padding_mask=kpm.reshape(B, S),
                key_padding_mask_mode="add",
                attn_mask=am_flat, attn_mask_mode="add")
        return fref

    bm = BlockMask.from_layout(layout, block)

    def fm(q, k, v, kpm):
        B, _, S, _ = q.shape
        return masked_flash_attention(q, k, v, bm,
                                      key_mask=kpm.reshape(B, S),
                                      sm_scale=sm_scale,
                                      interpret=interpret)
    return fm


def _sparse_attention_fn(layout: np.ndarray, block: int, sm_scale: float,
                         has_am: bool, interpret: bool):
    """Returns f(q, k, v, kpm[, am]) -> o with a custom VJP, where q/k/v are
    (B, H, S, D), kpm a pre-blocked additive (B, nk, 1, block) mask and am a
    pre-blocked additive (nq, nk, block, block) mask. Nonzero-block triples
    are closed over as static data and fed to Mosaic via scalar prefetch."""
    from deepspeed_tpu.ops.sparse_attention import banded as _banded
    key = (layout.shape, layout.tobytes(), block, float(sm_scale), has_am,
           interpret, USE_MASKED_FLASH, USE_SPLASH_V2, USE_COARSE,
           _FORCE_COARSE_BLOCK, _COARSE_TILE_BUDGET, USE_BANDED,
           USE_HYBRID, _banded._FORCE_BLOCKS)
    if key in _FN_CACHE:
        return _FN_CACHE[key]

    if USE_MASKED_FLASH and not has_am:
        fm = _build_masked_fn(layout, block, float(sm_scale), interpret)
        _FN_CACHE[key] = fm
        return fm

    if USE_BANDED and not has_am:
        planned = _banded.plan(layout, block, interpret)
        if planned is not None:
            bp, blocks = planned
            fb = _banded.build_banded_fn(layout.shape, block, bp,
                                         float(sm_scale), blocks,
                                         interpret)
            _FN_CACHE[key] = fb
            return fb
        if USE_HYBRID and USE_SPLASH_V2:
            from deepspeed_tpu.ops.sparse_attention import hybrid as _h
            hplan = _h.plan_hybrid(layout, block, interpret)
            if hplan is not None:
                fh = _h.build_hybrid_fn(layout, block, hplan,
                                        float(sm_scale), interpret)
                _FN_CACHE[key] = fh
                return fh

    H, nq, nk = layout.shape
    coarse_block = (_pick_coarse_block(layout, block, has_am)
                    if USE_SPLASH_V2 else None)
    use_v2 = USE_SPLASH_V2 and (interpret or block % 128 == 0
                                or coarse_block is not None)
    if not use_v2 and USE_SPLASH_V2 and not interpret:
        # v2 wanted but the block width can't be a DMA lane dim and no
        # coarse walk tile fits either. The v1 per-triple kernels are
        # RETIRED as a dispatch target (launch overhead ~row-degree x):
        # route to the unified masked kernel, whose resident mode
        # handles any block size, instead of silently selecting v1.
        from deepspeed_tpu.utils.logging import log_once
        log_once(("v1-retired", block, layout.shape),
                 f"block_sparse_attention: block={block} cannot "
                 "DMA-stream (not a 128 multiple) and no coarse walk "
                 "tile divides the sequence — routing to the unified "
                 "masked kernel (resident K/V) instead of the retired "
                 "per-triple v1 kernels.")
        fm = _build_masked_fn(layout, block, float(sm_scale), interpret,
                              has_am=has_am)
        _FN_CACHE[key] = fm
        return fm
    if use_v2:
        # row-run kernels: one program per block row, K/V (and the
        # deduped attn-mask tiles) streamed by DMA (blocksparse_v2.py)
        # — ~row-degree x fewer program launches. Compiled mode needs
        # 128-multiple WALK blocks: a streamed tile puts the block width
        # in the DMA lane dim, which Mosaic requires to be 128-aligned.
        # When the cost model picked a coarse walk tile, the fine layout
        # (any block size) rides the streamed-mask channel instead.
        from deepspeed_tpu.ops.sparse_attention.blocksparse_v2 import (
            build_v2_impls)
        fwd2, bwd2 = build_v2_impls(layout, block, sm_scale, interpret,
                                    has_am=has_am,
                                    coarse_block=coarse_block)

        if has_am:
            @jax.custom_vjp
            def f2(q, k, v, kpm, am):
                return fwd2(q, k, v, kpm, am)[0]

            def f2_fwd(q, k, v, kpm, am):
                o, lse = fwd2(q, k, v, kpm, am)
                return o, (q, k, v, kpm, am, o, lse)

            def f2_bwd(res, g):
                q, k, v, kpm, am, o, lse = res
                dq, dk, dv = bwd2(q, k, v, kpm, am, o, lse, g)
                return (dq, dk, dv, jnp.zeros_like(kpm),
                        jnp.zeros_like(am))
        else:
            @jax.custom_vjp
            def f2(q, k, v, kpm):
                return fwd2(q, k, v, kpm, None)[0]

            def f2_fwd(q, k, v, kpm):
                o, lse = fwd2(q, k, v, kpm, None)
                return o, (q, k, v, kpm, o, lse)

            def f2_bwd(res, g):
                q, k, v, kpm, o, lse = res
                dq, dk, dv = bwd2(q, k, v, kpm, None, o, lse, g)
                return dq, dk, dv, jnp.zeros_like(kpm)

        f2.defvjp(f2_fwd, f2_bwd)
        _FN_CACHE[key] = f2
        return f2
    rt = build_triples(layout)                            # row-major walk
    ct = build_triples(np.ascontiguousarray(layout.transpose(0, 2, 1)))
    T = rt[0].shape[0]
    CT = ct[0].shape[0]
    compiler_params = None
    if pltpu is not None and not interpret:
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"))

    # index-map convention (repeated inline in every BlockSpec below):
    # i = batch, t = triple id; row triples encode h * nq + qb, so
    # bh = i * H + tr[t] // nq and qb = tr[t] % nq; column-major triples
    # (cr) encode h * nk + kb analogously.

    def fwd_impl(q, k, v, kpm, am):
        B, _, S, D = q.shape
        qr = q.reshape(B * H, S, D)
        kr = k.reshape(B * H, S, D)
        vr = v.reshape(B * H, S, D)

        kernel = functools.partial(_bs_fwd_kernel, sm_scale=sm_scale)
        in_specs = [
            pl.BlockSpec((1, block, D),
                         lambda i, t, tr, *_: (i * H + tr[t] // nq,
                                               tr[t] % nq, 0)),
            pl.BlockSpec((1, block, D),
                         lambda i, t, tr, tc, *_: (i * H + tr[t] // nq,
                                                   tc[t], 0)),
            pl.BlockSpec((1, block, D),
                         lambda i, t, tr, tc, *_: (i * H + tr[t] // nq,
                                                   tc[t], 0)),
            pl.BlockSpec((1, 1, 1, block),
                         lambda i, t, tr, tc, *_: (i, tc[t], 0, 0)),
        ]
        args = [qr, kr, vr, kpm]
        if has_am:
            in_specs.append(pl.BlockSpec(
                (1, 1, block, block),
                lambda i, t, tr, tc, *_: (tr[t] % nq, tc[t], 0, 0)))
            args.append(am)
        else:
            kernel = _drop_am(kernel, 9)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(B, T),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, block, D),
                             lambda i, t, tr, *_: (i * H + tr[t] // nq,
                                                   tr[t] % nq, 0)),
                pl.BlockSpec((1, block, 1),
                             lambda i, t, tr, *_: (i * H + tr[t] // nq,
                                                   tr[t] % nq, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((block, 1), jnp.float32),      # running max
                pltpu.VMEM((block, 1), jnp.float32),      # running sum
                pltpu.VMEM((block, D), jnp.float32),      # output accum
            ])
        o, lse = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
                jax.ShapeDtypeStruct((B * H, S, 1), jnp.float32),
            ],
            interpret=interpret,
            compiler_params=compiler_params,
        )(*(jnp.asarray(x) for x in rt), *args)
        return o.reshape(B, H, S, D), lse

    def bwd_impl(q, k, v, kpm, am, o, lse, g):
        B, _, S, D = q.shape
        qr = q.reshape(B * H, S, D)
        kr = k.reshape(B * H, S, D)
        vr = v.reshape(B * H, S, D)
        dor = g.reshape(B * H, S, D)
        delta = jnp.sum(dor.astype(jnp.float32) *
                        o.reshape(B * H, S, D).astype(jnp.float32),
                        axis=-1, keepdims=True)           # (B*H, S, 1)

        # ---- dq (row-major triples) ----
        kernel = functools.partial(_bs_dq_kernel, sm_scale=sm_scale)
        in_specs = [
            pl.BlockSpec((1, block, D),
                         lambda i, t, tr, *_: (i * H + tr[t] // nq,
                                               tr[t] % nq, 0)),
            pl.BlockSpec((1, block, D),
                         lambda i, t, tr, tc, *_: (i * H + tr[t] // nq,
                                                   tc[t], 0)),
            pl.BlockSpec((1, block, D),
                         lambda i, t, tr, tc, *_: (i * H + tr[t] // nq,
                                                   tc[t], 0)),
            pl.BlockSpec((1, 1, 1, block),
                         lambda i, t, tr, tc, *_: (i, tc[t], 0, 0)),
        ]
        args = [qr, kr, vr, kpm]
        if has_am:
            in_specs.append(pl.BlockSpec(
                (1, 1, block, block),
                lambda i, t, tr, tc, *_: (tr[t] % nq, tc[t], 0, 0)))
            args.append(am)
        else:
            kernel = _drop_am(kernel, 9)
        in_specs += [
            pl.BlockSpec((1, block, D),
                         lambda i, t, tr, *_: (i * H + tr[t] // nq,
                                               tr[t] % nq, 0)),
            pl.BlockSpec((1, block, 1),
                         lambda i, t, tr, *_: (i * H + tr[t] // nq,
                                               tr[t] % nq, 0)),
            pl.BlockSpec((1, block, 1),
                         lambda i, t, tr, *_: (i * H + tr[t] // nq,
                                               tr[t] % nq, 0)),
        ]
        args += [dor, lse, delta]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(B, T),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, block, D),
                                   lambda i, t, tr, *_: (i * H + tr[t] // nq,
                                                         tr[t] % nq, 0)),
            scratch_shapes=[pltpu.VMEM((block, D), jnp.float32)])
        dq = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
            interpret=interpret,
            compiler_params=compiler_params,
        )(*(jnp.asarray(x) for x in rt), *args)

        # ---- dk, dv (column-major triples; crow = h * nk + kb) ----
        kernel = functools.partial(_bs_dkv_kernel, sm_scale=sm_scale)
        in_specs = [
            pl.BlockSpec((1, block, D),
                         lambda i, t, cr, cc, *_: (i * H + cr[t] // nk,
                                                   cc[t], 0)),
            pl.BlockSpec((1, block, D),
                         lambda i, t, cr, *_: (i * H + cr[t] // nk,
                                               cr[t] % nk, 0)),
            pl.BlockSpec((1, block, D),
                         lambda i, t, cr, *_: (i * H + cr[t] // nk,
                                               cr[t] % nk, 0)),
            pl.BlockSpec((1, 1, 1, block),
                         lambda i, t, cr, *_: (i, cr[t] % nk, 0, 0)),
        ]
        args = [qr, kr, vr, kpm]
        if has_am:
            in_specs.append(pl.BlockSpec(
                (1, 1, block, block),
                lambda i, t, cr, cc, *_: (cc[t], cr[t] % nk, 0, 0)))
            args.append(am)
        else:
            kernel = _drop_am(kernel, 9)
        in_specs += [
            pl.BlockSpec((1, block, D),
                         lambda i, t, cr, cc, *_: (i * H + cr[t] // nk,
                                                   cc[t], 0)),
            pl.BlockSpec((1, block, 1),
                         lambda i, t, cr, cc, *_: (i * H + cr[t] // nk,
                                                   cc[t], 0)),
            pl.BlockSpec((1, block, 1),
                         lambda i, t, cr, cc, *_: (i * H + cr[t] // nk,
                                                   cc[t], 0)),
        ]
        args += [dor, lse, delta]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(B, CT),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, block, D),
                             lambda i, t, cr, *_: (i * H + cr[t] // nk,
                                                   cr[t] % nk, 0)),
                pl.BlockSpec((1, block, D),
                             lambda i, t, cr, *_: (i * H + cr[t] // nk,
                                                   cr[t] % nk, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((block, D), jnp.float32),
                pltpu.VMEM((block, D), jnp.float32),
            ])
        dk, dv = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((B * H, S, D), k.dtype),
                jax.ShapeDtypeStruct((B * H, S, D), v.dtype),
            ],
            interpret=interpret,
            compiler_params=compiler_params,
        )(*(jnp.asarray(x) for x in ct), *args)
        return (dq.reshape(q.shape), dk.reshape(k.shape),
                dv.reshape(v.shape))

    if has_am:
        @jax.custom_vjp
        def f(q, k, v, kpm, am):
            return fwd_impl(q, k, v, kpm, am)[0]

        def f_fwd(q, k, v, kpm, am):
            o, lse = fwd_impl(q, k, v, kpm, am)
            return o, (q, k, v, kpm, am, o, lse)

        def f_bwd(res, g):
            q, k, v, kpm, am, o, lse = res
            dq, dk, dv = bwd_impl(q, k, v, kpm, am, o, lse, g)
            return dq, dk, dv, jnp.zeros_like(kpm), jnp.zeros_like(am)
    else:
        @jax.custom_vjp
        def f(q, k, v, kpm):
            return fwd_impl(q, k, v, kpm, None)[0]

        def f_fwd(q, k, v, kpm):
            o, lse = fwd_impl(q, k, v, kpm, None)
            return o, (q, k, v, kpm, o, lse)

        def f_bwd(res, g):
            q, k, v, kpm, o, lse = res
            dq, dk, dv = bwd_impl(q, k, v, kpm, None, o, lse, g)
            return dq, dk, dv, jnp.zeros_like(kpm)

    f.defvjp(f_fwd, f_bwd)
    _FN_CACHE[key] = f
    return f


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
    routes through the jnp oracle — it defeats sparse storage anyway.
    """
    B, H, S, D = q.shape
    layout = np.asarray(layout)
    assert layout.ndim == 3 and layout.shape[0] == H, \
        f"layout heads {layout.shape} vs q heads {H}"
    assert S % layout.shape[1] == 0, (S, layout.shape)
    block = S // layout.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(D)
    if interpret is None:
        interpret = not _use_pallas()
    if force_reference or rpe is not None:
        return block_sparse_attention_reference(
            q, k, v, layout, sm_scale=sm_scale,
            key_padding_mask=key_padding_mask,
            key_padding_mask_mode=key_padding_mask_mode,
            attn_mask=attn_mask, attn_mask_mode=attn_mask_mode, rpe=rpe)

    kpm = jnp.zeros((B, S), jnp.float32) if key_padding_mask is None else \
        _to_additive(key_padding_mask, key_padding_mask_mode)
    kpm = _block_kpm(kpm, block)
    f = _sparse_attention_fn(layout, block, float(sm_scale),
                             has_am=attn_mask is not None,
                             interpret=interpret)
    if attn_mask is not None:
        am = _block_am(_to_additive(attn_mask, attn_mask_mode), block)
        return f(q, k, v, kpm, am)
    return f(q, k, v, kpm)
