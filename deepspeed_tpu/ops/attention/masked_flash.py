"""ONE mask-parameterized Pallas flash-attention kernel (training side).

Dense, causal, banded (Longformer-class) and BigBird block-sparse
attention differ only in *which K/V tiles a query block touches*. This
module is the one online-softmax kernel for all of them, parameterized
by a static :class:`BlockMask`: ``flash_attention`` compiles it with a
dense or causal mask, ``block_sparse_attention`` with a SparsityConfig
layout. (``flash.py`` keeps its own chunk kernels for ring attention
and for a causal call with ``sq != sk``.)

Design (the PR 8 paged-decode recipe applied to training):

- **Scalar-prefetched CSR walk.** The mask compiles to a per-(head,
  query-block) column list delivered through
  ``pltpu.PrefetchScalarGridSpec`` (SMEM): each program walks only its
  row's nonzero K/V tiles with an inner ``fori_loop``, so FLOPs and HBM
  bytes scale with nonzero blocks, not S².
- **Partial tiles mask in registers.** A mask item is FULL (every cell
  computed — the reference's block-level mask semantics) or PARTIAL: an
  elementwise predicate evaluated from iota arithmetic in registers —
  the causal diagonal (``q_idx >= k_idx``), a window's far edge
  (``q_idx - k_idx <= w``) and/or the banded fine structure (global
  prefix + sliding window at the layout's fine block granularity).
  That is what lets a 128-fine-block Longformer layout *walk 512-wide
  MXU tiles* with zero mask bytes from HBM.
- **The mask costs only where it cuts.** A row's items are listed one
  run a kind, FULL first, and each run is a loop of its own whose body
  is traced for that STATIC kind: the FULL body computes no index, no
  predicate and no select (without a key mask no validity select
  either: its scores are finite), a partial body only the comparisons
  of its kind's bits. ``sm_scale`` is folded once a program into the
  resident operand instead of multiplied into every score.
- **Stream vs resident.** Below ``flash.STREAM_THRESHOLD`` the per-head
  K/V arrays ride as VMEM-resident blocked refs sliced at
  ``cols[i] * block``; at/above it they stay in HBM pre-tiled TRANSPOSED
  as ``(rows, n_blocks, D, block)`` and stream through double-buffered
  ``make_async_copy`` DMA (2 tiles of VMEM at any S; the block width is
  the 128-aligned lane dim).
- **Forward + custom-vjp backward.** dq re-walks the CSR rows; dk/dv
  walk column-major via CSC metadata (one program per key block,
  streaming q/do), flash-style recompute from the stored lse. The
  in-kernel counter-hash dropout (``flash.dropout_keep_mask``) is keyed
  on absolute ``(seed, batch*head, q_idx, k_idx)`` so the forward and
  both backward passes regenerate identical bits — and so a dense
  BlockMask reproduces ``flash.py``'s dropout pattern exactly.
- **GQA native.** ``kv_heads < heads``: each group of consecutive q
  heads reads its shared K/V row via the index map (resident) or the
  DMA row select (streamed); dk/dv accumulate per-q-head fp32 partials
  summed per group outside (the ``flash.py`` scheme).

The IDENTICAL kernel runs ``interpret=True`` on CPU (scalar prefetch,
HBM refs, dynamic-index DMA all interpret), which is what makes parity
against the existing oracles (``attention_reference``,
``block_sparse_attention_reference``) tier-1-testable hardware-free.

Sharding: a pallas_call cannot be auto-partitioned by GSPMD — wrap it
with ``parallel/pallas_shard.sharded_masked_flash`` to run under a mesh
(head-sharded; requires a head-uniform mask).
"""

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from deepspeed_tpu.ops.attention import flash as _flash
from deepspeed_tpu.ops.attention.flash import (NEG_INF, STREAM_THRESHOLD,
                                               _stream_layout,
                                               dropout_keep_mask,
                                               dropout_seed_from_rng)

try:
    from jax.experimental.pallas import tpu as pltpu
except ImportError:  # pragma: no cover
    pltpu = None

__all__ = ["BlockMask", "masked_flash_attention", "masked_flash_cost",
           "masked_flash_reference"]

# scores below this are structurally masked (several -1e30 additive
# terms may stack; finite bf16 scores never approach it)
VALID_THRESH = -1e28

# partial-tile predicate bits (BlockMask.kinds cell values); a tile's
# kind is static, and its loop body evaluates only its kind's bits
KIND_FULL = 0          # every cell computed (block-level mask semantics)
KIND_CAUSAL = 1        # elementwise q_idx >= k_idx (diagonal tiles)
KIND_BAND = 2          # banded fine structure (global prefix + window)
KIND_WINDOW = 4        # elementwise q_idx - k_idx <= w (a window's far
#                        edge; element-exact bands only, fine block 1)

# test hooks: force the streamed / resident K-V path regardless of
# sequence length (None = auto by STREAM_THRESHOLD)
_FORCE_STREAM: Optional[bool] = None


def _iter_cost_us(blk: int) -> float:
    # per-inner-iteration cost: a fixed floor (loop + DMA re-arm) plus
    # MXU work linear in tile width. Only ratios matter — it picks
    # between walking many fine tiles and fewer coarse tiles whose masked
    # lanes ride register predicates.
    return 2.0 + 22.0 * (blk / 512.0)


class BandedParams(NamedTuple):
    g_r: int      # global ROW prefix, in fine blocks (rows that see all)
    g_c: int      # global COL prefix, in fine blocks (cols all rows see)
    w: int        # band half-width, in fine blocks
    causal: bool  # block-level lower-triangular clip


def detect_banded(layout: np.ndarray) -> Optional[BandedParams]:
    """Match a (H, nb, nb) 0/1 layout against the global-prefix + band
    predicate. Returns params or None (per-head layouts, non-prefix
    globals, random blocks, fully dense all decline)."""
    L = np.asarray(layout).astype(bool)
    if L.ndim != 3 or L.shape[1] != L.shape[2] or L.shape[1] == 0:
        return None
    l = L[0]
    if not (L == l[None]).all():
        return None
    n = l.shape[0]
    idx = np.arange(n)
    rb, cb = idx[:, None], idx[None, :]
    for causal in (False, True):
        clip = (cb <= rb) if causal else np.ones((n, n), bool)
        # global prefixes: leading rows/cols equal to their clip pattern
        row_full = (l == clip).all(axis=1)
        col_full = (l == clip).all(axis=0)
        g_r = 0
        while g_r < n and row_full[g_r]:
            g_r += 1
        g_c = 0
        while g_c < n and col_full[g_c]:
            g_c += 1
        if g_r >= n:          # fully dense: nothing to coarsen
            continue
        # infer w from the last row (never a global row here): its
        # non-global cols must be a contiguous run ending at the diagonal
        last = np.nonzero(l[n - 1, g_c:])[0] + g_c
        if len(last) == 0:
            # pure-global layout (no band): the band predicate would need
            # a w=-1 "empty band" special case — leave it to the fine
            # walk (rare, and tiny at any realistic density)
            continue
        run = np.arange(int(last.min()), n)
        if len(last) != len(run) or not (last == run).all():
            continue
        w = (n - 1) - int(last.min())
        pred = ((rb < g_r) | (cb < g_c) | (np.abs(rb - cb) <= w)) & clip
        if (pred == l).all():
            return BandedParams(g_r, g_c, w, bool(causal))
    return None


class BlockMask:
    """Static block-level attention mask for the unified kernel.

    ``active``: (Hm, nq, nk) bool — which (q-block, k-block) tiles are
    walked; ``kinds``: (Hm, nq, nk) uint8 bitmask over active tiles
    (KIND_CAUSAL / KIND_BAND / KIND_WINDOW; 0 = full). ``Hm`` is 1 for
    head-uniform masks (dense, causal, propagated sparse layouts — the
    common case, and the only one the shard_map head wrap accepts) or
    the full head count for per-head layouts. ``band`` carries the static fine
    structure for KIND_BAND tiles:
    ``(fine_block, w, g_r, g_c, causal_clip)`` in fine-block units
    (KIND_WINDOW tiles read its ``w`` alone).

    Instances are immutable, hashable (usable as a ``custom_vjp``
    static argument) and cache their CSR/CSC walk metadata.
    """

    def __init__(self, active: np.ndarray, kinds: np.ndarray, block: int,
                 seq_q: int, seq_k: int,
                 band: Optional[Tuple[int, int, int, int, bool]] = None,
                 fine_block: Optional[int] = None):
        active = np.ascontiguousarray(np.asarray(active, bool))
        kinds = np.ascontiguousarray(np.asarray(kinds, np.uint8))
        assert active.ndim == 3 and active.shape == kinds.shape, (
            active.shape, kinds.shape)
        Hm, nq, nk = active.shape
        assert nq * block == seq_q and nk * block == seq_k, (
            active.shape, block, seq_q, seq_k)
        self.active = active
        self.kinds = kinds
        self.block = int(block)
        self.seq_q = int(seq_q)
        self.seq_k = int(seq_k)
        self.heads = Hm
        self.band = tuple(band) if band is not None else None
        # the layout's original block granularity (== block unless the
        # walk was coarsened); reporting only
        self.fine_block = int(fine_block or block)
        # the kinds that occur, ascending (FULL first): one loop each
        self.run_kinds = tuple(
            int(x) for x in np.unique(kinds[active])) or (KIND_FULL,)
        self._key = (self.block, self.seq_q, self.seq_k, self.band,
                     active.tobytes(), kinds.tobytes())
        self._csr = None
        self._csc = None

    # ---------------------------------------------------- constructors
    @classmethod
    def dense(cls, seq_q: int, seq_k: int, block: int) -> "BlockMask":
        nq, nk = seq_q // block, seq_k // block
        return cls(np.ones((1, nq, nk), bool),
                   np.zeros((1, nq, nk), np.uint8), block, seq_q, seq_k)

    @classmethod
    def causal(cls, seq: int, block: int) -> "BlockMask":
        """Square causal mask: tiles below the diagonal are FULL, the
        diagonal tiles apply the elementwise clip, above is skipped."""
        nb = seq // block
        r = np.arange(nb)[:, None]
        c = np.arange(nb)[None, :]
        active = (r >= c)[None]
        kinds = np.where(r == c, KIND_CAUSAL, KIND_FULL
                         ).astype(np.uint8)[None]
        return cls(active, kinds * active, block, seq, seq)

    @classmethod
    def causal_window(cls, seq: int, window: int,
                      block: int) -> "BlockMask":
        """Square causal mask inside a sliding window: query ``i`` sees
        key ``j`` where ``j <= i`` and ``i - j < window``, to the
        element. Tiles wholly inside the band are FULL and tiles
        outside are never walked; a tile carries only the comparison
        that can cut it: the diagonal's the causal one, one the window's
        far edge crosses ``i - j <= window - 1`` (a diagonal tile wider
        than the window both)."""
        assert window >= 1, window
        nb = seq // block
        d = np.arange(nb)[:, None] - np.arange(nb)[None, :]
        # a tile's pairs span i - j in [d*block - (block-1), d*block + block-1]
        active = (d >= 0) & (d * block - (block - 1) <= window - 1)
        kinds = (np.where(d == 0, KIND_CAUSAL, 0)
                 | np.where(d * block + (block - 1) > window - 1,
                            KIND_WINDOW, 0)).astype(np.uint8)
        return cls(active[None], (kinds * active)[None], block, seq, seq,
                   band=(1, window - 1, 0, 0, True), fine_block=1)

    @classmethod
    def from_layout(cls, layout: np.ndarray, fine_block: int,
                    walk_block: Optional[int] = None) -> "BlockMask":
        """A SparsityConfig layout (H, nb, nb) as a BlockMask.

        Head-identical layouts collapse to one mask head (metadata
        shrinks by H and the head-sharded wrap becomes legal). When the
        realized layout matches the banded predicate
        (:func:`detect_banded` — BSLongformer-class), the walk is
        COARSENED to a larger MXU-friendly tile and the fine structure
        rides the in-register KIND_BAND predicate; tiles fully inside
        the band stay FULL. Non-banded layouts (BigBird random blocks,
        per-head layouts) walk at the fine block. ``walk_block`` forces
        a specific coarse tile (0 forces the fine walk)."""
        layout = np.asarray(layout)
        assert layout.ndim == 3 and layout.shape[1] == layout.shape[2], \
            layout.shape
        if (layout == layout[:1]).all():
            layout = layout[:1]                  # head-uniform: collapse
        H, nb, _ = layout.shape
        S = nb * fine_block
        fine = layout.astype(bool)

        bp = None
        if H == 1:
            bp = detect_banded(layout)
        cb = cls._pick_walk_block(fine, fine_block, S, bp, walk_block)
        if cb is None:
            return cls(fine, np.zeros_like(fine, np.uint8), fine_block,
                       S, S, fine_block=fine_block)
        f = cb // fine_block
        nc = nb // f
        sub = fine.reshape(1, nc, f, nc, f)
        coarse_any = sub.any(axis=(2, 4))
        coarse_all = sub.all(axis=(2, 4))
        kinds = np.where(coarse_any & ~coarse_all, KIND_BAND, KIND_FULL
                         ).astype(np.uint8)
        band = (fine_block, bp.w, bp.g_r, bp.g_c, bool(bp.causal))
        return cls(coarse_any, kinds, cb, S, S, band=band,
                   fine_block=fine_block)

    @staticmethod
    def _pick_walk_block(fine, fine_block, S, bp, walk_block):
        """Coarse walk tile (or None for the fine walk): requires a
        banded-describable layout (the predicate must reproduce every
        partial tile's content exactly) and a modeled win over the fine
        walk's per-iteration overhead. An explicitly requested
        walk_block that cannot be honored raises rather than silently
        measuring the fine walk."""
        if walk_block == 0:
            return None
        if bp is None:
            if walk_block is not None:
                raise ValueError(
                    f"walk_block={walk_block} requested but the layout "
                    "is not banded-describable (per-head, random blocks, "
                    "or non-prefix globals) — coarse partial tiles need "
                    "the register band predicate. Use walk_block=0 (fine "
                    "walk) or a banded layout.")
            return None
        if walk_block is not None:
            assert walk_block > fine_block and \
                walk_block % fine_block == 0 and S % walk_block == 0, (
                    walk_block, fine_block, S)
            return walk_block
        nnz_f = int(fine.sum())
        best = None
        for cb in (512, 256):
            if cb <= fine_block or cb % fine_block or S % cb:
                continue
            f = cb // fine_block
            nc = (S // fine_block) // f
            nnz_c = int(fine.reshape(1, nc, f, nc, f).any(
                axis=(2, 4)).sum())
            cost = nnz_c * _iter_cost_us(cb)
            if cost < nnz_f * _iter_cost_us(fine_block) * 0.9 and \
                    (best is None or cost < best[0]):
                best = (cost, cb)
        return best[1] if best else None

    # ------------------------------------------------------- metadata
    @property
    def nq(self) -> int:
        return self.seq_q // self.block

    @property
    def nk(self) -> int:
        return self.seq_k // self.block

    @property
    def nnz(self) -> int:
        return int(self.active.sum())

    @property
    def has_partials(self) -> bool:
        return self.run_kinds != (KIND_FULL,)

    @property
    def n_full(self) -> int:
        return int((self.active & (self.kinds == KIND_FULL)).sum())

    def csr(self):
        """(offs, ends, cols) over rows mh * nq + r. A row's items are
        ``cols[offs[row]:offs[row] + ends[-1, row]]``, ordered one run a
        kind of ``run_kinds`` (FULL first, ascending columns inside a
        run); run ``i`` of the row ends at item ``ends[i, row]``."""
        if self._csr is None:
            self._csr = self._runs(self.active, self.kinds)
        return self._csr

    def csc(self):
        """(offs, ends, rows) over cols mh * nk + c — the column-major
        walk the dk/dv pass follows, in the same runs."""
        if self._csc is None:
            self._csc = self._runs(
                np.ascontiguousarray(self.active.transpose(0, 2, 1)),
                np.ascontiguousarray(self.kinds.transpose(0, 2, 1)))
        return self._csc

    def _runs(self, active, kinds):
        H, nr, _ = active.shape
        offs, idxs = [], []
        ends = np.zeros((len(self.run_kinds), H * nr), np.int32)
        for h in range(H):
            for r in range(nr):
                offs.append(len(idxs))
                for i, kind in enumerate(self.run_kinds):
                    idxs.extend(np.nonzero(
                        active[h, r] & (kinds[h, r] == kind))[0].tolist())
                    ends[i, h * nr + r] = len(idxs) - offs[-1]
        return (np.asarray(offs, np.int32), ends,
                np.asarray(idxs if idxs else [0], np.int32))

    def dense_additive(self) -> np.ndarray:
        """(Hm, Sq, Sk) additive 0 / NEG_INF expansion — the oracle view
        of what the kernel computes tile-by-tile."""
        b = self.block
        keep = np.kron(self.active, np.ones((b, b), bool))
        qi = np.arange(self.seq_q)[:, None]
        ki = np.arange(self.seq_k)[None, :]
        kinds = np.kron(self.kinds, np.ones((b, b), np.uint8))
        if (kinds & KIND_CAUSAL).any():
            keep &= ~((kinds & KIND_CAUSAL).astype(bool)) | (qi >= ki)
        if self.band is not None and (kinds & KIND_BAND).any():
            fb, w, g_r, g_c, clip = self.band
            qf, kf = qi // fb, ki // fb
            ok = (qf < g_r) | (kf < g_c) | (np.abs(qf - kf) <= w)
            if clip:
                ok &= kf <= qf
            keep &= ~((kinds & KIND_BAND).astype(bool)) | ok
        if (kinds & KIND_WINDOW).any():
            keep &= ~((kinds & KIND_WINDOW).astype(bool)) | (
                qi - ki <= self.band[1])
        return np.where(keep, 0.0, NEG_INF).astype(np.float32)

    def describe(self) -> str:
        s = f"masked(block={self.block}, nnz={self.nnz}/" \
            f"{self.heads * self.nq * self.nk}, full={self.n_full}, " \
            f"partial={self.nnz - self.n_full}"
        if self.block != self.fine_block:
            s += f", coarsened from {self.fine_block}"
        return s + ")"

    # ----------------------------------------------------- hash / eq
    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return isinstance(other, BlockMask) and self._key == other._key


# --------------------------------------------------------------------- #
# cost model: analytic accounting proportional to nonzero blocks
# --------------------------------------------------------------------- #
def masked_flash_cost(mask: BlockMask, batch: int, heads: int,
                      head_dim: int, dtype_bytes: int = 2,
                      backward: bool = False):
    """Modeled MXU FLOPs and HBM bytes for one forward (optionally +
    backward) pass: analytic accounting, cross-checked structurally
    against the CSR metadata the kernel actually walks
    (``test_masked_flash.py::TestCostModel``).

    The mask-proportional work is separated from the constant terms:
    ``flops`` (QK^T + PV dots per walked item; the dq/dkv recompute and
    grad dots with ``backward``) and ``kv_bytes`` (the K and V tiles
    each item DMAs — what the CSR walk saves vs S^2) scale with nonzero
    blocks; ``io_bytes`` (q read, o/lse write per block row — S*D
    regardless of the mask) does not. ``bytes`` is their sum."""
    hm = heads if mask.heads == 1 else 1       # items cover heads/Hm heads
    items = mask.nnz * hm * batch
    rows = mask.heads * mask.nq * hm * batch
    b, d = mask.block, head_dim
    dots_per_item = 2 if not backward else 2 + 6   # fwd QK+PV; bwd dq:
    # QK+dOV+dsK, dkv: QK+dOV+pdO+dsQ minus shared recompute accounting
    flops = items * dots_per_item * 2 * b * b * d
    kv_tile = b * d * dtype_bytes
    q_tile = b * d * dtype_bytes
    row_io = q_tile + q_tile + b * 4               # q read, o write, lse
    kv_bytes = items * 2 * kv_tile
    io_bytes = rows * row_io
    if backward:
        kv_bytes *= 2                              # dq pass + dkv pass
        io_bytes += rows * 3 * q_tile              # do read, dq/dkv out
    return {"flops": int(flops), "kv_bytes": int(kv_bytes),
            "io_bytes": int(io_bytes),
            "bytes": int(kv_bytes + io_bytes),
            "items": int(items), "block": b}


# --------------------------------------------------------------------- #
# reference (oracle) implementation
# --------------------------------------------------------------------- #
def masked_flash_reference(q, k, v, mask: BlockMask, key_mask=None,
                           sm_scale=None, dropout_rate: float = 0.0,
                           dropout_seed=None):
    """Dense jnp oracle with the mask expanded additively — exact-zero
    probabilities for structurally masked cells, zero output for fully
    masked rows (``block_sparse_attention_reference`` semantics), the
    kernels' hash dropout."""
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(q.shape[-1])
    if k.shape[1] != q.shape[1]:
        rep = q.shape[1] // k.shape[1]
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    if key_mask is not None:
        s = s + key_mask.reshape(
            key_mask.shape[0], 1, 1, -1).astype(jnp.float32)
    s = s + jnp.asarray(mask.dense_additive())[None]
    m = jnp.max(s, axis=-1, keepdims=True)
    m_safe = jnp.where(m <= VALID_THRESH, 0.0, m)
    p = jnp.where(s > VALID_THRESH, jnp.exp(s - m_safe), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    p = p / jnp.where(l == 0.0, 1.0, l)
    if dropout_rate > 0.0:
        b_, h_, sq_, sk_ = p.shape
        keep = _flash.dropout_mask_reference(dropout_seed, b_, h_, sq_,
                                             sk_, dropout_rate)
        p = jnp.where(keep, p, 0.0) / (1.0 - dropout_rate)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


# --------------------------------------------------------------------- #
# in-kernel helpers
# --------------------------------------------------------------------- #
def _tile_idx(q0, k0, bq, bk):
    # (bq, 1) / (1, bk) vectors — every consumer broadcasts (flash.py's
    # dropout-hash optimization carries over unchanged)
    q_idx = q0 + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
    k_idx = k0 + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
    return q_idx, k_idx


def _partial_keep(kind, q_idx, k_idx, band):
    """Elementwise keep of a walked tile of STATIC ``kind`` (never
    FULL): only the comparisons of the kind's bits are traced. The
    causal bit clips to q_idx >= k_idx; the window bit to
    q_idx - k_idx <= w; the band bit applies the fine-block structure
    (global prefix | window, plus the layout's own block-level causal
    clip)."""
    keep = None
    if kind & KIND_CAUSAL:
        keep = q_idx >= k_idx
    if kind & KIND_WINDOW:
        assert band[0] == 1, band      # element-exact bands only
        ok = q_idx <= k_idx + band[1]
        keep = ok if keep is None else keep & ok
    if kind & KIND_BAND:
        fb, w, g_r, g_c, clip = band
        qf = q_idx // fb
        kf = k_idx // fb
        ok = (qf < g_r) | (kf < g_c) | (jnp.abs(qf - kf) <= w)
        if clip:
            ok &= kf <= qf
        keep = ok if keep is None else keep & ok
    return keep


def _cut_scores(s, kind, q0, k0, block, band, dropout_rate):
    """A tile's scores with what its STATIC kind cuts set to NEG_INF,
    and the tile's indices where a cut tile or dropout needs them: a
    FULL tile without dropout computes neither."""
    if kind == KIND_FULL and dropout_rate == 0.0:
        return s, None, None
    q_idx, k_idx = _tile_idx(q0, k0, block, block)
    if kind != KIND_FULL:
        s = jnp.where(_partial_keep(kind, q_idx, k_idx, band), s, NEG_INF)
    return s, q_idx, k_idx


def _walk(ends_ref, row, n_rows, run_kinds, make_body):
    """A row's items in its runs: one ``fori_loop`` a kind, FULL first,
    each with the body of its static kind (online softmax does not care
    in which order the tiles come). The item index runs on through the
    loops, so the streamed path's slot parity does too. The loops carry
    nothing: the accumulators live in VMEM scratch, so a second loop
    costs no copy of them at its boundaries (at 512 x 128 float32 they
    are 64 vector registers' worth each, all there are)."""
    lo = 0
    for i, kind in enumerate(run_kinds):
        hi = ends_ref[i * n_rows + row]
        jax.lax.fori_loop(lo, hi, make_body(kind), None)
        lo = hi


# the running max and sum are kept replicated over a vector register's
# lanes: a (rows, 1) column would be broadcast again at every use
_LANES = 128


def _lanes(x, n):
    """A lane-replicated (rows, 128) statistic at ``n`` lanes."""
    if n <= _LANES:
        return x[:, :n]
    if n % _LANES == 0:
        return pltpu.repeat(x, n // _LANES, axis=1)
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _fold_scale(x, sm_scale):
    # sm_scale once a program on the resident operand, not on every
    # score: multiplied in float32, cast back to what the MXU takes
    return (x.astype(jnp.float32) * sm_scale).astype(x.dtype)


def _dma(src, row, c, buf, slot, sem):
    # src: full (rows, n_blocks, D, block) in HBM, pre-tiled TRANSPOSED
    # (Mosaic requires the DMA lane dim 128-aligned — the block width
    # is, head_dim often is not); whole-tile copy
    return pltpu.make_async_copy(src.at[row, c], buf.at[slot],
                                 sem.at[slot])


def _drop_kpm(kernel, n_before):
    """No-key-mask variant: the dense/causal training path (the hot
    loop) must not pay an all-zero (B, Sk) mask operand + per-tile add
    — insert kpm_ref=None at its positional slot instead."""
    def wrapped(*refs, **kw):
        return kernel(*refs[:n_before], None, *refs[n_before:], **kw)
    return wrapped


# --------------------------------------------------------------------- #
# kernels
# --------------------------------------------------------------------- #
def _mf_fwd_kernel(offs_ref, ends_ref, cols_ref, seed_ref,
                   q_ref, k_ref, v_ref, kpm_ref, o_ref, lse_ref,
                   *scratch, sm_scale, block, H, Hkv, Hm, nq, seq_k,
                   band, run_kinds, dropout_rate, stream):
    m_ref, l_ref, acc_ref = scratch[:3]
    if stream:
        kbuf, vbuf, ksem, vsem = scratch[3:]
    i = pl.program_id(0)                       # b * H + h
    j = pl.program_id(1)                       # q block
    h = jax.lax.rem(i, H)
    row = jax.lax.rem(h, Hm) * nq + j
    n = ends_ref[(len(run_kinds) - 1) * Hm * nq + row]
    base = offs_ref[row]
    kv_row = (i // H) * Hkv + h // (H // Hkv)
    q = _fold_scale(q_ref[0], sm_scale)        # (block, D)
    d = q.shape[-1]

    if stream:
        @pl.when(n > 0)
        def _prologue():
            c0 = cols_ref[base]
            _dma(k_ref, kv_row, c0, kbuf, 0, ksem).start()
            _dma(v_ref, kv_row, c0, vbuf, 0, vsem).start()

    def make_body(kind):
        # a FULL tile's scores are finite unless a key mask empties a
        # row, so only a cut tile or a key mask needs the validity select
        guard = kind != KIND_FULL or kpm_ref is not None

        def body(t, _):
            m, l = m_ref[...], l_ref[...]
            c = cols_ref[base + t]
            if stream:
                @pl.when(t + 1 < n)
                def _prefetch_next():
                    cn = cols_ref[base + t + 1]
                    slot = jax.lax.rem(t + 1, 2)
                    _dma(k_ref, kv_row, cn, kbuf, slot, ksem).start()
                    _dma(v_ref, kv_row, cn, vbuf, slot, vsem).start()
                slot = jax.lax.rem(t, 2)
                _dma(k_ref, kv_row, c, kbuf, slot, ksem).wait()
                _dma(v_ref, kv_row, c, vbuf, slot, vsem).wait()
                k, v = kbuf[slot], vbuf[slot]  # transposed: (D, block)
            else:
                k = k_ref[0, pl.ds(c * block, block), :]
                v = v_ref[0, pl.ds(c * block, block), :]
            s = jax.lax.dot_general(
                q, k, (((1,), (0 if stream else 1,)), ((), ())),
                preferred_element_type=jnp.float32)
            if kpm_ref is not None:
                s += kpm_ref[0, 0, pl.ds(c * block, block)][None, :]
            s, q_idx, k_idx = _cut_scores(
                s, kind, j * block, c * block, block, band, dropout_rate)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            if guard:
                # exact-zero probability for structurally masked cells;
                # rows with no valid entry keep l == 0 and fall out as
                # zero output
                m_safe = jnp.where(m_new <= VALID_THRESH, 0.0, m_new)
                p = jnp.where(s > VALID_THRESH,
                              jnp.exp(s - _lanes(m_safe, block)), 0.0)
            else:
                p = jnp.exp(s - _lanes(m_new, block))
            m_ref[...] = m_new
            l_ref[...] = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            if dropout_rate > 0.0:
                keep = dropout_keep_mask(seed_ref[0], i, q_idx, k_idx,
                                         seq_k, dropout_rate)
                p = jnp.where(keep, p, 0.0)
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v,
                (((1,), (1 if stream else 0,)), ((), ())),
                preferred_element_type=jnp.float32)
            acc_ref[...] = acc_ref[...] * _lanes(alpha, d) + pv
        return body

    m_ref[...] = jnp.full((block, _LANES), NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros((block, _LANES), jnp.float32)
    acc_ref[...] = jnp.zeros((block, d), jnp.float32)
    _walk(ends_ref, row, Hm * nq, run_kinds, make_body)
    m, l = m_ref[:, :1], l_ref[:, :1]
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = acc_ref[...] / l_safe
    if dropout_rate > 0.0:
        out = out * (1.0 / (1.0 - dropout_rate))
    o_ref[0] = out.astype(o_ref.dtype)
    lse_ref[0] = jnp.where(l == 0.0, NEG_INF,
                           jnp.where(m <= VALID_THRESH, 0.0, m)
                           + jnp.log(l_safe))


def _mf_dq_kernel(offs_ref, ends_ref, cols_ref, seed_ref,
                  q_ref, k_ref, v_ref, kpm_ref, do_ref, lse_ref,
                  delta_ref, dq_ref, *scratch, sm_scale, block, H, Hkv,
                  Hm, nq, seq_k, band, run_kinds, dropout_rate, stream):
    acc_ref = scratch[0]
    if stream:
        kbuf, vbuf, ksem, vsem = scratch[1:]
    i = pl.program_id(0)
    j = pl.program_id(1)
    h = jax.lax.rem(i, H)
    row = jax.lax.rem(h, Hm) * nq + j
    n = ends_ref[(len(run_kinds) - 1) * Hm * nq + row]
    base = offs_ref[row]
    kv_row = (i // H) * Hkv + h // (H // Hkv)
    q = _fold_scale(q_ref[0], sm_scale)
    do = do_ref[0]
    lse = lse_ref[0, :, 0]
    delta = delta_ref[0, :, 0]
    d = q.shape[-1]

    if stream:
        @pl.when(n > 0)
        def _prologue():
            c0 = cols_ref[base]
            _dma(k_ref, kv_row, c0, kbuf, 0, ksem).start()
            _dma(v_ref, kv_row, c0, vbuf, 0, vsem).start()

    def make_body(kind):
        guard = kind != KIND_FULL or kpm_ref is not None

        def body(t, _):
            c = cols_ref[base + t]
            if stream:
                @pl.when(t + 1 < n)
                def _prefetch_next():
                    cn = cols_ref[base + t + 1]
                    slot = jax.lax.rem(t + 1, 2)
                    _dma(k_ref, kv_row, cn, kbuf, slot, ksem).start()
                    _dma(v_ref, kv_row, cn, vbuf, slot, vsem).start()
                slot = jax.lax.rem(t, 2)
                _dma(k_ref, kv_row, c, kbuf, slot, ksem).wait()
                _dma(v_ref, kv_row, c, vbuf, slot, vsem).wait()
                k, v = kbuf[slot], vbuf[slot]  # transposed: (D, block)
            else:
                k = k_ref[0, pl.ds(c * block, block), :]
                v = v_ref[0, pl.ds(c * block, block), :]
            s = jax.lax.dot_general(
                q, k, (((1,), (0 if stream else 1,)), ((), ())),
                preferred_element_type=jnp.float32)
            if kpm_ref is not None:
                s += kpm_ref[0, 0, pl.ds(c * block, block)][None, :]
            s, q_idx, k_idx = _cut_scores(
                s, kind, j * block, c * block, block, band, dropout_rate)
            p = jnp.exp(s - lse[:, None])
            if guard:
                p = jnp.where(s > VALID_THRESH, p, 0.0)
            dp = jax.lax.dot_general(
                do, v, (((1,), (0 if stream else 1,)), ((), ())),
                preferred_element_type=jnp.float32)
            if dropout_rate > 0.0:
                keep = dropout_keep_mask(seed_ref[0], i, q_idx, k_idx,
                                         seq_k, dropout_rate)
                dp = jnp.where(keep, dp * (1.0 / (1.0 - dropout_rate)),
                               0.0)
            ds = p * (dp - delta[:, None])
            acc_ref[...] += jax.lax.dot_general(
                ds.astype(k.dtype), k,
                (((1,), (1 if stream else 0,)), ((), ())),
                preferred_element_type=jnp.float32)
        return body

    acc_ref[...] = jnp.zeros((block, d), jnp.float32)
    _walk(ends_ref, row, Hm * nq, run_kinds, make_body)
    dq_ref[0] = (acc_ref[...] * sm_scale).astype(dq_ref.dtype)


def _mf_dkv_kernel(coffs_ref, cends_ref, crows_ref, seed_ref,
                   q_ref, k_ref, v_ref, kpm_ref, do_ref, lse_ref,
                   delta_ref, dk_ref, dv_ref, *scratch, sm_scale, block,
                   H, Hm, nk, seq_k, band, run_kinds, dropout_rate,
                   stream):
    dk_acc, dv_acc = scratch[:2]
    if stream:
        qbuf, dobuf, qsem, dosem = scratch[2:]
    i = pl.program_id(0)                       # b * H + h (q heads)
    jb = pl.program_id(1)                      # k block
    h = jax.lax.rem(i, H)
    col = jax.lax.rem(h, Hm) * nk + jb
    n = cends_ref[(len(run_kinds) - 1) * Hm * nk + col]
    base = coffs_ref[col]
    k = _fold_scale(k_ref[0], sm_scale)        # (block, D)
    v = v_ref[0]
    d = k.shape[-1]
    kpm_row = (kpm_ref[0, 0, pl.ds(jb * block, block)]
               if kpm_ref is not None else None)

    if stream:
        @pl.when(n > 0)
        def _prologue():
            r0 = crows_ref[base]
            _dma(q_ref, i, r0, qbuf, 0, qsem).start()
            _dma(do_ref, i, r0, dobuf, 0, dosem).start()

    def make_body(kind):
        guard = kind != KIND_FULL or kpm_ref is not None

        def body(t, _):
            rq = crows_ref[base + t]
            if stream:
                @pl.when(t + 1 < n)
                def _prefetch_next():
                    rn = crows_ref[base + t + 1]
                    slot = jax.lax.rem(t + 1, 2)
                    _dma(q_ref, i, rn, qbuf, slot, qsem).start()
                    _dma(do_ref, i, rn, dobuf, slot, dosem).start()
                slot = jax.lax.rem(t, 2)
                _dma(q_ref, i, rq, qbuf, slot, qsem).wait()
                _dma(do_ref, i, rq, dobuf, slot, dosem).wait()
                q, do = qbuf[slot], dobuf[slot]    # transposed: (D, block)
            else:
                q = q_ref[0, pl.ds(rq * block, block), :]
                do = do_ref[0, pl.ds(rq * block, block), :]
            lse = lse_ref[0, 0, pl.ds(rq * block, block)]
            delta = delta_ref[0, 0, pl.ds(rq * block, block)]
            s = jax.lax.dot_general(
                q, k, (((0 if stream else 1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)        # (bq, bk)
            if kpm_row is not None:
                s += kpm_row[None, :]
            s, q_idx, k_idx = _cut_scores(
                s, kind, rq * block, jb * block, block, band, dropout_rate)
            p = jnp.exp(s - lse[:, None])
            if guard:
                p = jnp.where(s > VALID_THRESH, p, 0.0)
            dp = jax.lax.dot_general(
                do, v, (((0 if stream else 1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)        # (bq, bk)
            if dropout_rate > 0.0:
                keep = dropout_keep_mask(seed_ref[0], i, q_idx, k_idx,
                                         seq_k, dropout_rate)
                inv_kp = 1.0 / (1.0 - dropout_rate)
                pd = jnp.where(keep, p * inv_kp, 0.0)
                dp = jnp.where(keep, dp * inv_kp, 0.0)
            else:
                pd = p
            dv_acc[...] += jax.lax.dot_general(
                pd.astype(do.dtype), do,
                (((0,), (1 if stream else 0,)), ((), ())),
                preferred_element_type=jnp.float32)        # (bk, D)
            ds = p * (dp - delta[:, None])
            dk_acc[...] += jax.lax.dot_general(
                ds.astype(q.dtype), q,
                (((0,), (1 if stream else 0,)), ((), ())),
                preferred_element_type=jnp.float32)        # (bk, D)
        return body

    dk_acc[...] = jnp.zeros((block, d), jnp.float32)
    dv_acc[...] = jnp.zeros((block, d), jnp.float32)
    _walk(cends_ref, col, Hm * nk, run_kinds, make_body)
    dk_ref[0] = (dk_acc[...] * sm_scale).astype(dk_ref.dtype)
    dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


# --------------------------------------------------------------------- #
# pallas_call wrappers
# --------------------------------------------------------------------- #
def _use_stream(mask: BlockMask, interpret: bool) -> bool:
    if _FORCE_STREAM is not None:
        return _FORCE_STREAM
    if max(mask.seq_q, mask.seq_k) < STREAM_THRESHOLD:
        return False
    if mask.block % 128 != 0 and not interpret:
        # the streamed tile's lane dim is the block width, which Mosaic
        # requires 128-aligned; long irregular-block masks stay resident
        _flash.log_once(
            ("masked-stream", mask.block, mask.seq_q, mask.seq_k),
            f"masked_flash: block {mask.block} at seq "
            f"({mask.seq_q}, {mask.seq_k}) cannot DMA-stream (lane "
            "alignment); K/V stay VMEM-resident — expect VMEM pressure "
            "at this length. Use 128-multiple blocks.", warn=True)
        return False
    return True


def _kernel_statics(mask: BlockMask, H, Hkv, sm_scale, rate, stream):
    return dict(sm_scale=sm_scale, block=mask.block, H=H, Hkv=Hkv,
                Hm=mask.heads, nq=mask.nq, seq_k=mask.seq_k,
                band=mask.band, run_kinds=mask.run_kinds,
                dropout_rate=rate, stream=stream)


def _walk_scalars(runs, seed):
    # the scalar-prefetched walk: a row's offset, its runs' ends
    # (flattened (run, row)), the items; then the dropout seed
    offs, ends, idxs = runs
    return [jnp.asarray(offs), jnp.asarray(ends.reshape(-1)),
            jnp.asarray(idxs), seed.reshape(1).astype(jnp.int32)]


def _f32_scratch(*shapes):
    return [pltpu.VMEM(shape, jnp.float32) for shape in shapes]


def _stream_scratch(d, block, dt_a, dt_b):
    return [pltpu.VMEM((2, d, block), dt_a),
            pltpu.VMEM((2, d, block), dt_b),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,))]


def _masked_fwd(q, k, v, kpm, seed, mask, sm_scale, interpret, rate,
                has_kpm=True):
    b, h, sq, d = q.shape
    hkv = k.shape[1]
    sk = k.shape[2]
    blk = mask.block
    stream = _use_stream(mask, interpret)
    G = h // hkv

    qr = q.reshape(b * h, sq, d)
    kr = k.reshape(b * hkv, sk, d)
    vr = v.reshape(b * hkv, sk, d)

    kernel = functools.partial(
        _mf_fwd_kernel, **_kernel_statics(mask, h, hkv, sm_scale, rate,
                                          stream))
    if not has_kpm:
        kernel = _drop_kpm(kernel, 7)       # 4 scalars + q, k, v
    if stream:
        kv_spec = pl.BlockSpec(memory_space=pltpu.HBM)
        kr = _stream_layout(kr, blk)
        vr = _stream_layout(vr, blk)
    else:
        kv_spec = pl.BlockSpec(
            (1, sk, d),
            lambda i, j, *_: ((i // h) * hkv + (i % h) // G, 0, 0))
    in_specs = [
        pl.BlockSpec((1, blk, d), lambda i, j, *_: (i, j, 0)),   # q
        kv_spec, kv_spec,
    ]
    args = [qr, kr, vr]
    if has_kpm:
        in_specs.append(
            pl.BlockSpec((1, 1, sk), lambda i, j, *_: (i // h, 0, 0)))
        args.append(kpm.reshape(b, 1, sk))
    scalars = _walk_scalars(mask.csr(), seed)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(b * h, mask.nq),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, blk, d), lambda i, j, *_: (i, j, 0)),
            pl.BlockSpec((1, blk, 1), lambda i, j, *_: (i, j, 0)),
        ],
        # running max and sum (lane-replicated), the output's
        # accumulator; then the streamed path's tiles
        scratch_shapes=_f32_scratch((blk, _LANES), (blk, _LANES), (blk, d))
        + (_stream_scratch(d, blk, k.dtype, v.dtype) if stream else []))
    o, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, sq, 1), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=_flash._compiler_params(interpret, stream),
    )(*scalars, *args)
    return o.reshape(b, h, sq, d), lse


def _masked_bwd(res, g, mask, sm_scale, interpret, rate,
                has_kpm=True):
    q, k, v, kpm, seed, o, lse = res
    b, h, sq, d = q.shape
    hkv = k.shape[1]
    G = h // hkv
    sk = k.shape[2]
    blk = mask.block
    stream = _use_stream(mask, interpret)
    do = g
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)                               # (b,h,sq)

    qr = q.reshape(b * h, sq, d)
    kr = k.reshape(b * hkv, sk, d)
    vr = v.reshape(b * hkv, sk, d)
    dor = do.reshape(b * h, sq, d)
    kpm_args = [kpm.reshape(b, 1, sk)] if has_kpm else []
    lser = lse.reshape(b * h, sq, 1)
    deltar = delta.reshape(b * h, sq, 1)
    compiler_params = _flash._compiler_params(interpret, stream)

    # ---- dq (CSR row walk) ----
    kernel = functools.partial(
        _mf_dq_kernel, **_kernel_statics(mask, h, hkv, sm_scale, rate,
                                         stream))
    if not has_kpm:
        kernel = _drop_kpm(kernel, 7)       # 4 scalars + q, k, v
    if stream:
        kv_spec = pl.BlockSpec(memory_space=pltpu.HBM)
        k_arg, v_arg = _stream_layout(kr, blk), _stream_layout(vr, blk)
    else:
        kv_spec = pl.BlockSpec(
            (1, sk, d),
            lambda i, j, *_: ((i // h) * hkv + (i % h) // G, 0, 0))
        k_arg, v_arg = kr, vr
    row_spec = pl.BlockSpec((1, blk, d), lambda i, j, *_: (i, j, 0))
    row_vec = pl.BlockSpec((1, blk, 1), lambda i, j, *_: (i, j, 0))
    scalars = _walk_scalars(mask.csr(), seed)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(b * h, mask.nq),
        in_specs=[row_spec, kv_spec, kv_spec] + ([
            pl.BlockSpec((1, 1, sk), lambda i, j, *_: (i // h, 0, 0))]
            if has_kpm else []) + [row_spec, row_vec, row_vec],
        out_specs=row_spec,
        scratch_shapes=_f32_scratch((blk, d))               # dq
        + (_stream_scratch(d, blk, k.dtype, v.dtype) if stream else []))
    dq = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        interpret=interpret,
        compiler_params=compiler_params,
    )(*scalars, qr, k_arg, v_arg, *kpm_args, dor, lser, deltar)

    # ---- dk, dv (CSC column walk, per-q-head partials) ----
    kernel = functools.partial(
        _mf_dkv_kernel, sm_scale=sm_scale, block=blk, H=h, Hm=mask.heads,
        nk=mask.nk, seq_k=sk, band=mask.band, run_kinds=mask.run_kinds,
        dropout_rate=rate, stream=stream)
    if not has_kpm:
        kernel = _drop_kpm(kernel, 7)       # 4 scalars + q, k, v
    if stream:
        q_spec = pl.BlockSpec(memory_space=pltpu.HBM)
        q_arg, do_arg = _stream_layout(qr, blk), _stream_layout(dor, blk)
    else:
        q_spec = pl.BlockSpec((1, sq, d), lambda i, j, *_: (i, 0, 0))
        q_arg, do_arg = qr, dor
    col_spec = pl.BlockSpec(
        (1, blk, d),
        lambda i, j, *_: ((i // h) * hkv + (i % h) // G, j, 0))
    scalars = _walk_scalars(mask.csc(), seed)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(b * h, mask.nk),
        in_specs=[
            q_spec,                                          # q (full)
            col_spec, col_spec,                              # k, v tiles
        ] + ([pl.BlockSpec((1, 1, sk), lambda i, j, *_: (i // h, 0, 0))]
             if has_kpm else []) + [
            q_spec,                                          # do (full)
            pl.BlockSpec((1, 1, sq), lambda i, j, *_: (i, 0, 0)),  # lse
            pl.BlockSpec((1, 1, sq), lambda i, j, *_: (i, 0, 0)),  # delta
        ],
        out_specs=[
            pl.BlockSpec((1, blk, d), lambda i, j, *_: (i, j, 0)),
            pl.BlockSpec((1, blk, d), lambda i, j, *_: (i, j, 0)),
        ],
        scratch_shapes=_f32_scratch((blk, d), (blk, d))     # dk, dv
        + (_stream_scratch(d, blk, q.dtype, do.dtype) if stream else []))
    dk, dv = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            # GQA: fp32 per-q-head partials so the group sum really
            # accumulates at fp32 (flash.py's scheme)
            jax.ShapeDtypeStruct((b * h, sk, d),
                                 jnp.float32 if G > 1 else k.dtype),
            jax.ShapeDtypeStruct((b * h, sk, d),
                                 jnp.float32 if G > 1 else v.dtype),
        ],
        interpret=interpret,
        compiler_params=compiler_params,
    )(*scalars, q_arg, kr, vr, *kpm_args, do_arg,
      lser.reshape(b * h, 1, sq), deltar.reshape(b * h, 1, sq))

    dq = dq.reshape(b, h, sq, d)
    if G > 1:
        dk = dk.reshape(b, hkv, G, sk, d).sum(2).astype(k.dtype)
        dv = dv.reshape(b, hkv, G, sk, d).sum(2).astype(v.dtype)
    else:
        dk = dk.reshape(b, hkv, sk, d)
        dv = dv.reshape(b, hkv, sk, d)
    return dq, dk, dv


# --------------------------------------------------------------------- #
# custom vjp + public API
# --------------------------------------------------------------------- #
# seed rides as a traced int32 array (a per-step dropout seed must not
# recompile); its cotangent is None. The BlockMask is a hashable static.
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def masked_flash_call(q, k, v, kpm, seed, mask, sm_scale, interpret,
                      rate, has_kpm=True):
    """Low-level entry (all operands explicit — what
    ``parallel/pallas_shard.sharded_masked_flash`` wraps in shard_map).
    Prefer :func:`masked_flash_attention`. With ``has_kpm=False`` the
    (then-unused, dummy-shaped) ``kpm`` operand never reaches the
    kernels — the dense/causal hot path pays no all-zero mask add."""
    o, _ = _masked_fwd(q, k, v, kpm, seed, mask, sm_scale, interpret,
                       rate, has_kpm=has_kpm)
    return o


def _mf_vjp_fwd(q, k, v, kpm, seed, mask, sm_scale, interpret, rate,
                has_kpm=True):
    o, lse = _masked_fwd(q, k, v, kpm, seed, mask, sm_scale, interpret,
                         rate, has_kpm=has_kpm)
    return o, (q, k, v, kpm, seed, o, lse)


def _mf_vjp_bwd(mask, sm_scale, interpret, rate, has_kpm, res, g):
    q, k, v, kpm, seed, o, lse = res
    dq, dk, dv = _masked_bwd((q, k, v, kpm, seed, o, lse), g, mask,
                             sm_scale, interpret, rate, has_kpm=has_kpm)
    return dq, dk, dv, jnp.zeros_like(kpm), None


masked_flash_call.defvjp(_mf_vjp_fwd, _mf_vjp_bwd)


def masked_flash_attention(q, k, v, mask: BlockMask, key_mask=None,
                           sm_scale: Optional[float] = None,
                           dropout_rate: float = 0.0,
                           dropout_rng=None,
                           interpret: Optional[bool] = None):
    """Blocked flash attention under a static :class:`BlockMask`.

    q: (B, H, Sq, D); k, v: (B, kv_heads, Sk, D) with
    ``H % kv_heads == 0`` (GQA served natively). ``mask.heads`` must be
    1 (head-uniform) or H. ``key_mask``: optional *additive* key mask,
    (B, Sk) or BERT-style (B, 1, 1, Sk). O(S) memory, O(nonzero
    blocks) compute/bytes; fwd + custom-vjp bwd; in-kernel hash
    dropout (requires ``dropout_rng``).
    """
    b, h, sq, d = q.shape
    hkv = k.shape[1]
    sk = k.shape[2]
    assert h % hkv == 0 and k.shape == v.shape, (q.shape, k.shape,
                                                 v.shape)
    assert mask.seq_q == sq and mask.seq_k == sk, (
        f"mask geometry ({mask.seq_q}, {mask.seq_k}) vs inputs "
        f"({sq}, {sk})")
    assert mask.heads in (1, h), (
        f"mask heads {mask.heads} must be 1 (uniform) or {h}")
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(d)
    if interpret is None:
        interpret = not _flash._use_pallas()
    dropout_rate = float(dropout_rate)
    if dropout_rate > 0.0:
        assert dropout_rng is not None, \
            "masked_flash_attention: dropout_rate > 0 requires dropout_rng"
        assert dropout_rate < 1.0, dropout_rate
        seed = dropout_seed_from_rng(dropout_rng)
    else:
        seed = jnp.zeros((1, 1), jnp.int32)
    if key_mask is None:
        # dummy operand: has_kpm=False keeps it out of the kernels
        kpm = jnp.zeros((b, 1), jnp.float32)
    else:
        kpm = key_mask.reshape(b, sk).astype(jnp.float32)
    return masked_flash_call(q, k, v, kpm, seed, mask, float(sm_scale),
                             bool(interpret), dropout_rate,
                             key_mask is not None)
