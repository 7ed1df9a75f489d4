"""Pallas paged-attention decode kernel — serve from pages in place.

The paged KV cache (PR 7, ``inference/kv_cache.py``) made serving
*capacity* paged, but the decode step still materialized each row's
full ``max_len``-bounded K/V stripe through
:func:`~deepspeed_tpu.ops.attention.page_pool.gather_paged_kv` before
running dense attention — per-step decode bandwidth stayed O(max_len)
regardless of how many tokens were actually in flight. This module is the missing
half of that design (vLLM's PagedAttention, PAPERS.md, fused with the
flash online-softmax core this repo already carries in
``ops/attention/flash.py``): a Pallas TPU kernel that computes decode
attention *directly against the page pool*, so a row at cache position
``p`` reads exactly its ``p // page_size + 1`` live pages — O(live
tokens), not O(max_len).

Design:

- **Grid** ``(batch,)``. Each program owns one sequence's page walk for
  ALL its heads: the DMA tile is a page of whole pool rows,
  ``pool[layer, page]`` = ``(page_size, kv_heads * head_dim)`` — the
  array's full last two dimensions, so no head is cut out of the lanes
  and the kernel is one algorithm at every head width (ISSUE 30; it cut
  head slices before, which Mosaic takes only at multiples of 128, and
  every GPT-2 width gathered).
- **Heads separated by the contraction.** The row's queries are spread
  block-diagonally, head ``(kh, g)`` on row ``g * kv_rows + kh`` with
  its values in kv head ``kh``'s lanes of the pool row: one dot
  ``Qbd . Ktile^T`` is every head's scores, ``P . Vtile`` every head's
  context in its own lanes of its row. The MXU does ``kv_heads`` times
  the useful FLOPs; the program is bound by bytes. K/V pages are read
  once for all heads and GQA falls out of the placement (llama serves
  with no head expansion).
- **Layer, block tables, positions in SMEM.** They enter through
  ``PrefetchScalarGridSpec`` scalar prefetch, so page ids are available
  to index DMAs before the kernel body runs, and every layer's call is
  the same kernel. The page walk is bounded by each row's OWN live page
  count — the kernel never touches reserved-but-unwritten pages.
- **A block of pages a loop turn, double-buffered.** ``128 //
  page_size`` pages (8 of 16 tokens: one MXU tile of keys) land in one
  ``(block tokens, row width)`` VMEM buffer; block ``i+1``'s page
  copies are issued before block ``i`` is consumed (``flash.py``'s
  streaming idiom) — 2 blocks of VMEM per stream at any pool size. Of
  a row's last block only the live pages are copied; the tail is
  masked.
- **A run of pages is one copy.** Where a block's live pages are
  consecutive ids in the pool (the allocator hands a request its pages
  in runs of a block: ``inference/paging.py``) a stream's copy of the
  block is ONE descriptor, a walk's last block the binary pieces of
  its live count; any other block goes page by page. Which blocks are
  runs is read off the tables once a decode program
  (:func:`_block_runs`) and rides in SMEM beside them (ISSUE 54: the
  copies were bound by their descriptors, 32 a turn of the latent
  walk).
- **One stream of blocks across the sequences.** The grid runs in
  order, and a walk's LAST turn issues the next sequence's first block
  into the free slot (scratch and semaphores outlive a grid step), so
  only the call's first walk starts with nothing in flight (ISSUE 38:
  at about two turns a walk the un-overlapped first block was most of
  a program).
- **Online softmax in fp32.** Running (m, l, acc) across the walk, keys
  and values to the MXU in the pool dtype (bf16 in production) with
  fp32 accumulation, and the probabilities NOT rounded to that dtype
  (:func:`_probs_dot`) — no lower precision than the gather path it
  replaces. Positions past the row's cache position AND anything
  mapped to the reserved null page 0 are masked *inside* the kernel,
  so the all-null tables of inactive slots produce finite zeros
  (discarded by the host) rather than NaN.

The same kernel runs under ``interpret=True`` on CPU — scalar
prefetch, HBM refs, dynamic-index async copies and semaphores are all
interpretable — which is what makes exact greedy parity against the
gather path tier-1-testable without hardware
(tests/unit/test_paged_attention.py).

Compiled-TPU legality: Mosaic needs the DMA tile's lane (minor) dim a
whole number of 128-lane tiles and its sublane dim of 8-row tiles. The
tile is ``(page_size, kv_heads * head_dim)``, so the compiled path
needs ``kv_heads * head_dim % 128 == 0`` — 16 x 64 (GPT-2 345M) and
8 x 128 (llama GQA) are, 25 x 64 = 1,600 (GPT-2 XL) is not — and
``page_size % 8 == 0``. The int8 pool's scale rows are
``(page_size, kv_heads * scale_blocks)`` fp32 — 16 lanes for GPT-2 —
so the int8 arity is refused by the compiler and runs in interpret
mode only. :func:`paged_decode_supported` is the one predicate the
serving engine consults; unsupported geometries fall back to the
gather path with a one-line log (see docs/inference.md's fallback
matrix) — the gather path remains the numerics oracle either way, and
the reader of everything with more than one query row.
"""

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

try:
    from jax.experimental.pallas import tpu as pltpu
except ImportError:  # pragma: no cover
    pltpu = None

from deepspeed_tpu.ops.attention.flash import NEG_INF, _use_pallas

__all__ = ["paged_decode_attention", "paged_decode_reference",
           "paged_decode_supported", "decode_read_bytes",
           "live_pages", "block_pages", "dequantize_pool", "quantize_kv",
           "latent_decode_attention", "latent_decode_reference"]


def live_pages(cache_position, page_size: int):
    """Pages a row at ``cache_position`` (its just-written token's
    position) actually reads: positions ``0..cache_position`` span
    ``cache_position // page_size + 1`` pages. Works on ints and
    arrays."""
    return cache_position // page_size + 1


def paged_decode_supported(page_size: int, head_dim: int,
                           dtype=jnp.bfloat16,
                           backend: Optional[str] = None,
                           kv_heads: int = 1) -> Tuple[bool, str]:
    """Can the Pallas decode kernel run for this cache geometry on this
    backend? Returns ``(ok, reason)`` — the one predicate the serving
    engine consults before compiling the paged decode program.

    Off-TPU the kernel runs in interpret mode (pure jax semantics, no
    layout constraints) — always supported. On TPU the DMA tile is one
    page of whole pool rows, ``(page_size, kv_heads * head_dim)``, and
    the head width itself is nothing Mosaic sees: it needs the ROW a
    whole number of 128-lane tiles (16 x 64 and 8 x 128 are; GPT-2 XL's
    25 x 64 = 1,600 is not: its HBM rows are padded to 1,664 and a
    1,600-lane slice of them is refused) and the page a whole number of
    8-row sublane tiles. ``kv_heads`` is the kv heads of the pool the
    kernel is handed: under a serving mesh, one shard's. ``dtype`` is
    the POOL dtype: an int8 pool also streams its
    ``(page_size, kv_heads * scale_blocks)`` fp32 scale rows, 16 lanes
    for GPT-2, so int8 gathers on TPU
    (tests/unit/test_tpu_compile.py holds this predicate to the
    compiler).
    """
    if pltpu is None:
        return False, "pallas tpu backend unavailable"
    if backend is None:
        backend = jax.default_backend()
    if backend != "tpu":
        return True, "interpret mode (CPU oracle path)"
    if jnp.dtype(dtype).itemsize == 1:
        return False, ("int8 pool: the (page_size, kv_heads * "
                       "scale_blocks) fp32 scale rows are not a whole "
                       "number of 128-lane tiles")
    width = kv_heads * head_dim
    if width % 128 != 0:
        return False, (f"pool row of {kv_heads} x {head_dim} = {width} "
                       "lanes is not a whole number of 128-lane tiles "
                       "(the page DMA's lane dim)")
    if page_size % 8 != 0:
        return False, (f"page_size {page_size} not a multiple of the "
                       "8-row sublane tile")
    return True, "compiled pallas kernel"


def decode_read_bytes(cache_positions: Sequence[int], page_size: int,
                      pages_per_seq: int, kv_heads: int, head_dim: int,
                      dtype_bytes: int = 2, scale_blocks: int = 0):
    """Modeled K+V bytes one decode step reads from the pool, paged
    kernel vs gather stripe: analytic accounting that the compiled-HLO
    audit cross-checks structurally (tests/unit/test_paged_attention.py).

    The kernel reads each row's live pages once per layer:
    ``live_pages * page_size * kv_heads * head_dim`` K plus the same V.
    The gather fallback materializes the full ``pages_per_seq``-wide
    stripe per row regardless of how short the row is. Returns
    ``(pallas_bytes, gather_bytes)`` per layer for the whole batch.

    For the int8 pool pass ``dtype_bytes=1`` and
    ``scale_blocks=spec.scale_blocks``: each token row also streams its
    per-row fp32 scales (K and V), the ``quant_serving_bytes`` KV lever.
    """
    positions = [int(p) for p in cache_positions]
    per_tok = kv_heads * head_dim * dtype_bytes * 2          # K and V
    per_tok += kv_heads * scale_blocks * 4 * 2               # fp32 scales
    pallas = sum(live_pages(p, page_size) * page_size * per_tok
                 for p in positions)
    gather = len(positions) * pages_per_seq * page_size * per_tok
    return pallas, gather


# --------------------------------------------------------------------- #
# reference (oracle / fallback) — the gather path's math, kept here so
# kernel tests can pin parity without importing a model family
# --------------------------------------------------------------------- #
def quantize_kv(x, scale_blocks: int = 1):
    """Symmetric int8 absmax quantization of new K/V values per token
    row: ``x`` (..., hd) float -> (q (..., hd) int8, scales (..., nb)
    fp32) with ``nb = scale_blocks`` blocks along head_dim. The inverse
    of :func:`dequantize_pool`'s math — the models' paged write path
    quantizes each appended row with this before scattering into the
    int8 pool (EQuARX: the bytes at rest are int8, attention math stays
    fp32)."""
    hd = x.shape[-1]
    nb = max(int(scale_blocks), 1)
    blk = hd // nb
    xb = x.astype(jnp.float32).reshape(x.shape[:-1] + (nb, blk))
    absmax = jnp.max(jnp.abs(xb), axis=-1)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(xb / scale[..., None]), -127, 127)
    return (q.reshape(x.shape).astype(jnp.int8),
            scale.astype(jnp.float32))


def dequantize_pool(pool, scales):
    """fp32 view of int8 keys or values with the head width last:
    ``pool`` (..., hd) int8 (a gathered stripe, a page tile), ``scales``
    (..., nb) fp32 per-token-row absmax scales with nb dividing hd. The
    gather/oracle-path dequant — the Pallas kernel applies the same math
    per streamed tile in VMEM."""
    hd = pool.shape[-1]
    nb = scales.shape[-1]
    s = jnp.repeat(scales, hd // nb, axis=-1)
    return pool.astype(jnp.float32) * s


def paged_decode_reference(q, kpool, vpool, block_tables, cache_position,
                           sm_scale: Optional[float] = None,
                           k_scales=None, v_scales=None, layer: int = 0):
    """Dense oracle: gather each row's full logical stripe of layer
    ``layer`` from the pool, mask positions past ``cache_position``,
    softmax in fp32 — exactly what the models' gather fallback computes
    for a seq-1 query. q: (B, H, hd); pools:
    (layers, num_pages, page_size, kv_heads * hd), one token a row and
    heads major within it; block_tables: (B, P) int32; cache_position:
    (B,) int32 (position of the already-written current token). With
    ``k_scales``/``v_scales``
    ((layers, num_pages, page_size, kv_heads * nb) fp32) the pools are
    int8 and dequantized after the gather. Returns (B, H, hd)."""
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(q.shape[-1])
    B, H, hd = q.shape
    KH = kpool.shape[-1] // hd

    def stripe(pool):                      # -> (B, KH, P * ps, w)
        rows = pool[layer, block_tables]
        return rows.reshape(B, -1, KH, rows.shape[-1] // KH).transpose(
            0, 2, 1, 3)
    kc, vc = stripe(kpool), stripe(vpool)
    if k_scales is not None:
        kc = dequantize_pool(kc, stripe(k_scales))
        vc = dequantize_pool(vc, stripe(v_scales))
    qg = q.reshape(B, KH, H // KH, hd)
    s = jnp.einsum("bkgd,bkld->bkgl", qg.astype(jnp.float32),
                   kc.astype(jnp.float32)) * sm_scale
    k_idx = jnp.arange(kc.shape[2])
    mask = k_idx[None, :] <= cache_position[:, None]        # (B, L)
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bkgl,bkld->bkgd", p, vc.astype(jnp.float32))
    return ctx.reshape(B, H, hd).astype(q.dtype)


def latent_decode_reference(q, pool, block_tables, cache_position,
                            sm_scale: float, value_lanes: int,
                            layer: int = 0):
    """Dense oracle of :func:`latent_decode_attention`: each row's whole
    logical stripe of latent rows gathered from layer ``layer`` of the
    pool ``(layers, num_pages, page_size, width)``, every head's query
    ``q`` (B, H, width) against it, positions past ``cache_position``
    masked, softmax in fp32, the probabilities against the stripe's
    first ``value_lanes`` lanes. Returns (B, H, value_lanes)."""
    B = q.shape[0]
    rows = pool[layer, block_tables].reshape(B, -1, pool.shape[-1]).astype(
        jnp.float32)
    s = jnp.einsum("bhw,blw->bhl", q.astype(jnp.float32), rows,
                   precision=jax.lax.Precision.HIGHEST) * sm_scale
    mask = jnp.arange(rows.shape[1])[None, :] <= cache_position[:, None]
    p = jax.nn.softmax(jnp.where(mask[:, None, :], s, NEG_INF), axis=-1)
    ctx = jnp.einsum("bhl,blc->bhc", p, rows[..., :value_lanes],
                     precision=jax.lax.Precision.HIGHEST)
    return ctx.astype(q.dtype)


# --------------------------------------------------------------------- #
# the kernel
# --------------------------------------------------------------------- #
# Tokens a loop turn: a block is this many tokens' worth of whole pages
# (at least one page). 128 is one MXU tile of keys, 8 pages of 16, and
# the best size at GPT-2 345M's serving shape (160 rows of about 170
# live tokens, 24 layers; ms a decode call in the kernel alone, PERF.md
# §6, PR 38: 16: 19.5, 64: 8.3, 128: 6.6, 256: 7.1): fewer turns a walk
# against more of the last block's tile unused. A row with more live
# tokens only has more turns to save. One constant for every model,
# chosen by nothing a user sets; every size is tested and compiled.
_BLOCK_TOKENS = 128


# The latent arity's tokens a loop turn, swept where its rows are long
# (193 rows of about 2,900 live tokens, 5 layers; ms the five calls in
# the kernel alone, my chip runs, PR 43): pages of 16 tokens, 128: 17.4,
# 256: 13.6, 512: 11.5, 1024: 11.4; pages of 64, 128: 14.7, 256: 10.2,
# 512: 8.1, 1024: 7.8. A turn's fixed cost (the page copies' issue and
# wait, the softmax's bookkeeping) bounds the walk, not bytes; past 512
# a turn's tile of probabilities (64 x 1,024 float32) gains 3%. The
# pair pool at long rows gains too (65 rows of 2,400 tokens, 8 kv heads
# of 128, four layers: 128: 4.82, 256: 4.15, 512: 3.99) and keeps its
# 128 until a change of its own re-measures the short rows it was
# chosen at: the arities' constants differ by what was measured, not by
# what the kernel can do. WITH RUNS (PR 54: a block of consecutive
# pages is one descriptor; the same shape, the tables out of a churned
# extent allocator, 1,180 of 1,184 turns runs; my chip runs, PR 54,
# parent -> change): pages of 16, 512: 12.1 -> 8.7, 1024: 12.0 -> 7.8;
# pages of 64, 512: 8.6 -> 8.4. Pages of 64 still beat pages of 16 at
# 512 a turn, by 3% where it was 29%, and lose to them at 1,024; a
# shuffled table (every block page by page) reads 12.2 where the
# parent's kernel reads 12.0. What is left of a turn is its products
# and bookkeeping, so 1,024 a turn now buys 11%: a change of its own.
_LATENT_BLOCK_TOKENS = 512


def block_pages(page_size: int, latent: bool = False) -> int:
    """Pages of ``page_size`` tokens that one loop turn of the walk
    streams and waits for: whole pages, at least one. ``latent``: the
    walk over a latent pool (:func:`latent_decode_attention`)."""
    tokens = _LATENT_BLOCK_TOKENS if latent else _BLOCK_TOKENS
    return max(1, tokens // page_size)


def _round_up(n: int, to: int) -> int:
    return -(-n // to) * to


def _probs_dot(p, vt):
    """``p (rows, tokens) float32`` times a value tile
    ``(tokens, width)``, accumulated in float32 with the probabilities
    NOT rounded to the tile's dtype. A bf16 tile goes to the MXU as it
    is: ``p`` is split into three bf16 terms that sum to it exactly
    (8 + 8 + 8 mantissa bits), stacked on the rows of ONE dot, so the
    tile is loaded once and every product is exact in float32. A
    float32 tile (a float32 pool, a dequantized int8 one) contracts
    float32 operands at ``Precision.HIGHEST``."""
    if vt.dtype != jnp.bfloat16:
        return jax.lax.dot_general(
            p, vt.astype(jnp.float32), (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
    rows = p.shape[0]
    terms, rest = [], p
    for _ in range(3):
        t = rest.astype(jnp.bfloat16)
        terms.append(t)
        rest = rest - t.astype(jnp.float32)
    out = jax.lax.dot_general(
        jnp.concatenate(terms, axis=0), vt, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return out[:rows] + out[rows:2 * rows] + out[2 * rows:]


def _decode_kernel(layer_ref, tables_ref, pos_ref, runs_ref, q_ref, k_ref,
                   *rest, sm_scale, page_size, head_dim, quantized,
                   value_lanes=None):
    """One sequence's program: walk the row's live pages from the pool,
    ``block_pages`` whole pool rows' pages a loop turn through
    double-buffered DMA, and online-softmax EVERY head's query against
    the streamed block with one pair of dots. Its first block was
    issued by the program before it; its last turn issues the next
    one's.

    The heads are separated by the contraction, not by a lane slice.
    ``q_ref`` is ``(1, groups, width)``: row ``g`` holds member ``g``
    of every kv head's query group in that kv head's lanes of the pool
    row. The program spreads it block-diagonally — query head
    ``(g, kh)`` on row ``g * kv_rows + kh``, its ``head_dim`` values
    where they were, zeros elsewhere — so ``q . Ktile^T`` is every
    head's scores and ``p . Vtile`` holds head ``(g, kh)``'s context in
    the same lanes of its row (the rest of the row is other heads'
    values under this head's probabilities, dropped at the end).
    ``kv_rows`` is the kv heads rounded up to whole sublane tiles (zero
    rows: nothing of them is kept).

    ``quantized`` adds two operand refs (the per-token-row fp32 scale
    pools) and two scale scratch buffers: each walked page streams its
    int8 K/V rows AND its scale rows, and the dequant happens right
    after the block lands in VMEM — the int8 bytes are what crossed
    HBM, the math below (scores, online softmax, accumulation) stays
    fp32 exactly like the dense-pool path.

    ``value_lanes`` (an int) is the LATENT arity
    (:func:`latent_decode_attention`): ONE pool whose row is every
    head's key, ``[c | k_r | zeros]``, and whose first ``value_lanes``
    lanes are every head's value. There is one stream: a page is copied
    ONCE and the landed tile is both operands, ``q . tile^T`` the scores
    and ``p . tile[:, :value_lanes]`` the context. All the heads share
    the one key row, so ``q_ref`` ``(1, heads, width)`` is the dot's
    operand as it comes (no block-diagonal spread, no row dropped) and
    ``o_ref`` is ``(1, heads, value_lanes)``."""
    latent = value_lanes is not None
    if latent:
        o_ref, kbuf, ksem, base_ref = rest
        vbuf = kbuf
        streams = ((k_ref, kbuf, ksem),)
    elif quantized:
        v_ref, *rest = rest
        (ks_ref, vs_ref, o_ref, kbuf, vbuf, ksbuf, vsbuf,
         ksem, vsem, kssem, vssem, base_ref) = rest
        streams = ((k_ref, kbuf, ksem), (v_ref, vbuf, vsem),
                   (ks_ref, ksbuf, kssem), (vs_ref, vsbuf, vssem))
    else:
        v_ref, o_ref, kbuf, vbuf, ksem, vsem, base_ref = rest
        streams = ((k_ref, kbuf, ksem), (v_ref, vbuf, vsem))
    b = pl.program_id(0)
    last_seq = pl.num_programs(0) - 1
    layer = layer_ref[0]
    pos = pos_ref[b]
    table_pages = tables_ref.shape[1]
    tokens, width = kbuf.shape[1:]
    block_pages = tokens // page_size
    pool_pages = k_ref.shape[1]

    def _pages(seq):
        # positions 0..pos are attended (this call's token was written
        # BEFORE attention — write_paged_kv_cache runs first), spanning
        # exactly pos // page_size + 1 pages: the O(live tokens) bound
        return pos_ref[seq] // page_size + 1

    num_blk = (_pages(b) + block_pages - 1) // block_pages
    groups = q_ref.shape[1]
    if latent:
        kv_rows, q = 1, q_ref[0]
    else:
        kv_rows = _round_up(width // head_dim, 8)
        if groups * kv_rows % 16:
            kv_rows = _round_up(kv_rows, 16)
        # row kh of a group keeps kv head kh's lanes
        lane = jax.lax.broadcasted_iota(jnp.int32, (kv_rows, width), 1)
        first = jax.lax.broadcasted_iota(
            jnp.int32, (kv_rows, width), 0) * head_dim
        own = (lane >= first) & (lane < first + head_dim)
        q = jnp.concatenate(
            [jnp.where(own, q_ref[0, g:g + 1, :].astype(jnp.float32), 0.0)
             for g in range(groups)], axis=0).astype(q_ref.dtype)

    def _page_id(seq, blk, j):
        # clamped: the last block's unwalked tail may lie past the table
        return tables_ref[seq, jnp.minimum(blk * block_pages + j,
                                           table_pages - 1)]

    def _live_in(seq, blk):
        return _pages(seq) - blk * block_pages

    def _for_live_pages(seq, blk, slot, fn):
        """``fn(copy)`` for every copy of the LIVE pages of sequence
        ``seq``'s block ``blk``, which lands in ``slot``: the pages past
        the row's count are never touched. Where ``runs_ref`` says the
        block's live pages are consecutive ids in the pool (as an
        allocator that hands out runs lays them, ``inference/
        paging.py``), a stream's copy of a full block is ONE descriptor
        and of a walk's last, short block the binary pieces of its
        count, each over whole pages; else one a page."""
        live = jnp.minimum(_live_in(seq, blk), block_pages)

        def _by_page():
            for j in range(block_pages):
                def _page(j=j):
                    page = _page_id(seq, blk, j)
                    rows = pl.ds(j * page_size, page_size)
                    for ref, buf, sem in streams:
                        fn(pltpu.make_async_copy(ref.at[layer, page],
                                                 buf.at[slot, rows],
                                                 sem.at[slot]))
                if j == 0:
                    _page()              # a walked block has a live page
                else:
                    pl.when(j < live)(_page)
        if block_pages == 1:
            return _by_page()

        def _as_run():
            first = _page_id(seq, blk, 0)

            def _copy(done, n):
                # pages ``done .. done + n`` of the block in ONE copy a
                # stream, from the pool as rows of tokens (a view)
                for ref, buf, sem in streams:
                    rows = ref.reshape(ref.shape[0], -1, ref.shape[-1])
                    fn(pltpu.make_async_copy(
                        rows.at[layer, pl.ds((first + done) * page_size,
                                             n * page_size)],
                        buf.at[slot, pl.ds(done * page_size,
                                           n * page_size)],
                        sem.at[slot]))

            def _pieces():
                # a walk's last block: the binary pieces of its count,
                # the pieces above one holding ``live``'s higher bits
                for bit in reversed(range(
                        min(block_pages - 1, pool_pages).bit_length())):
                    pl.when((live & (1 << bit)) != 0)(
                        lambda bit=bit: _copy(
                            (live >> (bit + 1)) << (bit + 1), 1 << bit))
            if block_pages > pool_pages:     # (no pool holds such a run)
                return _pieces()
            jax.lax.cond(live == block_pages,
                         lambda: _copy(0, block_pages), _pieces)
        jax.lax.cond(runs_ref[seq, blk] != 0, _as_run, _by_page)

    def _start(seq, blk, slot):
        if block_pages > 1:
            # what a DMA does not fill (a walk's last block's tail: the
            # slot keeps an older block's rows) is masked out of the
            # scores below, but a NaN value times a zero probability is
            # NaN: the value side of a block that lands short starts
            # from zeros
            @pl.when(_live_in(seq, blk) < block_pages)
            def _zero_values():
                # (the latent tile is its own value side)
                for buf in (vbuf, vsbuf) if quantized else (vbuf,):
                    buf[slot] = jnp.zeros(buf.shape[1:], buf.dtype)
        _for_live_pages(seq, blk, slot, lambda c: c.start())

    # The blocks of ALL the sequences are one stream through the two
    # slots: a walk's first block is issued by the program BEFORE it,
    # in its own last turn, so no program starts with nothing in
    # flight. ``base_ref`` carries the slot of this program's block 0
    # from grid step to grid step (scratch and semaphores outlive one).
    @pl.when(b == 0)
    def _first_walk():
        base_ref[0] = 0
        _start(0, 0, 0)
    base = base_ref[0]
    base_ref[0] = jax.lax.rem(base + num_blk, 2)

    def body(blk, carry):
        m, l, acc = carry

        slot = jax.lax.rem(base + blk, 2)
        more = blk + 1 < num_blk

        @pl.when(more | (b < last_seq))
        def _prefetch_next():
            # this walk's next block, else the next walk's first
            _start(jnp.where(more, b, b + 1), jnp.where(more, blk + 1, 0),
                   1 - slot)
        _for_live_pages(b, blk, slot, lambda c: c.wait())
        kt = kbuf[slot]                               # (tokens, width)
        vt = kt[:, :value_lanes] if latent else vbuf[slot]
        if quantized:
            nb = ksbuf.shape[-1]
            lanes = width // nb
            # per-token-row blockwise dequant of the landed block:
            # (tokens, width) int8 * (tokens, nb) scales, a scale a
            # run of ``lanes`` lanes
            kt = (kt.astype(jnp.float32).reshape(tokens, nb, lanes)
                  * ksbuf[slot][:, :, None]).reshape(tokens, width)
            vt = (vt.astype(jnp.float32).reshape(tokens, nb, lanes)
                  * vsbuf[slot][:, :, None]).reshape(tokens, width)
        s = jax.lax.dot_general(
            q, kt, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # (rows, tokens)
        # in-kernel masking: positions past the row's cache position,
        # and anything the table maps to the reserved null page 0 (the
        # all-null tables of inactive slots) — finite garbage out,
        # never NaN
        local = jax.lax.broadcasted_iota(jnp.int32, (1, tokens), 1)
        page_of = jnp.zeros((1, tokens), jnp.int32)
        for j in range(block_pages):
            page_of = jnp.where(local >= j * page_size, _page_id(b, blk, j),
                                page_of)
        valid = (blk * tokens + local <= pos) & (page_of != 0)
        s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        # a fully-masked block leaves m_new at NEG_INF and p at
        # exp(0) = 1 — re-mask so masked positions never reach l/acc
        p = jnp.where(valid, p, 0.0)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + _probs_dot(p, vt)
        return m_new, l_new, acc_new

    rows = groups * kv_rows
    m0 = jnp.full((rows, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((rows, 1), jnp.float32)
    acc0 = jnp.zeros((rows, value_lanes if latent else width),
                     jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, num_blk, body, (m0, l0, acc0))
    ctx = acc / jnp.where(l == 0.0, 1.0, l)
    if latent:
        o_ref[0] = ctx.astype(o_ref.dtype)
        return
    # row (g, kh) keeps kv head kh's lanes; summed over kh that is
    # group member g's context for every kv head, in the pool row's
    # own layout
    for g in range(groups):
        part = ctx[g * kv_rows:(g + 1) * kv_rows]
        o_ref[0, g:g + 1, :] = jnp.sum(
            jnp.where(own, part, 0.0), axis=0,
            keepdims=True).astype(o_ref.dtype)


def _block_runs(block_tables, cache_position, page_size, block_pages,
                pool_pages):
    """``(rows, blocks) int32``: is the walk's block ``blk`` of a row a
    RUN, its live pages consecutive ids in the pool? Read off the
    tables, whatever laid them, once a decode program: the same
    operation in every layer's call. (A pool of fewer pages than a
    block holds no longer run.)"""
    rows, width = block_tables.shape
    blocks = -(-width // block_pages)
    tables = jnp.pad(block_tables,
                     ((0, 0), (0, blocks * block_pages - width))).reshape(
                         rows, blocks, block_pages)
    live = jnp.clip(
        live_pages(cache_position, page_size)[:, None]
        - jnp.arange(blocks, dtype=jnp.int32) * block_pages, 0, block_pages)
    at = jnp.arange(block_pages, dtype=jnp.int32)
    follows = (tables == tables[:, :, :1] + at) | (at >= live[:, :, None])
    return (jnp.all(follows, axis=-1)
            & (live <= pool_pages)).astype(jnp.int32)


def _compiler_params(interpret):
    if pltpu is None or interpret:
        return None
    # a program is one sequence's walk and issues the next one's first
    # block: the grid runs in order
    return pltpu.CompilerParams(dimension_semantics=("arbitrary",))


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret",
                                             "block_tokens"))
def _paged_decode_call(q, kpool, vpool, scales, block_tables,
                       cache_position, layer, sm_scale, interpret,
                       block_tokens):
    """Shared pallas_call builder for the dense-pool and int8-pool
    arities; ``scales`` is None or the (k_scales, v_scales) pair. The
    kernel is handed the whole stacked pool, pinned in HBM, and indexes
    ``layer`` itself: a ``pool[layer]`` operand would be a copy of the
    layer. ``layer`` rides in SMEM beside the tables, so every layer's
    call is the same kernel. ``block_tokens`` is the tokens a loop turn
    streams: whole pages."""
    B, H, hd = q.shape
    ps, width = kpool.shape[2:]
    KH = width // hd
    G = H // KH
    quantized = scales is not None
    # (B, G, width): member g of every kv head's group, in the kv
    # head's lanes; the MXU takes the pool's dtype, dequantized int8
    # tiles are fp32
    qg = q.reshape(B, KH, G, hd).transpose(0, 2, 1, 3).reshape(
        B, G, width).astype(jnp.float32 if quantized else kpool.dtype)
    kernel = functools.partial(_decode_kernel, sm_scale=sm_scale,
                               page_size=ps, head_dim=hd,
                               quantized=quantized)
    # pools stay pinned in HBM; the kernel DMAs the walked pages' whole
    # (page_size, width) tiles — never the stripe
    pools = [kpool, vpool] + (list(scales) if quantized else [])
    in_specs = [pl.BlockSpec((1, G, width), lambda b, *_: (b, 0, 0))]
    in_specs += [pl.BlockSpec(memory_space=pltpu.HBM)] * len(pools)
    scratch = [pltpu.VMEM((2, block_tokens, pool.shape[-1]), pool.dtype)
               for pool in pools]
    scratch += [pltpu.SemaphoreType.DMA((2,))] * len(pools)
    scratch += [pltpu.SMEM((1,), jnp.int32)]      # slot of a walk's block 0
    grid_spec = pltpu.PrefetchScalarGridSpec(
        # layer, tables, positions and the blocks that are runs prefetch
        # into SMEM: page ids must be available to index the DMAs before
        # the body runs
        num_scalar_prefetch=4,
        grid=(B,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, G, width), lambda b, *_: (b, 0, 0)),
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, G, width), q.dtype),
        interpret=interpret,
        compiler_params=_compiler_params(interpret),
    )(layer, block_tables, cache_position,
      _block_runs(block_tables, cache_position, ps, block_tokens // ps,
                  kpool.shape[1]), qg, *pools)
    # (B, G, KH * hd) -> heads in q's order, kh major
    return out.reshape(B, G, KH, hd).transpose(0, 2, 1, 3).reshape(B, H, hd)


@functools.partial(jax.jit, static_argnames=(
    "sm_scale", "value_lanes", "interpret", "block_tokens"))
def _latent_decode_call(q, pool, block_tables, cache_position, layer,
                        sm_scale, value_lanes, interpret, block_tokens):
    """:func:`_paged_decode_call` for the latent arity: one pool pinned
    in HBM, one stream, the queries as they come."""
    B, H, width = q.shape
    kernel = functools.partial(_decode_kernel, sm_scale=sm_scale,
                               page_size=pool.shape[2], head_dim=width,
                               quantized=False, value_lanes=value_lanes)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B,),
        in_specs=[pl.BlockSpec((1, H, width), lambda b, *_: (b, 0, 0)),
                  pl.BlockSpec(memory_space=pltpu.HBM)],
        out_specs=pl.BlockSpec((1, H, value_lanes),
                               lambda b, *_: (b, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, block_tokens, width), pool.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SMEM((1,), jnp.int32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, value_lanes), q.dtype),
        interpret=interpret,
        compiler_params=_compiler_params(interpret),
    )(layer, block_tables, cache_position,
      _block_runs(block_tables, cache_position, pool.shape[2],
                  block_tokens // pool.shape[2], pool.shape[1]),
      q.astype(pool.dtype), pool)


def latent_decode_attention(q, pool, block_tables, cache_position,
                            sm_scale: float, value_lanes: int,
                            interpret: Optional[bool] = None,
                            layer: int = 0):
    """Decode attention in its ABSORBED form straight from a latent page
    pool (``inference/kv_cache.LatentPoolSpec``), O(live tokens): the
    page walk, double buffer and stream across sequences of
    :func:`paged_decode_attention`, over ONE pool whose page is copied
    once for keys and values.

    q: ``(B, heads, width)``, one token a row, each head's query already
    carried into the row's space (``[q_n W_uk^T | q_r | zeros]``); pool:
    ``(layers, num_pages, page_size, width)``, a token's row ``[c | k_r
    | zeros]``; block_tables, cache_position, ``layer`` as there.
    ``sm_scale`` multiplies the scores. Returns ``(B, heads,
    value_lanes)``: each head's probabilities against the rows' first
    ``value_lanes`` lanes (the context in latent space; the caller
    carries it out through ``W_uv``). Callers gate the compiled path on
    :func:`paged_decode_supported` with ``head_dim`` the row's lanes and
    one kv head."""
    assert q.ndim == 3 and pool.ndim == 4 and \
        q.shape[-1] == pool.shape[-1] and value_lanes <= pool.shape[-1], (
            q.shape, pool.shape, value_lanes)
    assert block_tables.shape[0] == q.shape[0] and \
        cache_position.shape == (q.shape[0],), (
            block_tables.shape, cache_position.shape)
    if interpret is None:
        interpret = not _use_pallas()
    return _latent_decode_call(q, pool, block_tables.astype(jnp.int32),
                               cache_position.astype(jnp.int32),
                               jnp.full((1,), layer, jnp.int32),
                               float(sm_scale), int(value_lanes),
                               bool(interpret),
                               block_pages(pool.shape[2], latent=True)
                               * pool.shape[2])


def paged_decode_attention(q, kpool, vpool, block_tables, cache_position,
                           sm_scale: Optional[float] = None,
                           interpret: Optional[bool] = None,
                           k_scales=None, v_scales=None, layer: int = 0):
    """Decode attention straight from the page pool — O(live tokens).

    q: ``(B, q_heads, head_dim)`` — ONE query token per row (the seq-1
    decode specialization; q post-RoPE for llama). kpool/vpool: the
    stacked pool ``(layers, num_pages, page_size, kv_heads * head_dim)``
    — one token a row, heads major within it — of which the static
    ``layer`` is read, with ``q_heads % kv_heads == 0`` (GQA served
    natively — each group of ``q_heads/kv_heads`` query rows shares its
    kv head's page stream).
    block_tables: ``(B, pages_per_seq)`` int32 (entries past a row's
    reservation = the null page 0). cache_position: ``(B,)`` int32 —
    the position of this call's ALREADY-WRITTEN token; the row attends
    positions ``<= cache_position`` across its
    ``cache_position // page_size + 1`` live pages, and nothing else is
    read from HBM. Returns ``(B, q_heads, head_dim)`` in q's dtype,
    matching the gather path's math (fp32 softmax, masked identically).

    ``k_scales``/``v_scales``
    ((layers, num_pages, page_size, kv_heads * nb) fp32, both or
    neither) select the int8-pool arity: the pools are
    int8 payload and each walked page's scale tile streams alongside,
    dequantized in VMEM after the DMA lands (PR 17 — the decode step
    moves ~half the bytes per live token).

    ``interpret=None`` auto-selects: compiled on TPU, interpret mode
    elsewhere (the tier-1 CPU parity path). Callers gate the compiled
    path on :func:`paged_decode_supported`.
    """
    assert q.ndim == 3, f"paged decode takes (B, H, hd) queries, got " \
        f"{q.shape}"
    B, H, hd = q.shape
    assert kpool.ndim == 4 and kpool.shape[-1] % hd == 0 and \
        kpool.shape == vpool.shape, (q.shape, kpool.shape, vpool.shape)
    KH = kpool.shape[-1] // hd
    assert H % KH == 0, (q.shape, kpool.shape)
    assert block_tables.shape[0] == B and cache_position.shape == (B,), (
        block_tables.shape, cache_position.shape)
    assert (k_scales is None) == (v_scales is None), \
        "int8 pool needs BOTH k_scales and v_scales"
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(hd)
    if interpret is None:
        interpret = not _use_pallas()
    scales = None
    if k_scales is not None:
        nb, rem = divmod(k_scales.shape[-1], KH)
        assert k_scales.shape[:3] == kpool.shape[:3] and rem == 0 and \
            hd % nb == 0, (k_scales.shape, kpool.shape)
        scales = (k_scales, v_scales)
    return _paged_decode_call(q, kpool, vpool, scales,
                              block_tables.astype(jnp.int32),
                              cache_position.astype(jnp.int32),
                              jnp.full((1,), layer, jnp.int32),
                              float(sm_scale), bool(interpret),
                              block_pages(kpool.shape[2]) * kpool.shape[2])
