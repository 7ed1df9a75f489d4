"""Pallas paged-attention decode kernel — serve from pages in place.

The paged KV cache (PR 7, ``inference/kv_cache.py``) made serving
*capacity* paged, but the decode step still materialized each row's
full ``max_len``-bounded K/V stripe through
:func:`~deepspeed_tpu.models.gpt2.gather_paged_kv` before running dense
attention — per-step decode bandwidth stayed O(max_len) regardless of
how many tokens were actually in flight. This module is the missing
half of that design (vLLM's PagedAttention, PAPERS.md, fused with the
flash online-softmax core this repo already carries in
``ops/attention/flash.py``): a Pallas TPU kernel that computes decode
attention *directly against the page pool*, so a row at cache position
``p`` reads exactly its ``p // page_size + 1`` live pages — O(live
tokens), not O(max_len).

Design:

- **Grid** ``(batch, kv_heads)``. Each program owns one sequence's
  page walk for one kv head; the ``q_heads / kv_heads`` query rows of
  that head's GQA group ride in the program's q block — K/V pages are
  read once per group, never replicated per q head (llama serves with
  no head expansion).
- **Block tables in SMEM.** The per-slot block tables and cache
  positions enter through ``PrefetchScalarGridSpec`` scalar prefetch,
  so page ids are available to index DMAs before the kernel body runs.
  The page walk is bounded by each row's OWN live page count — the
  kernel never touches reserved-but-unwritten pages.
- **Double-buffered DMA.** K and V page tiles stream
  ``pool[layer, page_id, :, kv_head * hd:(kv_head + 1) * hd]`` (one
  token a pool row, heads major within it) → VMEM through 2-deep
  async-copy buffers
  (``flash.py``'s streaming idiom): page ``i+1``'s copy is issued
  before page ``i`` is consumed — 2 tiles of VMEM per stream at any
  pool size.
- **Online softmax in fp32.** Running (m, l, acc) across the page walk,
  MXU dots take the pool dtype (bf16 in production) with fp32
  accumulation — the flash kernels' precision. Positions past the
  row's cache position AND anything mapped to the reserved null page 0
  are masked *inside* the kernel, so the all-null tables of inactive
  slots produce finite garbage (discarded by the host) rather than
  NaN.

The same kernel runs under ``interpret=True`` on CPU — scalar
prefetch, HBM refs, dynamic-index async copies and semaphores are all
interpretable — which is what makes exact greedy parity against the
gather path tier-1-testable without hardware
(tests/unit/test_paged_attention.py).

Compiled-TPU legality: Mosaic requires the DMA tile's lane (minor) dim
to be 128-aligned; the streamed tile is ``(page_size, head_dim)``, cut
out of the pool's ``kv_heads * head_dim`` lanes at a multiple of
``head_dim``, so the compiled path needs ``head_dim % 128 == 0`` (plus
a sublane-tile page size). The int8 pool's per-page scale tile is
``(page_size, scale_blocks)`` fp32 — its lane dim is 1..4, so the int8
arity is refused by the compiler at every page geometry and runs in
interpret mode only. :func:`paged_decode_supported` is the one
predicate the serving engine consults; unsupported geometries fall back
to the gather path with a one-line log (see docs/inference.md's
fallback matrix) — the gather path remains the numerics oracle either
way.
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
           "live_pages", "dequantize_pool", "quantize_kv"]


def live_pages(cache_position, page_size: int):
    """Pages a row at ``cache_position`` (its just-written token's
    position) actually reads: positions ``0..cache_position`` span
    ``cache_position // page_size + 1`` pages. Works on ints and
    arrays."""
    return cache_position // page_size + 1


def paged_decode_supported(page_size: int, head_dim: int,
                           dtype=jnp.bfloat16,
                           backend: Optional[str] = None
                           ) -> Tuple[bool, str]:
    """Can the Pallas decode kernel run for this cache geometry on this
    backend? Returns ``(ok, reason)`` — the one predicate the serving
    engine consults before compiling the paged decode program.

    Off-TPU the kernel runs in interpret mode (pure jax semantics, no
    layout constraints) — always supported. On TPU the DMA tile is
    ``(page_size, head_dim)``: Mosaic needs the lane dim 128-aligned
    (``head_dim % 128``) and the sublane dim a full tile
    (8 fp32 / 16 bf16 rows), so small pages or narrow heads fall back
    to the gather path. ``dtype`` is the POOL dtype: an int8 pool also
    streams a ``(page_size, scale_blocks)`` fp32 scale tile per page,
    whose lane dim Mosaic refuses whatever the page geometry, so int8
    always gathers on TPU (tests/unit/test_tpu_compile.py holds this
    predicate to the compiler).
    """
    if pltpu is None:
        return False, "pallas tpu backend unavailable"
    if backend is None:
        backend = jax.default_backend()
    if backend != "tpu":
        return True, "interpret mode (CPU oracle path)"
    if head_dim % 128 != 0:
        return False, (f"head_dim {head_dim} not a multiple of 128 "
                       "(DMA lane dim)")
    itemsize = jnp.dtype(dtype).itemsize
    if itemsize == 1:
        return False, ("int8 pool: the (page_size, scale_blocks) fp32 "
                       "scale tile's lane dim is not 128-aligned")
    sublane = 16 if itemsize == 2 else 8
    if page_size % sublane != 0:
        return False, (f"page_size {page_size} not a multiple of the "
                       f"{sublane}-row sublane tile for "
                       f"{jnp.dtype(dtype).name}")
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


# --------------------------------------------------------------------- #
# the kernel
# --------------------------------------------------------------------- #
def _decode_kernel(tables_ref, pos_ref, q_ref, k_ref, v_ref, *rest,
                   sm_scale, page_size, quantized, layer):
    """One (sequence, kv head) program: walk the row's live pages from
    the pool via double-buffered DMA, online-softmax the GQA group's
    queries against each streamed page tile.

    ``quantized`` adds two operand refs (the per-token-row fp32 scale
    pools) and two scale scratch buffers: each walked page streams its
    int8 K/V tile AND its (page_size, nb) scale tile, and the dequant
    happens right after the DMA'd tile lands in VMEM — the int8 bytes
    are what crossed HBM, the math below (scores, online softmax,
    accumulation) stays fp32 exactly like the dense-pool path."""
    if quantized:
        (ks_ref, vs_ref, o_ref, kbuf, vbuf, ksbuf, vsbuf,
         ksem, vsem, kssem, vssem) = rest
    else:
        o_ref, kbuf, vbuf, ksem, vsem = rest
    b = pl.program_id(0)
    kh = pl.program_id(1)
    pos = pos_ref[b]
    # positions 0..pos are attended (this call's token was written
    # BEFORE attention — write_paged_kv_cache runs first), spanning
    # exactly pos // page_size + 1 pages: the O(live tokens) bound
    num_pg = pos // page_size + 1
    q = q_ref[0, 0]                                   # (G, hd)
    if quantized:
        q = q.astype(jnp.float32)   # dequantized tiles are fp32

    def _tile(ref, page, width):
        # head kh's (page_size, width) tile of one page: a pool row is a
        # token with its heads side by side, so the head is a lane range
        lanes = pl.ds(pl.multiple_of(kh * width, width), width)
        return ref.at[layer, page, :, lanes]

    def _copies(i):
        page = tables_ref[b, i]
        slot = jax.lax.rem(i, 2)
        hd = kbuf.shape[-1]
        copies = [
            pltpu.make_async_copy(_tile(k_ref, page, hd), kbuf.at[slot],
                                  ksem.at[slot]),
            pltpu.make_async_copy(_tile(v_ref, page, hd), vbuf.at[slot],
                                  vsem.at[slot])]
        if quantized:
            nb = ksbuf.shape[-1]
            copies += [
                pltpu.make_async_copy(_tile(ks_ref, page, nb),
                                      ksbuf.at[slot], kssem.at[slot]),
                pltpu.make_async_copy(_tile(vs_ref, page, nb),
                                      vsbuf.at[slot], vssem.at[slot])]
        return copies

    def _start(i):
        for c in _copies(i):
            c.start()

    _start(0)                                         # num_pg >= 1 always

    def body(i, carry):
        m, l, acc = carry

        @pl.when(i + 1 < num_pg)
        def _prefetch_next():
            _start(i + 1)
        page = tables_ref[b, i]
        slot = jax.lax.rem(i, 2)
        for c in _copies(i):
            c.wait()
        kt = kbuf[slot]                               # (page_size, hd)
        vt = vbuf[slot]
        if quantized:
            hd = kt.shape[-1]
            nb = ksbuf.shape[-1]
            blk = hd // nb
            # per-token-row blockwise dequant of the landed tile:
            # (ps, hd) int8 * (ps, nb) scales broadcast per block
            kt = (kt.astype(jnp.float32).reshape(page_size, nb, blk)
                  * ksbuf[slot][:, :, None]).reshape(page_size, hd)
            vt = (vt.astype(jnp.float32).reshape(page_size, nb, blk)
                  * vsbuf[slot][:, :, None]).reshape(page_size, hd)
        s = jax.lax.dot_general(
            q, kt, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale   # (G, ps)
        # in-kernel masking: positions past the row's cache position,
        # and anything the table maps to the reserved null page 0 (the
        # all-null tables of inactive slots) — finite garbage out,
        # never NaN
        offs = i * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1)
        valid = (offs <= pos) & (page != 0)
        s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])
        # a fully-masked tile leaves m_new at NEG_INF and p at
        # exp(0) = 1 — re-mask so masked positions never reach l/acc
        p = jnp.where(valid, p, 0.0)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[:, None] + jax.lax.dot_general(
            p.astype(vt.dtype), vt, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    G, hd = q.shape
    m0 = jnp.full((G,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((G,), jnp.float32)
    acc0 = jnp.zeros((G, hd), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, num_pg, body, (m0, l0, acc0))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[0, 0] = (acc / l_safe[:, None]).astype(o_ref.dtype)


def _compiler_params(interpret):
    if pltpu is None or interpret:
        return None
    # batch programs are independent; the kv-head dim drives the DMA
    # sequence and stays arbitrary
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))


def _paged_decode_pallas(q, kpool, vpool, scales, block_tables,
                         cache_position, sm_scale, interpret, layer):
    """Shared pallas_call builder for the dense-pool and int8-pool
    arities; ``scales`` is None or the (k_scales, v_scales) pair. The
    kernel is handed the whole stacked pool, pinned in HBM, and indexes
    ``layer`` itself: a ``pool[layer]`` operand would be a copy of the
    layer."""
    B, H, hd = q.shape
    ps, width = kpool.shape[2:]
    KH = width // hd
    G = H // KH
    qg = q.reshape(B, KH, G, hd)
    quantized = scales is not None
    kernel = functools.partial(_decode_kernel, sm_scale=sm_scale,
                               page_size=ps, quantized=quantized,
                               layer=layer)
    in_specs = [
        pl.BlockSpec((1, 1, G, hd), lambda b, k, *_: (b, k, 0, 0)),
        # pools stay pinned in HBM; the kernel DMAs one
        # (page_size, hd) tile per walked page — never the stripe
        pl.BlockSpec(memory_space=pltpu.HBM),
        pl.BlockSpec(memory_space=pltpu.HBM),
    ]
    scratch = [
        pltpu.VMEM((2, ps, hd), kpool.dtype),
        pltpu.VMEM((2, ps, hd), vpool.dtype),
    ]
    operands = [block_tables, cache_position, qg, kpool, vpool]
    if quantized:
        nb = scales[0].shape[-1] // KH
        # scale pools ride in HBM too: one (page_size, nb) fp32 tile
        # DMAs alongside each int8 page tile
        in_specs += [pl.BlockSpec(memory_space=pltpu.HBM),
                     pl.BlockSpec(memory_space=pltpu.HBM)]
        scratch += [pltpu.VMEM((2, ps, nb), jnp.float32),
                    pltpu.VMEM((2, ps, nb), jnp.float32)]
        operands += [scales[0], scales[1]]
    scratch += [pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((2,))]
    if quantized:
        scratch += [pltpu.SemaphoreType.DMA((2,)),
                    pltpu.SemaphoreType.DMA((2,))]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        # tables + positions prefetch into SMEM: page ids must be
        # available to index the DMAs before the body runs
        num_scalar_prefetch=2,
        grid=(B, KH),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, G, hd),
                               lambda b, k, *_: (b, k, 0, 0)),
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KH, G, hd), q.dtype),
        interpret=interpret,
        compiler_params=_compiler_params(interpret),
    )(*operands)
    return out.reshape(B, H, hd)


@functools.partial(jax.jit,
                   static_argnames=("sm_scale", "interpret", "layer"))
def _paged_decode_call(q, kpool, vpool, block_tables, cache_position,
                       sm_scale, interpret, layer):
    return _paged_decode_pallas(q, kpool, vpool, None, block_tables,
                                cache_position, sm_scale, interpret, layer)


@functools.partial(jax.jit,
                   static_argnames=("sm_scale", "interpret", "layer"))
def _paged_decode_call_quant(q, kpool, vpool, k_scales, v_scales,
                             block_tables, cache_position, sm_scale,
                             interpret, layer):
    return _paged_decode_pallas(q, kpool, vpool, (k_scales, v_scales),
                                block_tables, cache_position, sm_scale,
                                interpret, layer)


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
    if k_scales is not None:
        nb, rem = divmod(k_scales.shape[-1], KH)
        assert k_scales.shape[:3] == kpool.shape[:3] and rem == 0 and \
            hd % nb == 0, (k_scales.shape, kpool.shape)
        return _paged_decode_call_quant(
            q, kpool, vpool, k_scales, v_scales,
            block_tables.astype(jnp.int32),
            cache_position.astype(jnp.int32), float(sm_scale),
            bool(interpret), int(layer))
    return _paged_decode_call(q, kpool, vpool,
                              block_tables.astype(jnp.int32),
                              cache_position.astype(jnp.int32),
                              float(sm_scale), bool(interpret), int(layer))
