"""The paged KV pool's writer and readers, for every model family: where
a call's tokens go (:func:`paged_write_index`), the one write whose
index granularity follows the input (:func:`write_paged_kv_cache`), the
stripe gather, and the three readers a call's shape picks between
(:func:`paged_attend`). The Pallas kernels are in
``ops/attention/paged.py``, the pool's geometry in
``inference/kv_cache.py``.
"""

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.ops.attention import flash
from deepspeed_tpu.ops.attention.flash import NEG_INF, flash_attention
from deepspeed_tpu.ops.attention.paged import (dequantize_pool,
                                               paged_decode_attention,
                                               quantize_kv)
from deepspeed_tpu.profiling.spans import scope


def causal_cache_mask(cache_position, q_len: int, kv_len: int):
    """Causal mask over a KV cache that respects per-row cache offsets.

    ``cache_position``: (B,) int32 — absolute position of each row's
    FIRST query token in its stream (the number of tokens already in
    that row's cache). Query j of row b therefore sits at position
    ``cache_position[b] + j`` and may attend exactly the cache slots
    ``<= `` that position: everything written before it plus the slots
    this same call writes at/before its own position. Returns a bool
    (B, 1, q_len, kv_len) mask (broadcasts over heads). The shared
    offset-mask home for the cached prefill/decode paths of every model
    family — the serving engine's bucketed programs pin their numerics
    on it.
    """
    q_pos = cache_position[:, None] + jnp.arange(q_len)[None, :]
    k_idx = jnp.arange(kv_len)
    return k_idx[None, None, None, :] <= q_pos[:, None, :, None]


class PagedWriteIndex(NamedTuple):
    """Where a call's tokens go in a paged pool
    (:func:`paged_write_index`). ``pages`` and ``aligned`` are None
    where the call's width is not whole pages."""
    page: jax.Array         # (B * S,) int32: token (b, j)'s page
    offset: jax.Array       # (B * S,) int32: its row inside that page
    pages: Optional[jax.Array]      # (B * S / page_size,) int32
    aligned: Optional[jax.Array]    # () bool: every row starts a page


def paged_write_index(block_table, cache_position, num_tokens: int,
                      page_size: int) -> PagedWriteIndex:
    """Where this call's tokens go in a paged pool, computed once a call
    and shared by every layer's write. ``page`` and ``offset``, each
    ``(B * num_tokens,)`` int32, row-major over (row, token): row b's
    token j lands in page
    ``block_table[b, (cache_position[b]+j) // page_size]`` at offset
    ``(cache_position[b]+j) % page_size``. Positions past the table's
    logical extent — and unreserved table entries, which the host
    allocator leaves at 0 — land in the reserved null page 0, whose
    garbage ``causal_cache_mask`` keeps unread.

    Where ``num_tokens`` is whole pages (a static fact: the prompt
    buckets, a chunk width) the index also gives ``pages``,
    ``(B * num_tokens / page_size,)`` int32, the page of each run of
    ``page_size`` tokens — row b's j-th is
    ``block_table[b, cache_position[b] // page_size + j]`` under the same
    null-page rule — and ``aligned``, the one run-time fact that makes
    those runs WHOLE pages: every row of the call starts on a page
    boundary (a prompt at 0, a prefix hit that ends a page, a later
    chunk). :func:`write_paged_kv_cache` then writes a page an index."""
    P = block_table.shape[1]
    with scope("kv_write"):
        pos = cache_position[:, None] + jnp.arange(num_tokens)[None, :]
        slot = pos // page_size
        page = jnp.where(
            slot < P,
            jnp.take_along_axis(block_table, jnp.minimum(slot, P - 1),
                                axis=1),
            0)
        pages = aligned = None
        if num_tokens % page_size == 0:
            pages = page[:, ::page_size].reshape(-1)
            aligned = jnp.all(cache_position % page_size == 0)
        return PagedWriteIndex(page.reshape(-1),
                               (pos % page_size).reshape(-1), pages, aligned)


def write_paged_kv_cache(pool, layer: int, new, index: PagedWriteIndex):
    """Write ``new`` (B, heads, S, w) into the stacked paged pool
    ``(layers, num_pages, page_size, heads * w)`` in place, heads major
    within a token's row (``index`` from :func:`paged_write_index`).
    ``w`` is head_dim for the payload pools, scale_blocks for an int8
    pool's scale leaves, the whole row for a one-head latent pool.
    ``pool`` and ``new`` may be matching tuples of leaves (a layer's keys
    and values; an int8 pool's payloads and scales): they share the
    index, and one conditional covers them.

    ONE write whose index granularity follows the input. A call of
    whole pages (``index.pages``: ``S % page_size == 0``) whose rows all
    start on a page boundary (``index.aligned``, read at RUN time inside
    the one program a bucket has) writes ``S / page_size`` whole pages a
    row, ``pool.at[layer, pages]``, one scatter index a page: on the v5e
    a scatter costs about 140 ns an index whatever the index moves, so a
    page of 16 rows lands in the time of one (PR 44). Any other call —
    decode's one row, a verify's ``k + 1``, a ragged width, a batch with
    one row that starts mid-page — writes token (b, j) to
    ``[layer, page[b*S+j], offset[b*S+j]]``, one index a row; where the
    width is not whole pages that is the only form traced. Both forms
    put the same bytes in the same place: a page's tail past a row's
    true length holds the pad tokens' keys either way (the causal mask
    never reads them and decode overwrites them in order), pad rows and
    slots past a reservation land in the null page.

    The index dimensions lead and whole rows are written, so under a
    donated pool either scatter aliases its operand, through the
    conditional too: no layer is sliced out and nothing of the pool's
    size is copied (ISSUE 28; ``tests/unit/test_tpu_compile.py``)."""
    with scope("kv_write"):
        rows = jax.tree_util.tree_map(_token_rows, pool, new)
    if index.pages is None:
        return _write_token_rows(pool, layer, rows, index)
    return _write_pages_or_rows(pool, layer, rows, index)


def _token_rows(leaf, new):
    """``new`` (B, heads, S, w) as the pool leaf's rows (B * S, heads * w)."""
    B, H, S, w = new.shape
    return new.astype(leaf.dtype).transpose(0, 2, 1, 3).reshape(B * S, H * w)


def _write_token_rows(pool, layer, rows, index):
    """:func:`write_paged_kv_cache`, one scatter index a token row."""
    with scope("kv_write"):
        return jax.tree_util.tree_map(
            lambda leaf, x: leaf.at[layer, index.page, index.offset].set(x),
            pool, rows)


@jax.jit
def _write_pages_or_rows(pool, layer, rows, index):
    """:func:`write_paged_kv_cache` at a width of whole pages: whole
    pages or token rows, as ``index.aligned`` says when it runs. Behind
    a ``jit`` of its own with the LAYER a traced scalar, so that a
    program's layers share ONE trace and ONE lowering of the conditional
    and its four scatters (as :func:`_own_keys`): traced a layer, it
    cost each of the cell's twelve prefill programs 0.4-0.9 s before
    the compile cache is even asked, 5-10 s of `setup_s`. ``rows`` come
    made (:func:`_token_rows`): re-laid in here they were written out
    once more before the scatter, 0.4 ms a prefill (my chip runs, PR
    44)."""
    def whole_pages(pool):
        return jax.tree_util.tree_map(
            lambda leaf, x: leaf.at[layer, index.pages].set(
                x.reshape(-1, *leaf.shape[2:])), pool, rows)
    with scope("kv_write"):
        return jax.lax.cond(
            index.aligned, whole_pages,
            lambda pool: _write_token_rows(pool, layer, rows, index), pool)


def gather_paged_kv(pool, layer: int, block_table, kv_heads: int):
    """Assemble each row's logical K or V stripe of one layer from the
    stacked paged pool: ``(B, pages_per_seq)`` block table over
    ``(layers, num_pages, page_size, kv_heads * w)`` ->
    ``(B, kv_heads, pages_per_seq * page_size, w)``. Gathered position
    ``t * page_size + o`` is the row's absolute cache position, so
    :func:`causal_cache_mask` applies unchanged — unmapped table entries
    surface the null page, always masked. The gather moves whole
    lane-dense rows; the split of a row into its heads comes after it.

    NB: this materializes each row's full logical stripe (every table
    entry it is handed) each call — per-step decode reads are bounded
    by the TABLE WIDTH, not the tokens actually live. It is the reader
    of every query of more than one row that does not start at cache
    position 0 (a prefixed prefill, a later chunk, spec-verify; see
    :func:`paged_attend`), the paged paths' numerics oracle, and decode's
    fallback where the fused Pallas decode kernel
    (``ops/attention/paged.py`` — streams whole rows of the live pages
    only, at every head width whose pool row is whole 128-lane tiles)
    can't run: an int8 pool, a row like GPT-2 XL's 1,600 lanes. The
    serving engine additionally clamps the decode table width to the
    batch's live page bucket so even this fallback stops paying full
    ``max_len`` bandwidth
    (``inference.paged_kv.decode_page_buckets``)."""
    B, P = block_table.shape
    ps, width = pool.shape[2:]
    with scope("kv_gather"):
        return pool[layer, block_table].reshape(
            B, P * ps, kv_heads, width // kv_heads).transpose(0, 2, 1, 3)


def write_paged_layer(pools, layer: int, k, v, index: PagedWriteIndex):
    """One layer's new K/V (each (B, kv_heads, S, hd)) into the stacked
    pool tree, in place; returns the updated tree. The pair
    ``(kpool, vpool)`` stores them as they come; the int8 4-tuple
    ``(kpool, vpool, kscale, vscale)`` quantizes per token row
    (``ops.attention.paged.quantize_kv``), payload and scales landing
    through the same write (:func:`write_paged_kv_cache`: whole pages
    or token rows, as ``index`` says)."""
    if len(pools) == 4:
        nb = pools[2].shape[-1] // k.shape[1]
        with scope("kv_write"):
            k, k_s = quantize_kv(k, nb)
            v, v_s = quantize_kv(v, nb)
        new = (k, v, k_s, v_s)
    else:
        new = (k, v)
    return write_paged_kv_cache(tuple(pools), layer, new, index)


def gather_paged_layer(pools, layer: int, block_table, kv_heads: int):
    """One layer's ``(kc, vc)`` stripes, each
    (B, kv_heads, pages_per_seq * page_size, hd), gathered from the
    stacked pool tree — float32 after ``dequantize_pool`` where the tree
    is the int8 4-tuple, else in the pool's dtype."""
    kc, vc = (gather_paged_kv(pool, layer, block_table, kv_heads)
              for pool in pools[:2])
    if len(pools) == 4:
        with scope("kv_gather"):
            kc = dequantize_pool(kc, gather_paged_kv(
                pools[2], layer, block_table, kv_heads))
            vc = dequantize_pool(vc, gather_paged_kv(
                pools[3], layer, block_table, kv_heads))
    return kc, vc


def paged_decode_ctx(q, pools, layer: int, block_table, cache_position,
                     sm_scale=None):
    """The seq-1 fused-kernel dispatch the families share: run
    :func:`deepspeed_tpu.ops.attention.paged.paged_decode_attention`
    against layer ``layer`` of the (already-written) stacked pool tree
    and restore the (B, H, 1, hd) context layout. One home so the kernel
    call contract cannot drift between gpt2 and llama. The int8 4-tuple
    selects the kernel's scale arity — the per-page scale tiles stream
    into the kernel and dequant happens in VMEM. ``sm_scale`` (None:
    ``head_dim ** -0.5``) is what the scores are multiplied by.

    Under a serving mesh the engine traces its compiled programs inside
    ``parallel/pallas_shard.pallas_kernel_mesh``; consulting that
    context here wraps the kernel in shard_map over the mesh's head
    axis (pools stay sharded over kv heads — the O(live tokens) read
    survives GSPMD instead of falling back to gather)."""
    from deepspeed_tpu.parallel.pallas_shard import (current_kernel_mesh,
                                                     sharded_paged_decode)
    kpool, vpool = pools[:2]
    k_scales, v_scales = pools[2:] if len(pools) == 4 else (None, None)
    km = current_kernel_mesh()
    with scope("attn_cached"):
        if km is not None:
            out = sharded_paged_decode(q[:, :, 0], kpool, vpool,
                                       block_table, cache_position,
                                       mesh=km.mesh, axis=km.axis,
                                       sm_scale=sm_scale,
                                       k_scales=k_scales,
                                       v_scales=v_scales, layer=layer)
        else:
            out = paged_decode_attention(q[:, :, 0], kpool, vpool,
                                         block_table, cache_position,
                                         sm_scale=sm_scale,
                                         k_scales=k_scales,
                                         v_scales=v_scales, layer=layer)
        return out[:, :, None, :]


# float32 scores (B * heads * S * S elements) up to which a call that
# attends to its own keys runs the family's stripe mathematics over them
# and not the flash kernel: the kernel's grid is a program a (row, head)
# and at tiles this small it is bound by their launches. One GPT-2 345M
# layer on the v5e, 8 x 64 / 128 / 256 (2 to 32 MB of scores): 9 / 11 /
# 34 us against the kernel's 68 / 75 / 103; at 8 x 512 (134 MB) 582
# against 198, at 32 x 256 590 against 427 (my chip runs, PR 40)
_OWN_KEYS_DENSE_SCORES = 1 << 23

# query rows of the flash kernel's smallest tile: a narrower or ragged
# call falls to its O(S^2) reference there, so it keeps the stripe
_OWN_KEYS_ROWS = 16


def own_keys_attention(q, k, v, cache_position, stripe_attention,
                       sm_scale=None):
    """Causal attention of ``q`` (B, heads, S, hd) over the call's own
    ``k``, ``v`` (B, kv_heads, S, hd): what a row that starts at cache
    position 0 may see is exactly what this call has just computed, so
    nothing is read back out of the pool. ``causal_cache_mask`` at
    position 0 IS the causal mask: a small call hands its own keys to
    the family's ``stripe_attention`` as a stripe of S positions (all
    zeros in ``cache_position``), a larger one to the training kernel
    (``ops/attention/flash.flash_attention``: GQA native, operands as
    they come, float32 accumulation and softmax, no (S, S) scores in
    HBM). Chosen by the call's shape (``_OWN_KEYS_DENSE_SCORES``).
    ``sm_scale`` (None: ``head_dim ** -0.5``) is the kernel's score
    scale; a family with another builds it into its ``stripe_attention``
    too."""
    from deepspeed_tpu.parallel.pallas_shard import current_kernel_mesh
    B, H, S, _ = q.shape
    return _own_keys(q, k, v, cache_position,
                     stripe_attention=stripe_attention,
                     dense=B * H * S * S <= _OWN_KEYS_DENSE_SCORES,
                     interpret=not flash._use_pallas(),
                     kernel_mesh=current_kernel_mesh(), sm_scale=sm_scale)


@functools.partial(jax.jit, static_argnames=(
    "stripe_attention", "dense", "interpret", "kernel_mesh", "sm_scale"))
def _own_keys(q, k, v, cache_position, stripe_attention, dense, interpret,
              kernel_mesh, sm_scale=None):
    """:func:`own_keys_attention` behind a ``jit`` of its own, so that a
    program's layers share ONE trace and ONE lowering of it: traced a
    layer, the flash kernel cost each program of 24 layers 4.5 s of
    tracing and 5.5 s of lowering to Mosaic before the compile cache is
    even asked (my chip run, PR 40: 27 s of the cell's set-up over three
    programs). The static arguments are everything the trace depends on
    besides the operands; ``kernel_mesh`` (the engine's trace context,
    which ``flash_attention`` reads for itself) only keys it."""
    del kernel_mesh
    if dense:
        return stripe_attention(q, k, v, cache_position)
    with scope("attn_core"):
        return flash_attention(q, k, v, causal=True, sm_scale=sm_scale,
                               interpret=interpret)


# rows of a prefix that one loop turn of :func:`prefix_own_attention`
# has gathered, expanded and handed to the flash kernel (whole pages)
PREFIX_BLOCK = 2048


def prefix_block_rows(table_tokens: int) -> int:
    """Rows of one block of :func:`prefix_own_attention`'s walk over a
    block table of ``table_tokens`` positions."""
    return min(PREFIX_BLOCK, table_tokens)


def prefix_own_attention(q, k, v, cache_position, prefix_block,
                         table_tokens: int, sm_scale=None, *,
                         prefix_scope: str):
    """Attention of a CHUNK's queries ``q`` (B, heads, S, hd), which sit
    at positions ``cache_position[b] + j``, over the call's own ``k``,
    ``v`` (B, heads, S, hd), causally, AND over the ``cache_position[b]``
    rows earlier chunks left in the pool: one softmax over both, exact,
    with no (S x prefix) scores in HBM. The own rows go through the
    flash kernel as :func:`own_keys_attention`'s do; the prefix a block
    at a time: ``prefix_block(j, rows) -> (k_j, v_j)`` (B, heads, rows,
    hd) are the keys and values at positions ``[j * rows, (j + 1) *
    rows)`` as the family reads them back through its block table
    (``rows`` = :func:`prefix_block_rows`), handed to the same kernel without the causal cut and
    with an additive mask that hides positions at or past the row's
    start; each block's normalised partial joins the running one by its
    log-sum-exp (``ops/attention/ring._combine``: the ring prefill's
    merge). The loop runs to the LONGEST prefix of the call's rows and
    no further: a bucket of rows at position 0 runs no turn. The
    prefix's part runs under the family's registered ``prefix_scope``."""
    from deepspeed_tpu.ops.attention.ring import _combine
    hd = q.shape[-1]
    interpret = not flash._use_pallas()
    scale = float(sm_scale) if sm_scale is not None else hd ** -0.5
    with scope("attn_core"):
        o, lse = flash._flash_fwd(q, k, v, None, True, scale, interpret)
    rows = prefix_block_rows(table_tokens)

    def turn(j, acc):
        k_j, v_j = prefix_block(j, rows)
        seen = (j * rows + jnp.arange(rows))[None, :] \
            < cache_position[:, None]
        hide = jnp.where(seen, 0.0, flash.NEG_INF).astype(jnp.float32)
        o_j, lse_j = flash._flash_fwd(q, k_j, v_j, hide[:, None, None, :],
                                      False, scale, interpret)
        return _combine(*acc, o_j, lse_j)

    with scope(prefix_scope):
        turns = (jnp.max(cache_position) + rows - 1) // rows
        o, _ = jax.lax.fori_loop(0, turns, turn,
                                 (o.astype(jnp.float32), lse))
    return o.astype(q.dtype)


def paged_attend(q, k, v, pools, layer: int, block_table, cache_position,
                 index, out_box, attn_kernel: str, stripe_attention,
                 sm_scale=None):
    """Layer ``layer`` of the paged cached forward, for every family
    (prefill-into-pages and paged decode alike): write this call's K/V
    into the stacked pool tree (:func:`write_paged_layer`, where
    ``index`` from :func:`paged_write_index` says: whole pages for a
    call of whole pages whose rows all start on a page boundary, token
    rows for any other), then attend, through one of three readers
    chosen by what the call shows:

    - one query row (decode, and any seq-1 prefill bucket) with
      ``attn_kernel="pallas"``: the fused paged-attention kernel
      straight against the pool (:func:`paged_decode_ctx` — only live
      pages are read);
    - many rows that ALL start at cache position 0 (a prompt bucket with
      no shared prefix, a chunked prefill's first chunk):
      :func:`own_keys_attention` over the call's own ``k``, ``v`` — the
      pool is written and not read, and a prompt of 64 attends to 64
      keys, not to the table's 640. Picked at RUN time inside the one
      program a bucket has (``lax.cond`` on the positions), and traced
      only where the call's shape can use it: rows a multiple of
      ``_OWN_KEYS_ROWS``, the plain pool pair in the keys' own dtype
      (after an int8 or a narrower pool a decode sees ROUNDED keys, and
      the first token sees the same), no context-parallel mesh (the
      ring keeps its prefill);
    - anything else (a batch with a prefixed row, a later chunk, a
      spec-verify call): each row's logical stripe gathered back and
      handed to the family's ``stripe_attention(q, kc, vc,
      cache_position)`` (the numerics oracle / fallback).

    ``sm_scale`` (None: ``head_dim ** -0.5``) is handed to the two
    kernels; ``stripe_attention`` is the family's own and carries its
    scale itself. The updated tree — the pair, or the int8 4-tuple —
    returns through ``out_box``."""
    from deepspeed_tpu.parallel.pallas_shard import current_cp_mesh
    written = write_paged_layer(pools, layer, k, v, index)
    out_box.append(written)
    rows = q.shape[2]
    if attn_kernel == "pallas" and rows == 1:
        return paged_decode_ctx(q, written, layer, block_table,
                                cache_position, sm_scale)
    cp = current_cp_mesh() if rows > 1 else None

    def stripe():
        kc, vc = gather_paged_layer(written, layer, block_table,
                                    k.shape[1])
        if cp is not None:
            # context-parallel chunked prefill (ISSUE 19): under the
            # engine's CP trace context, the chunk's sequence axis runs
            # ring-sharded over the serving mesh — same stripe, same
            # absolute-position causal rule (GQA folds group-wise inside
            # the ring)
            from deepspeed_tpu.ops.attention.ring import \
                ring_prefill_attention
            return ring_prefill_attention(q, kc, vc, cache_position,
                                          cp.mesh, cp.axis)
        return stripe_attention(q, kc, vc, cache_position)

    if (rows % _OWN_KEYS_ROWS == 0 and cp is None and len(written) == 2
            and written[0].dtype == k.dtype):
        return jax.lax.cond(
            jnp.all(cache_position == 0),
            lambda: own_keys_attention(q, k, v, cache_position,
                                       stripe_attention, sm_scale), stripe)
    return stripe()


def gqa_stripe_attention(q, kc, vc, cache_position, sm_scale=None):
    """Group-wise attention of ``q`` (B, heads, S, hd) over a whole
    kv_heads-sized key/value stripe (B, kv_heads, kv_len, hd) under the
    shared ``causal_cache_mask``, in float32: no head is replicated.
    ``sm_scale`` (None: ``hd ** -0.5``) multiplies the scores. The
    ``stripe_attention`` of every GQA family (llama, the hybrids'
    softmax layers): the numerics oracle behind :func:`paged_attend`."""
    B, H, S, hd = q.shape
    hkv = kc.shape[1]
    qg = q.reshape(B, hkv, H // hkv, S, hd)
    scores = jnp.einsum("bkgsd,bkld->bkgsl", qg.astype(jnp.float32),
                        kc.astype(jnp.float32))
    scores = scores / np.sqrt(hd) if sm_scale is None else scores * sm_scale
    mask = causal_cache_mask(cache_position, S, kc.shape[2])
    scores = jnp.where(mask[:, :, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bkgsl,bkld->bkgsd", probs, vc.astype(jnp.float32))
    return ctx.reshape(B, H, S, hd).astype(q.dtype)
