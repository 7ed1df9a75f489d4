"""Attention over a LEARNED SELECTION of a row's tokens, inside the paged
pool (``models/keye_vl2.py``): an indexer scores every token a query may
see against ONE small key a token a layer (the cache tree's third leaf,
``inference/kv_cache.IndexedPairCache``), the ``topk`` best are chosen
EXACTLY (ties to the lower position), and the softmax runs over those
alone. Two readers, one rule:

- :func:`decode_attention`, one query a row: every live indexer key is
  read and scored by ONE Pallas kernel that walks the row's live pages
  of the leaf, a block of pages a loop turn and a run of consecutive
  pool ids one copy (:func:`indexer_decode_keys`; the XLA scorer over
  the gathered table, :func:`_stripe_scores`, stays as the tests'
  reference), the choice is ``lax.top_k`` over the row's scores (whose
  ties go to the lower index), and the chosen tokens' keys and values
  are read BY ROW, ``pool[layer, page, offset]``: ``topk`` rows a pool
  a layer, however long the context.
- :func:`chunk_attention`, a chunk's queries over the rows earlier
  chunks left in the pool and over its own: the scores are kept a block
  of keys at a time as int32 keys that order as the scores do, a
  query's ``topk``-th largest is found by 32 counting passes over them
  (one a bit, no sort), and the flash kernel runs a block at a time
  under the mask a (query, key) that the threshold gives, partials
  merged by their log-sum-exp. Nothing is gathered a row: at ``topk``
  2,048 a chunk of 2,048 queries would gather 8.6 GB a layer, and the
  masked kernel's products over every live key cost less than that up
  to contexts of about 128k (docs/keye_vl2.md has both costs).

A context of at most ``topk`` positions selects everything BY THE SAME
CODE: there is no dense path beside these.

ONE rule, two forms of it, because the two readers want different
things of the choice. A chunk wants a MASK a (query, key): the flash
kernel takes it as it is, and a sort of 2,048 queries' rows of up to
69,632 keys would cost a hundred times the counting passes. Decode
wants a LIST of ``topk`` positions a row, to read rows by (page,
offset), and a mask still has to be compacted into one: on a v5e, at 17
rows of 69,632 keys, the threshold by counting and its ties cost 0.57
ms, and compacting the mask into 2,048 places 5.7 ms more by a running
count and a scatter or 6.2 by a search a place, beside 1.67 ms for
``lax.top_k`` whole (PERF.md section 6, PR 55). Decode keeps the sort
until a compaction is found that beats it; both forms share
:func:`score_keys`, hold the same tie rule and are tested against one
dense oracle.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

try:
    from jax.experimental.pallas import tpu as pltpu
except ImportError:  # pragma: no cover
    pltpu = None

from deepspeed_tpu.ops.attention import flash
from deepspeed_tpu.ops.attention.flash import NEG_INF
from deepspeed_tpu.ops.attention.page_pool import prefix_block_rows
from deepspeed_tpu.ops.attention.paged import (_block_runs,
                                               _compiler_params, live_pages)
from deepspeed_tpu.profiling.spans import scope

# what no score's key falls to: a position a query may not see
_NO_KEY = np.iinfo(np.int32).min


def _one_zero(scores):
    """A zero is +0 whatever the weights' signs, so that equal scores
    are equal keys."""
    return jnp.where(scores == 0.0, 0.0, scores)


def indexer_scores(qi, wi, ki):
    """``I(t, s) = sum_j w[t, j] ReLU(q[t, j] . k[s])``: ``qi`` (B, S,
    heads, d), ``wi`` (B, S, heads) float32 (the head's weight, the
    constant factors folded in), ``ki`` (B, L, d) -> (B, S, L)
    float32."""
    s = jnp.einsum("bshd,bld->bshl", qi, ki,
                   preferred_element_type=jnp.float32)
    return _one_zero(jnp.sum(jax.nn.relu(s) * wi[..., None], axis=2))


def write_index_keys(pool, layer: int, ki, index):
    """This call's indexer keys ``ki`` (B, 1, S, d) into the stacked
    leaf ``(layers, pages, page_size / 2, 2 d)``, in place, where
    ``index`` (``page_pool.paged_write_index``'s, the pair's own) says:
    token (b, j) is the ``offset % 2``-th half of row ``offset // 2`` of
    its page. A call of whole pages whose rows all start a page writes a
    page an index (the rows re-read two a pool row: the same bytes); any
    other call writes a token at a time, the first halves and then the
    second, each a row read, one half replaced, and written back (a
    token of the other half goes to the null page meanwhile)."""
    B, _, S, d = ki.shape
    rows = ki.astype(pool.dtype).reshape(B * S, d)

    def token_rows(pool):
        lanes = jnp.arange(2 * d) // d
        row, twice = index.offset // 2, jnp.tile(rows, (1, 2))
        for half in (0, 1):
            page = jnp.where(index.offset % 2 == half, index.page, 0)
            new = jnp.where(lanes[None, :] == half, twice,
                            pool[layer, page, row])
            pool = pool.at[layer, page, row].set(new)
        return pool

    with scope("kv_write"):
        if index.pages is None:
            return token_rows(pool)
        return jax.lax.cond(
            index.aligned,
            lambda pool: pool.at[layer, index.pages].set(
                rows.reshape(-1, *pool.shape[2:])),
            token_rows, pool)


def _standing_twice(qi, wi):
    """One query a row against pool rows of TWO tokens: ``qi`` (B,
    heads, d), ``wi`` (B, heads) -> the queries (B, 2 heads, 2 d), the
    first ``heads`` against a row's first half (zeros over its second)
    and the others against its second, and their weights (B, 2 heads),
    so that a row is read once and never re-laid."""
    d = qi.shape[-1]
    half = lambda left, right: jnp.pad(qi, ((0, 0), (0, 0), (left, right)))
    return (jnp.concatenate([half(0, d), half(d, 0)], axis=1),
            jnp.concatenate([wi, wi], axis=1))


def _stripe_scores(qi, wi, rows):
    """:func:`indexer_scores` of ONE query a row against a stripe of
    pool rows as they are held, two tokens a row: ``qi`` (B, heads, d),
    ``wi`` (B, heads), ``rows`` (B, R, 2 d) -> (B, 2 R) float32 in
    position order (the queries :func:`_standing_twice`). What
    :func:`indexer_decode_keys` computes a block at a time: its
    reference in the tests, called by no program."""
    B, H, _ = qi.shape
    q, w = _standing_twice(qi, wi)
    s = jnp.einsum("bhd,brd->bhr", q, rows,
                   preferred_element_type=jnp.float32)
    s = jax.nn.relu(s) * w[..., None]
    total = jnp.stack([jnp.sum(s[:, :H], axis=1),
                       jnp.sum(s[:, H:], axis=1)], axis=-1)
    return _one_zero(total).reshape(B, -1)


def score_keys(scores, seen):
    """float32 ``scores`` as int32 keys that order exactly as they do;
    ``seen`` (broadcast against them) False: ``_NO_KEY``, below every
    score's key."""
    bits = jax.lax.bitcast_convert_type(scores.astype(jnp.float32),
                                        jnp.int32)
    keys = jnp.where(bits < 0, bits ^ jnp.int32(0x7fffffff), bits)
    return jnp.where(seen, keys, _NO_KEY)


def _key_of(u):
    """A uint32 in the keys' order as the int32 key it stands for."""
    return jax.lax.bitcast_convert_type(u ^ jnp.uint32(0x80000000),
                                        jnp.int32)


def kth_largest_key(count, k: int, shape):
    """The largest key ``T`` with ``count(jnp.greater_equal, T) >= k``:
    the ``k``-th largest of each row's keys, built a bit at a time from
    the top (32 calls of ``count``; ``count(op, cand)`` -> how many of a
    row's keys stand in ``op`` to ``cand``, both ``shape``). A row with
    fewer than ``k`` keys that are not ``_NO_KEY`` gives ``_NO_KEY``."""
    def bit(i, t):
        cand = t | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        return jnp.where(count(jnp.greater_equal, _key_of(cand)) >= k,
                         cand, t)
    return _key_of(jax.lax.fori_loop(0, 32, bit,
                                     jnp.zeros(shape, jnp.uint32)))


def _kept(keys, threshold, need, ties_before):
    """Which of a block's ``keys`` (B, S, rows) are selected under each
    query's ``threshold`` (B, S): every key above it, and of the keys
    EQUAL to it the first ``need`` (B, S) in position order,
    ``ties_before`` (B, S) of which earlier blocks held. Returns (the
    mask, the ties so far)."""
    rows = keys.shape[-1]
    t = threshold[..., None]
    tie = (keys == t) & (keys != _NO_KEY)
    # ties at lower positions of this block: a product with the strict
    # upper triangle (0/1 operands, float32 sums: exact)
    before = jnp.arange(rows)[:, None] < jnp.arange(rows)[None, :]
    rank = jnp.dot(tie.astype(jnp.bfloat16), before.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32).astype(jnp.int32)
    keep = (keys > t) | (tie & ((ties_before[..., None] + rank)
                                < need[..., None]))
    return keep, ties_before + jnp.sum(tie, axis=-1, dtype=jnp.int32)


def chunk_attention(q, k, v, qi, wi, ki, cache_position, prefix_keys,
                    prefix_pair, table_tokens: int, topk: int, sm_scale,
                    probe=None):
    """A chunk's attention over its selected tokens. ``q`` (B, heads, S,
    hd) sit at positions ``cache_position[b] + j``; ``k``, ``v`` (B,
    kv_heads, S, hd) and ``ki`` (B, S, d) are the call's own rows (as
    the pool holds them); ``qi`` (B, S, ih, d) and ``wi`` (B, S, ih)
    the indexer's queries and weights. The prefix comes a block of
    ``rows`` = :func:`page_pool.prefix_block_rows` positions at a time:
    ``prefix_keys(j, rows)`` -> (B, rows, d) indexer keys and
    ``prefix_pair(j, rows)`` -> (k_j, v_j) (B, kv_heads, rows, hd) at
    positions ``[j * rows, (j + 1) * rows)``, as the family reads them
    back through its block table. Every loop runs to the LONGEST prefix
    of the call's rows and no further. ``probe`` (a list, eager calls
    only) receives the own block's selection mask."""
    from deepspeed_tpu.ops.attention.ring import NEG_BIG, _combine
    B, H, S, hd = q.shape
    rows = prefix_block_rows(table_tokens)
    blocks = -(-table_tokens // rows)
    interpret = not flash._use_pallas()
    turns = (jnp.max(cache_position) + rows - 1) // rows

    with scope("indexer"):
        own = score_keys(indexer_scores(qi, wi, ki),
                         jnp.tril(jnp.ones((S, S), bool))[None])

        def score(j, held):
            seen = (j * rows + jnp.arange(rows))[None, :] \
                < cache_position[:, None]
            keys = score_keys(indexer_scores(qi, wi, prefix_keys(j, rows)),
                              seen[:, None, :])
            return held.at[j].set(keys)

        # a block a leading index; the blocks past ``turns`` are never
        # read
        held = jax.lax.fori_loop(
            0, turns, score, jnp.full((blocks, B, S, rows), _NO_KEY,
                                      jnp.int32))

    with scope("select"):
        def count(op, cand):
            at = cand[..., None]
            return jax.lax.fori_loop(
                0, turns,
                lambda j, c: c + jnp.sum(op(held[j], at), axis=-1,
                                         dtype=jnp.int32),
                jnp.sum(op(own, at), axis=-1, dtype=jnp.int32))

        threshold = kth_largest_key(count, topk, (B, S))
        need = topk - count(jnp.greater, threshold)

    def attend(keep, k_j, v_j, causal):
        hide = jnp.where(keep, 0.0, NEG_INF).astype(jnp.bfloat16)
        return flash._flash_fwd(q, k_j, v_j, hide[:, None], causal,
                                float(sm_scale), interpret)

    with scope("sparse_prefix"):
        def turn(j, acc):
            o, lse, ties = acc
            keep, ties = _kept(held[j], threshold, need, ties)
            return (*_combine(o, lse, *attend(keep, *prefix_pair(j, rows),
                                              False)), ties)

        o, lse, ties = jax.lax.fori_loop(
            0, turns, turn,
            (jnp.zeros((B, H, S, hd), jnp.float32),
             jnp.full((B, H, S), NEG_BIG, jnp.float32),
             jnp.zeros((B, S), jnp.int32)))
    with scope("sparse_attn"):
        # the own rows stand at the highest positions: their ties last
        keep, _ = _kept(own, threshold, need, ties)
        if probe is not None:
            probe.append(keep)
        o, _ = _combine(o, lse, *attend(keep, k, v, True))
    return o.astype(q.dtype)


# Tokens a loop turn of the decode indexer's walk, whole pages. The
# leaf's page is small (16 tokens of 128 B: 2 KB) and a turn costs 0.3
# us before its first byte, so a turn has to hold many pages. Swept
# where the rows are long (17 rows of a mean 35k live tokens, 6 layers,
# the tables out of the churned allocator, every turn a run; ms the six
# calls alone, 0.23 of it the planes' interleave, my chip runs, PR 56):
# 8 pages a turn 7.80, 32: 2.69, 64: 1.61, 128: 1.13, 256: 0.97,
# beside 9.30 for the XLA gather scorer; a shuffled table (every block
# page by page) 7.72 at 128. At 128 a turn is as much its bytes as its
# own cost (256 KB: 0.31 us of 0.53), 256 buys 0.16 ms of a 28.8 ms
# step, and an extent of 256 pages is a ninth of a request: 128. One
# constant, chosen by nothing a user sets, and the allocator's run for
# the family (``block_pages``).
_INDEX_BLOCK_TOKENS = 2048


def block_pages(page_size: int) -> int:
    """Pages of ``page_size`` tokens that one loop turn of
    :func:`indexer_decode_keys` copies and scores: whole pages, at
    least one. The run an allocator owes the family."""
    return max(1, _INDEX_BLOCK_TOKENS // page_size)


def _indexer_decode_kernel(layer_ref, tables_ref, pos_ref, runs_ref, q_ref,
                           w_ref, pool_ref, o_ref, buf, sem, base_ref):
    """One row's program: walk the row's LIVE pages of the indexer leaf,
    ``buf.shape[1]`` pages a loop turn through a double buffer, and
    score the landed block as :func:`_stripe_scores` +
    :func:`score_keys` do, into block ``blk`` of ``o_ref`` ``(1, blocks,
    2, block rows)``: plane 0 the rows' first halves (the even
    positions), plane 1 their second. What the walk never reads stays
    ``_NO_KEY``. The copying rule is ``paged._decode_kernel``'s: a block
    whose live pages are consecutive pool ids (``runs_ref``) is one
    copy, a walk's last short block the binary pieces of its count, any
    other block page by page; the rows' blocks are one stream through
    the two slots, a walk's last turn issuing the next row's first
    block. A copy indexes the leaf's LEADING dimensions: a page of 8
    rows of bfloat16 is one ``T(8,128)(2,1)`` tile of the leaf as XLA
    lays it and half of Mosaic's 16-row tile, so a view of the leaf as
    rows would cut tiles at an odd page, and this cuts none."""
    b = pl.program_id(0)
    last_seq = pl.num_programs(0) - 1
    layer = layer_ref[0]
    pos = pos_ref[b]
    per_turn, page_rows, lanes = buf.shape[1:]
    page_size = 2 * page_rows
    rows = per_turn * page_rows
    pool_pages = pool_ref.shape[1]
    heads = q_ref.shape[1] // 2

    def _pages(seq):
        return live_pages(pos_ref[seq], page_size)

    num_blk = (_pages(b) + per_turn - 1) // per_turn

    def _for_live_pages(seq, blk, slot, fn):
        """``fn(copy)`` for every copy of the live pages of row
        ``seq``'s block ``blk``, which lands in ``slot``."""
        live = jnp.minimum(_pages(seq) - blk * per_turn, per_turn)

        def _by_page():
            def _page(j, _):
                # (a live page lies inside the table)
                page = tables_ref[seq, blk * per_turn + j]
                fn(pltpu.make_async_copy(pool_ref.at[layer, page],
                                         buf.at[slot, j], sem.at[slot]))
                return _
            jax.lax.fori_loop(0, live, _page, 0)
        if per_turn == 1:
            return _by_page()

        def _as_run():
            first = tables_ref[seq, blk * per_turn]

            def _copy(done, n):
                fn(pltpu.make_async_copy(
                    pool_ref.at[layer, pl.ds(first + done, n)],
                    buf.at[slot, pl.ds(done, n)], sem.at[slot]))

            def _pieces():
                for bit in reversed(range(
                        min(per_turn - 1, pool_pages).bit_length())):
                    pl.when((live & (1 << bit)) != 0)(
                        lambda bit=bit: _copy(
                            (live >> (bit + 1)) << (bit + 1), 1 << bit))
            if per_turn > pool_pages:        # (no pool holds such a run)
                return _pieces()
            jax.lax.cond(live == per_turn, lambda: _copy(0, per_turn),
                         _pieces)
        jax.lax.cond(runs_ref[seq, blk] != 0, _as_run, _by_page)

    def _start(seq, blk, slot):
        _for_live_pages(seq, blk, slot, lambda c: c.start())

    @pl.when(b == 0)
    def _first_walk():
        base_ref[0] = 0
        _start(0, 0, 0)
    base = base_ref[0]
    base_ref[0] = jax.lax.rem(base + num_blk, 2)

    # everything the walk does not reach
    o_ref[...] = jnp.full(o_ref.shape, _NO_KEY, jnp.int32)
    q, w = q_ref[0], w_ref[0]
    # position of (plane, row) in a block: 2 row + plane
    at = 2 * jax.lax.broadcasted_iota(jnp.int32, (2, rows), 1) \
        + jax.lax.broadcasted_iota(jnp.int32, (2, rows), 0)

    def body(blk, _):
        slot = jax.lax.rem(base + blk, 2)
        more = blk + 1 < num_blk

        @pl.when(more | (b < last_seq))
        def _prefetch_next():
            # this walk's next block, else the next walk's first
            _start(jnp.where(more, b, b + 1), jnp.where(more, blk + 1, 0),
                   1 - slot)
        _for_live_pages(b, blk, slot, lambda c: c.wait())
        s = jax.lax.dot_general(
            q, buf[slot].reshape(rows, lanes), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)       # (2 heads, rows)
        s = jax.nn.relu(s) * w
        total = _one_zero(jnp.concatenate(
            [jnp.sum(s[:heads], axis=0, keepdims=True),
             jnp.sum(s[heads:], axis=0, keepdims=True)], axis=0))
        # (a short block's unread rows lie past ``pos``)
        o_ref[0, blk] = score_keys(total, 2 * blk * rows + at <= pos)
        return _

    jax.lax.fori_loop(0, num_blk, body, 0)


@functools.partial(jax.jit, static_argnames=("interpret", "block_tokens"))
def _indexer_decode_call(qi, wi, pool, block_tables, cache_position, layer,
                         interpret, block_tokens):
    """The ``pallas_call`` of :func:`indexer_decode_keys`: the stacked
    leaf pinned in HBM and indexed by ``layer`` from SMEM beside the
    tables, the positions and the blocks that are runs (every layer's
    call is the same kernel). ``block_tokens`` is the tokens a loop turn
    copies: whole pages."""
    B, H, _ = qi.shape
    page_rows, lanes = pool.shape[2:]
    ps = 2 * page_rows
    per_turn = block_tokens // ps
    blocks = -(-block_tables.shape[1] // per_turn)
    rows = per_turn * page_rows
    q, w = _standing_twice(qi, wi)
    q, w = q.astype(pool.dtype), w[..., None].astype(jnp.float32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B,),
        in_specs=[pl.BlockSpec((1, 2 * H, lanes), lambda b, *_: (b, 0, 0)),
                  pl.BlockSpec((1, 2 * H, 1), lambda b, *_: (b, 0, 0)),
                  pl.BlockSpec(memory_space=pltpu.HBM)],
        out_specs=pl.BlockSpec((1, blocks, 2, rows),
                               lambda b, *_: (b, 0, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, per_turn, page_rows, lanes),
                                   pool.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SMEM((1,), jnp.int32)],
    )
    planes = pl.pallas_call(
        _indexer_decode_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, blocks, 2, rows), jnp.int32),
        interpret=interpret,
        compiler_params=_compiler_params(interpret),
    )(layer, block_tables, cache_position,
      _block_runs(block_tables, cache_position, ps, per_turn,
                  pool.shape[1]), q, w, pool)
    # the two planes interleaved into position order: ONE transpose
    return planes.transpose(0, 1, 3, 2).reshape(B, -1)[
        :, :block_tables.shape[1] * ps]


def indexer_decode_keys(qi, wi, index_pool, layer: int, block_tables,
                        cache_position, interpret=None):
    """One query a row scored against the row's LIVE indexer keys, read
    off the stacked leaf ``(layers, pages, page_size / 2, 2 d)`` by a
    Pallas walk of the row's ``cache_position // page_size + 1`` pages:
    ``qi`` (B, heads, d), ``wi`` (B, heads) float32 -> ``(B, table
    positions) int32`` keys in position order, what
    ``score_keys(_stripe_scores(...))`` gives over the gathered table,
    ``_NO_KEY`` past ``cache_position``. Off the chip the kernel runs
    ``interpret=True``, as every walk does."""
    assert qi.ndim == 3 and index_pool.ndim == 4 and \
        index_pool.shape[-1] == 2 * qi.shape[-1], (qi.shape,
                                                   index_pool.shape)
    assert block_tables.shape[0] == qi.shape[0] and \
        cache_position.shape == (qi.shape[0],), (
            block_tables.shape, cache_position.shape)
    if interpret is None:
        interpret = not flash._use_pallas()
    ps = 2 * index_pool.shape[2]
    return _indexer_decode_call(qi, wi, index_pool,
                                block_tables.astype(jnp.int32),
                                cache_position.astype(jnp.int32),
                                jnp.full((1,), layer, jnp.int32),
                                bool(interpret), block_pages(ps) * ps)


def decode_attention(q, pools, index_pool, layer: int, block_tables,
                     cache_position, qi, wi, topk: int, sm_scale,
                     probe=None):
    """One query a row over its selected tokens, straight off the pools
    (already written at this row's position). ``q`` (B, heads, hd);
    ``pools`` the (keys, values) pair ``(layers, pages, page_size,
    lanes)`` and ``index_pool`` the indexer leaf, two tokens a row
    (:func:`write_index_keys`); ``qi`` (B, ih, d), ``wi`` (B, ih). The
    indexer walks each row's live pages of its leaf
    (:func:`indexer_decode_keys`); the readers of keys and values read
    ``min(topk, table positions)`` token rows each, by (page, offset).
    Returns (B,
    heads, hd) in ``q``'s dtype. ``probe`` (a list, eager calls only)
    receives (the chosen positions, which of them count)."""
    kpool, vpool = pools
    B, H, hd = q.shape
    ps = kpool.shape[2]
    positions = block_tables.shape[1] * ps
    hkv = kpool.shape[-1] // hd
    with scope("indexer"):
        keys = indexer_decode_keys(qi, wi, index_pool, layer, block_tables,
                                   cache_position)
    with scope("select"):
        # equal keys: the lower position first (``lax.top_k``'s rule)
        best, chosen = jax.lax.top_k(keys, min(topk, positions))
        counts = best != _NO_KEY
    if probe is not None:
        probe.append((chosen, counts))
    with scope("sparse_attn"):
        page = jnp.take_along_axis(block_tables, chosen // ps, axis=1)
        offset = chosen % ps
        taken = lambda pool: pool[layer, page, offset].reshape(
            B, -1, hkv, hd)
        kc, vc = taken(kpool), taken(vpool)
        qg = q.reshape(B, hkv, H // hkv, hd)
        s = jnp.einsum("bkgd,bskd->bkgs", qg, kc,
                       preferred_element_type=jnp.float32) * sm_scale
        s = jnp.where(counts[:, None, None, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        ctx = jnp.einsum("bkgs,bskd->bkgd", p, vc.astype(jnp.float32))
    return ctx.reshape(B, H, hd).astype(q.dtype)
