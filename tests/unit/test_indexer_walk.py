"""The decode indexer's page walk (ISSUE 56, ops/attention/indexed.py
``indexer_decode_keys``): one Pallas kernel that copies each row's LIVE
pages of the indexer leaf, a block of pages a loop turn (a run of
consecutive pool ids as one copy, a walk's last short block the binary
pieces of its count, any other block page by page), and scores the
landed block in VMEM. In interpret mode on the CPU, against the XLA
scorer over the gathered table that it replaced
(``score_keys(_stripe_scores(...))``, kept as the reference).

The operands are dyadic rationals (quarters and eighths of small
integers), so that every float32 sum is exact in whatever order a
backend adds it up: the keys are then equal TO THE BIT, and an unequal
key is a wrong page, a wrong half or a wrong mask, never a rounding.
One case holds plain normal operands to the reference within float32
rounding."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.attention import indexed

PS, BLOCK = 16, 8           # tokens a page; pages a turn in these cases
PAGES = 200


def _operands(seed, rows, heads, d, dtype, dyadic=True, layers=2):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    part = (lambda x, n: jnp.round(x * n) / n) if dyadic else \
        (lambda x, n: x)
    pool = part(jax.random.normal(ks[0], (layers, PAGES, PS // 2, 2 * d)),
                4).astype(dtype)
    qi = part(jax.random.normal(ks[1], (rows, heads, d)), 4).astype(dtype)
    wi = part(jax.random.normal(ks[2], (rows, heads)), 8)
    return pool, qi, wi


def _reference(qi, wi, pool, layer, tables, pos):
    """The XLA scorer the engine ran before the walk: the table's pages
    gathered whole, every position past the row's masked."""
    rows, positions = tables.shape[0], tables.shape[1] * PS
    stripe = pool[layer, tables].reshape(rows, positions // 2,
                                         pool.shape[-1])
    return indexed.score_keys(
        indexed._stripe_scores(qi, wi, stripe),
        jnp.arange(positions)[None, :] <= pos[:, None])


def _run(first, n):
    return list(range(first, first + n))


def _scattered(seed, n, low=1):
    return list(np.random.RandomState(seed).permutation(
        np.arange(low, PAGES))[:n])


def _table(rows, width=40):
    table = np.zeros((len(rows), width), np.int32)
    for r, pages in enumerate(rows):
        table[r, :len(pages)] = pages
    return table


# name -> (tables' rows of page ids, positions)
CASES = {
    # rows at ragged positions, pages anywhere
    "ragged_positions": ([_scattered(1, 30), _scattered(2, 30),
                          _scattered(3, 30), _scattered(4, 30)],
                         [5, 211, 333, 479]),
    # a position ON a page boundary (a page's first token) and the one
    # before it (the page before's last), at a block's edge and inside
    "page_boundaries": ([_run(3, 30), _run(40, 30), _scattered(5, 30),
                         _scattered(6, 30)],
                        [BLOCK * PS, BLOCK * PS - 1, 5 * PS, 5 * PS - 1]),
    # an inactive row between two live ones: an all-null table at 0
    "an_inactive_row": ([_run(9, 20), [], _scattered(7, 20)],
                        [300, 0, 301]),
    # the allocator's layout: a run a block, a last block out of another
    "a_table_of_runs": ([_run(1, 8) + _run(65, 8) + _run(33, 5),
                         _run(97, 8) + _run(17, 8) + _run(120, 8)],
                        [20 * PS + 7, 24 * PS - 1]),
    "a_table_of_scattered_pages": ([_scattered(8, 24), _scattered(9, 24)],
                                   [24 * PS - 1, 17 * PS + 2]),
    # runs, a block broken in its middle, and a run again, in ONE row
    "runs_and_scattered_in_one_row": (
        [_run(1, 8) + [50, 51, 52, 90, 91, 92, 93, 94] + _run(17, 8)
         + _scattered(10, 8, low=100) + _run(60, 3)],
        [34 * PS + 9]),
    # a walk's last block of 1, 2, 3 and ``block - 1`` live pages, as a
    # run (the binary pieces of its count) and page by page
    "last_block_of_1": ([_run(1, 9), _scattered(11, 9)],
                        [8 * PS + 4, 8 * PS]),
    "last_block_of_2": ([_run(1, 10), _scattered(12, 10)],
                        [9 * PS + 4, 10 * PS - 1]),
    "last_block_of_3": ([_run(1, 11), _scattered(13, 11)],
                        [10 * PS + 15, 10 * PS]),
    "last_block_of_block_less_1": ([_run(1, 15), _scattered(14, 15),
                                    _run(30, 7)],
                                   [14 * PS + 1, 15 * PS - 1, 7 * PS - 2]),
}


def _keys(case, heads=4, d=8, dtype=jnp.float32, dyadic=True, block=BLOCK):
    rows, pos = CASES[case]
    tables, pos = jnp.asarray(_table(rows)), jnp.asarray(pos, jnp.int32)
    pool, qi, wi = _operands(len(case), len(rows), heads, d, dtype, dyadic)
    got = indexed._indexer_decode_call(
        qi, wi, pool, tables, pos, jnp.full((1,), 1, jnp.int32), True,
        block * PS)
    want = jax.jit(_reference, static_argnums=3)(qi, wi, pool, 1, tables,
                                                 pos)
    return np.asarray(got), np.asarray(want), np.asarray(pos)


def _holds_no_key_where_nothing_is_seen(got, pos):
    at = np.arange(got.shape[1])[None, :]
    assert (got[at > pos[:, None]] == indexed._NO_KEY).all()
    assert (got[at <= pos[:, None]] != indexed._NO_KEY).all()


@pytest.mark.parametrize("case", sorted(CASES) + [
    "published_row", "one_page_a_turn", "the_modules_block",
    "normal_operands", "decode_attention"])
def test_the_walk_scores_what_the_gathered_table_scored(case, monkeypatch):
    if case == "decode_attention":
        return _decode_attention_through_the_walk(monkeypatch)
    if case == "published_row":
        # the leaf as the cell holds it: 16 heads of 64 against 128
        # lanes of bfloat16, one bfloat16 product, float32 sums
        got, want, pos = _keys("runs_and_scattered_in_one_row", heads=16,
                               d=64, dtype=jnp.bfloat16)
    elif case == "one_page_a_turn":     # (no run is longer than a page)
        got, want, pos = _keys("ragged_positions", block=1)
    elif case == "the_modules_block":
        # 128 pages a turn over a table of 40: one short block a row
        got, want, pos = _keys(
            "a_table_of_runs",
            block=indexed.block_pages(PS))
        assert indexed.block_pages(PS) * PS == indexed._INDEX_BLOCK_TOKENS
    elif case == "normal_operands":
        got, want, pos = _keys("ragged_positions", dyadic=False)
        _holds_no_key_where_nothing_is_seen(got, pos)
        # keys back to the scores they stand for
        scores = lambda k: np.where(k < 0, k ^ 0x7fffffff, k).astype(
            np.int32).view(np.float32)
        seen = got != indexed._NO_KEY
        np.testing.assert_allclose(scores(got)[seen], scores(want)[seen],
                                   rtol=0, atol=2e-5)
        return
    else:
        got, want, pos = _keys(case)
    np.testing.assert_array_equal(got, want)
    _holds_no_key_where_nothing_is_seen(got, pos)
    assert got.shape == (len(pos), 40 * PS)


def _decode_attention_through_the_walk(monkeypatch):
    """``decode_attention`` over a walk of several turns: the chosen
    positions are the dense oracle's set and the context is the softmax
    over exactly those rows (the case of test_keye_vl2.py, at 4 pages a
    turn of the walk: 70 positions are two turns, the last of one
    page)."""
    from tests.unit import test_keye_vl2 as cases
    monkeypatch.setattr(indexed, "_INDEX_BLOCK_TOKENS", 4 * PS)
    q, kpool, vpool, ipool, tables, qi, wi, kc, vc, kic = cases._case(
        5, 2, 70)
    want, sets = cases._oracle(q, kc, vc, kic, qi, wi)
    probe = []
    got = indexed.decode_attention(
        q[:, :, -1], (kpool, vpool), ipool, 0, tables,
        jnp.asarray([69, 69]), qi[:, -1], wi[:, -1], cases.TOPK,
        16 ** -0.5, probe)
    chosen, counts = probe[0]
    assert bool(counts.all())
    for b in range(2):
        assert sorted(np.asarray(chosen[b])) == list(
            np.flatnonzero(np.asarray(sets[b, -1])))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[:, :, -1]),
                               atol=2e-5)


def test_the_walk_copies_a_run_whole_its_pieces_or_a_page():
    """What the kernel copies, read off its jaxpr at the cell's block of
    128 pages: one page (the page-by-page arm, a loop over the live
    pages), 1, 2, 4 ... 64 pages (the binary pieces of a short last
    block) and all 128 (a full block that is a run): nothing else, and
    every copy indexes the leaf's LEADING dimensions (no view of the
    leaf as rows, whose 8-row pages are half a bfloat16 tile)."""
    import re
    spec = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype)
    text = re.sub(r"\s+", " ", str(jax.make_jaxpr(
        lambda *a: indexed._indexer_decode_call(*a, False, 2048))(
            spec((17, 16, 64), jnp.bfloat16), spec((17, 16), jnp.float32),
            spec((6, 38913, 8, 128), jnp.bfloat16),
            spec((17, 4352), jnp.int32), spec((17,), jnp.int32),
            spec((1,), jnp.int32))))
    starts = re.findall(r"dma_start\(p0\) (\S+) -> (\S+)", text)
    # the call's first block and a turn's prefetch: two sites of each
    assert len(starts) == 2 * (1 + 7 + 1)
    pages = set()
    for src, dst in starts:
        # ``ref[layer,page,:,:]`` or ``ref[layer,first:first+n,:,:]``
        window = src[src.index("[") + 1:].split(",")[1]
        pages.add(int(window.split("+")[-1]) if ":" in window else 1)
        assert src.endswith(",:,:]") and dst.endswith(",:,:]"), (src, dst)
    assert pages == {1, 2, 4, 8, 16, 32, 64, 128}
