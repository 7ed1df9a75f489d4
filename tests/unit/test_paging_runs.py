"""The page allocator hands out RUNS (ISSUE 54): pages 1..N-1 lie in
aligned extents of ``run_pages`` consecutive ids, a request's pages are
whole extents on the block boundaries of its table and what is left over
out of one broken extent, and the decode reader copies such a block with
one descriptor (``ops/attention/paged.py``; its cases are in
test_paged_attention.py). What a page pool could admit before it still
admits: ``alloc(n)`` succeeds exactly when ``n <= free_pages``. Pure
host code, no jax.
"""

import numpy as np
import pytest

from deepspeed_tpu.inference.paging import (PageAllocator, pages_for,
                                            run_leads)
from deepspeed_tpu.inference.scheduler import Request, Scheduler


def _blocks(pages, run_pages, at=0):
    """The table entries of ``pages`` laid from index ``at``, a list a
    block of ``run_pages`` entries (the first one may start inside)."""
    table = [None] * at + list(pages)
    return [[p for p in table[i:i + run_pages] if p is not None]
            for i in range(at // run_pages * run_pages, len(table),
                           run_pages)]


def _is_extent(block, run_pages):
    return (len(block) == run_pages and (block[0] - 1) % run_pages == 0
            and block == list(range(block[0], block[0] + run_pages)))


def _is_run(block):
    return block == list(range(block[0], block[0] + len(block)))


@pytest.mark.parametrize("n", [1, 7, 8, 9, 16, 21, 40])
@pytest.mark.parametrize("run_pages", [4, 8, 32])
def test_a_fresh_pool_lays_extents_on_the_tables_blocks(run_pages, n):
    """Whole extents first, each ascending and consecutive from an
    aligned id, a block of the table each; then the pages left over,
    ascending and consecutive out of ONE extent."""
    al = PageAllocator(1 + 12 * run_pages, 16, run_pages=run_pages)
    pages = al.alloc(n)
    assert len(pages) == len(set(pages)) == n
    assert all(1 <= p < al.num_pages and al.refcount(p) == 1
               for p in pages)
    blocks = _blocks(pages, run_pages)
    for block in blocks[:n // run_pages]:
        assert _is_extent(block, run_pages)
    if n % run_pages:
        tail = blocks[-1]
        assert len(tail) == n % run_pages and _is_run(tail)
        assert len({(p - 1) // run_pages for p in tail}) == 1
    assert al.free_pages == 12 * run_pages - n
    assert al.debug_state()["extents_free"] == 12 - pages_for(n, run_pages)


@pytest.mark.parametrize("at", [0, 1, 3, 8, 13])
def test_pages_laid_behind_a_shared_prefix_start_on_the_next_block(at):
    """``alloc(n, at)``: the table already holds ``at`` pages (a shared
    prefix), so single pages fill its open block and the extents start
    at the next multiple of ``run_pages``."""
    rp = 8
    al = PageAllocator(1 + 6 * rp, 16, run_pages=rp)
    pages = al.alloc(27, at=at)
    assert len(set(pages)) == 27
    head = -at % rp
    blocks = _blocks(pages[head:], rp)
    whole = (27 - head) // rp
    assert all(_is_extent(b, rp) for b in blocks[:whole])
    assert all(_is_run(b) for b in blocks[whole:])
    # the head took no page of an extent the request holds whole
    held = {(b[0] - 1) // rp for b in blocks[:whole]}
    assert not held & {(p - 1) // rp for p in pages[:head]}


@pytest.mark.parametrize("run_pages", [1, 4, 8])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_alloc_succeeds_exactly_when_the_pages_are_free(seed, run_pages):
    """The parent's rule, step by step under a seeded churn of mixed
    sizes: ``alloc(n)`` gives ``n`` pages when ``n <= free_pages`` and
    None, taking nothing, otherwise; no page is ever held twice."""
    rs = np.random.RandomState(seed)
    al = PageAllocator(70, 4, prefix_cache=False, run_pages=run_pages)
    free = 69                     # the parent's free list, as a count
    held = []
    refused = granted = 0
    for _ in range(600):
        if rs.randint(3) and held:
            pages = held.pop(rs.randint(len(held)))
            al.free(pages)
            free += len(pages)
        else:
            n = int(rs.randint(1, 30))
            before = al.debug_state()
            pages = al.alloc(n, at=int(rs.randint(0, 9)))
            if n <= free:
                assert pages is not None and len(pages) == n
                held.append(pages)
                free -= n
                granted += 1
            else:
                assert pages is None and al.debug_state() == before
                refused += 1
        assert al.free_pages == free and al.pages_in_use == 69 - free
        live = [p for pages in held for p in pages]
        assert len(live) == len(set(live)) == 69 - free
        assert all(1 <= p < 70 for p in live)
    assert refused >= 5 and granted > 100
    for pages in held:
        al.free(pages)
    # every extent is whole again
    assert al.debug_state()["extents_free"] == 69 // run_pages
    assert al.free_pages == 69


def test_an_extent_freed_whole_is_handed_out_whole_again():
    al = PageAllocator(1 + 4 * 8, 16, run_pages=8)
    a, b = al.alloc(16), al.alloc(16)
    assert al.alloc(1) is None
    al.free(a)
    assert al.debug_state()["extents_free"] == 2
    c = al.alloc(16)
    assert sorted(c) == sorted(a)
    assert all(_is_extent(blk, 8) for blk in _blocks(c, 8))
    # page by page and in any order, an extent is whole once all of it
    # is back
    al.free(b[3:] + c)
    assert al.debug_state()["extents_free"] == 3
    al.free(b[:3][::-1])
    assert al.debug_state()["extents_free"] == 4


def test_a_block_takes_single_pages_when_no_extent_is_whole():
    """Four extents of 8, each with one page held: 28 pages are free
    and no run of 8. The parent admitted 20 pages here; so does this."""
    al = PageAllocator(1 + 4 * 8, 16, run_pages=8)
    pins = [al.alloc(8) for _ in range(4)]
    for pages in pins:
        al.free(pages[1:])
    assert al.free_pages == 28 and al.debug_state()["extents_free"] == 0
    pages = al.alloc(20)
    assert pages is not None and len(set(pages)) == 20
    assert not any(_is_extent(b, 8) for b in _blocks(pages, 8))
    assert al.alloc(9) is None and al.free_pages == 8
    al.free(pages)
    for pin in pins:
        al.free(pin[:1])
    assert al.debug_state()["extents_free"] == 4


def test_left_over_pages_share_a_broken_extent_before_breaking_another():
    al = PageAllocator(1 + 4 * 8, 16, run_pages=8)
    a = al.alloc(3)                  # breaks one extent
    b = al.alloc(4)                  # its five free pages hold four
    assert al.debug_state()["extents_free"] == 3
    assert {(p - 1) // 8 for p in a} == {(p - 1) // 8 for p in b}
    assert _is_run(a) and _is_run(b)
    c = al.alloc(2)                  # one left there: break another
    assert al.debug_state()["extents_free"] == 2 and _is_run(c)
    assert {(p - 1) // 8 for p in c} != {(p - 1) // 8 for p in a}


def test_a_pool_that_ends_inside_an_extent_keeps_its_last_pages():
    """13 usable pages at runs of 8: one extent and five pages that are
    never whole, and all 13 can be held."""
    al = PageAllocator(14, 16, run_pages=8)
    assert al.debug_state()["extents_free"] == 1 and al.free_pages == 13
    pages = al.alloc(13)
    assert sorted(pages) == list(range(1, 14))
    assert _is_extent(pages[:8], 8)
    al.free(pages)
    assert al.debug_state()["extents_free"] == 1 and al.free_pages == 13


@pytest.mark.parametrize("seed", [0, 1])
def test_run_pages_1_reproduces_the_plain_free_list(seed):
    """With a block of one page the allocator is the parent's: pages
    popped off the end of a LIFO list, freed ones pushed back."""
    rs = np.random.RandomState(seed)
    al = PageAllocator(40, 4, prefix_cache=False, run_pages=1)
    plain = list(range(1, 40))
    held = []
    for _ in range(300):
        if rs.randint(2) and held:
            pages = held.pop(rs.randint(len(held)))
            al.free(pages)
            plain.extend(pages)
        else:
            n = int(rs.randint(1, 9))
            pages = al.alloc(n, at=int(rs.randint(0, 5)))
            if n > len(plain):
                assert pages is None
                continue
            assert pages == [plain.pop() for _ in range(n)]
            held.append(pages)
    assert PageAllocator(9, 4).run_pages == 1      # what a caller gets


def test_run_pages_is_validated_and_reported():
    with pytest.raises(ValueError, match="run_pages"):
        PageAllocator(9, 4, run_pages=0)
    state = PageAllocator(1 + 3 * 8, 16, run_pages=8).debug_state()
    assert (state["run_pages"], state["extents_free"],
            state["pages_free"]) == (8, 3, 24)


# ------------------------------------------------------------ the scheduler
def _sched(run_pages=4, pages=1 + 12 * 4, ps=4, slots=4, **kw):
    return Scheduler(slots, (8, 16, 32), (1, 2), 64,
                     allocator=PageAllocator(pages, ps,
                                             run_pages=run_pages), **kw)


def test_a_requests_table_holds_extents_on_its_blocks():
    s = _sched()
    s.submit(Request(prompt=list(range(1, 14)), max_new_tokens=25))
    (batch,) = s.admit()
    pages = s.slots[batch.slot_ids[0]].pages
    assert len(pages) == pages_for(13 + 25, 4) == 10
    blocks = _blocks(pages, 4)
    assert [_is_extent(b, 4) for b in blocks] == [True, True, False]
    assert _is_run(blocks[2])


def test_a_shared_prefix_that_ends_inside_a_block():
    """The owner's 5 prompt pages are an extent and one page; a second
    request reuses them (refcount 2, a prefix hit), fills the block the
    prefix ends in with single pages and starts its extents on the next
    boundary; the full block inside the prefix is the owner's run."""
    s = _sched()
    al = s.allocator
    prompt = list(range(1, 21))                     # 5 full pages
    s.submit(Request(prompt=prompt + [50], max_new_tokens=3))
    (first,) = s.admit()
    owner = s.slots[first.slot_ids[0]].pages
    assert len(owner) == 6 and _is_extent(owner[:4], 4)
    s.submit(Request(prompt=prompt + [60, 61], max_new_tokens=34))
    (second,) = s.admit()
    pages = s.slots[second.slot_ids[0]].pages
    assert len(pages) == pages_for(22 + 34, 4) == 14
    assert pages[:5] == owner[:5]
    assert all(al.refcount(p) == 2 for p in pages[:5])
    assert al.prefix_hit_requests == 1 and al.prefix_hit_tokens == 20
    assert al.shared_duplicate_tokens == 20
    blocks = _blocks(pages, 4)
    # [shared extent] [shared page + 3 single] [extent] [tail of 2]
    assert _is_extent(blocks[0], 4) and _is_extent(blocks[2], 4)
    assert blocks[1][0] == owner[4] and not _is_extent(blocks[1], 4)
    assert _is_run(blocks[3]) and len(blocks[3]) == 2
    assert len(set(pages)) == 14 and not set(pages[5:]) & set(owner)
    # the reader evicts: the shared pages stay the owner's
    s.evict(s.slots[second.slot_ids[0]].request.uid)
    assert all(al.refcount(p) == 1 for p in owner)
    assert al.shared_duplicate_tokens == 0
    s.evict(s.slots[first.slot_ids[0]].request.uid)
    assert al.pages_in_use == 0
    assert al.debug_state()["extents_free"] == 12


def _brute_run_turns(tables, positions, ps, rp):
    """The reader's rule from the tables alone: a walked block is a run
    when its live pages are consecutive ids."""
    turns = 0
    for row, pos in zip(tables, positions):
        live = pos // ps + 1
        for at in range(0, live, rp):
            block = [int(p) for p in row[at:min(at + rp, live)]]
            turns += _is_run(block)
    return turns


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_run_turns_counts_what_the_tables_hold(seed):
    """``Scheduler.run_turns`` (kept a slot at admission, numpy over the
    rows a step) against the rule read off the dispatch's own tables,
    over a churn that leaves extents, single pages and shared
    prefixes in the tables."""
    rs = np.random.RandomState(seed)
    s = _sched(run_pages=4, pages=1 + 14 * 4, slots=6)
    common = [int(t) for t in rs.randint(1, 50, 12)]
    seen = set()
    for step in range(60):
        for _ in range(int(rs.randint(0, 3))):
            own = [int(t) for t in rs.randint(1, 50, rs.randint(1, 15))]
            prompt = (common[:int(rs.randint(0, 4)) * 4] + own)[:30]
            s.submit(Request(prompt=prompt,
                             max_new_tokens=int(rs.randint(2, 30))))
        for batch in s.admit():
            s.record_tokens({sid: 1 for sid in batch.slot_ids})
        sids, _, poss, _, _ = s.decode_state()
        if not sids:
            continue
        tables = s.block_table_rows(6, 16)
        got = s.run_turns(sids, poss)
        want = _brute_run_turns(tables[sids], poss, 4, 4)
        walked = sum(pages_for(p // 4 + 1, 4) for p in poss)
        assert got == want and 0 < got <= walked
        seen.add(got == walked)
        s.issue_tokens(sids)
        s.record_tokens({sid: 2 for sid in sids})
        if rs.randint(4) == 0 and s.slots[sids[0]] is not None:
            s.evict(s.slots[sids[0]].request.uid)
    assert seen == {True, False}      # all runs, and not all runs


def test_run_leads_reads_each_blocks_consecutive_head():
    leads = run_leads([5, 6, 7, 8, 20, 22, 23, 24, 9, 10], 4, 4)
    assert leads.tolist() == [4, 1, 2, 1]
    assert run_leads([3, 2, 1], 1, 4).tolist() == [1, 1, 1, 1]
    assert run_leads([], 4, 2).tolist() == [1, 1]


@pytest.mark.parametrize("attn_kernel", ["pallas", "gather"])
def test_the_engine_sizes_the_runs_by_its_readers_block(attn_kernel):
    """``run_pages`` is no option: the engine gives its allocators the
    pages a loop turn of ITS decode reader streams, and 1 where the
    gather reader (which takes a table in any order) runs."""
    from deepspeed_tpu.inference import InferenceEngine
    from deepspeed_tpu.ops.attention.paged import block_pages
    from tests.unit.test_inference import TINY_INF, tiny_gpt2
    engine = InferenceEngine(
        *tiny_gpt2(), dict(TINY_INF, paged_kv={
            "enabled": True, "page_size": 4, "attn_kernel": attn_kernel}))
    want = block_pages(4) if attn_kernel == "pallas" else 1
    assert engine._decode_attn_path == attn_kernel
    state = engine.debug_state()["page_pool"]
    assert state["run_pages"] == engine.scheduler.allocator.run_pages \
        == want
    assert state["extents_free"] == (state["num_pages"] - 1) // want
    engine.close()


def test_an_indexer_familys_engine_hands_out_the_indexer_walks_runs(
        monkeypatch):
    """ISSUE 56: a family with an indexer leaf reads it by a page walk
    of ITS OWN block (``ops/attention/indexed.block_pages``), so the
    engine gives its allocator runs of that many pages, and the
    `serve/decode` span's ``read_pages``, ``read_turns``, ``run_turns``
    and ``block_tokens`` are counted with that block: held here against
    a count by hand over one request's decode steps (2 pages a turn, so
    that a context of 40-45 positions is two turns)."""
    import jax.numpy as jnp
    from deepspeed_tpu.inference import InferenceEngine
    from deepspeed_tpu.ops.attention import indexed
    from tests.unit import test_keye_vl2 as keye
    monkeypatch.setattr(indexed, "_INDEX_BLOCK_TOKENS", 32)
    decodes = []
    plain = InferenceEngine._span

    def recording(self, name, **args):
        if name == "serve/decode":
            decodes.append(args)
        return plain(self, name, **args)

    monkeypatch.setattr(InferenceEngine, "_span", recording)
    cfg = keye.TINY
    params = keye.kv2.init_keye_vl2_params(cfg, keye.jax.random.PRNGKey(3),
                                           jnp.float32)
    engine = InferenceEngine(cfg, params, keye.INFERENCE,
                             dtype=jnp.float32)
    ps = engine.paged_spec.page_size
    per_turn = indexed.block_pages(ps)
    assert per_turn == 32 // ps == 2
    assert engine._decode_attn_path == "pallas"
    assert "indexer page walk" in engine._decode_attn_reason
    state = engine.debug_state()["page_pool"]
    assert state["run_pages"] == engine.scheduler.allocator.run_pages \
        == per_turn
    keye._serve(engine, keye._prompts([40]), new=6)
    engine.close()
    idle = engine._rows - 1
    assert len(decodes) >= 5
    for a in decodes:
        # the one live row at position p scores p + 1 keys
        pos = a["scored_tokens"] - 1
        live = pos // ps + 1
        assert 40 <= pos <= 45
        assert a["block_tokens"] == per_turn * ps
        assert a["read_pages"] == live + idle
        assert a["read_turns"] == -(-live // per_turn) + idle
        # a fresh pool lays the request's pages as whole extents: every
        # turn a run, an inactive row's null page a run of one
        assert a["run_turns"] == a["read_turns"]
        assert 0 < a["read_pages"] < engine._rows * a["table_pages"]
