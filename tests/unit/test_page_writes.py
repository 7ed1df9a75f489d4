# Copyright The DeepSpeed-TPU authors. Licensed under Apache 2.0.
"""A prefill that starts on a page boundary writes whole pages (ISSUE 44).

``ops/attention/page_pool.write_paged_kv_cache`` (every family's pool
write) picks the granularity of its scatter's index from what the call
shows:

- a width of whole pages (``S % page_size == 0``, known when the program
  is traced) whose rows ALL start on a page boundary (read from the
  positions at RUN time, inside the one program a bucket has) -> one
  index a PAGE, ``pool.at[layer, pages]``;
- the same width with one row that starts mid-page -> one index a token
  row, the other branch of the same conditional;
- any other width (decode's one row, a verify's ``k + 1``, a ragged
  chunk) -> token rows, the only form traced.

Both forms put the same bytes in the same place; every case is tiny and
the parent's program (token rows alone) is the oracle.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import InferenceEngine
from deepspeed_tpu.models import axk1
from deepspeed_tpu.ops.attention import page_pool
from deepspeed_tpu.ops.attention.page_pool import (PagedWriteIndex,
                                                   paged_write_index,
                                                   write_paged_kv_cache,
                                                   write_paged_layer)
from tests.unit.test_inference import tiny_gpt2, tiny_llama
from tests.unit.test_paged_attention import _np_write, _quantize_pools

LAYERS, LAYER, HEADS, HD, PS, TABLE = 2, 1, 2, 8, 4, 6   # 24 positions a row
SEQ = 2 * PS                                             # two pages a row


def _rows_only(index):
    """The index the parent made: no whole pages to write."""
    return index._replace(pages=None, aligned=None)


def _tables(rows, reserved):
    """Distinct non-null pages a row as far as ``reserved[b]`` entries,
    the rest unreserved (0: the null page); a row of 0 is a pad row."""
    tables = np.zeros((rows, TABLE), np.int32)
    nxt = 1
    for b, n in enumerate(reserved):
        tables[b, :n] = np.arange(nxt, nxt + n)
        nxt += n
    return tables, nxt


# positions, reserved table entries a row
CASES = {
    "position_0": ([0, 0, 0], [2, 2, 2]),
    # a row rides two shared pages, another four: whole pages further on
    "page_boundary": ([2 * PS, 0, 4 * PS], [4, 2, 6]),
    # a pad row (null table), a row of 3 true tokens (ONE page reserved:
    # its second page of pad tokens goes to the null page, the first
    # page's tail holds pad tokens' keys) and a row at the table's end,
    # whose second page lies past the extent
    "pad_and_short_rows": ([0, 0, 5 * PS], [0, 1, 6]),
}


def _leaves(kind, rng, pages):
    """(pools, new) of a kind of pool tree: the plain pair, the int8
    4-tuple (payloads and scale leaves) and a one-head latent row."""
    noise = lambda *shape: jnp.asarray(rng.randn(*shape), jnp.float32)
    if kind == "latent":
        return ((noise(LAYERS, pages, PS, 16),),
                (noise(3, 1, SEQ, 16),))
    pools = (noise(LAYERS, pages, PS, HEADS * HD),
             noise(LAYERS, pages, PS, HEADS * HD))
    k, v = noise(3, HEADS, SEQ, HD), noise(3, HEADS, SEQ, HD)
    if kind == "int8":
        pools = _quantize_pools(*pools, kv_heads=HEADS)
    return pools, (k, v)


def _write(kind, pools, new, index):
    if kind == "latent":
        return (write_paged_kv_cache(pools[0], LAYER, new[0], index),)
    return write_paged_layer(pools, LAYER, *new, index)


@pytest.mark.parametrize("kind", ["pair", "int8", "latent"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_whole_pages_leave_the_pool_the_token_rows_leave(case, kind):
    """Pool for pool, bit for bit, the null page included: the same
    (page, offset, value) triples land in the same order, an index a
    page or an index a row; every leaf of the tree, the other layer
    untouched."""
    positions, reserved = CASES[case]
    tables, pages = _tables(3, reserved)
    pools, new = _leaves(kind, np.random.RandomState(44), pages)
    tables, positions = jnp.asarray(tables), jnp.asarray(positions, jnp.int32)

    @jax.jit
    def both(pools, new):
        index = paged_write_index(tables, positions, SEQ, PS)
        return (_write(kind, pools, new, index),
                _write(kind, pools, new, _rows_only(index)), index.aligned)

    paged, rows, aligned = both(pools, new)
    assert bool(aligned)
    assert len(paged) == len(pools) == {"pair": 2, "int8": 4, "latent": 1}[kind]
    for got, want, was in zip(paged, rows, pools):
        assert got.dtype == was.dtype and got.shape == was.shape
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        np.testing.assert_array_equal(np.asarray(got)[1 - LAYER],
                                      np.asarray(was)[1 - LAYER])
    assert not np.array_equal(np.asarray(paged[0]), np.asarray(pools[0]))
    if kind == "pair":      # and both are the plain write, null page aside
        want = _np_write(pools[0], LAYER, new[0], np.asarray(tables),
                         np.asarray(positions))
        np.testing.assert_array_equal(np.asarray(paged[0])[:, 1:],
                                      want[:, 1:])


def test_the_index_names_each_whole_page_under_the_null_page_rule():
    """Row b's j-th page is ``table[b, position // page_size + j]``; a
    slot past the table's extent and an unreserved entry are the null
    page; ``aligned`` is the one run-time fact."""
    positions, reserved = CASES["pad_and_short_rows"]
    tables, _ = _tables(3, reserved)
    index = paged_write_index(jnp.asarray(tables),
                              jnp.asarray(positions, jnp.int32), SEQ, PS)
    assert isinstance(index, PagedWriteIndex)
    assert index.page.shape == index.offset.shape == (3 * SEQ,)
    assert index.pages.shape == (3 * SEQ // PS,) \
        and index.pages.dtype == jnp.int32
    np.testing.assert_array_equal(
        np.asarray(index.pages).reshape(3, 2),
        [[0, 0], [tables[1, 0], 0], [tables[2, 5], 0]])
    assert bool(index.aligned)
    off = paged_write_index(jnp.asarray(tables),
                            jnp.asarray([0, PS, 2 * PS + 1], jnp.int32),
                            SEQ, PS)
    assert not bool(off.aligned)
    for width in (1, PS + 1, SEQ - 1):          # not whole pages
        ragged = paged_write_index(jnp.asarray(tables),
                                   jnp.zeros((3,), jnp.int32), width, PS)
        assert ragged.pages is None and ragged.aligned is None
        assert ragged.page.shape == (3 * width,)


@pytest.mark.parametrize("misaligned", [0, 1, 2])
def test_one_row_that_starts_inside_a_page_sends_the_batch_by_rows(
        misaligned):
    """One program, both ways: the positions pick the branch when it
    RUNS. With one row a token past a page boundary the pool is the
    plain write's (whole pages would have put that row's tokens a row
    too early), with every row on a boundary it is too."""
    tables, pages = _tables(3, [6, 6, 6])
    pools, (k, _) = _leaves("pair", np.random.RandomState(45), pages)

    @jax.jit
    def program(pool, positions):
        return write_paged_kv_cache(
            pool, LAYER, k, paged_write_index(jnp.asarray(tables), positions,
                                              SEQ, PS))

    for positions in ([PS, 0, 2 * PS],
                      [PS + (misaligned == 0), misaligned == 1,
                       2 * PS + 3 * (misaligned == 2)]):
        got = program(pools[0], jnp.asarray(positions, jnp.int32))
        np.testing.assert_array_equal(
            np.asarray(got), _np_write(pools[0], LAYER, k, tables, positions))
    assert program._cache_size() == 1


def _primitives(jaxpr, name):
    """Equations of primitive ``name`` anywhere in a jaxpr."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == name
        n += sum(_primitives(sub, name)
                 for sub in jax.core.jaxprs_in_params(eqn.params))
    return n


@pytest.mark.parametrize("width,why", [
    (1, "decode"), (5, "a verify of k + 1"), (SEQ - 2, "a ragged chunk"),
    (SEQ, "whole pages")], ids=lambda v: str(v).replace(" ", "_"))
@pytest.mark.parametrize("kind", ["pair", "int8"])
def test_a_width_that_is_not_whole_pages_traces_one_branch(kind, width, why):
    """Decided when the program is traced, from the width alone: a
    scatter a leaf and no conditional, as the parent's; whole pages
    trace ONE conditional over the layer's leaves, a scatter a leaf in
    each branch."""
    tables, pages = _tables(3, [6, 6, 6])
    pools, (k, v) = _leaves(kind, np.random.RandomState(46), pages)

    def write(pools, positions):
        index = paged_write_index(jnp.asarray(tables), positions, width, PS)
        return write_paged_layer(pools, LAYER, k[:, :, :width],
                                 v[:, :, :width], index)
    jaxpr = jax.make_jaxpr(write)(pools, jnp.zeros((3,), jnp.int32)).jaxpr
    whole = width % PS == 0
    assert _primitives(jaxpr, "cond") == whole, why
    assert _primitives(jaxpr, "scatter") == len(pools) * (1 + whole), why


# ------------------------------------------------------------ the engines
def _parent_writes(monkeypatch):
    """The parent's programs: every write an index a token row."""
    real = page_pool.write_paged_kv_cache

    def rows(pool, layer, new, index):
        return real(pool, layer, new, _rows_only(index))
    monkeypatch.setattr(page_pool, "write_paged_kv_cache", rows)
    monkeypatch.setattr(axk1, "write_paged_kv_cache", rows)


def _solar():
    from deepspeed_tpu.models import solar_open2 as so
    from tests.unit.test_solar_open2 import TINY
    return TINY, so.init_solar_open2_params(TINY, jax.random.PRNGKey(3))


def _granite():
    from tests.unit.test_granite_hybrid import TINY, _params
    return TINY, _params(TINY)


def _axk1():
    from tests.unit.test_axk1 import TINY
    return TINY, axk1.init_axk1_params(TINY, jax.random.PRNGKey(3))


# pages of 16 (the default), buckets of one and two pages
HYBRID = {"max_batch_size": 3, "batch_buckets": [1, 2],
          "prompt_buckets": [16, 32], "max_seq_len": 64,
          "paged_kv": {"num_pages": 14, "prefix_cache": False}}
PLAIN = {"max_batch_size": 3, "prompt_buckets": [8, 16],
         "batch_buckets": [2], "max_seq_len": 32,
         "paged_kv": {"page_size": 4, "num_pages": 24}}
FAMILIES = {"gpt2": (tiny_gpt2, PLAIN), "llama": (tiny_llama, PLAIN),
            "solar_open2": (_solar, HYBRID),
            "granite_hybrid": (_granite, HYBRID), "axk1": (_axk1, HYBRID)}


def _generate(family, inf=None, prompts=None, new_tokens=6, spans=None):
    make, default = FAMILIES[family]
    cfg, params = make()
    inf = inf or default
    if prompts is None:
        # rows shorter than their bucket (a page's tail holds pad
        # tokens' keys until decode overwrites it, a row in order), a
        # row that fills a page, a pad row beside the third prompt
        rs = np.random.RandomState(7)
        top = min(inf["prompt_buckets"][-1], 27)
        prompts = [list(map(int, rs.randint(1, 60, n)))
                   for n in (top, 3, inf["paged_kv"].get("page_size", 16))]
    engine = InferenceEngine(cfg, params, inf, dtype=jnp.float32)
    if spans is not None:
        real_span = engine._span

        def spy(name, **args):
            if name == "serve/prefill":
                spans.append(args)
            return real_span(name, **args)
        engine._span = spy
    out = engine.generate(prompts, max_new_tokens=new_tokens,
                          temperature=0.0)
    engine.close()
    return out


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_served_greedy_tokens_are_the_parents(family, monkeypatch):
    """Prefill by whole pages, then decode a row an index into the
    pages' tails: every family's greedy tokens are those of the engine
    whose every write is an index a row, and the span says the page
    form engaged (``page_write_tokens`` = the dispatch's real tokens)."""
    spans = []
    got = _generate(family, spans=spans)
    assert spans and all(
        s["page_write_tokens"] == s["real_tokens"] > 0 for s in spans)
    _parent_writes(monkeypatch)
    assert got == _generate(family)


SHARED = [3, 1, 4, 1, 5, 9, 2, 6]              # two whole pages of 4
REUSE = [SHARED + [5, 3, 5], [2, 7, 1, 8], SHARED + [8, 9, 7, 9, 3]]


@pytest.mark.parametrize("family", ["gpt2", "llama"])
def test_a_prefix_hit_writes_whole_pages_from_a_later_boundary(family,
                                                               monkeypatch):
    """The third prompt rides the first's two pages: its row starts at
    position 8, a page boundary that is not 0, and still lands by whole
    pages (the reader is the stripe there); tokens are the parent's."""
    spans = []
    inf = dict(PLAIN, prompt_buckets=[16])      # two to a dispatch
    got = _generate(family, inf, REUSE, 4, spans)
    assert [(s["real_tokens"], s["own_key_tokens"], s["page_write_tokens"])
            for s in spans] == [(15, 15, 15), (5, 0, 5)]
    _parent_writes(monkeypatch)
    assert got == _generate(family, inf, REUSE, 4)


@pytest.mark.parametrize("family,chunk", [("gpt2", 16), ("llama", 16),
                                          ("gpt2", 6)])
def test_a_chunked_prompt_lands_the_parents_pages(family, chunk, monkeypatch):
    """A prompt of 20 in chunks of 16 (whole pages of 4: the second
    chunk starts at 16, by pages) and in chunks of 6 (no whole pages:
    rows alone): the tokens of the whole-prompt prefill and of the
    parent's writes."""
    long = [[1, 2, 3, 4] * 5, [5, 6, 7]]
    chunked = dict(PLAIN, prompt_buckets=[4], chunked_prefill={
        "enabled": True, "chunk_tokens": chunk})
    got = _generate(family, chunked, long, 4)
    assert got == _generate(family, dict(PLAIN, prompt_buckets=[4, 32]),
                            long, 4)
    _parent_writes(monkeypatch)
    assert got == _generate(family, chunked, long, 4)


@pytest.mark.parametrize("why,inf,want", [
    ("a bucket that is not whole pages",
     dict(PLAIN, prompt_buckets=[6, 14]), 0),
    ("the dense cache", dict(PLAIN, paged_kv={"enabled": False}), 0),
    ("whole pages", PLAIN, 11)], ids=lambda v: v.replace(" ", "_")
    if isinstance(v, str) else "")
def test_the_span_mirrors_the_programs_predicate(why, inf, want):
    spans = []
    _generate("gpt2", inf, REUSE[:1], 2, spans)
    assert [(s["real_tokens"], s["page_write_tokens"]) for s in spans] == [
        (11, want)], why


# --------------------------------------------------- what is declared
REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks")


def test_the_share_is_declared_for_the_four_serving_cells():
    import json
    name = "prefill_page_write_share.sat"
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # appended in PR 44, nothing moved; what later PRs add comes after
    names = [m["name"] for m in bench["per_layer"]]
    entry = bench["per_layer"][names.index(name)]
    assert names[names.index(name) + 1] == "serve_prefill_build_ms.sat"
    # the four serving cells of PR 44's day first; after them, by name,
    # any later serving cell whose prompts are prefilled whole (one
    # whose every prompt goes in chunks, PR 48's, has no `serve/prefill`
    # span to read)
    cells = [w["name"] for w in bench["workloads"]]
    listed = entry.pop("workloads")
    assert entry == {
        "name": name, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "page pool",
        "moves": "serve_tokens_per_s"}
    assert listed[:4] == [c for c in cells[:6] if ".serve-" in c]
    assert all(".serve-" in c and c in cells for c in listed[4:])
    assert "lfm2-24b-a2b.serve-chat-saturated" in listed
    assert "kimi-linear-48b-a3b.serve-longctx-saturated" not in listed
    with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
        spec = json.load(f)
    assert (spec["reader"], spec["params"]) == (
        "span_args_share", {"span": "serve/prefill",
                            "of": ["page_write_tokens"],
                            "over": ["real_tokens"]})
    assert (spec["unit"], spec["layer"], spec["moves"]) == (
        entry["unit"], entry["layer"], entry["moves"])


@pytest.mark.parametrize("spans,want", [
    ([dict(real_tokens=40, page_write_tokens=40),
      dict(real_tokens=60, page_write_tokens=0)], 40.0),
    # the parent's spans carry no such argument: nothing to read
    ([dict(real_tokens=40, own_key_tokens=40)], None)],
    ids=["this_tree", "the_parent"])
def test_the_reader_gives_the_share_or_nothing(spans, want, monkeypatch):
    """`span_args_share` over the spans of a traced run: the share where
    the program carries the counter, None (the metric left out of the
    line, no error) where it does not, as on the parent commit."""
    import types
    monkeypatch.syspath_prepend(BENCH)
    from loader import load_module
    reader = load_module("readers", "span_args_share")
    monkeypatch.setattr(reader.pt, "load", lambda trace_dir: {
        "host": [("serve/prefill", 0, 1, args) for args in spans]})
    got = reader.read(None, None, types.SimpleNamespace(trace_dir=None),
                      "serve/prefill", ["page_write_tokens"],
                      ["real_tokens"])
    assert got == want
