"""Keye-VL-2.0-style sparse attention SERVED (models/keye_vl2.py): an
indexer that picks ``topk`` tokens a query inside the paged cache
(ops/attention/indexed.py), exactly and with ties to the lower position,
in chunks that read the prefix back and in decode that reads the chosen
rows by (page, offset); the THIRD leaf of the pair's cache tree
(inference/kv_cache.py); the rotation over three position streams; the
softmax router with no shared expert; and what the engine refuses for
the family, against the plain float32 reference
(benchmarks/reference/keye_vl2_reference.py) on seeded weights at tiny
sizes on the CPU, with ``topk`` well below the contexts."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu  # noqa: F401
from deepspeed_tpu.inference import InferenceEngine
from deepspeed_tpu.inference.kv_cache import (IndexedPairCache,
                                              init_paged_kv_cache,
                                              paged_kv_bytes,
                                              paged_spec_for)
from deepspeed_tpu.inference.scheduler import Request
from deepspeed_tpu.models import keye_vl2 as kv2
from deepspeed_tpu.models.lfm2 import rotate_half_split
from deepspeed_tpu.ops import moe
from deepspeed_tpu.ops.attention import flash, indexed, page_pool
from deepspeed_tpu.profiling import spans

BENCH = os.path.join(os.path.dirname(__file__), "..", "..", "benchmarks")
sys.path.insert(0, BENCH)
from families import keye_vl2 as family  # noqa: E402
from reference import keye_vl2_reference as reference  # noqa: E402

CHUNK, TOPK = 16, 12
TINY = kv2.KeyeVL2Config(
    vocab_size=512, hidden_size=64, num_layers=3, num_heads=4,
    num_kv_heads=2, head_dim=16, moe_intermediate_size=32, num_experts=16,
    experts_per_token=4, mrope_section=(2, 3, 3), indexer_num_heads=4,
    indexer_head_dim=8, indexer_topk=TOPK, max_position_embeddings=256,
    # wider than the published 0.02, which at hidden 64 leaves every
    # logit within 0.01 of every other
    initializer_range=0.2, experts_held=(0, 4),
    vocab_held=(0, 128))
INFERENCE = {"max_batch_size": 3, "batch_buckets": [1, 2],
             "prompt_buckets": [CHUNK], "max_seq_len": 112,
             "chunked_prefill": {"enabled": True, "chunk_tokens": CHUNK},
             "paged_kv": {"num_pages": 30, "prefix_cache": False}}
# 1, 2 and 5 chunks; a last chunk of ONE token; a prompt that ends on a
# chunk boundary; one that fits the prompt bucket and is no chunk at all;
# one shorter than ``topk`` (it selects everything, by the same code)
PROMPTS = (5, CHUNK, CHUNK + 1, 2 * CHUNK, 5 * CHUNK - 7, 3 * CHUNK + 1)


@pytest.fixture(scope="module")
def model():
    params = kv2.init_keye_vl2_params(TINY, jax.random.PRNGKey(3),
                                      jnp.float32)
    return TINY, params, jax.jit(family.reference_logits(TINY))


@pytest.fixture(autouse=True)
def _small_prefix_blocks(monkeypatch):
    """Two pages a block of the prefix walk, so that a prefix of 64
    rows takes more than one turn of it."""
    monkeypatch.setattr(page_pool, "PREFIX_BLOCK", 32)


def _prompts(lengths, seed=0):
    rs = np.random.RandomState(seed)
    return [list(map(int, rs.randint(0, 128, n))) for n in lengths]


def _gaps(ref, params, finished, width=112):
    """The worst gap of every served token under the reference's pick."""
    worst = 0.0
    for f in finished:
        seq = list(f.prompt) + list(f.tokens)
        ids = np.zeros((1, width), np.int32)
        ids[0, :len(seq)] = seq
        logits = np.asarray(ref(params, jnp.asarray(ids)))[0]
        for t in range(len(f.prompt), len(seq)):
            worst = max(worst, float(logits[t - 1].max()
                                     - logits[t - 1][seq[t]]))
    return worst


def _serve(engine, prompts, new=6):
    reqs = [Request(prompt=p, max_new_tokens=new, temperature=0.0, seed=i,
                    eos_id=None) for i, p in enumerate(prompts)]
    uids = [engine.submit(r) for r in reqs]
    done = {f.uid: f for f in engine.run()}
    return [done[u] for u in uids]


def test_plain_forward_and_its_selected_sets_equal_the_reference(model):
    """Logits AND, a layer, the set every query selects (12 of up to 40
    positions): the program's choice is the reference's, exactly."""
    cfg, params, ref = model
    ids = jax.random.randint(jax.random.PRNGKey(4), (2, 40), 0, 128)
    chosen = []
    got = kv2.keye_vl2_forward(params, cfg, ids, dtype=jnp.float32,
                               selected=chosen)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref(params, ids)),
                               atol=2e-4)
    for row in range(2):
        sets = []
        reference.logits(params, ids[row:row + 1],
                         family.reference_config(cfg), sets=sets)
        assert len(sets) == len(chosen) == cfg.num_layers
        for mine, theirs in zip(chosen, sets):
            theirs = np.asarray(theirs)
            np.testing.assert_array_equal(np.asarray(mine[row]), theirs)
            # every position up to ``topk``, then ``topk`` of them
            assert list(theirs.sum(-1)) == [min(t + 1, TOPK)
                                            for t in range(40)]


def test_chunks_then_decode_through_the_cache_equal_the_reference(model):
    """Prompts of 1, 2 and 5 chunks prefilled through chunk dispatches
    that score and attend the prefix in the pools, then decoded through
    the cache by rows read at (page, offset): every served token is the
    ONE full forward's."""
    cfg, params, ref = model
    engine = InferenceEngine(cfg, params, INFERENCE, dtype=jnp.float32)
    assert isinstance(engine._cache, IndexedPairCache)
    finished = _serve(engine, _prompts(PROMPTS))
    chunks = engine._chunk_dispatches
    engine.close()
    assert all(f.finish_reason == "length" and len(f.tokens) == 6
               for f in finished)
    assert chunks >= 5
    assert _gaps(ref, params, finished) < 2e-3


def _case(seed, batch, length, ties=False):
    """Random pools behind shuffled block tables that hold ``length``
    positions a row, and the indexer's operands of a query at each."""
    ih, idim, hkv, hd, ps = 4, 8, 2, 16, 16
    pages = -(-length // ps)
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    order = np.random.RandomState(seed).permutation(
        np.arange(1, 1 + batch * pages)).reshape(batch, pages)
    tables = jnp.asarray(order, jnp.int32)
    pool = lambda k, w: jax.random.normal(
        k, (1, 1 + batch * pages, ps, w), jnp.float32)
    kpool, vpool, ipool = pool(ks[0], hkv * hd), pool(ks[1], hkv * hd), \
        pool(ks[2], idim)
    if ties:        # many equal scores: a few values an indexer key takes
        ipool = jnp.round(ipool)
    qi = jax.random.normal(ks[3], (batch, length, ih, idim), jnp.float32)
    if ties:
        qi = jnp.round(qi)
    wi = jax.random.normal(ks[4], (batch, length, ih), jnp.float32)
    if ties:
        wi = jnp.sign(wi)
    q = jax.random.normal(ks[5], (batch, 4, length, hd), jnp.float32)
    stripe = lambda p: p[0, tables].reshape(batch, pages * ps, -1)[:, :length]
    # the indexer leaf as it is held: two tokens a row, the same bytes
    held = ipool.reshape(1, -1, ps // 2, 2 * idim)
    return (q, kpool, vpool, held, tables, qi, wi, stripe(kpool),
            stripe(vpool), stripe(ipool))


def _oracle(q, kc, vc, kic, qi, wi):
    """(context (B, heads, S, hd), sets (B, S, S)) by the reference's
    own selection over dense scores."""
    B, H, S, hd = q.shape
    scores = indexed.indexer_scores(qi, wi, kic)
    sets = jnp.stack([reference.selection(scores[b], 0, TOPK)
                      for b in range(B)])
    hkv = kc.shape[-1] // hd
    k = kc.reshape(B, S, hkv, hd)
    v = vc.reshape(B, S, hkv, hd)
    qg = q.reshape(B, hkv, H // hkv, S, hd)
    s = jnp.einsum("bkgqd,bskd->bkgqs", qg, k) * hd ** -0.5
    p = jax.nn.softmax(jnp.where(sets[:, None, None], s, -jnp.inf), -1)
    return jnp.einsum("bkgqs,bskd->bkgqd", p, v).reshape(B, H, S, hd), sets


@pytest.mark.parametrize("ties", [False, True])
def test_decode_reads_the_rows_the_reference_selects(ties):
    """One query a row at the last position of 70: the chosen positions
    are the reference's set (equal scores to the lower position), and
    the context is the softmax over exactly those rows."""
    q, kpool, vpool, ipool, tables, qi, wi, kc, vc, kic = _case(
        5, 2, 70, ties)
    want, sets = _oracle(q, kc, vc, kic, qi, wi)
    probe = []
    got = indexed.decode_attention(
        q[:, :, -1], (kpool, vpool), ipool, 0, tables,
        jnp.asarray([69, 69]), qi[:, -1], wi[:, -1], TOPK, 16 ** -0.5,
        probe)
    chosen, counts = probe[0]
    assert bool(counts.all())
    for b in range(2):
        assert sorted(np.asarray(chosen[b])) == list(
            np.flatnonzero(np.asarray(sets[b, -1])))
    if ties:        # the choice did cut through a run of equal scores
        scores = np.asarray(indexed.indexer_scores(qi, wi, kic))[0, -1]
        kth = np.sort(scores)[-TOPK]
        assert (scores == kth).sum() > 1
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[:, :, -1]),
                               atol=2e-5)


def test_a_short_decode_row_selects_everything_by_the_same_code():
    q, kpool, vpool, ipool, tables, qi, wi, kc, vc, kic = _case(6, 2, 70)
    probe = []
    indexed.decode_attention(
        q[:, :, 4], (kpool, vpool), ipool, 0, tables, jnp.asarray([4, 0]),
        qi[:, 4], wi[:, 4], TOPK, 0.25, probe)
    chosen, counts = probe[0]
    assert list(np.asarray(counts).sum(-1)) == [5, 1]
    assert sorted(np.asarray(chosen[0])[np.asarray(counts[0])]) == [
        0, 1, 2, 3, 4]


@pytest.mark.parametrize("ties", [False, True])
@pytest.mark.parametrize("starts", [(48, 0), (32, 32), (0, 0)])
def test_a_chunk_selects_over_prefix_and_own_rows_exactly(starts, ties):
    """A chunk of 16 queries at any start (two rows of a batch at
    different starts; a first chunk): the context equals the dense
    oracle's under the reference's selection, the threshold found by
    counting and the ties given out in position order across the
    prefix's blocks and the own rows."""
    length = 64
    q, kpool, vpool, ipool, tables, qi, wi, kc, vc, kic = _case(
        7, 2, length, ties)
    want, sets = _oracle(q, kc, vc, kic, qi, wi)
    start = jnp.asarray(starts, jnp.int32)
    own = lambda t, axis: jnp.stack([jax.lax.dynamic_slice_in_dim(
        t[b], starts[b], CHUNK, axis) for b in range(2)])
    ps, per = 16, 2
    block = lambda j: jax.lax.dynamic_slice_in_dim(tables, j * per, per, 1)
    rows_of = lambda pool, j, rows: pool[0, block(j)].reshape(
        2, rows, 2, 16).transpose(0, 2, 1, 3)
    heads = lambda t: t.reshape(2, CHUNK, 2, 16).transpose(0, 2, 1, 3)
    probe = []
    got = indexed.chunk_attention(
        own(q, 1), heads(own(kc, 0)), heads(own(vc, 0)), own(qi, 0),
        own(wi, 0), own(kic, 0), start,
        lambda j, rows: ipool[0, block(j)].reshape(2, rows, 8),
        lambda j, rows: (rows_of(kpool, j, rows), rows_of(vpool, j, rows)),
        tables.shape[1] * ps, TOPK, 16 ** -0.5, probe)
    for b in range(2):
        s = starts[b]
        np.testing.assert_allclose(
            np.asarray(got[b]), np.asarray(want[b, :, s:s + CHUNK]),
            atol=2e-5)
        np.testing.assert_array_equal(
            np.asarray(probe[0][b]),
            np.asarray(sets[b, s:s + CHUNK, s:s + CHUNK]))


def test_the_kth_largest_key_is_found_by_counting():
    scores = jnp.asarray(np.random.RandomState(1).randn(5, 300)
                         .astype(np.float32)).at[:, ::7].set(0.25)
    seen = jnp.arange(300)[None, :] < jnp.asarray([300, 40, 12, 5, 1])[:, None]
    keys = indexed.score_keys(scores, seen)
    count = lambda op, cand: jnp.sum(op(keys, cand[:, None]), -1,
                                     dtype=jnp.int32)
    got = indexed.kth_largest_key(count, TOPK, (5,))
    want = np.sort(np.where(np.asarray(seen), np.asarray(scores), -np.inf),
                   -1)[:, -TOPK]
    back = np.asarray(indexed.score_keys(jnp.asarray(want), True))
    # fewer than ``topk`` positions seen: below every score's key
    assert list(np.asarray(got)[3:]) == [np.iinfo(np.int32).min] * 2
    np.testing.assert_array_equal(np.asarray(got)[:3], back[:3])
    # the keys order as the scores do, zero's two signs as one
    order = np.argsort(np.asarray(scores[0]), kind="stable")
    assert (np.diff(np.asarray(keys[0])[order]) >= 0).all()
    assert int(indexed.indexer_scores(
        jnp.ones((1, 1, 1, 2)), -jnp.ones((1, 1, 1)),
        -jnp.ones((1, 3, 2)))[0, 0, 0]) == 0


def test_flash_takes_a_mask_a_query_and_key():
    """The one change to the kernel the chunks ride: an additive mask a
    (query, key), shared by the heads, beside the causal cut."""
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    q = jax.random.normal(ks[0], (2, 4, 32, 16))
    k = jax.random.normal(ks[1], (2, 2, 32, 16))
    v = jax.random.normal(ks[2], (2, 2, 32, 16))
    keep = jax.random.bernoulli(ks[3], 0.5, (2, 32, 32)) | jnp.eye(32, dtype=bool)
    hide = jnp.where(keep, 0.0, flash.NEG_INF).astype(jnp.bfloat16)
    for causal in (False, True):
        o, _ = flash._flash_fwd(q, k, v, hide[:, None], causal, 0.25, True)
        seen = keep & jnp.tril(jnp.ones((32, 32), bool)) if causal else keep
        s = jnp.einsum("bkgqd,bksd->bkgqs", q.reshape(2, 2, 2, 32, 16),
                       k) * 0.25
        p = jax.nn.softmax(jnp.where(seen[:, None, None], s, -jnp.inf), -1)
        want = jnp.einsum("bkgqs,bksd->bkgqd", p, v).reshape(2, 4, 32, 16)
        np.testing.assert_allclose(np.asarray(o), np.asarray(want),
                                   atol=2e-5)


def test_the_rotation_splits_its_frequencies_over_three_streams(model):
    """Equal streams: the plain rotation, bit for bit. Unequal (image
    positions): the program's forward is the reference's."""
    cfg, params, _ = model
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 4, 24, 16))
    at = jnp.broadcast_to(jnp.arange(24) + 3, (2, 24))
    same = kv2.rotate_mrope(x, jnp.broadcast_to(at, (3, 2, 24)), 1e7,
                            (2, 3, 3))
    np.testing.assert_array_equal(np.asarray(same),
                                  np.asarray(rotate_half_split(x, at, 1e7)))
    rs = np.random.RandomState(9)
    streams = jnp.asarray(np.sort(rs.randint(0, 40, (3, 1, 24)), -1))
    assert not bool((streams[0] == streams[1]).all())
    moved = kv2.rotate_mrope(x[:1], streams, 1e7, (2, 3, 3))
    assert float(jnp.abs(moved - same[:1]).max()) > 0.1
    ids = jax.random.randint(jax.random.PRNGKey(5), (1, 24), 0, 128)
    got = kv2.keye_vl2_forward(params, cfg, ids, dtype=jnp.float32,
                               position_streams=streams)
    want = reference.logits(params, ids, family.reference_config(cfg),
                            streams=jnp.moveaxis(streams, 1, 0))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)
    text = kv2.keye_vl2_forward(params, cfg, ids, dtype=jnp.float32)
    assert float(jnp.abs(got - text).max()) > 1e-3
    with pytest.raises(ValueError, match="split"):
        kv2.rotate_mrope(x, jnp.broadcast_to(at, (3, 2, 24)), 1e7, (2, 3, 4))
    with pytest.raises(ValueError, match="image positions are not served"):
        kv2.keye_vl2_forward(params, cfg, ids, kv_cache=(),
                             position_streams=streams)


@pytest.mark.parametrize("served", ["every_row", "served"])
def test_the_eight_shares_add_up_to_the_uncut_layer(served):
    """The expert parts of all the shares (8 chips of 2 experts here, as
    the cell's 16 of 128) are the reference's whole layer: no shared
    expert is counted anywhere."""
    cfg = TINY
    h2 = jax.random.normal(jax.random.PRNGKey(11), (48, cfg.hidden_size))
    ks = jax.random.split(jax.random.PRNGKey(12), 4)
    f, e = cfg.moe_intermediate_size, cfg.num_experts
    n = lambda k, shape: jax.random.normal(k, shape, jnp.float32) * 0.2
    whole = {"w_gate": n(ks[0], (e, cfg.hidden_size, f)),
             "w_up": n(ks[1], (e, cfg.hidden_size, f)),
             "w_down": n(ks[2], (e, f, cfg.hidden_size))}
    router = n(ks[3], (cfg.hidden_size, e))
    ref_cfg = family.reference_config(cfg)
    with jax.default_matmul_precision("highest"):
        weights, ref_idx = reference.route(h2, router, ref_cfg)
        want = reference.experts(h2, weights, whole, (0, e))
        idx, p, _ = moe.route_top_k(h2, router, cfg.experts_per_token)
        np.testing.assert_array_equal(np.asarray(idx), np.asarray(ref_idx))
        np.testing.assert_allclose(np.asarray(p.sum(-1)), 1.0, atol=1e-6)
        raw, _ = reference.route(h2, router, ref_cfg, "router_weights")
        # the chosen p as they are add up to less than one
        assert float(raw.sum(-1).min()) < 0.7
        parts = []
        for first in range(0, e, 2):
            mine = jax.tree_util.tree_map(lambda a: a[first:first + 2],
                                          whole)
            if served == "every_row":
                y, _ = moe.held_experts_every_row(
                    h2, idx, p, mine, (first, 2), jax.nn.silu)
            else:
                y, _, _ = moe.served_experts(
                    h2, idx, p, mine, (first, 2), e, jax.nn.silu)
            parts.append(y)
    assert len(parts) == 8
    assert float(jnp.abs(parts[0]).max()) > 1e-3      # a share is a part
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(want),
                               atol=2e-4)


def test_the_cache_tree_has_a_third_leaf_and_its_bytes_are_counted(model):
    """keys, values AND one indexer key a token a layer over the same
    pages; ``paged_kv_bytes`` and what the engine reports a token count
    EVERY leaf (the satellite repair)."""
    cfg, params, _ = model
    spec = paged_spec_for(cfg, 30, 16, 112, dtype=jnp.bfloat16)
    tree = init_paged_kv_cache(spec)
    assert isinstance(tree, IndexedPairCache)
    assert tree.keys.shape == tree.values.shape == (3, 30, 16, 32)
    assert tree.index_keys.shape == (3, 30, 8, 16)       # two tokens a row
    assert paged_kv_bytes(spec) == sum(leaf.nbytes for leaf in tree) \
        == 3 * 30 * 16 * (2 * 32 + 8) * 2
    engine = InferenceEngine(cfg, params, INFERENCE)
    held = family.cache_bytes(cfg, engine)
    assert held == {"per_token": 3 * (2 * 32 + 8) * 2, "per_slot": 0}
    assert engine._kv_bpt == held["per_token"]
    assert engine._page_bytes == 16 * held["per_token"]
    engine.close()
    # the pair's own tree is what it was
    from deepspeed_tpu.models.llama import LlamaConfig
    pair = paged_spec_for(LlamaConfig(num_layers=2, hidden_size=64,
                                      num_heads=4, num_kv_heads=2), 9, 16, 64)
    assert pair.index_width == 0 and len(init_paged_kv_cache(pair)) == 2
    assert paged_kv_bytes(pair) == 2 * 2 * 9 * 16 * 32 * 2
    with pytest.raises(ValueError, match="no int8 form"):
        paged_spec_for(cfg, 30, 16, 112, dtype=jnp.int8)


def test_a_decode_step_leaves_a_mid_prefill_slots_rows_as_they_are(model):
    """A slot between two chunks rides the decode dispatch as a row with
    an all-null table: the decode writes ITS token's three rows to the
    null page, and the pages the chunks wrote (indexer keys among them)
    are untouched."""
    cfg, params, _ = model
    engine = InferenceEngine(cfg, params, INFERENCE, dtype=jnp.float32)
    short, long_ = _prompts((5, 4 * CHUNK + 3), seed=2)
    engine.submit(Request(prompt=short, max_new_tokens=20, temperature=0.0,
                          seed=0, eos_id=None))
    engine.step()
    engine.submit(Request(prompt=long_, max_new_tokens=4, temperature=0.0,
                          seed=1, eos_id=None))
    sched = engine.scheduler
    while sched.slots[1] is None or sched.chunk_span(1)[0] < 2 * CHUNK:
        engine.step()
    assert not sched.slots[1].issued          # still mid-prefill
    pages = list(sched.slots[1].pages)
    before = [np.asarray(leaf[:, pages]) for leaf in engine._cache]
    chunks = engine._chunk_dispatches
    engine._decode_phase()
    engine.debug_state()
    assert engine._chunk_dispatches == chunks
    for was, leaf in zip(before, engine._cache):
        np.testing.assert_array_equal(was, np.asarray(leaf[:, pages]))
    assert float(np.abs(before[2][:, :2]).max()) > 0       # and written
    finished = {f.uid: f for f in engine.run()}
    engine.close()
    assert len(finished) == 2


def test_the_planted_faults_move_the_reference(model):
    """The controls' knobs at tiny sizes (a selection of 12 among 64
    under sharp heads: one key in or out of it moves a logit, so
    bfloat16 products are loud here): each planted fault and the float8
    products move the logits further than bfloat16 products do, a fault
    of the selection three times as far; None is the reference
    itself."""
    cfg, params, ref = model
    ids = jnp.asarray(np.random.RandomState(4).randint(0, 128, (1, 64)))
    plain = np.asarray(ref(params, ids))
    rms = lambda **lower: float(np.sqrt(np.mean((np.asarray(jax.jit(
        family.reference_logits(cfg, **lower))(params, ids)) - plain) ** 2)))
    noise = rms(products="bfloat16")
    assert 0 < noise < 0.4 * np.sqrt(np.mean(plain ** 2))
    assert rms(products="float8_e5m2") > 2 * noise
    assert set(family.PLANTED) == {
        "no_selection", "newest_in_place_of_chosen", "indexer_without_w",
        "indexer_without_relu", "chunk_scores_own_rows_only",
        "stale_indexer_rows", "router_weights_raw"}
    for lower in family.PLANTED.values():
        lower = {**lower, **({"chunk": CHUNK} if "chunk" in lower else {})}
        moved = rms(**lower)
        assert moved > (1.3 if lower["fault"] == "router_weights" else 3) \
            * noise, lower
    # a fault planted at chunks no prompt reaches changes nothing
    assert rms(fault="chunk_scores", chunk=64) < 1e-5
    # the indexer's keys rounded alone may move no selection here
    assert rms(state_dtype="bfloat16") < noise
    assert 0 < rms(round_to="bfloat16") < noise
    with pytest.raises(ValueError, match="no planted fault"):
        family.reference_logits(cfg, fault="other")(params, ids)
    with pytest.raises(ValueError, match="needs `chunk`"):
        family.reference_logits(cfg, fault="stale_index")(params, ids)


REFUSED = {
    "prefix_cache": ({"paged_kv": {"num_pages": 30, "prefix_cache": True}},
                     "prefix cache"),
    "dense_cache": ({"paged_kv": {"enabled": False},
                     "chunked_prefill": {"enabled": False}}, "dense cache"),
    "spec_decode": ({"spec_decode": {"enabled": True, "k": 2}},
                    "speculative decoding"),
    "disagg": ({"disagg": {"enabled": True}}, "disaggregated"),
    "int8_pool": ({"paged_kv": {"num_pages": 30, "prefix_cache": False,
                                "kv_dtype": "int8"}}, "int8 page pool"),
    "quantized_weights": ({"quantize_weights": "int8"},
                          "quantized weights"),
    "mesh": ({"mesh": {"axes": {"model": 2}}}, "serving mesh"),
}


@pytest.mark.parametrize("feature", sorted(REFUSED))
def test_the_family_refuses_by_name_what_it_cannot_follow(model, feature):
    cfg, params, _ = model
    asked, named = REFUSED[feature]
    with pytest.raises(ValueError, match="an indexer key a token in a "
                       "third leaf") as said:
        InferenceEngine(cfg, params, {**INFERENCE, **asked})
    assert named in str(said.value)
    assert "chunked prefill" not in str(said.value)


def test_a_request_of_the_family_cannot_be_exported_or_imported(model):
    cfg, params, _ = model
    engine = InferenceEngine(cfg, params, INFERENCE)
    for call in (lambda: engine.export_request(0),
                 lambda: engine.import_request(None),
                 engine.warm_migration):
        with pytest.raises(NotImplementedError, match="indexer key"):
            call()
    engine.close()


def test_the_spans_carry_what_the_indexer_scored_and_selected(
        model, monkeypatch):
    """``live_tokens``, ``scored_tokens``, ``selected_tokens`` and
    ``dense_rows`` on `serve/decode` and `serve/chunk`, sums a reader
    can add up over spans."""
    cfg, params, _ = model
    chunks, decodes = [], []
    plain = InferenceEngine._span

    def recording(self, name, **args):
        if name == "serve/chunk":
            chunks.append(args)
        if name == "serve/decode":
            decodes.append(args)
        return plain(self, name, **args)

    monkeypatch.setattr(InferenceEngine, "_span", recording)
    engine = InferenceEngine(cfg, params, INFERENCE, dtype=jnp.float32)
    lengths = [3 * CHUNK + 1, 2 * CHUNK + 4]
    _serve(engine, _prompts(lengths, seed=3), new=3)
    engine.close()
    tri = lambda n: n * (n + 1) // 2
    capped = lambda n: tri(min(n, TOPK)) + max(n - TOPK, 0) * TOPK
    assert sum(a["scored_tokens"] for a in chunks) == sum(
        tri(n) for n in lengths)
    assert sum(a["selected_tokens"] for a in chunks) == sum(
        capped(n) for n in lengths)
    # only a first chunk that ends within ``topk`` positions is dense
    assert sum(a["dense_rows"] for a in chunks) == 0
    assert all(a["live_tokens"] >= a["real_tokens"] for a in chunks)
    assert len(decodes) >= 2
    for a in decodes:
        # a row at position p scores p + 1 keys: its own among them
        assert a["selected_tokens"] <= a["scored_tokens"] \
            <= a["live_tokens"] + a["rows"]
        assert a["dense_rows"] == 0
    first = InferenceEngine._selection_counters(engine, [(0, 5), (40, 1)])
    assert first == {"scored_tokens": 15 + 41, "selected_tokens": 15 + 12,
                     "dense_rows": 1}


def test_the_new_names_are_registered():
    assert {"indexer", "select", "sparse_attn", "sparse_prefix",
            "moe_route", "moe_experts"} <= set(spans.DEVICE_SCOPES)
    from deepspeed_tpu.inference import engine as eng
    assert eng._FAMILIES[kv2.KeyeVL2Config][0] == "keye_vl2"


def test_the_cut_counts_659m_parameters():
    """The configuration file's sizes through the family: the
    arithmetic of docs/keye_vl2.md, and every published width kept."""
    with open(os.path.join(BENCH, "configs",
                           "keye-vl-2.0-30b-a3b.json")) as f:
        config = json.load(f)
    cfg = family.serve_model_of(config)
    attn, indexer, router, expert, tables = kv2.keye_vl2_param_count(cfg)
    assert (attn, indexer, router, expert) == (
        18_874_368 + 256, 2_261_120, 262_144, 4_718_592)
    assert tables == 2 * 18_992 * 2_048 + 2_048 + 6 * 4_096
    assert family.param_count(cfg) == 659_190_016
    shapes = jax.eval_shape(lambda: kv2.init_keye_vl2_params(
        cfg, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(a.shape)) for a in
               jax.tree_util.tree_leaves(shapes)) == 659_190_016
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.moe_intermediate_size, cfg.experts_per_token,
            cfg.num_experts, cfg.indexer_num_heads, cfg.indexer_head_dim,
            cfg.indexer_topk, cfg.rope_theta, cfg.mrope_section) == (
        2048, 32, 4, 128, 768, 8, 128, 16, 64, 2048, 1e7, (16, 24, 24))
    assert (cfg.num_layers, cfg.held, cfg.vocab_rows) == (6, (0, 16), 18992)
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 48,
                                   "num_experts": 128, "vocab_size": 151936}
    # a token's cache: 13,056 B over the six layers
    spec = paged_spec_for(cfg, 9, 16, 64)
    assert paged_kv_bytes(spec) // (9 * 16) == 13_056
    described = family.describe_served(cfg)
    assert described["weight_bytes"] == 2 * 659_190_016
