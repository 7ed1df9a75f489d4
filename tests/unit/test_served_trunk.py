# Copyright The DeepSpeed-TPU authors. Licensed under Apache 2.0.
"""The served trunk's contract with a family (ISSUE 46), held by a
family that exists only here: a GQA mixer over a page-pool pair through
``ops/attention/page_pool.py``, two layers of it (one with a dense
feed-forward, one with routed experts), served through
``models/served_trunk.served_forward`` and held against its own plain
forward. Tiny, float32, on the CPU."""

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models.served_trunk import ServedFamily, served_forward
from deepspeed_tpu.ops.attention.page_pool import (gqa_stripe_attention,
                                                   paged_attend)
from deepspeed_tpu.ops.moe import route_top_k

VOCAB, HIDDEN, HEADS, KV_HEADS, HEAD_DIM, FF, EXPERTS, HELD = (
    64, 32, 4, 2, 8, 16, 4, 2)
PAGE, PAGES = 16, 4


class ToyConfig(NamedTuple):
    rms_norm_eps: float = 1e-5
    num_experts: int = EXPERTS
    held: Tuple[int, int] = (0, HELD)


def _toy_mixer(lp, h, call, cache, n):
    """Causal GQA attention, the ``n``-th layer of a pool PAIR that is
    the whole cache tree."""
    B, S, _ = h.shape
    heads = lambda t, k: t.reshape(B, S, k, HEAD_DIM).transpose(0, 2, 1, 3)
    ap = lp["attn"]
    q, k, v = (heads(h @ ap["wq"], HEADS), heads(h @ ap["wk"], KV_HEADS),
               heads(h @ ap["wv"], KV_HEADS))
    if cache is None:
        ctx = gqa_stripe_attention(q, k, v, jnp.zeros((B,), jnp.int32))
    else:
        box = []
        ctx = paged_attend(q, k, v, cache, n, call.tables, call.positions,
                           call.index, box, call.reader,
                           gqa_stripe_attention)
        cache = box[0]
    return ctx.transpose(0, 2, 1, 3).reshape(B, S, -1) @ ap["wo"], cache


TOY = ServedFamily(
    layers=(("toy", "dense"), ("toy", "experts")),
    mixers={"toy": _toy_mixer},
    route=lambda flat, router: route_top_k(flat, router, 2),
    expert_tile=(128, 128, 128))


def _params():
    keys = iter(jax.random.split(jax.random.PRNGKey(46), 32))
    n = lambda *shape: 0.3 * jax.random.normal(next(keys), shape,
                                               jnp.float32)
    norm = lambda: {"w": 1.0 + n(HIDDEN)}
    swiglu = lambda *lead: {"w_gate": n(*lead, HIDDEN, FF),
                            "w_up": n(*lead, HIDDEN, FF),
                            "w_down": n(*lead, FF, HIDDEN)}
    attn = lambda: {"wq": n(HIDDEN, HEADS * HEAD_DIM),
                    "wk": n(HIDDEN, KV_HEADS * HEAD_DIM),
                    "wv": n(HIDDEN, KV_HEADS * HEAD_DIM),
                    "wo": n(HEADS * HEAD_DIM, HIDDEN)}
    return {
        "tok_emb": n(VOCAB, HIDDEN), "lm_head": n(VOCAB, HIDDEN),
        "ln_f": norm(),
        "h_0": {"ln_1": norm(), "ln_2": norm(), "attn": attn(),
                "mlp": swiglu()},
        "h_1": {"ln_1": norm(), "ln_2": norm(), "attn": attn(),
                "router": n(HIDDEN, EXPERTS), "experts": swiglu(HELD),
                "shared": swiglu()},
    }


def _forward(family, params, ids, cache=None, **serving):
    call = dict(cache_position=None, block_tables=None,
                paged_attn_kernel="gather", lengths=None, slots=None,
                active=None, with_counts=False)
    call.update(serving)
    with jax.default_matmul_precision("highest"):
        return served_forward(family, params, ToyConfig(), ids, jnp.float32,
                              cache, **call)


@pytest.mark.parametrize("reader", ["gather", "pallas"])
def test_a_prefill_then_a_decode_equal_the_plain_forward(reader):
    """17 positions plainly, and 16 as a served prefill then the 17th as
    a decode step over the pages the prefill wrote (both layers' ``n``
    their own layer of the pool): the same logits, to the tolerance the
    families' own tests hold float32 against float32 at."""
    params = _params()
    ids = jax.random.randint(jax.random.PRNGKey(7), (1, 17), 0, VOCAB)
    want = _forward(TOY, params, ids)
    assert want.shape == (1, 17, VOCAB) and float(jnp.std(want)) > 0.3
    pool = jnp.zeros((2, PAGES, PAGE, KV_HEADS * HEAD_DIM), jnp.float32)
    tables = jnp.asarray([[1, 2]], jnp.int32)
    logits, cache, counts = _forward(
        TOY, params, ids[:, :16], (pool, pool),
        cache_position=jnp.zeros((1,), jnp.int32), block_tables=tables,
        paged_attn_kernel=reader, lengths=jnp.asarray([16]),
        slots=jnp.asarray([0]), with_counts=True)
    assert logits.shape == (1, 1, VOCAB)       # the last true position
    np.testing.assert_allclose(np.asarray(logits[:, 0]),
                               np.asarray(want[:, 15]), atol=2e-4)
    # one expert layer: (rows its turns worked, rows static turns would)
    assert counts.shape == (1, 2) and counts.dtype == jnp.int32
    # both layers wrote their own layer of the pool, page 1 alone
    assert all(float(jnp.abs(leaf[l, 1]).max()) > 0 for leaf in cache
               for l in range(2))
    assert all(float(jnp.abs(leaf[:, 2:]).max()) == 0 for leaf in cache)
    logits, cache, counts = _forward(
        TOY, params, ids[:, 16:], cache,
        cache_position=jnp.asarray([16], jnp.int32), block_tables=tables,
        paged_attn_kernel=reader, active=jnp.asarray([True]),
        with_counts=True)
    np.testing.assert_allclose(np.asarray(logits[:, 0]),
                               np.asarray(want[:, 16]), atol=2e-4)
    # (landed on a held expert, the fullest held expert's): one row's
    # top-2 of 4 experts, 2 of them held
    assert counts.shape == (1, 2) and 0 <= int(counts[0, 1]) <= int(
        counts[0, 0]) <= 2
    assert all(float(jnp.abs(leaf[l, 2, 0]).max()) > 0 for leaf in cache
               for l in range(2))


def test_a_constant_a_family_leaves_out_is_not_in_its_program():
    """The three multipliers and the tied head are the family's to
    state: stated, each moves the logits; left None, no multiply or
    divide by one is traced."""
    params = _params()
    ids = jax.random.randint(jax.random.PRNGKey(8), (2, 8), 0, VOCAB)
    plain = _forward(TOY, params, ids)
    for knob in ("embedding_multiplier", "residual_multiplier",
                 "logits_scaling"):
        got = _forward(TOY._replace(**{knob: 2.0}), params, ids)
        assert float(jnp.abs(got - plain).max()) > 1e-2, knob
    tied = _forward(TOY._replace(head="tok_emb"), params, ids)
    assert float(jnp.abs(tied - plain).max()) > 1e-2
    ones = TOY._replace(embedding_multiplier=1.0, residual_multiplier=1.0,
                        logits_scaling=1.0)
    count = lambda family: len(jax.make_jaxpr(
        lambda p: _forward(family, p, ids))(params).jaxpr.eqns)
    assert count(ones) > count(TOY)


def test_a_layer_kind_without_a_mixer_is_refused_by_name():
    family = TOY._replace(layers=(("toy", "dense"), ("window", "experts")))
    with pytest.raises(ValueError, match="window"):
        _forward(family, _params(), jnp.zeros((1, 4), jnp.int32))


def test_a_family_without_a_shared_expert_has_none_in_its_program():
    """A layer with no ``shared`` leaf: its routed sum alone, and no
    ``moe_shared`` scope traced; with the leaf, the shared expert's
    output on top."""
    params = _params()
    ids = jax.random.randint(jax.random.PRNGKey(9), (2, 8), 0, VOCAB)
    with_shared = _forward(TOY, params, ids)
    bare = {**params, "h_1": {k: v for k, v in params["h_1"].items()
                              if k != "shared"}}
    without = _forward(TOY, bare, ids)
    assert float(jnp.abs(with_shared - without).max()) > 1e-2
    zeroed = {**params, "h_1": {**params["h_1"], "shared": jax.tree_util
                                .tree_map(jnp.zeros_like,
                                          params["h_1"]["shared"])}}
    np.testing.assert_allclose(np.asarray(_forward(TOY, zeroed, ids)),
                               np.asarray(without), atol=1e-6)
    scopes = lambda p: jax.jit(lambda q: _forward(TOY, q, ids)).lower(
        p).as_text(debug_info=True)
    assert "/moe_shared" in scopes(params)
    assert "/moe_shared" not in scopes(bare)
    assert "/moe_experts" in scopes(bare)


class _PagesAndTails(NamedTuple):
    """A cache tree without a state leaf: the pool pair and one more
    per-slot leaf (``kv_cache.PagedTailCache``'s shape)."""
    keys: jnp.ndarray
    values: jnp.ndarray
    tails: jnp.ndarray


def test_a_tree_without_a_state_leaf_goes_through_the_trunk():
    """The trunk names no leaf: a mixer over (keys, values, tails) gets
    the tree as the engine built it, replaces ITS leaves and hands it
    on; the page size is read off the first leaf."""
    from deepspeed_tpu.models.served_trunk import paged_pair_mixer

    def softmax(ap, config, h, dtype, pages, token_positions):
        assert token_positions.shape == h.shape[:2]
        B, S, _ = h.shape
        heads = lambda t, k: t.reshape(B, S, k, HEAD_DIM).transpose(
            0, 2, 1, 3)
        q, k, v = (heads(h @ ap["wq"], HEADS), heads(h @ ap["wk"], KV_HEADS),
                   heads(h @ ap["wv"], KV_HEADS))
        if pages is None:
            ctx, pools = gqa_stripe_attention(
                q, k, v, jnp.zeros((B,), jnp.int32)), None
        else:
            box = []
            ctx = paged_attend(q, k, v, pages.pools, pages.layer,
                               pages.tables, pages.positions, pages.index,
                               box, pages.reader, gqa_stripe_attention)
            pools = box[0]
        return ctx.transpose(0, 2, 1, 3).reshape(B, S, -1) @ ap["wo"], pools

    def tailed(lp, h, call, cache, n):
        # keeps the last normed input of every row it is given
        y = h @ lp["attn"]["wq"][:, :HIDDEN]
        if cache is not None:
            rows = call.slots if h.shape[1] > 1 else jnp.arange(h.shape[0])
            cache = cache._replace(
                tails=cache.tails.at[n, rows].set(h[:, -1]))
        return y, cache

    family = TOY._replace(
        layers=(("tailed", "dense"), ("paged", "experts")),
        mixers={"tailed": tailed,
                "paged": paged_pair_mixer(softmax, positions=True)},
        token_positions=True)
    params = _params()
    ids = jax.random.randint(jax.random.PRNGKey(10), (1, 17), 0, VOCAB)
    want = _forward(family, params, ids)
    pool = jnp.zeros((1, PAGES, PAGE, KV_HEADS * HEAD_DIM), jnp.float32)
    tree = _PagesAndTails(pool, pool, jnp.zeros((1, 2, HIDDEN)))
    tables = jnp.asarray([[1, 2]], jnp.int32)
    logits, tree = _forward(
        family, params, ids[:, :16], tree,
        cache_position=jnp.zeros((1,), jnp.int32), block_tables=tables,
        lengths=jnp.asarray([16]), slots=jnp.asarray([1]))
    assert isinstance(tree, _PagesAndTails)
    np.testing.assert_allclose(np.asarray(logits[:, 0]),
                               np.asarray(want[:, 15]), atol=2e-4)
    assert float(jnp.abs(tree.tails[0, 1]).max()) > 0
    assert float(jnp.abs(tree.tails[0, 0]).max()) == 0
    logits, tree = _forward(
        family, params, ids[:, 16:], tree,
        cache_position=jnp.asarray([16], jnp.int32), block_tables=tables,
        active=jnp.asarray([True]))
    np.testing.assert_allclose(np.asarray(logits[:, 0]),
                               np.asarray(want[:, 16]), atol=2e-4)
