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
