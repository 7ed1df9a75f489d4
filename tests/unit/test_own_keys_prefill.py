# Copyright The DeepSpeed-TPU authors. Licensed under Apache 2.0.
"""A prompt that starts at position 0 attends to its own keys (ISSUE 40).

``ops/attention/page_pool.paged_attend`` (every family's) writes the
pool and then, for a query of many rows, picks its reader from what the
call shows:

- every row at cache position 0 -> the call's own ``k``, ``v`` (nothing
  gathered back out of the pool): the family's stripe mathematics over
  them where the scores are small, the training flash kernel above that;
- any row further on (a shared prefix, a later chunk) -> the gathered
  stripe, the program the parent ran, chosen at RUN time inside the one
  program a bucket has;
- a shape the kernel cannot use (a verify width, an int8 pool, a pool
  narrower than the keys, a context-parallel chunk) -> the stripe alone,
  ONE branch in the program.

The kernel runs in the interpreter here; every case is tiny.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference import InferenceEngine
from deepspeed_tpu.models.gpt2 import gpt2_forward
from deepspeed_tpu.models.llama import llama_forward
from deepspeed_tpu.ops.attention import page_pool
from tests.unit.test_inference import tiny_gpt2, tiny_llama

ROWS, SEQ, PAGE, TABLE = 4, 16, 4, 8       # a table of 32 positions
# one token, a ragged row, a full bucket, and a pad row (null table)
LENGTHS = [1, 7, SEQ, 0]
FAMILIES = {"gpt2": (tiny_gpt2, gpt2_forward),
            "llama": (tiny_llama, llama_forward)}


def _case(family, pool_dtype=jnp.float32, int8=False):
    """(config, params, forward, pools, ids, tables) of a four-row call
    whose last row is padding."""
    make, forward = FAMILIES[family]
    cfg, params = make()
    kv_heads = getattr(cfg, "num_kv_heads", cfg.num_heads)
    width = kv_heads * (cfg.hidden_size // cfg.num_heads)
    pages = 1 + ROWS * TABLE

    def pool(w=width, dtype=pool_dtype):
        return jnp.zeros((cfg.num_layers, pages, PAGE, w), dtype)
    pools = (pool(), pool())
    if int8:
        pools = (pool(dtype=jnp.int8), pool(dtype=jnp.int8),
                 pool(kv_heads, jnp.float32), pool(kv_heads, jnp.float32))
    tables = np.arange(1, pages, dtype=np.int32).reshape(ROWS, TABLE)
    tables[-1] = 0
    ids = np.random.RandomState(40).randint(1, 61, (ROWS, SEQ))
    return cfg, params, forward, pools, ids.astype(np.int32), tables


def _prefill(family, positions, seq=SEQ, dtype=jnp.float32, **case):
    """The family's cached forward over the paged pool, as a function of
    nothing (closed over its operands), so that it can be run or traced."""
    cfg, params, forward, pools, ids, tables = _case(family, **case)

    def run():
        return forward(params, cfg, jnp.asarray(ids[:, :seq]), dtype=dtype,
                       kv_cache=pools,
                       cache_position=jnp.asarray(positions, jnp.int32),
                       block_tables=jnp.asarray(tables))
    return run


def _conds(jaxpr):
    """``cond`` equations anywhere in a jaxpr that pick a READER: the
    conditional whose branches scatter is the pool's write picking its
    index granularity (``tests/unit/test_page_writes.py``)."""
    n = 0
    for eqn in jaxpr.eqns:
        subs = list(jax.core.jaxprs_in_params(eqn.params))
        n += eqn.primitive.name == "cond" and not any(
            e.primitive.name == "scatter" for sub in subs for e in sub.eqns)
        n += sum(_conds(sub) for sub in subs)
    return n


def _branches(run):
    return _conds(jax.make_jaxpr(run)().jaxpr)


def _stripe_only(monkeypatch):
    """The parent's program: no call is wide enough for its own keys."""
    monkeypatch.setattr(page_pool, "_OWN_KEYS_ROWS", 1 << 30)


def _poison_own_keys(monkeypatch):
    """Whatever attends to its own keys reads NaN."""
    monkeypatch.setattr(page_pool, "own_keys_attention",
                        lambda q, *_: jnp.full_like(q, jnp.nan))


@pytest.fixture(params=["dense", "flash"])
def form(request, monkeypatch):
    """Both forms of the own-keys reader at the tests' tiny shapes: the
    stripe mathematics (what a shape this small gets) and the flash
    kernel (forced: no score matrix is small enough)."""
    if request.param == "flash":
        monkeypatch.setattr(page_pool, "_OWN_KEYS_DENSE_SCORES", 0)
    return request.param


@pytest.mark.parametrize("family", ["gpt2", "llama"])
def test_own_keys_give_the_stripes_logits_and_the_same_pool(family, form,
                                                            monkeypatch):
    """Rows of 1, 7 and 16 true tokens and a pad row, all at position 0:
    the logits of every true position are the stripe branch's and the
    plain (cacheless, float32) forward's, and every page but the null
    one holds the same keys and values."""
    cfg, params, forward, _, ids, _ = _case(family)
    run = _prefill(family, [0] * ROWS)
    traced = jax.make_jaxpr(run)()
    assert _conds(traced.jaxpr) == cfg.num_layers
    assert str(traced).count("pallas_call") == (
        cfg.num_layers if form == "flash" else 0)
    own, own_pools = jax.jit(run)()
    _stripe_only(monkeypatch)
    stripe_run = _prefill(family, [0] * ROWS)
    assert _branches(stripe_run) == 0
    stripe, stripe_pools = jax.jit(stripe_run)()
    plain = forward(params, cfg, jnp.asarray(ids), dtype=jnp.float32)
    for row, n in enumerate(LENGTHS):
        np.testing.assert_allclose(own[row, :n], stripe[row, :n],
                                   rtol=0, atol=2e-5)
        np.testing.assert_allclose(own[row, :n], plain[row, :n],
                                   rtol=0, atol=2e-5)
    for a, b in zip(own_pools, stripe_pools):
        np.testing.assert_allclose(a[:, 1:], b[:, 1:], rtol=0, atol=1e-6)


@pytest.mark.parametrize("family", ["gpt2", "llama"])
def test_the_positions_pick_the_branch_at_run_time(family, monkeypatch):
    """One program, both ways: all rows at 0 run the own-keys branch (it
    is poisoned here, and the logits show it); one row on a shared
    prefix sends the whole batch through the stripe, the stripe-only
    program's operations on the same operands."""
    prefixed = [0, PAGE, 0, 0]
    _poison_own_keys(monkeypatch)
    program = jax.jit(lambda pos: _prefill(family, pos)()[0])
    at_zero = program(jnp.zeros((ROWS,), jnp.int32))
    mixed = program(jnp.asarray(prefixed, jnp.int32))
    assert np.isnan(np.asarray(at_zero)).all()
    assert np.isfinite(np.asarray(mixed)).all()
    _stripe_only(monkeypatch)
    before = jax.jit(lambda pos: _prefill(family, pos)()[0])(
        jnp.asarray(prefixed, jnp.int32))
    # the same operations; inside a conditional the compiler fuses them
    # its own way, so the last bit of a float32 sum may differ
    np.testing.assert_allclose(np.asarray(mixed), np.asarray(before),
                               rtol=0, atol=1e-6)


@pytest.mark.parametrize("why,kwargs", [
    ("a verify width", dict(seq=5)),
    ("rows not a multiple of the kernel's tile", dict(seq=12)),
    ("an int8 pool", dict(int8=True)),
    ("a pool narrower than the keys", dict(pool_dtype=jnp.bfloat16)),
], ids=lambda v: v if isinstance(v, str) else "")
def test_a_shape_the_kernel_cannot_use_keeps_one_branch(why, kwargs,
                                                        monkeypatch):
    """Decided when the program is traced, from the operands alone: no
    conditional, and nothing attends to its own keys at position 0."""
    _poison_own_keys(monkeypatch)
    for family in FAMILIES:
        run = _prefill(family, [0] * ROWS, **kwargs)
        assert _branches(run) == 0, why
        assert np.isfinite(np.asarray(run()[0], np.float32)).all()


def test_a_context_parallel_chunk_keeps_the_ring(monkeypatch):
    from jax.sharding import Mesh
    from deepspeed_tpu.parallel.pallas_shard import context_prefill_mesh
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("model",))
    assert _branches(_prefill("gpt2", [0] * ROWS)) == 2
    with context_prefill_mesh(mesh, "model"):
        assert _branches(_prefill("gpt2", [0] * ROWS)) == 0


# ------------------------------------------------------------ the engine
INF = {"max_batch_size": 3, "prompt_buckets": [16], "batch_buckets": [2],
       "max_seq_len": 32, "max_new_tokens": 4,
       "paged_kv": {"page_size": 4, "num_pages": 24}}
SHARED = [3, 1, 4, 1, 5, 9, 2, 6]              # two whole pages
PROMPTS = [SHARED + [5, 3, 5], [2, 7, 1, 8], SHARED + [8, 9, 7, 9, 3]]


def _generate(family, inf, prompts=PROMPTS, spans=None):
    cfg, params = FAMILIES[family][0]()
    engine = InferenceEngine(cfg, params, inf, dtype=jnp.float32)
    if spans is not None:
        real_span = engine._span

        def spy(name, **args):
            if name == "serve/prefill":
                spans.append(args)
            return real_span(name, **args)
        engine._span = spy
    # two to a dispatch: the third prompt comes while the first lives
    out = engine.generate(prompts, max_new_tokens=4, temperature=0.0)
    engine.close()
    return out


@pytest.mark.parametrize("family", ["gpt2", "llama"])
def test_prefix_reuse_takes_the_stripe_and_the_span_says_which(
        family, form, monkeypatch):
    """Greedy tokens across the two readers: a dispatch of two prompts
    from position 0 (own keys), then one that rides the first's two
    pages (the stripe), against the stripe-only engine; ``own_key_tokens`` on the span is
    the dispatch's real tokens or 0."""
    spans = []
    got = _generate(family, INF, spans=spans)
    assert [(s["real_tokens"], s["own_key_tokens"]) for s in spans] == [
        (15, 15), (5, 0)]
    _stripe_only(monkeypatch)
    assert got == _generate(family, INF)


@pytest.mark.parametrize("family,form", [("gpt2", "flash"),
                                         ("llama", "dense")], indirect=["form"])
def test_a_chunked_prompt_starts_on_its_own_keys_and_goes_on_by_stripe(
        family, form, monkeypatch):
    """A prompt of 20 in chunks of 16: the first chunk attends to its
    own keys, the second (position 16) to the stripe, and the first
    token and all that follow are the whole-prompt prefill's (one bucket
    of 32, own keys throughout) and the stripe-only engine's."""
    long = [[1, 2, 3, 4] * 5, [5, 6, 7]]
    chunked = dict(INF, prompt_buckets=[4],
                   chunked_prefill={"enabled": True, "chunk_tokens": 16})
    whole = dict(INF, prompt_buckets=[4, 32])
    got = _generate(family, chunked, long)
    assert got == _generate(family, whole, long)
    _stripe_only(monkeypatch)
    assert got == _generate(family, chunked, long)


def test_a_serving_mesh_keeps_both_branches(monkeypatch):
    """Under the engine's GSPMD programs (heads over a model axis of 2)
    the conditional and the flash kernel inside it (shard_mapped by
    ``pallas_kernel_mesh``) give the one-device engine's tokens, with
    and without a prefixed row."""
    monkeypatch.setattr(page_pool, "_OWN_KEYS_DENSE_SCORES", 0)
    inf = dict(INF, paged_kv={"page_size": 8, "num_pages": 24})
    assert _generate("llama", dict(inf, mesh={"axes": {"model": 2}})) \
        == _generate("llama", inf)


def test_the_dense_cache_reports_no_own_keys():
    spans = []
    _generate("gpt2", dict(INF, paged_kv={"enabled": False}),
              PROMPTS[:1], spans=spans)
    assert [(s["real_tokens"], s["own_key_tokens"]) for s in spans] == [
        (11, 0)]
