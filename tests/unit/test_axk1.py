"""A.X-K1-style decoder SERVED (models/axk1.py): latent attention
through ONE latent page pool (inference/kv_cache.py), absorbed in decode
(ops/attention/paged.py) and expanded in prefill, the group-limited
sigmoid router (ops/moe.py) and what the engine refuses for the family,
against the plain float32 reference
(benchmarks/reference/axk1_reference.py) on seeded weights at tiny sizes
on the CPU."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu  # noqa: F401
from deepspeed_tpu.inference import InferenceEngine
from deepspeed_tpu.inference.kv_cache import (LatentPoolSpec,
                                              init_paged_kv_cache,
                                              latent_row_lanes,
                                              paged_kv_bytes,
                                              paged_spec_for)
from deepspeed_tpu.inference.scheduler import Request
from deepspeed_tpu.models import axk1 as ax
from deepspeed_tpu.models.gpt2 import GPT2Config
from deepspeed_tpu.ops import moe
from deepspeed_tpu.ops.attention.paged import (latent_decode_attention,
                                               latent_decode_reference)
from deepspeed_tpu.profiling import spans

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..",
                                "benchmarks"))
from families import axk1 as family  # noqa: E402
from reference import axk1_reference as reference  # noqa: E402

TINY = ax.AXK1Config(
    vocab_size=512, hidden_size=64, num_layers=3, num_heads=4,
    q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, intermediate_size=96,
    moe_intermediate_size=32, num_experts=32, experts_per_token=4,
    n_group=8, topk_group=4, max_position_embeddings=256,
    rope_original_max=32,
    # wider than the published 0.02, which at hidden 64 leaves every
    # logit within 0.01 of every other
    initializer_range=0.2, experts_held=(0, 2), vocab_held=(0, 128))
INFERENCE = {"max_batch_size": 3, "batch_buckets": [1, 2],
             "prompt_buckets": [16, 32], "max_seq_len": 64,
             "paged_kv": {"num_pages": 14, "prefix_cache": False}}


@pytest.fixture(scope="module")
def model():
    params = ax.init_axk1_params(TINY, jax.random.PRNGKey(3))
    return TINY, params, jax.jit(family.reference_logits(TINY))


def test_plain_forward_equals_the_reference(model):
    cfg, params, ref = model
    ids = jax.random.randint(jax.random.PRNGKey(4), (2, 40), 0, 128)
    with jax.default_matmul_precision("highest"):
        got = ax.axk1_forward(params, cfg, ids, dtype=jnp.float32)
    want = ref(params, ids)
    assert float(jnp.std(want)) > 0.3           # logits that tell tokens
    # float32 against float32: sums in another order
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-3)


def test_the_rotary_frequencies_and_the_scale_are_yarns():
    """The program's and the reference's own, and by hand at the
    published sizes: pairs under 10 turn as published, pairs past 23 a
    32nd as fast, scale 192^-1/2 x 1.3466^2."""
    np.testing.assert_allclose(
        ax.yarn_inv_freq(TINY),
        np.asarray(reference.inv_freq(family.reference_config(TINY))),
        rtol=1e-6)
    cfg = ax.AXK1Config()
    f = ax.yarn_inv_freq(cfg)
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(f[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(f[23:], plain[23:] / 32, rtol=1e-6)
    assert np.all(np.diff(f) < 0)
    mid = f[16] / plain[16]
    assert 1 / 32 < mid < 1
    assert round((0.1 * np.log(32) + 1), 4) == 1.3466
    assert cfg.sm_scale == pytest.approx(192 ** -0.5 * 1.3466 ** 2, rel=1e-4)


class _Recording(InferenceEngine):
    """The engine, its sampler also handing out the logits it samples
    from (in dispatch order: the host reads every dispatch's tokens)."""

    seen = None

    def _sample_tokens(self, logits, keys, temps):
        jax.debug.callback(
            lambda l: self.seen.append(np.asarray(l)), logits,
            ordered=True)
        return super()._sample_tokens(logits, keys, temps)


def _serve(cfg, params, prompts, new_tokens, dtype=jnp.float32,
           reader="pallas"):
    """[(tokens served, their logits rows)] a prompt."""
    inference = {**INFERENCE, "paged_kv": {**INFERENCE["paged_kv"],
                                           "attn_kernel": reader}}
    engine = _Recording(cfg, params, inference, dtype=dtype)
    engine.seen = []
    out = []
    for prompt in prompts:
        engine.seen.clear()
        uid = engine.submit(Request(prompt=prompt,
                                    max_new_tokens=new_tokens,
                                    temperature=0.0, seed=0, eos_id=None))
        done = {f.uid: f for f in engine.run()}[uid]
        # one request at a time in slot 0: row 0 of every dispatch
        out.append((done.tokens, [rows[0] for rows in engine.seen]))
    engine.close()
    return out


PROMPTS = [list(np.random.RandomState(0).randint(0, 128, n))
           for n in (21, 5)]


@pytest.mark.parametrize("reader", ["pallas", "gather"])
def test_prefill_then_decode_through_the_latent_pool_equal_the_reference(
        model, reader):
    """A prompt through a prefill bucket (expanded attention over its
    own rows, the latent rows written to the pages), then every decode
    step through the pool (absorbed through the Pallas reader, or
    expanded over the gathered stripe): each dispatch's logits are the
    reference's full forward's at that position. Float32 against
    float32, so 3e-3 is sums in another order; the bfloat16 engine
    below has to FAIL it."""
    cfg, params, ref = model
    with jax.default_matmul_precision("highest"):
        served = _serve(cfg, params, PROMPTS, 9, reader=reader)
    for prompt, (tokens, logits) in zip(PROMPTS, served):
        assert len(tokens) == 9 and len(logits) == 9
        seq = prompt + tokens
        want = np.asarray(ref(params, jnp.asarray([seq], jnp.int32)))[0]
        for j, row in enumerate(logits):
            at = len(prompt) - 1 + j
            np.testing.assert_allclose(row, want[at], atol=3e-3)
            assert tokens[j] == int(want[at].argmax())


def test_a_bfloat16_engine_fails_the_float32_tolerance_and_holds_its_own(
        model):
    """The same comparison with the engine computing in bfloat16: 3e-3
    refuses it (a float32 tolerance that bfloat16 passed would hold
    nothing). Its logits (rms 1.6 at these loud tiny weights) lie 0.018
    rms from the reference's, the worst of them 0.074: the reference's
    own with bfloat16 products lie 0.015 away, so that is bfloat16's
    eight bits and no more. With float8 products the reference lies
    0.61 away, with the latent row in float8 0.30: the bound of 0.05 rms
    stands between, three times over the one and six times under the
    nearer other."""
    cfg, params, ref = model
    served = _serve(cfg, params, PROMPTS[:1], 9, dtype=jnp.bfloat16)
    tokens, logits = served[0]
    seq = PROMPTS[0] + tokens
    ids = jnp.asarray([seq], jnp.int32)
    want = np.asarray(ref(params, ids))[0]
    got = np.stack(logits)
    at = len(PROMPTS[0]) - 1 + np.arange(9)
    worst = np.abs(got - want[at]).max()
    assert 3e-3 < worst < 0.25
    rms = np.sqrt(np.mean((got - want[at]) ** 2))
    assert rms < 0.05
    for lower in (dict(products=jnp.float8_e5m2),
                  dict(state_dtype=jnp.float8_e5m2)):
        low = np.asarray(jax.jit(family.reference_logits(
            cfg, **lower))(params, ids))[0]
        assert np.sqrt(np.mean((low[at] - want[at]) ** 2)) > 0.2, lower


def _one_layer_rows(cfg, params, ids, reader, start=None, pool=None):
    """A served call on ``ids`` (B, S) from positions ``start``."""
    B, S = ids.shape
    spec = paged_spec_for(cfg, 20, 16, 64, dtype=jnp.float32)
    if pool is None:
        pool = init_paged_kv_cache(spec)
    tables = jnp.asarray(np.arange(1, 1 + B * 4).reshape(B, 4), jnp.int32)
    start = jnp.zeros((B,), jnp.int32) if start is None else start
    with jax.default_matmul_precision("highest"):
        logits, pool = ax.axk1_forward(
            params, cfg, ids, dtype=jnp.float32, kv_cache=pool,
            cache_position=start, block_tables=tables,
            paged_attn_kernel=reader,
            lengths=jnp.full((B,), S, jnp.int32))
    return logits, pool


def test_absorbed_equals_expanded_on_the_same_rows(model):
    """One decode step over a pool that a prefill wrote: the Pallas
    reader in the latent's space (no key or value of a cached token
    formed) against the stripe reader that expands every gathered row.
    The same function reassociated, float32: 1e-4."""
    cfg, params, _ = model
    ids = jax.random.randint(jax.random.PRNGKey(5), (2, 32), 0, 128)
    _, pool = _one_layer_rows(cfg, params, ids, "pallas")
    step = jax.random.randint(jax.random.PRNGKey(6), (2, 1), 0, 128)
    at = jnp.asarray([32, 32], jnp.int32)
    absorbed, pool_a = _one_layer_rows(cfg, params, step, "pallas", at, pool)
    expanded, pool_e = _one_layer_rows(cfg, params, step, "gather", at, pool)
    assert float(jnp.std(expanded)) > 0.3
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(expanded),
                               atol=1e-4)
    # both wrote the step's row; the layers past the first see inputs
    # that differ by the readers' rounding
    np.testing.assert_allclose(np.asarray(pool_a[0]), np.asarray(pool_e[0]),
                               atol=1e-4)


def test_the_stripe_reader_of_a_later_chunk_equals_own_keys(model):
    """A prompt in two calls, the second starting at position 16 and
    reading the first's rows back from the pool (the stripe reader: the
    reader of everything that does not start at 0), against one call
    that attends to its own rows."""
    cfg, params, _ = model
    ids = jax.random.randint(jax.random.PRNGKey(7), (2, 32), 0, 128)
    whole, pool_w = _one_layer_rows(cfg, params, ids, "pallas")
    _, pool = _one_layer_rows(cfg, params, ids[:, :16], "gather")
    later, pool = _one_layer_rows(cfg, params, ids[:, 16:], "gather",
                                  jnp.asarray([16, 16], jnp.int32), pool)
    np.testing.assert_allclose(np.asarray(later), np.asarray(whole),
                               atol=1e-4)
    # the pool's pages of the rows hold the same latent rows either way
    np.testing.assert_allclose(np.asarray(pool[0][:, 1:9]),
                               np.asarray(pool_w[0][:, 1:9]), atol=1e-4)


def test_the_latent_reader_equals_its_dense_oracle():
    """The kernel alone (interpret mode), rows of 3, 100 and 191 live
    tokens over a shuffled table, both layers of the pool."""
    rs = np.random.RandomState(0)
    pool = jnp.asarray(rs.randn(2, 40, 16, 128), jnp.float32)
    q = jnp.asarray(rs.randn(3, 4, 128), jnp.float32)
    tables = jnp.asarray(rs.permutation(np.arange(1, 40))[:36].reshape(
        3, 12), jnp.int32)
    pos = jnp.asarray([2, 99, 190], jnp.int32)
    for layer in (0, 1):
        got = latent_decode_attention(q, pool, tables, pos, 0.3, 96,
                                      layer=layer)
        want = latent_decode_reference(q, pool, tables, pos, 0.3, 96,
                                       layer=layer)
        assert got.shape == (3, 4, 96)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)


def _route_by_hand(p, top_k, n_group, topk_group, scale):
    """The group-limited choice for ONE token, loops written out; ties
    to the lower index."""
    e = len(p)
    per = e // n_group
    scores = []
    for g in range(n_group):
        members = sorted(p[g * per:(g + 1) * per], reverse=True)
        scores.append(members[0] + members[1])
    kept = sorted(range(n_group), key=lambda g: (-scores[g], g))[:topk_group]
    allowed = [i for i in range(e) if i // per in kept]
    chosen = sorted(allowed, key=lambda i: (-p[i], i))[:top_k]
    total = sum(p[i] for i in chosen)
    return chosen, [p[i] / total * scale for i in chosen], kept


@pytest.mark.parametrize("case", ["random", "ties"])
def test_route_group_limited_equals_a_loop_written_out(case):
    """8 groups of 4, the 4 best groups by their two largest scores, the
    top 4 inside them, renormalised times 2.5. ``ties``: logits from a
    grid of three values, so that groups tie on their sums and experts
    tie inside a group; the lower index wins both."""
    rs = np.random.RandomState(1)
    hidden, e = 16, 32
    if case == "random":
        x = jnp.asarray(rs.randn(24, hidden), jnp.float32)
        w = jnp.asarray(rs.randn(hidden, e), jnp.float32)
    else:
        # x picks one row of w: the logits ARE the grid's values
        x = jnp.eye(hidden, dtype=jnp.float32)
        w = jnp.asarray(rs.randint(-1, 2, (hidden, e)), jnp.float32)
    idx, wts, p, kept = moe.route_group_limited(x, w, 4, 8, 4, 2.5)
    p_host = np.asarray(p, np.float64)
    tied = 0
    for t in range(x.shape[0]):
        chosen, weights, groups = _route_by_hand(list(p_host[t]), 4, 8, 4,
                                                 2.5)
        assert list(np.asarray(idx[t])) == chosen
        assert sorted(np.asarray(kept[t])) == sorted(groups)
        np.testing.assert_allclose(np.asarray(wts[t]), weights, rtol=1e-5)
        tied += len(set(p_host[t][chosen])) < 4
    np.testing.assert_allclose(np.asarray(wts).sum(-1), 2.5, rtol=1e-5)
    assert np.all((p_host > 0) & (p_host < 1))
    if case == "ties":
        assert tied > 8


def test_the_reference_routes_as_the_program_does(model):
    cfg, params, _ = model
    h2 = jax.random.normal(jax.random.PRNGKey(8), (40, cfg.hidden_size))
    router = params["h_1"]["router"]
    idx, w, _, kept = moe.route_group_limited(
        h2, router, cfg.experts_per_token, cfg.n_group, cfg.topk_group,
        cfg.routed_scaling_factor)
    spread, ref_idx, ref_kept = reference.route(
        h2[None], router, family.reference_config(cfg))
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ref_idx[0]))
    np.testing.assert_array_equal(np.sort(np.asarray(kept), -1),
                                  np.sort(np.asarray(ref_kept[0]), -1))
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(spread[0]), np.asarray(idx), -1),
        np.asarray(w), rtol=1e-5)


def _layer_case(cfg, seed, tokens=48):
    h2 = jax.random.normal(jax.random.PRNGKey(seed),
                           (tokens, cfg.hidden_size), jnp.float32)
    ks = jax.random.split(jax.random.PRNGKey(seed + 1), 7)
    f, e = cfg.moe_intermediate_size, cfg.num_experts
    n = lambda k, shape: jax.random.normal(k, shape, jnp.float32) * 0.2
    whole = {"w_gate": n(ks[0], (e, cfg.hidden_size, f)),
             "w_up": n(ks[1], (e, cfg.hidden_size, f)),
             "w_down": n(ks[2], (e, f, cfg.hidden_size))}
    shared = {"w_gate": n(ks[3], (cfg.hidden_size, f)),
              "w_up": n(ks[4], (cfg.hidden_size, f)),
              "w_down": n(ks[5], (f, cfg.hidden_size))}
    return h2, n(ks[6], (cfg.hidden_size, e)), whole, shared


@pytest.mark.parametrize("served", ["every_row", "served"])
def test_the_sixteen_shares_and_one_shared_expert_add_up_to_the_uncut_layer(
        served):
    """The expert parts of all the shares (16 chips of 2 experts here,
    half a router group each, as the cell's 12 of 192), plus the shared
    expert counted ONCE, are the reference's whole layer."""
    cfg = TINY
    h2, router, whole, shared = _layer_case(cfg, 11)
    ref_cfg = family.reference_config(cfg)
    with jax.default_matmul_precision("highest"):
        weights, _, _ = reference.route(h2[None], router, ref_cfg)
        want = reference.experts(h2[None], weights, whole,
                                 (0, cfg.num_experts), shared)[0]
        idx, p, _, _ = moe.route_group_limited(
            h2, router, cfg.experts_per_token, cfg.n_group, cfg.topk_group,
            cfg.routed_scaling_factor)
        parts = []
        for first in range(0, cfg.num_experts, 2):
            mine = jax.tree_util.tree_map(lambda a: a[first:first + 2],
                                          whole)
            if served == "every_row":
                y, _ = moe.held_experts_every_row(
                    h2, idx, p, mine, (first, 2), jax.nn.silu)
            else:
                y, _, _ = moe.served_experts(
                    h2, idx, p, mine, (first, 2), cfg.num_experts,
                    jax.nn.silu)
            parts.append(y)
        once = reference.experts(
            h2[None], jnp.zeros_like(weights), whole, (0, 1), shared)[0]
    assert len(parts) == 16
    assert float(jnp.abs(parts[0]).max()) > 1e-3      # a share is a part
    np.testing.assert_allclose(np.asarray(sum(parts) + once),
                               np.asarray(want), atol=2e-4)


def test_the_pool_holds_one_latent_row_a_token_a_layer(model):
    """(512 + 64) values at 640 lanes x 2 B a token a layer, ONE leaf:
    the engine's accounting, the family's `cache_bytes` and the
    arithmetic agree, and none is the per-head count."""
    assert latent_row_lanes(512, 64) == 640
    assert latent_row_lanes(32, 8) == 128
    published = ax.AXK1Config(num_layers=5)
    spec = paged_spec_for(published, 9, 16, 6144)
    assert isinstance(spec, LatentPoolSpec)
    assert spec.shape == (5, 9, 16, 640)
    assert (spec.kv_heads, spec.head_dim, spec.quantized) == (1, 640, False)
    assert paged_kv_bytes(spec) == 9 * 16 * 5 * 640 * 2
    per_head = 64 * (192 + 128) * 2
    assert 640 * 2 * 32 == per_head
    with pytest.raises(ValueError, match="no int8 form"):
        paged_spec_for(published, 9, 16, 6144, dtype=jnp.int8)
    cfg, params, _ = model
    engine = InferenceEngine(cfg, params, INFERENCE)
    (pool,) = engine._cache
    assert pool.shape == (3, 14, 16, 128) and pool.dtype == jnp.bfloat16
    held = family.cache_bytes(cfg, engine)
    assert held == {"per_token": 3 * 128 * 2, "per_slot": 0}
    assert engine._kv_bpt == held["per_token"]
    assert engine._page_bytes == 16 * held["per_token"]
    engine.close()
    # a family with keys and values keeps its pair
    assert len(init_paged_kv_cache(
        paged_spec_for(GPT2Config(), 8, 16, 64))) == 2


def test_a_decode_writes_the_row_after_the_norm_and_the_rotation(model):
    """What a page holds of a token: [RMSNorm(c) | RoPE(k_r) | zeros],
    held against the reference's own arithmetic for layer 0."""
    cfg, params, _ = model
    ids = jax.random.randint(jax.random.PRNGKey(9), (1, 16), 0, 128)
    _, (pool,) = _one_layer_rows(cfg, params, ids, "pallas")
    ref_cfg = family.reference_config(cfg)
    ap = params["h_0"]["attn"]
    with jax.default_matmul_precision("highest"):
        x = params["tok_emb"].astype(jnp.float32)[ids]
        h = reference._rms(x, params["h_0"]["ln_1"]["w"], 1e-6)
        kv = reference._mm(h, ap["wkv_a"])
        c = reference._rms(kv[..., :32], ap["kv_norm"], 1e-6)
        k_r = reference._rotate(kv[..., 32:], reference.inv_freq(ref_cfg))
    row = np.asarray(pool[0, 1])                     # page 1: tokens 0-15
    np.testing.assert_allclose(row[:, :32], np.asarray(c[0]), atol=1e-5)
    np.testing.assert_allclose(row[:, 32:40], np.asarray(k_r[0]), atol=1e-5)
    assert not row[:, 40:].any()
    assert np.abs(row[1:, 32:40] - np.asarray(kv[0, 1:, 32:])).max() > 1e-2


def test_the_planted_faults_move_the_reference(model):
    """The controls' knobs: each planted fault and the float8 products
    move the logits more than twice as far as bfloat16 products do; None
    is the reference itself, and an unknown fault is refused."""
    cfg, params, ref = model
    ids = jnp.asarray(np.random.RandomState(4).randint(0, 128, (1, 40)))
    plain = np.asarray(ref(params, ids))
    rms = lambda **lower: float(np.sqrt(np.mean((np.asarray(jax.jit(
        family.reference_logits(cfg, **lower))(params, ids)) - plain) ** 2)))
    noise = rms(products=jnp.bfloat16)
    assert 0 < noise < 0.05 * np.sqrt(np.mean(plain ** 2))
    assert rms(products=jnp.float8_e5m2) > 8 * noise
    for lower in family.PLANTED.values():
        lower = {k: jnp.dtype(v) if k == "state_dtype" else v
                 for k, v in lower.items()}
        assert rms(**lower) > 2 * noise, lower
    for lower in family.SHOWN.values():     # the expert half's: they move
        assert rms(**lower) > 0, lower      # it, by what its share is worth
    assert 0 < rms(state_dtype=jnp.bfloat16) < 3 * noise
    assert rms(round_to=jnp.bfloat16) == 0.0      # no recurrence to round
    np.testing.assert_array_equal(np.asarray(ref(params, ids)), plain)
    with pytest.raises(ValueError, match="no planted fault"):
        family.reference_logits(cfg, fault="other")(params, ids)


REFUSED = {
    "prefix_cache": {"paged_kv": {"num_pages": 14, "prefix_cache": True}},
    "dense_cache": {"paged_kv": {"enabled": False}},
    "chunked_prefill": {"chunked_prefill": {"enabled": True,
                                            "chunk_tokens": 16}},
    "spec_decode": {"spec_decode": {"enabled": True, "k": 2}},
    "disagg": {"disagg": {"enabled": True}},
    "int8_pool": {"paged_kv": {"num_pages": 14, "prefix_cache": False,
                               "kv_dtype": "int8"}},
    "quantized_weights": {"quantize_weights": "int8"},
    "mesh": {"mesh": {"axes": {"model": 2}}},
}


@pytest.mark.parametrize("feature", sorted(REFUSED))
def test_the_family_refuses_what_latent_rows_cannot_follow(model, feature):
    cfg, params, _ = model
    with pytest.raises(ValueError, match="latent rows"):
        InferenceEngine(cfg, params, {**INFERENCE, **REFUSED[feature]})


def test_a_request_of_the_family_cannot_be_exported_or_imported(model):
    cfg, params, _ = model
    engine = InferenceEngine(cfg, params, INFERENCE)
    for call in (lambda: engine.export_request(0),
                 lambda: engine.import_request(None),
                 engine.warm_migration):
        with pytest.raises(NotImplementedError, match="latent rows"):
            call()
    engine.close()


def test_the_decode_span_carries_the_experts_and_the_groups_counters(
        model, monkeypatch):
    """active, assignments, landed, fullest, held and group_rows on
    `serve/decode`: the counts of the last decode READ when the dispatch
    was planned (its tokens are taken after the next dispatch has been
    issued, so two steps before), read with the sampled tokens; the
    prefill span carries its expert turns' rows."""
    cfg, params, _ = model
    seen, prefills = [], []
    plain = InferenceEngine._span

    def recording(self, name, **args):
        if name == "serve/decode":
            seen.append(args)
        if name == "serve/prefill":
            prefills.append(args)
        return plain(self, name, **args)

    monkeypatch.setattr(InferenceEngine, "_span", recording)
    engine = InferenceEngine(cfg, params, INFERENCE)
    rs = np.random.RandomState(2)
    engine.generate([list(rs.randint(0, 128, 9)) for _ in range(2)],
                    max_new_tokens=5, temperature=0.0)
    worked, static = engine._moe_prefill_rows
    engine.close()
    assert 0 < worked <= static
    assert len(seen) >= 4
    layers = len(cfg.expert_layers)
    for args in seen:
        assert {"active", "assignments", "landed", "fullest", "held",
                "group_rows"} <= set(args) or args in seen[:2]
        assert args["held"] == 2
        assert args["assignments"] == args["active"] * 4 * layers
        assert 0 <= args["fullest"] <= args["landed"] <= 2 * 4 * layers
    assert seen[0]["landed"] == 0               # nothing decoded before
    # a row keeps 4 of 8 groups: about half the rows keep the held one's
    assert all(0 <= a["group_rows"] <= 2 * layers for a in seen[2:])
    assert any(a["group_rows"] > 0 for a in seen[2:])
    assert all(a["own_key_tokens"] == a["real_tokens"] for a in prefills)


def test_the_new_names_are_registered():
    assert {"mla_q", "mla_latent", "mla_absorb", "mla_expand",
            "mla_out"} <= set(spans.DEVICE_SCOPES)


def test_the_cut_counts_3491m_parameters():
    """The configuration file's sizes through the family: the
    arithmetic of docs/axk1.md."""
    import json
    path = os.path.join(os.path.dirname(__file__), "..", "..",
                        "benchmarks", "configs", "ax-k1.json")
    with open(path) as f:
        config = json.load(f)
    cfg = family.serve_model_of(config)
    mixer, dense, around, expert, table = ax.axk1_param_count(cfg)
    norms = 1536 + 512 + 2 * 7168
    assert (round((mixer - norms) / 1e6, 2), round(dense / 1e6, 2),
            round(around / 1e6, 2), round(expert / 1e6, 2)) == \
        (101.12, 396.36, 45.42, 44.04)
    assert round(table / 1e6, 1) == 293.6
    assert round(family.param_count(cfg) / 1e6) == 3491
    shapes = jax.eval_shape(
        lambda: ax.init_axk1_params(cfg, jax.random.PRNGKey(0)))
    leaves = jax.tree_util.tree_leaves(shapes)
    assert sum(int(np.prod(a.shape)) for a in leaves) == \
        family.param_count(cfg)
    held = sum(a.size * a.dtype.itemsize for a in leaves)
    assert 6.98e9 < held < 7.0e9                # bfloat16 as held
    assert cfg.expert_layers == (1, 2, 3, 4) and cfg.held == (0, 12)
    assert cfg.expert_counters == (8 * 4, 12)
    inference = config["serve"]["inference"]
    spec = paged_spec_for(cfg, inference["paged_kv"]["num_pages"],
                          inference["paged_kv"]["page_size"],
                          inference["max_seq_len"])
    assert (spec.num_pages - 1) * spec.page_size == 720896
    assert 4.61e9 < paged_kv_bytes(spec) < 4.62e9
    # the whole model at the published sizes: 519B
    whole = (61 * mixer + dense + 60 * (around + 192 * expert)
             + 2 * 163840 * 7168)
    assert 515e9 < whole < 523e9
    # every number of the catalog's row is in the file under its key
    row = next(json.loads(ln) for ln in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")
        if '"A.X-K1"' in ln) if os.path.exists(
        "/opt/skills/guides/model-configs/architectures.jsonl") else None
    if row is not None:
        for key, value in row["config"].items():
            if key not in config["reduced"]:
                assert config[key] == value, key
