"""Kimi-Linear-style hybrid SERVED IN CHUNKS (models/kimi_linear.py):
delta-rule layers that carry their slot's state and tail across chunk
boundaries, latent layers that read the prefix earlier chunks wrote back
from the pool a block at a time (ops/attention/page_pool.py), ONE cache
tree of the latent pool beside the state pools (inference/kv_cache.py),
the sigmoid router with its correction bias (ops/moe.py) and what the
engine refuses for the family, against the plain float32 reference
(benchmarks/reference/kimi_linear_reference.py) on seeded weights at
tiny sizes on the CPU."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu  # noqa: F401
from deepspeed_tpu.inference import InferenceEngine
from deepspeed_tpu.inference.kv_cache import LatentStateCache
from deepspeed_tpu.inference.scheduler import Request
from deepspeed_tpu.models import kimi_linear as kl
from deepspeed_tpu.ops import kda, moe
from deepspeed_tpu.ops.attention import page_pool
from deepspeed_tpu.profiling import spans

BENCH = os.path.join(os.path.dirname(__file__), "..", "..", "benchmarks")
sys.path.insert(0, BENCH)
from families import kimi_linear as family  # noqa: E402
from reference import kimi_linear_reference as reference  # noqa: E402

CHUNK = 16
TINY = kl.KimiLinearConfig(
    vocab_size=512, hidden_size=64, num_layers=5, latent_layers=(2, 4),
    num_heads=4, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, kda_num_heads=4, kda_head_dim=16, kda_gate_rank=8,
    intermediate_size=96, moe_intermediate_size=32, num_experts=16,
    experts_per_token=4, max_position_embeddings=256,
    # wider than the published 0.02, which at hidden 64 leaves every
    # logit within 0.01 of every other
    initializer_range=0.2, router_bias_std=0.2, experts_held=(0, 4),
    vocab_held=(0, 128))
INFERENCE = {"max_batch_size": 3, "batch_buckets": [1, 2],
             "prompt_buckets": [CHUNK], "max_seq_len": 112,
             "chunked_prefill": {"enabled": True, "chunk_tokens": CHUNK},
             "paged_kv": {"num_pages": 30, "prefix_cache": False}}
# 1, 2 and 5 chunks; a last chunk of ONE token; a prompt that ends on a
# chunk boundary; one that fits the prompt bucket and is no chunk at all
PROMPTS = (5, CHUNK, CHUNK + 1, 2 * CHUNK, 5 * CHUNK - 7, 3 * CHUNK + 1)


@pytest.fixture(scope="module")
def model():
    params = kl.init_kimi_linear_params(TINY, jax.random.PRNGKey(3),
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


def _serve(engine, prompts, new=5):
    reqs = [Request(prompt=p, max_new_tokens=new, temperature=0.0, seed=i,
                    eos_id=None) for i, p in enumerate(prompts)]
    uids = [engine.submit(r) for r in reqs]
    done = {f.uid: f for f in engine.run()}
    return [done[u] for u in uids]


def test_plain_forward_equals_the_reference(model):
    cfg, params, ref = model
    ids = jax.random.randint(jax.random.PRNGKey(4), (2, 40), 0, 128)
    got = kl.kimi_linear_forward(params, cfg, ids, dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref(params, ids)),
                               atol=2e-4)


@pytest.mark.parametrize("reader", ["pallas", "gather"])
def test_chunks_then_decode_through_the_cache_equal_the_reference(model,
                                                                  reader):
    """Prompts of 1, 2 and 5 chunks (a last chunk of one token, a prompt
    that ends on a chunk boundary) prefilled through chunk dispatches
    that carry the state and read the latent prefix, then decoded
    through the cache: every served token is the ONE full forward's."""
    cfg, params, ref = model
    inference = {**INFERENCE, "paged_kv": {**INFERENCE["paged_kv"],
                                           "attn_kernel": reader}}
    engine = InferenceEngine(cfg, params, inference, dtype=jnp.float32)
    assert engine._decode_attn_path == reader
    assert isinstance(engine._cache, LatentStateCache)
    finished = _serve(engine, _prompts(PROMPTS))
    chunks = engine._chunk_dispatches
    engine.close()
    assert all(f.finish_reason == "length" and len(f.tokens) == 5
               for f in finished)
    # every prompt past the bucket went in chunks (rows may share one)
    assert chunks >= 5
    assert _gaps(ref, params, finished) < 2e-3


def _until_first_token(engine, prompt, whole=False):
    """(the first token, the slot's state rows, its tail rows) as the
    prefill left them: before any decode has touched the slot."""
    engine.submit(Request(prompt=prompt, max_new_tokens=4, temperature=0.0,
                          seed=7, eos_id=None))
    if whole:
        engine._prefill_phase()         # a step would decode behind it
    while engine.scheduler.slots[0] is None \
            or not engine.scheduler.slots[0].issued:
        engine.step()                   # decode first, then ONE chunk
    # the first token has been asked for and no decode issued behind it:
    # its read, still waiting, is taken here
    engine.debug_state()
    cache = engine._cache
    return (engine.scheduler.slots[0].tokens[0],
            np.asarray(cache.state[:, 0]), np.asarray(cache.tails[:, 0]))


def test_chunked_prefill_equals_the_whole_prompts(model):
    """The same prompt through five chunks and through ONE bucket of 80:
    the first token is the same token, and the slot's state and tail
    rows agree to float32 rounding."""
    cfg, params, _ = model
    (prompt,) = _prompts([5 * CHUNK - 7], seed=5)
    chunked = InferenceEngine(cfg, params, INFERENCE, dtype=jnp.float32)
    whole = InferenceEngine(
        cfg, params, {**INFERENCE, "prompt_buckets": [5 * CHUNK],
                      "chunked_prefill": {"enabled": False}},
        dtype=jnp.float32)
    tok_c, state_c, tail_c = _until_first_token(chunked, prompt)
    tok_w, state_w, tail_w = _until_first_token(whole, prompt, whole=True)
    assert chunked._chunk_dispatches == 5 and whole._chunk_dispatches == 0
    chunked.close()
    whole.close()
    assert tok_c == tok_w
    assert np.abs(state_w).max() > 1e-3
    np.testing.assert_allclose(state_c, state_w, rtol=1e-4, atol=5e-6)
    np.testing.assert_allclose(tail_c, tail_w, rtol=1e-5, atol=5e-5)


def test_a_decode_between_two_chunks_leaves_the_slots_state_as_it_is(model):
    """A slot in the middle of its prefill rides the decode dispatch as
    an inactive row: its state row and tail come out bit for bit."""
    cfg, params, ref = model
    short, long_ = _prompts([5, 4 * CHUNK + 3], seed=6)
    engine = InferenceEngine(cfg, params, INFERENCE, dtype=jnp.float32)
    seen = []
    decode = engine._decode_phase

    def rows():
        return (np.asarray(engine._cache.state[:, 1]),
                np.asarray(engine._cache.tails[:, 1]))

    def watched_decode():
        mid = engine.scheduler.slots[1] is not None and \
            engine.scheduler.slots[1].chunk_pos not in (None, 0)
        before = rows()
        ran = decode()
        if mid and ran:
            seen.append((before, rows()))
        return ran

    engine._decode_phase = watched_decode
    finished = _serve(engine, [short, long_], new=8)
    engine.close()
    assert len(seen) >= 3           # a decode between every two chunks
    for (state_0, tail_0), (state_1, tail_1) in seen:
        assert np.abs(state_0).max() > 0
        np.testing.assert_array_equal(state_0, state_1)
        np.testing.assert_array_equal(tail_0, tail_1)
    assert _gaps(ref, params, finished) < 2e-3


def test_a_reused_slot_never_sees_its_predecessors_state(model):
    cfg, params, ref = model
    first, second = _prompts([2 * CHUNK + 5, 3 * CHUNK - 2], seed=8)
    one_slot = {**INFERENCE, "max_batch_size": 1, "batch_buckets": [1]}
    engine = InferenceEngine(cfg, params, one_slot, dtype=jnp.float32)
    both = _serve(engine, [first, second])
    engine.close()
    fresh = InferenceEngine(cfg, params, one_slot, dtype=jnp.float32)
    (alone,) = _serve(fresh, [second])
    fresh.close()
    assert both[1].tokens == alone.tokens
    assert _gaps(ref, params, both) < 2e-3


@pytest.mark.parametrize("padding", [0, 136])
def test_the_chunk_scan_from_a_carried_state_equals_the_sequential_form(
        padding):
    """``kda_chunk_scan`` from a NON-ZERO state, in two calls that hand
    the state over, against ``kda_sequential`` over the whole. With
    ``padding`` the second call is a bucket that much longer than its
    rows (noise past their lengths): the carried state goes through the
    64-token turns that are skipped as through the live ones."""
    rs = np.random.RandomState(2)
    B, S, H, d = 2, 96, 3, 16
    r = lambda *s: jnp.asarray(rs.randn(*s), jnp.float32)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q, k, v = unit(r(B, S, H, d)) * d ** -0.5, unit(r(B, S, H, d)), \
        r(B, S, H, d)
    g = -jnp.abs(r(B, S, H, d)) * 0.1
    beta = jax.nn.sigmoid(r(B, S, H))
    start = r(B, H, d, d) * 0.3
    want_o, want_s = kda.kda_sequential(q, k, v, g, beta, start)
    cut = 40                        # no multiple of the scan's own chunk
    o_1, s_1 = kda.kda_chunk_scan(q[:, :cut], k[:, :cut], v[:, :cut],
                                  g[:, :cut], beta[:, :cut], start)
    lengths = jnp.asarray([S - cut, S - cut - 9])
    tail = lambda a: jnp.concatenate(
        [a[:, cut:], jnp.abs(r(B, padding, *a.shape[2:]))], axis=1)
    o_2, s_2 = kda.kda_chunk_scan(tail(q), tail(k), tail(v), -tail(-g),
                                  tail(beta), s_1, lengths)
    assert np.isfinite(np.asarray(o_2)).all()
    np.testing.assert_allclose(np.asarray(o_1), np.asarray(want_o[:, :cut]),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(o_2[0, :S - cut]),
                               np.asarray(want_o[0, cut:]), atol=2e-5)
    np.testing.assert_allclose(np.asarray(s_2[0]), np.asarray(want_s[0]),
                               atol=2e-5)
    # the second row stops nine tokens early: its state is that of there
    _, early = kda.kda_sequential(q[1:, :S - 9], k[1:, :S - 9],
                                  v[1:, :S - 9], g[1:, :S - 9],
                                  beta[1:, :S - 9], start[1:])
    np.testing.assert_allclose(np.asarray(s_2[1]), np.asarray(early[0]),
                               atol=2e-5)


def _chunk_case(cfg, params, reader, starts=(48, 0), real=(16, 11)):
    """One chunk dispatch of two rows over a pool an earlier forward has
    filled to ``starts``: (logits, cache) under ``reader``."""
    from deepspeed_tpu.inference.kv_cache import (init_paged_kv_cache,
                                                  init_state_pool,
                                                  paged_spec_for,
                                                  state_pool_spec_for)
    spec = paged_spec_for(cfg, 20, 16, 112, dtype=jnp.float32)
    state = state_pool_spec_for(cfg, 3, tail_dtype=jnp.float32)
    cache = LatentStateCache(*init_paged_kv_cache(spec),
                             *init_state_pool(state))
    tables = np.zeros((2, spec.pages_per_seq), np.int32)
    tables[0, :5], tables[1, :5] = range(1, 6), range(6, 11)
    rs = np.random.RandomState(9)
    run = lambda ids, pos, lengths, cache, how: kl.kimi_linear_forward(
        params, cfg, jnp.asarray(ids), dtype=jnp.float32, kv_cache=cache,
        cache_position=jnp.asarray(pos, jnp.int32),
        block_tables=jnp.asarray(tables), paged_attn_kernel=how,
        lengths=jnp.asarray(lengths, jnp.int32),
        slots=jnp.asarray([0, 1], jnp.int32))
    # the prefix, written by the stripe oracle in ONE call of 48 rows
    _, cache = run(rs.randint(0, 128, (2, 48)), [0, 0],
                   [max(starts[0], 1), max(starts[1], 1)], cache, "gather")
    return run(rs.randint(0, 128, (2, 16)), starts, real, cache, reader)


def test_the_prefix_reader_in_blocks_equals_the_stripe_oracle(model):
    """A later chunk (48 rows of prefix: two turns of the walk at 32 a
    block, the second half full) beside a row at position 0 in ONE
    dispatch: the reader in blocks and the float32 stripe oracle give
    the same logits and leave the same rows."""
    cfg, params, _ = model
    got, cache_b = _chunk_case(cfg, params, "pallas")
    want, cache_s = _chunk_case(cfg, params, "gather")
    assert np.abs(np.asarray(want)).max() > 0.1
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-4)
    for a, b in zip(cache_b, cache_s):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)
    # ... and the prefix is READ: without it the logits are others
    alone, _ = _chunk_case(cfg, params, "pallas", starts=(0, 0))
    assert np.abs(np.asarray(alone[0]) - np.asarray(want[0])).max() > 1e-2


def _route_by_hand(p, bias, k, scale):
    ranked = sorted(range(len(p)), key=lambda i: (-(p[i] + bias[i]), i))[:k]
    total = sum(p[i] for i in ranked)
    return ranked, [p[i] / total * scale for i in ranked]


def test_the_router_chooses_by_score_plus_bias_and_weighs_by_score():
    rs = np.random.RandomState(1)
    x = jnp.asarray(rs.randn(24, 16), jnp.float32)
    w = jnp.asarray(rs.randn(16, 32), jnp.float32)
    bias = jnp.asarray(rs.randn(32) * 0.3, jnp.float32)
    idx, wts, p, _ = moe.route_group_limited(x, w, 4, 1, 1, 2.446,
                                             bias=bias)
    plain, _, _, _ = moe.route_group_limited(x, w, 4, 1, 1, 2.446)
    p_host = np.asarray(p, np.float64)
    for t in range(24):
        chosen, weights = _route_by_hand(list(p_host[t]),
                                         list(np.asarray(bias)), 4, 2.446)
        assert list(np.asarray(idx[t])) == chosen
        np.testing.assert_allclose(np.asarray(wts[t]), weights, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(wts).sum(-1), 2.446, rtol=1e-5)
    # the bias moves the choice; without one the function is what it was
    assert (np.asarray(idx) != np.asarray(plain)).any()
    zero, _, _, _ = moe.route_group_limited(x, w, 4, 1, 1, 2.446,
                                            bias=jnp.zeros(32))
    np.testing.assert_array_equal(np.asarray(zero), np.asarray(plain))


def test_the_reference_routes_as_the_program_does(model):
    cfg, params, _ = model
    h2 = jax.random.normal(jax.random.PRNGKey(8), (40, cfg.hidden_size))
    lp = params["h_1"]
    idx, w, _, _ = moe.route_group_limited(
        h2, lp["router"], cfg.experts_per_token, 1, 1,
        cfg.routed_scaling_factor, bias=lp["router_bias"])
    ref_cfg = family.reference_config(cfg)
    spread, ref_idx = reference.route(h2[None], lp["router"],
                                      lp["router_bias"], ref_cfg)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ref_idx[0]))
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(spread[0]), np.asarray(idx), -1),
        np.asarray(w), rtol=1e-5)
    _, no_bias = reference.route(h2[None], lp["router"], lp["router_bias"],
                                 ref_cfg, fault="router_bias")
    assert (np.asarray(no_bias) != np.asarray(ref_idx)).any()


@pytest.mark.parametrize("served", ["every_row", "served"])
def test_the_eight_shares_and_one_shared_expert_add_up_to_the_uncut_layer(
        served):
    """The expert parts of all the shares (8 chips of 2 experts here, as
    the cell's 32 of 256), plus the shared expert counted ONCE, are the
    reference's whole layer."""
    cfg = TINY
    h2 = jax.random.normal(jax.random.PRNGKey(11), (48, cfg.hidden_size))
    ks = jax.random.split(jax.random.PRNGKey(12), 8)
    f, e = cfg.moe_intermediate_size, cfg.num_experts
    n = lambda k, shape: jax.random.normal(k, shape, jnp.float32) * 0.2
    whole = {"w_gate": n(ks[0], (e, cfg.hidden_size, f)),
             "w_up": n(ks[1], (e, cfg.hidden_size, f)),
             "w_down": n(ks[2], (e, f, cfg.hidden_size))}
    shared = {"w_gate": n(ks[3], (cfg.hidden_size, f)),
              "w_up": n(ks[4], (cfg.hidden_size, f)),
              "w_down": n(ks[5], (f, cfg.hidden_size))}
    router, bias = n(ks[6], (cfg.hidden_size, e)), n(ks[7], (e,))
    ref_cfg = family.reference_config(cfg)
    with jax.default_matmul_precision("highest"):
        weights, _ = reference.route(h2[None], router, bias, ref_cfg)
        want = reference.experts(h2[None], weights, whole, (0, e), shared)[0]
        idx, p, _, _ = moe.route_group_limited(
            h2, router, cfg.experts_per_token, 1, 1,
            cfg.routed_scaling_factor, bias=bias)
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
        once = reference.experts(
            h2[None], jnp.zeros_like(weights), whole, (0, 1), shared)[0]
    assert len(parts) == 8
    assert float(jnp.abs(parts[0]).max()) > 1e-3      # a share is a part
    np.testing.assert_allclose(np.asarray(sum(parts) + once),
                               np.asarray(want), atol=2e-4)


def test_the_planted_faults_move_the_reference(model):
    """The controls' knobs at a chunk of 16: each planted fault and the
    float8 products move the logits more than twice as far as bfloat16
    products do; None is the reference itself."""
    cfg, params, ref = model
    ids = jnp.asarray(np.random.RandomState(4).randint(0, 128, (1, 64)))
    plain = np.asarray(ref(params, ids))
    rms = lambda **lower: float(np.sqrt(np.mean((np.asarray(jax.jit(
        family.reference_logits(cfg, **lower))(params, ids)) - plain) ** 2)))
    noise = rms(products="bfloat16")
    assert 0 < noise < 0.05 * np.sqrt(np.mean(plain ** 2))
    assert rms(products="float8_e5m2") > 8 * noise
    for lower in list(family.PLANTED.values()) + list(family.SHOWN.values()):
        lower = {**lower, **({"chunk": CHUNK} if "chunk" in lower else {})}
        assert rms(**lower) > 2 * noise, lower
    # a fault planted at chunks no prompt reaches changes nothing
    assert rms(fault="chunk_state", chunk=64) < 1e-5
    assert 0 < rms(state_dtype="bfloat16") < 3 * noise
    assert 0 < rms(round_to="bfloat16") < 3 * noise
    with pytest.raises(ValueError, match="no planted fault"):
        family.reference_logits(cfg, fault="other")(params, ids)
    with pytest.raises(ValueError, match="needs `chunk`"):
        family.reference_logits(cfg, fault="chunk_tail")(params, ids)


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
    with pytest.raises(ValueError, match="recurrent state and latent "
                       "rows") as said:
        InferenceEngine(cfg, params, {**INFERENCE, **asked})
    assert named in str(said.value)
    assert "chunked prefill" not in str(said.value)


def test_the_other_hybrids_still_refuse_chunked_prefill():
    from deepspeed_tpu.models import axk1 as ax, solar_open2 as so
    chunked = {"chunked_prefill": {"enabled": True, "chunk_tokens": 16},
               "paged_kv": {"prefix_cache": False}}
    for cfg in (ax.AXK1Config(), so.SolarOpen2Config()):
        with pytest.raises(ValueError, match="chunked prefill"):
            InferenceEngine(cfg, None, chunked)


def test_a_request_of_the_family_cannot_be_exported_or_imported(model):
    cfg, params, _ = model
    engine = InferenceEngine(cfg, params, INFERENCE)
    for call in (lambda: engine.export_request(0),
                 lambda: engine.import_request(None),
                 engine.warm_migration):
        with pytest.raises(NotImplementedError):
            call()
    engine.close()


def test_the_chunk_span_carries_what_a_reader_can_sum(model, monkeypatch):
    """rows, real_tokens, start_tokens, carried_rows, prefix_pairs and
    own_pairs on `serve/chunk`; the decode span the experts' counters."""
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
    _serve(engine, _prompts(lengths, seed=3))
    engine.close()
    assert sum(a["real_tokens"] for a in chunks) == sum(lengths)
    assert sum(a["rows"] for a in chunks) == 4 + 3
    assert sum(a["carried_rows"] for a in chunks) == 3 + 2
    starts = lambda n: range(0, n, CHUNK)
    assert sum(a["start_tokens"] for a in chunks) == sum(
        s for n in lengths for s in starts(n))
    assert sum(a["prefix_pairs"] for a in chunks) == sum(
        s * min(CHUNK, n - s) for n in lengths for s in starts(n))
    assert sum(a["own_pairs"] for a in chunks) == sum(
        min(CHUNK, n - s) ** 2 for n in lengths for s in starts(n))
    assert all(a["batch"] in (1, 2) and a["chunk"] == CHUNK for a in chunks)
    assert any(a["expert_rows_worked"] > 0 for a in chunks[1:])
    layers = len(cfg.expert_layers)
    for args in decodes:
        assert args["held"] == 4
        assert args["assignments"] == args["active"] * 4 * layers


def test_the_new_names_are_registered():
    assert "mla_prefix" in spans.DEVICE_SCOPES
    assert {"serve/chunk", "serve/chunk/build", "serve/chunk/dispatch",
            "serve/chunk/wait"} <= set(spans.HOST_SPANS)


def test_the_cut_counts_2366m_parameters():
    """The configuration file's sizes through the family: the
    arithmetic of docs/kimi_linear.md."""
    with open(os.path.join(BENCH, "configs",
                           "kimi-linear-48b-a3b.json")) as f:
        config = json.load(f)
    cfg = family.serve_model_of(config)
    kda_, latent, dense, around, expert, tables = \
        kl.kimi_linear_param_count(cfg)
    assert (kda_, latent, dense, expert, around - expert) == (
        39_514_272, 29_114_880, 63_700_992, 7_077_888, 590_080)
    assert cfg.recurrent_layers == (0, 1, 2, 4, 5, 6, 8)
    assert cfg.latent_layers == (3, 7) and cfg.held == (0, 32)
    assert family.param_count(cfg) == 2_366_229_344
    shapes = jax.eval_shape(lambda: kl.init_kimi_linear_params(
        cfg, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(a.shape)) for a in
               jax.tree_util.tree_leaves(shapes)) == 2_366_229_344
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    published = dict(hidden_size=2304, num_attention_heads=32,
                     kv_lora_rank=512, qk_nope_head_dim=128,
                     qk_rope_head_dim=64, v_head_dim=128,
                     intermediate_size=9216, moe_intermediate_size=1024,
                     num_experts_per_token=8, routed_scaling_factor=2.446,
                     rms_norm_eps=1e-5, head_dim=72, q_lora_rank=None)
    assert {k: config[k] for k in published} == published
    assert config["linear_attn_config"]["head_dim"] == 128
    assert config["linear_attn_config"]["num_heads"] == 32
    assert config["linear_attn_config"]["short_conv_kernel_size"] == 4
    # a slot's state and a token's latent rows, as docs/kimi_linear.md
    from deepspeed_tpu.inference.kv_cache import (paged_kv_bytes,
                                                  paged_spec_for,
                                                  state_pool_bytes,
                                                  state_pool_spec_for)
    inference = config["serve"]["inference"]
    row = state_pool_spec_for(cfg, 1)
    assert state_pool_bytes(row) == 7 * 32 * 128 * 128 * 4 \
        + 7 * 3 * 12288 * 2
    pages = paged_spec_for(cfg, inference["paged_kv"]["num_pages"], 16,
                           inference["max_seq_len"])
    assert pages.pages_per_seq == 1152 and pages.row_lanes == 640
    assert paged_kv_bytes(pages) == 2 * 49153 * 16 * 640 * 2
