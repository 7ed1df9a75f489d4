"""models/lfm2.py served: gated short convolutions among rotary
grouped-query attention normed a head, a cache tree of pages + tails and
NO state leaf (inference/kv_cache.py), a sigmoid router with a
correction bias and no shared expert, against the plain float32
reference (benchmarks/reference/lfm2_reference.py).
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu  # noqa: F401
from deepspeed_tpu.inference import InferenceEngine
from deepspeed_tpu.inference.kv_cache import (PagedTailCache,
                                              state_pool_bytes,
                                              state_pool_spec_for)
from deepspeed_tpu.inference.scheduler import Request
from deepspeed_tpu.models import lfm2
from deepspeed_tpu.models.served_trunk import _Call
from deepspeed_tpu.profiling import spans

BENCH = os.path.join(os.path.dirname(__file__), "..", "..", "benchmarks")
sys.path.insert(0, BENCH)
from families import lfm2 as family  # noqa: E402
from reference import lfm2_reference as reference  # noqa: E402

TINY = lfm2.LFM2Config(
    vocab_size=128, hidden_size=64, num_layers=6, num_heads=4,
    num_kv_heads=2, intermediate_size=96, moe_intermediate_size=32,
    num_dense_layers=2, num_experts=8, experts_per_token=2,
    max_position_embeddings=256,
    # wider than the published 0.02, which at hidden 64 leaves every
    # logit within 0.01 of every other
    initializer_range=0.2, router_bias_std=0.2, qk_norm_spread=2.0)
INFERENCE = {"max_batch_size": 3, "batch_buckets": [1, 2],
             "prompt_buckets": [16, 32], "max_seq_len": 64,
             "paged_kv": {"num_pages": 13, "prefix_cache": False}}


@pytest.fixture(scope="module")
def model():
    params = lfm2.init_lfm2_params(TINY, jax.random.PRNGKey(3), jnp.float32)
    return TINY, params, jax.jit(family.reference_logits(TINY))


def _prompts(lengths, seed=0):
    rs = np.random.RandomState(seed)
    return [list(map(int, rs.randint(0, 128, n))) for n in lengths]


def _logit_gaps(ref, params, finished, width=64):
    """The worst gap of every served token under the reference's pick,
    from the LOGITS of one full forward a request."""
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
    got = lfm2.lfm2_forward(params, cfg, ids, dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref(params, ids)),
                               atol=2e-4)


@pytest.mark.parametrize("reader", ["pallas", "gather"])
def test_a_bucket_of_unequal_prompts_then_decode_equal_the_reference(
        model, reader):
    """Prompts of unequal lengths in one bucket (padded positions reach
    neither tail nor logits), then decode through pages and tails: every
    served token's LOGITS are the one full forward's."""
    cfg, params, ref = model
    inference = {**INFERENCE, "paged_kv": {**INFERENCE["paged_kv"],
                                           "attn_kernel": reader}}
    engine = InferenceEngine(cfg, params, inference, dtype=jnp.float32)
    assert engine._decode_attn_path == reader
    assert isinstance(engine._cache, PagedTailCache)
    assert not hasattr(engine._cache, "state")
    finished = _serve(engine, _prompts((1, 2, 5, 16, 17, 29)), new=6)
    engine.close()
    assert all(f.finish_reason == "length" and len(f.tokens) == 6
               for f in finished)
    assert _logit_gaps(ref, params, finished) < 2e-3


def _mixer_call(cfg, lengths=None, slots=None, active=None):
    return _Call(cfg, jnp.float32, None, None, None, "gather", lengths,
                 slots, None, active)


@pytest.mark.parametrize("length", [1, 2, 3, 17])
def test_the_convolution_mixer_equals_the_references(model, length):
    cfg, params, _ = model
    lp = params["h_0"]
    h = jax.random.normal(jax.random.PRNGKey(length), (2, length, 64))
    got, _ = lfm2.conv_mixer(lp, h, _mixer_call(cfg), None, 0)
    with jax.default_matmul_precision("highest"):
        want = reference.short_conv(lp["conv"], h)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_the_tail_after_a_padded_prefill_is_the_tail_at_the_true_end(model):
    """A bucket row of 16 positions with 5 true ones leaves u_3, u_4 in
    its slot's tail; what the padding computed reaches nothing."""
    cfg, params, _ = model
    lp = params["h_0"]
    h = jax.random.normal(jax.random.PRNGKey(7), (2, 16, 64))
    tails = jnp.full((5, 4, 2, 64), 9.0)       # stale rows everywhere
    cache = PagedTailCache(None, None, tails)
    lengths, slots = jnp.asarray([5, 1]), jnp.asarray([2, 0])
    _, cache = lfm2.conv_mixer(lp, h, _mixer_call(cfg, lengths, slots),
                               cache, 1)
    b, _, x = jnp.split(h @ lp["conv"]["w_in"], 3, axis=-1)
    u = np.asarray(b * x)
    got = np.asarray(cache.tails[1])
    np.testing.assert_allclose(got[2], u[0, 3:5], atol=1e-5)
    # one true token: a zero before the sequence's start, then u_0
    np.testing.assert_allclose(got[0], np.stack([0 * u[1, 0], u[1, 0]]),
                               atol=1e-5)
    np.testing.assert_array_equal(got[[1, 3]], 9.0)
    np.testing.assert_array_equal(np.asarray(cache.tails[0]), 9.0)


def test_a_decode_leaves_an_inactive_slots_tail_bit_identical(model):
    cfg, params, _ = model
    lp = params["h_3"]
    h = jax.random.normal(jax.random.PRNGKey(8), (4, 1, 64))
    tails = jax.random.normal(jax.random.PRNGKey(9), (5, 4, 2, 64))
    active = jnp.asarray([True, False, True, False])
    y, cache = lfm2.conv_mixer(
        lp, h, _mixer_call(cfg, active=active),
        PagedTailCache(None, None, tails), 2)
    old, new = np.asarray(tails[2]), np.asarray(cache.tails[2])
    np.testing.assert_array_equal(new[[1, 3]], old[[1, 3]])
    # an active row: its tail moves on one position
    np.testing.assert_array_equal(new[[0, 2], 0], old[[0, 2], 1])
    b, _, x = jnp.split(h @ lp["conv"]["w_in"], 3, axis=-1)
    np.testing.assert_allclose(new[[0, 2], 1],
                               np.asarray(b * x)[[0, 2], 0], atol=1e-6)
    others = [0, 1, 3, 4]
    np.testing.assert_array_equal(np.asarray(cache.tails)[others],
                                  np.asarray(tails)[others])
    assert np.isfinite(np.asarray(y)).all()


def test_a_reused_slot_never_sees_its_predecessors_tail(model):
    cfg, params, ref = model
    first, second = _prompts([21, 9], seed=8)
    one_slot = {**INFERENCE, "max_batch_size": 1, "batch_buckets": [1]}
    engine = InferenceEngine(cfg, params, one_slot, dtype=jnp.float32)
    both = _serve(engine, [first, second])
    engine.close()
    fresh = InferenceEngine(cfg, params, one_slot, dtype=jnp.float32)
    (alone,) = _serve(fresh, [second])
    fresh.close()
    assert both[1].tokens == alone.tokens
    assert _logit_gaps(ref, params, both) < 2e-3


def test_slot_state_hands_out_the_slots_tail_row(model):
    """Between steps a slot's row of the tails is the last two products
    of every convolution layer before its pending position."""
    cfg, params, _ = model
    (prompt,) = _prompts([11], seed=5)
    engine = InferenceEngine(cfg, params, INFERENCE, dtype=jnp.float32)
    engine.submit(Request(prompt=prompt, max_new_tokens=4, temperature=0.0,
                          seed=0, eos_id=None))
    engine.step()
    engine.step()
    absorbed, row = engine.slot_state(0)
    engine.close()
    assert absorbed[:11] == prompt and len(absorbed) > 11
    assert row.shape == (len(cfg.conv_layers), 2, 64)
    # layer 0 reads the embedding alone: its tail by hand
    x = params["tok_emb"][jnp.asarray(absorbed[-2:])]
    h = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                          + cfg.rms_norm_eps) * params["h_0"]["ln_1"]["w"]
    b, _, xg = jnp.split(h @ params["h_0"]["conv"]["w_in"], 3, axis=-1)
    np.testing.assert_allclose(row[0], np.asarray(b * xg), atol=1e-5)


def test_queries_and_keys_are_normed_then_rotated_at_their_positions(model):
    """Past position 0: a row served at positions 37.. attends through
    keys normed a head and rotated where they stand, as the reference's
    full forward has them; un-normed or rotated at 0 it would not."""
    cfg, params, ref = model
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 3, 4, 16))
    at = jnp.asarray([[37, 38, 39]])
    got = lfm2.rotate_half_split(x.transpose(0, 2, 1, 3), at, 1e6)
    want = reference.rotate(x, at[0], 1e6).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    # the pair (i, i + 8) turns by 37 * theta^(-i / 8); a rotation
    # keeps each pair's length
    i, pos = 3, 37.0
    angle = pos * 1e6 ** (-i / 8)
    a, b = float(x[0, 0, 1, i]), float(x[0, 0, 1, i + 8])
    np.testing.assert_allclose(
        float(got[0, 1, 0, i]), a * np.cos(angle) - b * np.sin(angle),
        atol=1e-5)
    ids = jnp.asarray(_prompts([48], seed=2))
    plain = np.asarray(ref(params, ids))
    for fault in ("qk_unnormed", "rope_zero"):
        moved = np.asarray(jax.jit(family.reference_logits(
            cfg, fault=fault))(params, ids))
        assert np.abs(moved - plain)[0, 8:].max() > 0.05, fault
    got = lfm2.lfm2_forward(params, cfg, ids, dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got), plain, atol=2e-4)


def test_a_heads_norm_weights_are_seeded_away_from_one(model):
    """``qk_norm_spread`` s: a weight a channel of a head in [1 / s, s],
    q's and k's their own a layer; the program's default is ones."""
    cfg, params, _ = model
    seen = []
    for l in cfg.attention_layers:
        for name in ("q_norm", "k_norm"):
            w = np.asarray(params[f"h_{l}"]["attn"][name])
            assert w.shape == (cfg.head_dim,) and w.dtype == np.float32
            assert 0.5 <= w.min() < 0.8 and 1.25 < w.max() <= 2.0
            seen.append(w)
    assert all(np.abs(a - b).max() > 0.1
               for i, a in enumerate(seen) for b in seen[:i])
    plain = lfm2.init_lfm2_params(cfg._replace(qk_norm_spread=1.0),
                                  jax.random.PRNGKey(3), jnp.float32)
    l = cfg.attention_layers[0]
    assert np.all(np.asarray(plain[f"h_{l}"]["attn"]["q_norm"]) == 1.0)
    np.testing.assert_array_equal(np.asarray(plain[f"h_{l}"]["attn"]["wq"]),
                                  np.asarray(params[f"h_{l}"]["attn"]["wq"]))


def _route_by_hand(s, bias, k):
    ranked = s + bias
    idx = np.argsort(-ranked, axis=-1, kind="stable")[:, :k]
    top = np.take_along_axis(s, idx, -1)
    return idx, top / (top.sum(-1, keepdims=True) + 1e-6)


def test_the_router_chooses_by_score_plus_bias_and_weighs_by_score(model):
    cfg, params, _ = model
    flat = jax.random.normal(jax.random.PRNGKey(5), (9, 64))
    lp = params["h_2"]
    route = lfm2._family(cfg).route
    s = np.asarray(jax.nn.sigmoid(flat @ lp["router"]))
    bias = np.asarray(lp["router_bias"])
    idx, w, _ = route(flat, lp["router"], lp["router_bias"])
    want_idx, want_w = _route_by_hand(s, bias, 2)
    np.testing.assert_array_equal(np.asarray(idx), want_idx)
    np.testing.assert_allclose(np.asarray(w), want_w, atol=1e-6)
    # without b_e some token chooses otherwise
    idx0, _, _ = route(flat, lp["router"], jnp.zeros_like(lp["router_bias"]))
    assert (np.asarray(idx0) != want_idx).any()
    # ties go to the lower index: a router of zeros scores every expert
    # 0.5, and a bias that lifts experts 5 and 6 alike picks 5 then 6
    lifted = jnp.zeros((8,)).at[jnp.asarray([5, 6])].set(0.1)
    idx, w, _ = route(flat, jnp.zeros_like(lp["router"]), lifted)
    np.testing.assert_array_equal(np.asarray(idx), [[5, 6]] * 9)
    idx, _, _ = route(flat, jnp.zeros_like(lp["router"]), jnp.zeros((8,)))
    np.testing.assert_array_equal(np.asarray(idx), [[0, 1]] * 9)
    np.testing.assert_allclose(np.asarray(w), 0.5 / (1.0 + 1e-6), atol=1e-6)


def test_the_reference_routes_as_the_program_does(model):
    cfg, params, _ = model
    h2 = jax.random.normal(jax.random.PRNGKey(6), (1, 11, 64))
    lp = params["h_3"]
    spread, idx = reference.route(h2, lp["router"], lp["router_bias"],
                                  family.reference_config(cfg))
    got_idx, got_w, _ = lfm2._family(cfg).route(
        h2[0], lp["router"], lp["router_bias"])
    np.testing.assert_array_equal(np.asarray(idx[0]), np.asarray(got_idx))
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(spread[0]), np.asarray(got_idx), -1),
        np.asarray(got_w), atol=1e-6)


def test_no_shared_leaf_in_the_tree_and_no_shared_expert_scope_in_the_program(
        model):
    cfg, params, _ = model
    for l in cfg.expert_layers:
        assert "shared" not in params[f"h_{l}"]
        assert set(params[f"h_{l}"]) >= {"router", "router_bias", "experts"}
    assert "lm_head" not in params              # the table, tied
    ids = jnp.zeros((1, 8), jnp.int32)
    text = jax.jit(lambda p, i: lfm2.lfm2_forward(p, cfg, i)).lower(
        params, ids).as_text(debug_info=True)
    assert "moe_experts" in text and "conv_core" in text
    assert "attn_norm_rope" in text
    assert "/moe_shared" not in text


def test_the_planted_faults_move_the_reference(model):
    """The controls' knobs: each planted fault and the float8 products
    move the logits more than twice as far as bfloat16 products do;
    None is the reference itself."""
    cfg, params, ref = model
    ids = jnp.asarray(np.random.RandomState(4).randint(0, 128, (1, 64)))
    plain = np.asarray(ref(params, ids))
    rms = lambda **lower: float(np.sqrt(np.mean((np.asarray(jax.jit(
        family.reference_logits(cfg, **lower))(params, ids)) - plain) ** 2)))
    noise = rms(products="bfloat16")
    assert 0 < noise < 0.08 * np.sqrt(np.mean(plain ** 2))
    assert rms(products="float8_e5m2") > 8 * noise
    both = list(family.PLANTED.values())
    assert set(p["fault"] for p in both) == set(reference.FAULTS)
    for lower in both:
        assert rms(**lower) > 2 * noise, lower
    assert 0 < rms(state_dtype="bfloat16") < 3 * noise
    assert 0 < rms(round_to="bfloat16") < 3 * noise
    with pytest.raises(ValueError, match="no planted fault"):
        family.reference_logits(cfg, fault="other")(params, ids)


REFUSED = {
    "prefix_cache": ({"paged_kv": {"num_pages": 13, "prefix_cache": True}},
                     "prefix cache"),
    "dense_cache": ({"paged_kv": {"enabled": False}}, "dense cache"),
    "chunked_prefill": ({"chunked_prefill": {"enabled": True,
                                             "chunk_tokens": 16}},
                        "chunked prefill"),
    "spec_decode": ({"spec_decode": {"enabled": True, "k": 2}},
                    "speculative decoding"),
    "disagg": ({"disagg": {"enabled": True}}, "disaggregated"),
    "int8_pool": ({"paged_kv": {"num_pages": 13, "prefix_cache": False,
                                "kv_dtype": "int8"}}, "int8 page pool"),
    "quantized_weights": ({"quantize_weights": "int8"},
                          "quantized weights"),
    "mesh": ({"mesh": {"axes": {"model": 2}}}, "serving mesh"),
}


@pytest.mark.parametrize("feature", sorted(REFUSED))
def test_the_family_refuses_by_name_what_it_cannot_follow(model, feature):
    cfg, params, _ = model
    asked, named = REFUSED[feature]
    with pytest.raises(ValueError, match="per-slot convolution "
                       "tail") as said:
        InferenceEngine(cfg, params, {**INFERENCE, **asked})
    assert named in str(said.value)


def test_a_request_of_the_family_cannot_be_exported_or_imported(model):
    cfg, params, _ = model
    engine = InferenceEngine(cfg, params, INFERENCE)
    for call in (lambda: engine.export_request(0),
                 lambda: engine.import_request(None),
                 engine.warm_migration):
        with pytest.raises(NotImplementedError, match="convolution tail"):
            call()
    engine.close()


def test_the_tail_spec_builds_no_state_leaf_and_counts_its_bytes():
    spec = state_pool_spec_for(TINY, 4)
    assert not spec.has_state
    assert spec.tail_shape == (5, 4, 2, 64)
    assert state_pool_bytes(spec) == 5 * 4 * 2 * 64 * 2
    with open(os.path.join(BENCH, "configs", "lfm2-24b-a2b.json")) as f:
        cfg = family.serve_model_of(json.load(f))
    # 8 layers x 2 positions x 2,048 x 2 B a slot
    assert state_pool_bytes(state_pool_spec_for(cfg, 257)) == 257 * 65_536


def test_the_decode_span_carries_the_experts_counters(model, monkeypatch):
    """active, assignments, landed, fullest, held on `serve/decode`, and
    the rows the expert products worked: every held expert on every row
    of the slot table (the scratch row too), a layer."""
    cfg, params, _ = model
    decodes = []
    plain = InferenceEngine._span

    def recording(self, name, **args):
        if name == "serve/decode":
            decodes.append(args)
        return plain(self, name, **args)

    monkeypatch.setattr(InferenceEngine, "_span", recording)
    engine = InferenceEngine(cfg, params, INFERENCE, dtype=jnp.float32)
    _serve(engine, _prompts([9, 14], seed=3))
    engine.close()
    layers = len(cfg.expert_layers)
    for args in decodes:
        assert args["held"] == 8
        assert args["assignments"] == args["active"] * 2 * layers
        assert args["expert_rows_worked"] == engine._rows * 8 * layers
    # every expert is held: every assignment lands
    assert all(a["landed"] == 2 * 2 * layers for a in decodes[2:])


def test_the_new_names_are_registered():
    assert {"conv_proj", "conv_core", "attn_norm_rope"} <= set(
        spans.DEVICE_SCOPES)


def test_the_cut_counts_5267m_parameters():
    """The configuration file's sizes through the family: the arithmetic
    of docs/lfm2.md."""
    with open(os.path.join(BENCH, "configs", "lfm2-24b-a2b.json")) as f:
        config = json.load(f)
    cfg = family.serve_model_of(config)
    conv, attn, dense, router, expert, tables = lfm2.lfm2_param_count(cfg)
    assert (conv, attn, dense, router, expert) == (
        16_783_360, 10_485_888, 72_351_744, 131_136, 9_437_184)
    assert router + 64 * expert == 604_110_912
    assert tables == 134_217_728 + 2_048 + 10 * 4_096
    assert family.param_count(cfg) == 5_267_090_176
    assert cfg.kinds == ("conv", "conv", "full_attention", "conv", "conv",
                         "conv", "full_attention", "conv", "conv", "conv")
    assert cfg.held == (0, 64) and cfg.vocab_rows == 65_536
    assert cfg.expert_counters == (4 * 8, 64)
    leaves = jax.tree_util.tree_leaves(jax.eval_shape(
        lambda: lfm2.init_lfm2_params(cfg, jax.random.PRNGKey(0))))
    assert sum(int(np.prod(a.shape)) for a in leaves) == 5_267_090_176
    assert config["reduced"] == ["num_hidden_layers"]
    assert len(config["layer_types"]) == 40
