"""Solar-Open2-style hybrid decoder SERVED (models/solar_open2.py), its
recurrence (ops/kda.py), the per-slot state pool
(inference/kv_cache.py) and what the engine refuses for it, against the
plain float32 reference
(benchmarks/reference/solar_open2_reference.py) on seeded weights at
tiny sizes on the CPU."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu  # noqa: F401
from deepspeed_tpu.inference import InferenceEngine
from deepspeed_tpu.inference.kv_cache import (init_state_pool,
                                              paged_spec_for,
                                              state_pool_bytes,
                                              state_pool_spec_for)
from deepspeed_tpu.inference.scheduler import Request
from deepspeed_tpu.models import solar_open2 as so
from deepspeed_tpu.models.gpt2 import GPT2Config
from deepspeed_tpu.ops import kda, moe
from deepspeed_tpu.profiling import spans

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..",
                                "benchmarks"))
from families import solar_open2 as family  # noqa: E402
from reference import solar_open2_reference as reference  # noqa: E402

TINY = so.SolarOpen2Config(
    vocab_size=512, hidden_size=64, num_layers=4, num_heads=4,
    num_kv_heads=2, head_dim=32, gqa_layers=(0, 4), kda_num_heads=4,
    kda_head_dim=32, kda_gate_rank=16, moe_intermediate_size=32,
    num_experts=16, experts_per_token=4, max_position_embeddings=256,
    # wider than the published 0.02, which at hidden 64 leaves every
    # logit within 0.01 of every other
    initializer_range=0.2, experts_held=(0, 4), vocab_held=(0, 128))
INFERENCE = {"max_batch_size": 3, "batch_buckets": [1, 2],
             "prompt_buckets": [16, 32], "max_seq_len": 64,
             "paged_kv": {"num_pages": 14, "prefix_cache": False}}


@pytest.fixture(scope="module")
def model():
    params = so.init_solar_open2_params(TINY, jax.random.PRNGKey(3))
    return TINY, params, jax.jit(family.reference_logits(TINY))


def _recurrence_inputs(B, S, H, D, seed=0, strong=False):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (B, S, H, D))) * D ** -0.5
    k = unit(jax.random.normal(ks[1], (B, S, H, D)))
    v = jax.random.normal(ks[2], (B, S, H, D))
    # per step decays from e^-0.001 to e^-4.5 (strong: a chunk of 64
    # decays by e^-290, past what exp(-G) could hold)
    hi = 1.5 if strong else -1.0
    g = -jnp.exp(jax.random.uniform(ks[3], (B, S, H, D), minval=-7.0,
                                    maxval=hi))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (B, S, H)))
    s0 = jax.random.normal(ks[5], (B, H, D, D))
    return q, k, v, g, beta, s0


@pytest.mark.parametrize("S,strong,beta", [
    (64, False, None), (150, False, None), (130, True, None),
    (7, False, None),
    # the step at its upper end everywhere (an eigenvalue of -1 a token)
    # under decays no exp(-G) could hold: the kernel's own solve
    (130, True, 2.0)])
def test_chunked_scan_equals_the_sequential_recurrence(S, strong, beta):
    args = _recurrence_inputs(2, S, 3, 16, seed=S, strong=strong)
    if beta is not None:
        args = args[:4] + (jnp.full_like(args[4], beta),) + args[5:]
    with jax.default_matmul_precision("highest"):
        want_o, want_s = kda.kda_sequential(*args)
        got_o, got_s = jax.jit(kda.kda_chunk_scan)(*args)
    assert np.isfinite(np.asarray(got_o)).all()
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               atol=2e-5)


@pytest.mark.parametrize("S,true", [
    (96, (96, 41, 1)),
    # on a chunk's boundary, one past it, and a row with nothing in it:
    # two, one and all three of the 64-token turns are skipped
    (192, (64, 65, 0))])
def test_a_padded_bucket_ends_at_each_rows_true_length(S, true):
    """Ragged true lengths inside one padded bucket: outputs up to the
    length and the final state are those of the row alone (a row of
    length 0 hands its state back as it came), and what lies past a
    length is finite."""
    q, k, v, g, beta, s0 = _recurrence_inputs(3, S, 2, 16, seed=5)
    lengths = jnp.asarray(true)
    with jax.default_matmul_precision("highest"):
        got_o, got_s = jax.jit(kda.kda_chunk_scan)(q, k, v, g, beta, s0,
                                                   lengths)
        assert np.isfinite(np.asarray(got_o)).all()
        for row, n in enumerate(true):
            cut = lambda a: a[row:row + 1, :n]
            want_o, want_s = kda.kda_sequential(
                cut(q), cut(k), cut(v), cut(g), cut(beta),
                s0[row:row + 1])
            np.testing.assert_allclose(np.asarray(got_o[row, :n]),
                                       np.asarray(want_o[0]), atol=2e-5)
            np.testing.assert_allclose(np.asarray(got_s[row]),
                                       np.asarray(want_s[0]), atol=2e-5)
    if 0 in true:
        np.testing.assert_array_equal(np.asarray(got_s[true.index(0)]),
                                      np.asarray(s0[true.index(0)]))


def test_decode_update_is_one_step_of_the_recurrence_in_its_layer():
    """The kernel (in the interpreter) agrees with one sequential step,
    and the pool's other layers are untouched."""
    q, k, v, g, beta, _ = _recurrence_inputs(1, 5, 8, 16, seed=9)
    pool = jax.random.normal(jax.random.PRNGKey(1), (3, 5, 8, 16, 16))
    args = (q[0], k[0], v[0], g[0], beta[0])
    want_o, want_s = kda.kda_sequential(
        *(a[:, None] for a in args), pool[1])
    o, new = kda.kda_decode_update(pool, 1, *args)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o[:, 0]),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(new[1]), np.asarray(want_s),
                               atol=1e-5)
    np.testing.assert_array_equal(np.asarray(new[0]), np.asarray(pool[0]))
    np.testing.assert_array_equal(np.asarray(new[2]), np.asarray(pool[2]))


def test_plain_forward_equals_the_reference(model):
    cfg, params, ref = model
    ids = jax.random.randint(jax.random.PRNGKey(4), (2, 40), 0, 128)
    with jax.default_matmul_precision("highest"):
        got = so.solar_open2_forward(params, cfg, ids, dtype=jnp.float32)
    want = ref(params, ids)
    assert float(jnp.std(want)) > 0.3           # logits that tell tokens
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-3)


class _Recording(InferenceEngine):
    """The engine, its sampler also handing out the logits it samples
    from (in dispatch order: the host reads every dispatch's tokens)."""

    seen = None

    def _sample_tokens(self, logits, keys, temps):
        jax.debug.callback(
            lambda l: self.seen.append(np.asarray(l)), logits,
            ordered=True)
        return super()._sample_tokens(logits, keys, temps)


def _serve(cfg, params, prompts, new_tokens, engine=None):
    """({uid order: (tokens served, their logits rows)}, engine)."""
    if engine is None:
        engine = _Recording(cfg, params, INFERENCE, dtype=jnp.float32)
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
    return out, engine


def test_prefill_then_decode_through_the_engine_equal_the_reference(model):
    """Prompt through a prefill bucket (pages, state and tail written),
    then every decode step through both caches: each dispatch's logits
    are the reference's full forward's at that position."""
    cfg, params, ref = model
    rs = np.random.RandomState(0)
    prompts = [list(rs.randint(0, 128, n)) for n in (21, 5)]
    with jax.default_matmul_precision("highest"):
        served, _ = _serve(cfg, params, prompts, 9)
    for prompt, (tokens, logits) in zip(prompts, served):
        assert len(tokens) == 9 and len(logits) == 9
        seq = prompt + tokens
        want = np.asarray(ref(params, jnp.asarray([seq], jnp.int32)))[0]
        for j, row in enumerate(logits):
            at = len(prompt) - 1 + j
            np.testing.assert_allclose(row, want[at], atol=3e-3)
            assert tokens[j] == int(want[at].argmax())


def test_a_reused_slot_gives_the_logits_of_a_fresh_engine(model):
    """The second request takes the first one's slot: its prefill writes
    the row's state and tail whole, so nothing of the predecessor
    shows."""
    cfg, params, _ = model
    rs = np.random.RandomState(1)
    first, second = (list(rs.randint(0, 128, n)) for n in (30, 11))
    with jax.default_matmul_precision("highest"):
        used, engine = _serve(cfg, params, [first, second], 6)
        fresh, _ = _serve(cfg, params, [second], 6)
    assert used[1][0] == fresh[0][0]
    for a, b in zip(used[1][1], fresh[0][1]):
        np.testing.assert_array_equal(a, b)
    assert np.abs(np.asarray(engine._cache.state)).max() > 0  # one is kept


def test_a_slots_state_row_is_the_references_recurrence(model):
    """Mid-flight, after a prefill bucket with a pad row and some decode
    steps: `slot_state` names the tokens a slot's state has absorbed, and
    the row of the pool is what the plain forward's recurrence holds
    after exactly those (each delta-rule layer, its own true length)."""
    cfg, params, _ = model
    rs = np.random.RandomState(2)
    prompts = [list(rs.randint(0, 128, n)) for n in (27, 6, 14)]
    with jax.default_matmul_precision("highest"):
        engine = InferenceEngine(cfg, params, INFERENCE, dtype=jnp.float32)
        for i, prompt in enumerate(prompts):
            engine.submit(Request(prompt=prompt, max_new_tokens=12,
                                  temperature=0.0, seed=i, eos_id=None))
        for _ in range(5):
            engine.step()
        held = [engine.slot_state(i) for i in range(3)]
        assert all(h is not None for h in held)
        ids = np.zeros((3, 48), np.int32)
        for i, (absorbed, _) in enumerate(held):
            slot = engine.scheduler.slots[i]
            # the prompt and every served token but the pending one
            assert absorbed == (list(slot.request.prompt)
                                + slot.tokens)[:-1]
            ids[i, :len(absorbed)] = absorbed
        lengths = np.asarray([len(a) for a, _ in held], np.int32)
        want = np.asarray(jax.jit(family.reference_state(cfg))(
            params, jnp.asarray(ids), jnp.asarray(lengths)))
    assert want.shape == (3, len(cfg.recurrent_layers), cfg.kda_num_heads,
                          cfg.kda_head_dim, cfg.kda_head_dim)
    for (_, row), ref_row in zip(held, want):
        np.testing.assert_allclose(row, ref_row, atol=2e-4)
    assert np.abs(want).max() > 0.05
    engine.close()


def test_a_prompt_of_a_third_of_its_bucket_beside_a_pad_row(model,
                                                           monkeypatch):
    """A prompt of 11 tokens in the bucket of 32, one prompt in the batch
    bucket of two (``pad_prompts`` gives the pad row length 1): the
    experts work the true positions' assignments alone, and the first
    token and the slot's state row are the UNPADDED prompt's (the plain
    reference on the 11 tokens); the next `serve/prefill` span carries
    what this one's expert turns worked."""
    cfg, params, ref = model
    seen = []
    plain = InferenceEngine._span

    def recording(self, name, **args):
        if name == "serve/prefill":
            seen.append(args)
        return plain(self, name, **args)

    monkeypatch.setattr(InferenceEngine, "_span", recording)
    rs = np.random.RandomState(5)
    prompts = [list(rs.randint(0, 128, 11)) for _ in range(2)]
    with jax.default_matmul_precision("highest"):
        engine = InferenceEngine(
            cfg, params, {**INFERENCE, "batch_buckets": [2],
                          "prompt_buckets": [32]}, dtype=jnp.float32)
        for i, prompt in enumerate(prompts):
            engine.submit(Request(prompt=prompt, max_new_tokens=4,
                                  temperature=0.0, seed=i, eos_id=None))
            engine.step()
            absorbed, row = engine.slot_state(i)
            first = engine.scheduler.slots[i].tokens[0]
            assert absorbed[:11] == prompt and absorbed[11] == first
            logits = np.asarray(ref(params, jnp.asarray([prompt],
                                                        jnp.int32)))[0]
            assert first == int(logits[-1].argmax())
            want = np.asarray(jax.jit(family.reference_state(cfg))(
                params, jnp.asarray([absorbed], jnp.int32),
                jnp.asarray([len(absorbed)], jnp.int32)))[0]
            np.testing.assert_allclose(row, want, atol=2e-4)
    engine.close()
    assert [(a["batch"], a["prompt"], a["real_tokens"]) for a in seen] == [
        (2, 32, 11)] * 2
    # 4 layers, the bucket's 256 assignments in one static turn of 512
    assert seen[0]["expert_rows_worked"] == seen[0]["expert_rows_sorted"] == 0
    assert seen[1]["expert_rows_sorted"] == 4 * 512
    assert 0 < seen[1]["expert_rows_worked"] <= seen[1]["expert_rows_sorted"]


def test_the_reference_at_a_lower_precision_moves_as_its_precision(model):
    """The controls' knobs: products at bfloat16 move the logits a
    little, at float8 (e5m2) far more; a bfloat16 state moves the state
    it holds; None is the reference itself."""
    cfg, params, ref = model
    ids = jnp.asarray(np.random.RandomState(4).randint(0, 128, (1, 40)))
    plain = np.asarray(ref(params, ids))
    moved = {}
    for dtype in (jnp.bfloat16, jnp.float8_e5m2):
        low = np.asarray(jax.jit(family.reference_logits(
            cfg, products=dtype))(params, ids))
        moved[dtype] = np.sqrt(np.mean((low - plain) ** 2))
    assert 0 < moved[jnp.bfloat16] < 0.05 * np.sqrt(np.mean(plain ** 2))
    assert moved[jnp.float8_e5m2] > 8 * moved[jnp.bfloat16]
    # the knob is off again after a lowered trace
    np.testing.assert_array_equal(
        np.asarray(jax.jit(family.reference_logits(cfg))(params, ids)),
        plain)
    lengths = jnp.asarray([40], jnp.int32)
    state = np.asarray(jax.jit(family.reference_state(cfg))(
        params, ids, lengths))
    low = np.asarray(jax.jit(family.reference_state(
        cfg, state_dtype=jnp.bfloat16))(params, ids, lengths))
    rel = np.linalg.norm(low - state) / np.linalg.norm(state)
    assert 1e-4 < rel < 0.05


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


@pytest.mark.parametrize("served", ["every_row", "dropless"])
def test_the_shares_and_one_shared_expert_add_up_to_the_uncut_layer(served):
    """The expert parts of all the shares (4 chips of 4 experts here),
    plus the shared expert counted ONCE, are the reference's whole
    layer."""
    cfg = TINY
    h2, router, whole, shared = _layer_case(cfg, 11)
    ref_cfg = family.reference_config(cfg)
    with jax.default_matmul_precision("highest"):
        weights, _ = reference.route(h2[None], router, ref_cfg)
        want = reference.experts(h2[None], weights, whole,
                                 (0, cfg.num_experts), shared)[0]
        idx, p, _ = moe.route_top_k(h2, router, cfg.experts_per_token)
        parts = []
        for first in range(0, cfg.num_experts, 4):
            mine = jax.tree_util.tree_map(lambda a: a[first:first + 4],
                                          whole)
            if served == "every_row":
                y, _ = moe.held_experts_every_row(
                    h2, idx, p, mine, (first, 4), jax.nn.silu)
            else:
                y, _ = moe.dropless_experts(
                    h2, idx, p, mine, (first, 4), cfg.num_experts,
                    jax.nn.silu)
            parts.append(y)
        once = reference.experts(
            h2[None], jnp.zeros_like(weights), whole, (0, 1), shared)[0]
    assert float(jnp.abs(parts[0]).max()) > 1e-3      # a share is a part
    np.testing.assert_allclose(np.asarray(sum(parts) + once),
                               np.asarray(want), atol=2e-4)


def test_served_experts_do_not_follow_how_many_rows_land_here():
    """Routers that land nothing, a share and everything on the held
    experts: the output is the reference's each time and the traced
    program (shapes, trip counts) is the same one."""
    cfg = TINY
    h2, router, whole, _ = _layer_case(cfg, 21, tokens=12)
    mine = jax.tree_util.tree_map(lambda a: a[:4], whole)
    ref_cfg = family.reference_config(cfg)
    step = lambda idx, p: moe.held_experts_every_row(
        h2, idx, p, mine, (0, 4), jax.nn.silu)
    shapes, landed = set(), []
    for offset in (0, 4, None):
        idx, p, _ = moe.route_top_k(h2, router, cfg.experts_per_token)
        if offset is not None:      # every choice on / off the held four
            idx = (jnp.arange(4)[None, :] + offset) * jnp.ones_like(idx)
        shapes.add(str(jax.make_jaxpr(step)(idx, p)))
        with jax.default_matmul_precision("highest"):
            y, counts = step(idx, p)
            weights = jnp.sum(jax.nn.one_hot(idx, cfg.num_experts)
                              * p[..., None], axis=1)
            want = reference.experts(h2[None], weights[None], whole,
                                     (0, 4))[0]
        np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                                   atol=2e-4)
        landed.append(int(counts.sum()))
    assert len(shapes) == 1
    assert landed[0] == 12 * 4 and landed[1] == 0 and 0 < landed[2] < 48
    # rows that do not decode are not counted
    active = jnp.arange(12) < 5
    _, counts = moe.held_experts_every_row(
        h2, jnp.zeros((12, 4), jnp.int32) + jnp.arange(4), p, mine, (0, 4),
        jax.nn.silu, active)
    assert int(counts.sum()) == 5 * 4


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
def test_the_family_refuses_what_its_state_cannot_follow(model, feature):
    cfg, params, _ = model
    with pytest.raises(ValueError, match="recurrent state"):
        InferenceEngine(cfg, params, {**INFERENCE, **REFUSED[feature]})


def test_a_request_of_the_family_cannot_be_exported_or_imported(model):
    cfg, params, _ = model
    engine = InferenceEngine(cfg, params, INFERENCE)
    for call in (lambda: engine.export_request(0),
                 lambda: engine.import_request(None),
                 engine.warm_migration):
        with pytest.raises(NotImplementedError, match="recurrent state"):
            call()
    engine.close()


def test_the_state_pool_is_a_leaf_of_the_cache_tree_one_row_a_slot(model):
    cfg, params, _ = model
    engine = InferenceEngine(cfg, params, INFERENCE)
    kc, vc, state, tails = engine._cache
    assert kc.shape[0] == 1 == engine.paged_spec.num_layers   # softmax layers
    assert state.shape == (3, INFERENCE["max_batch_size"] + 1, 4, 32, 32)
    assert state.dtype == jnp.float32
    assert tails.shape == (3, 4, 3, 3 * 4 * 32)
    assert state_pool_bytes(engine.state_spec) == state.nbytes + tails.nbytes
    engine.close()
    # a family with no recurrent layer has no such leaves
    assert state_pool_spec_for(GPT2Config(), 4) is None
    assert paged_spec_for(GPT2Config(), 8, 16, 64).num_layers == \
        GPT2Config().num_layers
    spec = state_pool_spec_for(cfg, 5)
    assert [a.shape for a in init_state_pool(spec)] == \
        [spec.state_shape, spec.tail_shape]


def test_the_decode_span_carries_the_experts_counters(model, monkeypatch):
    """active, assignments, landed, fullest, held on `serve/decode`: the
    counts of the step before, read with the sampled tokens."""
    cfg, params, _ = model
    seen = []
    plain = InferenceEngine._span

    def recording(self, name, **args):
        if name == "serve/decode":
            seen.append(args)
        return plain(self, name, **args)

    monkeypatch.setattr(InferenceEngine, "_span", recording)
    engine = InferenceEngine(cfg, params, INFERENCE)
    rs = np.random.RandomState(2)
    engine.generate([list(rs.randint(0, 128, 9)) for _ in range(2)],
                    max_new_tokens=5, temperature=0.0)
    engine.close()
    assert len(seen) >= 4
    for args in seen:
        assert {"active", "assignments", "landed", "fullest",
                "held"} <= set(args)
        assert args["held"] == 4
        assert args["assignments"] == args["active"] * 4 * 4
        assert 0 <= args["fullest"] <= args["landed"] <= \
            2 * 4 * 4                          # at most two rows decoded
    assert seen[0]["landed"] == 0               # nothing decoded before
    assert any(a["landed"] > 0 for a in seen[1:])


def test_the_new_names_are_registered():
    assert {"kda_proj", "kda_scan", "kda_state", "attn_gate",
            "moe_shared"} <= set(spans.DEVICE_SCOPES)
    assert "serve/plan" in spans.HOST_SPANS


def test_the_cut_counts_3308m_parameters():
    """The configuration file's sizes through the family: the
    arithmetic of docs/solar_open2.md."""
    import json
    path = os.path.join(os.path.dirname(__file__), "..", "..",
                        "benchmarks", "configs", "solar-open2-250b.json")
    with open(path) as f:
        config = json.load(f)
    cfg = family.serve_model_of(config)
    kda_p, soft, around, expert, tables = so.solar_open2_param_count(cfg)
    assert (round(kda_p / 1e6, 2), round(soft / 1e6, 2),
            round(expert / 1e6, 2)) == (137.74, 109.05, 15.73)
    # router + shared + 40 held (646.18M) and the layer's two norms
    assert around + 40 * expert == 646184960 + 2 * 4096
    assert round(family.param_count(cfg) / 1e6) == 3308
    shapes = jax.eval_shape(
        lambda: so.init_solar_open2_params(cfg, jax.random.PRNGKey(0)))
    leaves = jax.tree_util.tree_leaves(shapes)
    assert sum(int(np.prod(a.shape)) for a in leaves) == \
        family.param_count(cfg)
    held = sum(a.size * a.dtype.itemsize for a in leaves)
    assert 6.6e9 < held < 6.65e9                # bfloat16 as held
    assert cfg.softmax_layers == (0,) and cfg.recurrent_layers == (1, 2, 3)
    # the whole model at the published sizes: 250B
    whole = (36 * kda_p + 12 * soft
             + 48 * (around + 320 * expert) + 2 * 196608 * 4096 + 4096)
    assert 250.0e9 < whole < 250.6e9
