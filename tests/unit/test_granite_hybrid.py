"""Granite-4.0-H-style hybrid decoder SERVED (models/granite_hybrid.py):
Mamba-2 mixers beside a NoPE softmax layer, the muP multipliers, the
tied sliced head, the state pool with unequal state widths and what the
engine refuses for it, against the plain float32 reference
(benchmarks/reference/granite_hybrid_reference.py) on seeded weights at
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
from deepspeed_tpu.inference.kv_cache import state_pool_bytes
from deepspeed_tpu.inference.scheduler import Request
from deepspeed_tpu.models import granite_hybrid as gh
from deepspeed_tpu.ops import moe
from deepspeed_tpu.profiling import spans

BENCH = os.path.join(os.path.dirname(__file__), "..", "..", "benchmarks")
sys.path.insert(0, BENCH)
from families import granite_hybrid as family  # noqa: E402
from reference import granite_hybrid_reference as reference  # noqa: E402

# four layers read from a longer published list (the fifth entry is not
# run); the attention scale is NOT head_dim ** -0.5 = 0.25
TINY = gh.GraniteHybridConfig(
    vocab_size=256, hidden_size=64, num_layers=4,
    layer_types=("mamba", "attention", "mamba", "mamba", "attention"),
    num_heads=4, num_kv_heads=2, head_dim=16, attention_multiplier=0.0625,
    mamba_n_heads=8, mamba_d_head=16, mamba_d_state=128, mamba_chunk_size=8,
    intermediate_size=32, shared_intermediate_size=48, num_experts=12,
    experts_per_token=4, max_position_embeddings=256, experts_held=(0, 6),
    vocab_held=(0, 128))
INFERENCE = {"max_batch_size": 3, "batch_buckets": [1, 2],
             "prompt_buckets": [16, 32], "max_seq_len": 64,
             "paged_kv": {"num_pages": 14, "prefix_cache": False}}


def _params(cfg, seed=3):
    """The seeded tree with what the initialiser leaves at one (the
    skip D, the norms) and at a width's own scale (the table: logits of
    rms 0.13 at hidden 64) moved, so that a forward that dropped one of
    them would show."""
    params = gh.init_granite_hybrid_params(cfg, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))
    wobble = lambda a: a * (1.0 + 0.3 * jax.random.normal(
        next(keys), a.shape, jnp.float32))
    params["tok_emb"] = params["tok_emb"] * 4
    params["ln_f"]["w"] = wobble(params["ln_f"]["w"])
    for l in range(cfg.num_layers):
        lp = params[f"h_{l}"]
        lp["ln_1"]["w"], lp["ln_2"]["w"] = (wobble(lp["ln_1"]["w"]),
                                            wobble(lp["ln_2"]["w"]))
        if "mamba" in lp:
            lp["mamba"]["d"] = wobble(lp["mamba"]["d"])
            lp["mamba"]["norm"] = wobble(lp["mamba"]["norm"])
    return params


@pytest.fixture(scope="module")
def model():
    return TINY, _params(TINY), jax.jit(family.reference_logits(TINY))


def test_the_published_layer_types_are_read_into_the_mixer_kinds():
    with open(os.path.join(BENCH, "configs",
                           "granite-4.0-h-small.json")) as f:
        config = json.load(f)
    assert len(config["layer_types"]) == 40      # kept whole as published
    assert [l for l, k in enumerate(config["layer_types"])
            if k == "attention"] == [5, 15, 25, 35]
    cfg = family.serve_model_of(config)
    assert cfg.num_layers == 10
    assert cfg.softmax_layers == (5,)
    assert cfg.recurrent_layers == (0, 1, 2, 3, 4, 6, 7, 8, 9)
    assert cfg.kv_cache_layers == 1
    assert TINY.softmax_layers == (1,) and TINY.recurrent_layers == (0, 2, 3)
    params = jax.eval_shape(
        lambda: gh.init_granite_hybrid_params(cfg, jax.random.PRNGKey(0)))
    assert ["attn" in params[f"h_{l}"] for l in range(10)] == \
        [l == 5 for l in range(10)]
    assert all(("mamba" in params[f"h_{l}"]) != ("attn" in params[f"h_{l}"])
               for l in range(10))
    assert "lm_head" not in params               # the table is TIED
    with pytest.raises(ValueError, match="layer_types"):
        TINY._replace(layer_types=("mamba", "conv", "mamba", "mamba")).kinds


def test_plain_forward_equals_the_reference(model):
    cfg, params, ref = model
    ids = jax.random.randint(jax.random.PRNGKey(4), (2, 40), 0, 128)
    with jax.default_matmul_precision("highest"):
        got = gh.granite_hybrid_forward(params, cfg, ids, dtype=jnp.float32)
    want = ref(params, ids)
    assert float(jnp.std(want)) > 0.3           # logits that tell tokens
    # float32 on both sides, other orders of summation (chunks of 8
    # against a token a step; grouped products against an expert a turn)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4)


@pytest.mark.parametrize("knob,value", [
    ("embedding_multiplier", 1.0), ("residual_multiplier", 1.0),
    ("logits_scaling", 1.0)])
def test_each_published_multiplier_is_in_the_forward(model, knob, value):
    """The same weights at the multiplier another family would assume
    give other logits: none of the three is dropped (two layers, one of
    each kind; the attention scale has a test of its own below)."""
    cfg, params, _ = model
    cfg = cfg._replace(num_layers=2)
    ids = jax.random.randint(jax.random.PRNGKey(5), (1, 8), 0, 128)
    want = jax.jit(family.reference_logits(cfg))(params, ids)
    with jax.default_matmul_precision("highest"):
        got = gh.granite_hybrid_forward(
            params, cfg._replace(**{knob: value}), ids, dtype=jnp.float32)
    assert float(jnp.abs(got - want).max()) > 0.05


class _Recording(InferenceEngine):
    """The engine, its sampler also handing out the logits it samples
    from (in dispatch order: the host reads every dispatch's tokens)."""

    seen = None

    def _sample_tokens(self, logits, keys, temps):
        jax.debug.callback(
            lambda l: self.seen.append(np.asarray(l)), logits,
            ordered=True)
        return super()._sample_tokens(logits, keys, temps)


def _serve_one_at_a_time(cfg, params, prompts, new_tokens):
    """[(tokens served, their logits rows)] a prompt, one request in
    flight at a time (slot 0: row 0 of every dispatch)."""
    engine = _Recording(cfg, params, INFERENCE, dtype=jnp.float32)
    engine.seen = []
    out = []
    for prompt in prompts:
        engine.seen.clear()
        uid = engine.submit(Request(prompt=prompt,
                                    max_new_tokens=new_tokens,
                                    temperature=0.0, seed=0, eos_id=None))
        done = {f.uid: f for f in engine.run()}[uid]
        out.append((done.tokens, [rows[0] for rows in engine.seen]))
    engine.close()
    return out


def test_prefill_then_decode_through_the_engine_equal_the_reference(model):
    """A prompt through each prefill bucket (pages, state and tail
    written; the scan crosses 1-4 chunks of 8), then every decode step
    through both caches at the published score scale: each dispatch's
    LOGITS are the reference's full forward's at that position."""
    cfg, params, ref = model
    rs = np.random.RandomState(0)
    prompts = [list(rs.randint(0, 128, n)) for n in (27, 5)]
    with jax.default_matmul_precision("highest"):
        served = _serve_one_at_a_time(cfg, params, prompts, 7)
    for prompt, (tokens, logits) in zip(prompts, served):
        assert len(tokens) == 7 and len(logits) == 7
        seq = prompt + tokens
        want = np.asarray(ref(params, jnp.asarray([seq], jnp.int32)))[0]
        for j, row in enumerate(logits):
            at = len(prompt) - 1 + j
            # float32 engine against float32 reference: the orders of
            # summation differ (chunks, pages, grouped products)
            np.testing.assert_allclose(row, want[at], atol=2e-4)
            assert tokens[j] == int(want[at].argmax())


def test_a_batch_with_a_pad_row_serves_what_each_prompt_alone_would(model):
    """Three prompts at once under a batch bucket of two: a prefill of
    two rows (true lengths under their bucket), then one of ONE prompt
    beside a pad row (the scratch row of the state pool); every slot's
    state row and every served token are the reference's, and the
    decode spans carry the experts' counters (4 x 4 assignments a row
    here, 6 held; 10 x 10 and 36 at the cell's sizes)."""
    cfg, params, ref = model
    assert gh.GraniteHybridConfig(num_layers=10, experts_held=(0, 36)
                                  ).expert_counters == (100, 36)
    rs = np.random.RandomState(2)
    prompts = [list(rs.randint(0, 128, n)) for n in (27, 9, 14)]
    prefills, decodes = [], []
    plain = InferenceEngine._span

    def recording(self, name, **args):
        if name == "serve/prefill":
            prefills.append((args["batch"], args["real_tokens"]))
        if name == "serve/decode":
            decodes.append(args)
        return plain(self, name, **args)

    with jax.default_matmul_precision("highest"):
        engine = InferenceEngine(cfg, params,
                                 {**INFERENCE, "batch_buckets": [2]},
                                 dtype=jnp.float32)
        engine._span = recording.__get__(engine)
        for i, prompt in enumerate(prompts):
            engine.submit(Request(prompt=prompt, max_new_tokens=8,
                                  temperature=0.0, seed=i, eos_id=None))
        for _ in range(4):
            engine.step()
        held = [engine.slot_state(i) for i in range(3)]
        assert all(h is not None for h in held)
        ids = np.zeros((3, 48), np.int32)
        for i, (absorbed, _) in enumerate(held):
            ids[i, :len(absorbed)] = absorbed
        lengths = np.asarray([len(a) for a, _ in held], np.int32)
        want = np.asarray(jax.jit(family.reference_state(cfg))(
            params, jnp.asarray(ids), jnp.asarray(lengths)))
        finished = {tuple(f.prompt): f.tokens for f in engine.run()}
    engine.close()
    # a prefill of ONE prompt in the bucket of two: a pad row
    assert all(b == 2 for b, _ in prefills)
    assert {real for _, real in prefills} & {27, 9, 14}
    # a state row is (heads, d_head, d_state): NOT square
    assert want.shape == (3, 3, 8, 16, 128)
    assert np.abs(want).max() > 1e-3
    for (_, row), ref_row in zip(held, want):
        np.testing.assert_allclose(row, ref_row,
                                   atol=2e-5 * np.abs(ref_row).max())
    for prompt in prompts:
        tokens = finished[tuple(prompt)]
        seq = prompt + tokens
        rows = np.asarray(ref(params, jnp.asarray([seq], jnp.int32)))[0]
        assert tokens == [int(rows[t - 1].argmax())
                          for t in range(len(prompt), len(seq))]
    for args in decodes:
        assert args["held"] == 6
        assert args["assignments"] == args["active"] * 4 * 4
        assert 0 <= args["fullest"] <= args["landed"] <= 3 * 4 * 4
    assert any(a["landed"] > 0 for a in decodes[1:])


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
            np.testing.assert_allclose(row, want, atol=2e-5 * np.abs(want).max())
    engine.close()
    assert [(a["batch"], a["prompt"], a["real_tokens"]) for a in seen] == [
        (2, 32, 11)] * 2
    # 4 layers, the bucket's 256 assignments in one static turn of 512
    assert seen[0]["expert_rows_worked"] == seen[0]["expert_rows_sorted"] == 0
    assert seen[1]["expert_rows_sorted"] == 4 * 512
    assert 0 < seen[1]["expert_rows_worked"] <= seen[1]["expert_rows_sorted"]


def test_the_published_score_scale_reaches_both_attention_readers():
    """`attention_multiplier` in the prefill reader (a prompt's own
    keys) AND in the paged decode reader (the Pallas kernel over the
    pages the prefill wrote): the attention layer alone against the
    reference's, which at head_dim ** -0.5 = 0.25 in place of the
    published 0.0625 either reader fails."""
    from deepspeed_tpu.models.served_trunk import _Pages
    from deepspeed_tpu.ops.attention.page_pool import paged_write_index
    cfg = TINY
    ks = jax.random.split(jax.random.PRNGKey(12), 5)
    n = lambda k, *shape: jax.random.normal(k, shape, jnp.float32)
    ap = {"wq": n(ks[0], 64, 64), "wk": n(ks[1], 64, 32),
          "wv": n(ks[2], 64, 32), "wo": n(ks[3], 64, 64) / 8}
    h = n(ks[4], 1, 17, 64)
    with jax.default_matmul_precision("highest"):
        want = reference._attention(ap, family.reference_config(cfg), h)
    tables = jnp.asarray([[1, 2]], jnp.int32)

    def served(config):
        """(the prompt's 16 positions through prefill, the 17th through
        decode)."""
        pools = (jnp.zeros((1, 4, 16, 32)), jnp.zeros((1, 4, 16, 32)))
        out = []
        for lo, hi in ((0, 16), (16, 17)):
            at = jnp.asarray([lo], jnp.int32)
            index = paged_write_index(tables, at, hi - lo, 16)
            with jax.default_matmul_precision("highest"):
                y, pools = gh._softmax_mixer(
                    ap, config, h[:, lo:hi], jnp.float32,
                    _Pages(pools, 0, tables, at, index, "pallas"))
            out.append(y)
        return out

    prefill, decode = served(cfg)
    # float32 against float32, another order of summation
    np.testing.assert_allclose(np.asarray(prefill), np.asarray(want[:, :16]),
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(decode), np.asarray(want[:, 16:]),
                               atol=2e-4)
    prefill, decode = served(
        cfg._replace(attention_multiplier=cfg.head_dim ** -0.5))
    assert float(jnp.abs(prefill - want[:, :16]).max()) > 0.1
    assert float(jnp.abs(decode - want[:, 16:]).max()) > 0.1


def test_the_score_scale_is_handed_to_the_two_kernels(monkeypatch):
    """`paged_attend` and `own_keys_attention` pass `sm_scale` on to
    `paged_decode_attention` and `flash_attention` (left out it stays
    None: each kernel's own head_dim ** -0.5, the other families'
    programs as they were)."""
    from deepspeed_tpu.ops.attention import page_pool
    seen = []
    plain_decode, plain_flash = page_pool.paged_decode_attention, \
        page_pool.flash_attention
    monkeypatch.setattr(
        page_pool, "paged_decode_attention",
        lambda *a, sm_scale=None, **kw: seen.append(("decode", sm_scale))
        or plain_decode(*a, sm_scale=sm_scale, **kw))
    monkeypatch.setattr(
        page_pool, "flash_attention",
        lambda *a, sm_scale=None, **kw: seen.append(("flash", sm_scale))
        or plain_flash(*a, sm_scale=sm_scale, **kw))
    monkeypatch.setattr(page_pool, "_OWN_KEYS_DENSE_SCORES", 0)
    page_pool._own_keys.clear_cache()
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 4, 16, 16))
    kv = jax.random.normal(jax.random.PRNGKey(1), (1, 2, 16, 16))
    zero = jnp.zeros((1,), jnp.int32)
    stripe = gh._stripe_attention_at(0.0625)
    got = page_pool.own_keys_attention(q, kv, kv, zero, stripe,
                                       sm_scale=0.0625)
    # the flash kernel (in the interpreter) at the handed scale is the
    # stripe mathematics at it
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(stripe(q, kv, kv, zero)), atol=2e-5)
    pools = (jnp.zeros((1, 4, 16, 32)), jnp.zeros((1, 4, 16, 32)))
    page_pool.paged_attend(
        q[:, :, :1], kv[:, :, :1], kv[:, :, :1], pools, 0,
        jnp.asarray([[1, 2]], jnp.int32), zero,
        page_pool.PagedWriteIndex(jnp.asarray([1]), jnp.asarray([0]), None,
                                  None), [], "pallas", stripe,
        sm_scale=0.0625)
    page_pool._own_keys.clear_cache()
    assert seen == [("flash", 0.0625), ("decode", 0.0625)]


def _layer_case(cfg, seed, tokens=40):
    h2 = jax.random.normal(jax.random.PRNGKey(seed),
                           (tokens, cfg.hidden_size), jnp.float32)
    ks = jax.random.split(jax.random.PRNGKey(seed + 1), 7)
    f, fs, e = (cfg.intermediate_size, cfg.shared_intermediate_size,
                cfg.num_experts)
    n = lambda k, shape: jax.random.normal(k, shape, jnp.float32) * 0.2
    whole = {"w_gate": n(ks[0], (e, cfg.hidden_size, f)),
             "w_up": n(ks[1], (e, cfg.hidden_size, f)),
             "w_down": n(ks[2], (e, f, cfg.hidden_size))}
    shared = {"w_gate": n(ks[3], (cfg.hidden_size, fs)),
              "w_up": n(ks[4], (cfg.hidden_size, fs)),
              "w_down": n(ks[5], (fs, cfg.hidden_size))}
    return h2, n(ks[6], (cfg.hidden_size, e)), whole, shared


@pytest.mark.parametrize("served", ["every_row", "dropless"])
def test_the_two_chips_parts_add_up_to_the_uncut_layer(served):
    """The SHARE (the guide's section 4): the expert parts of the two
    chips of one layer (6 of 12 experts each here), plus the shared
    expert counted ONCE, are the uncut reference's whole layer: top 4 of
    ALL 12 logits, softmax over those four."""
    cfg = TINY
    h2, router, whole, shared = _layer_case(cfg, 11)
    ref_cfg = family.reference_config(cfg)
    with jax.default_matmul_precision("highest"):
        weights, _ = reference.route(h2[None], router, ref_cfg)
        want = reference.experts(h2[None], weights, whole,
                                 (0, cfg.num_experts), shared)[0]
        idx, p, _ = moe.route_top_k(h2, router, cfg.experts_per_token)
        parts = []
        for first in (0, 6):
            mine = jax.tree_util.tree_map(lambda a: a[first:first + 6],
                                          whole)
            if served == "every_row":
                y, _ = moe.held_experts_every_row(
                    h2, idx, p, mine, (first, 6), jax.nn.silu)
            else:
                y, _ = moe.dropless_experts(
                    h2, idx, p, mine, (first, 6), cfg.num_experts,
                    jax.nn.silu)
            parts.append(y)
        once = reference.experts(
            h2[None], jnp.zeros_like(weights), whole, (0, 1), shared)[0]
    assert min(float(jnp.abs(y).max()) for y in parts) > 1e-3
    np.testing.assert_allclose(np.asarray(sum(parts) + once),
                               np.asarray(want), atol=2e-4)


def test_the_two_vocabulary_slices_concatenate_to_the_whole_head(model):
    """The tied table split two ways (ids drawn from the first slice):
    the cut program's logits are rows 0-127 of the uncut head's, the
    head over rows 128-255 of the same trunk the rest, and the two side
    by side are the uncut reference's."""
    cfg, params, _ = model
    cfg = cfg._replace(num_layers=2)
    whole = cfg._replace(vocab_held=(0, 0))
    other = (0.25 * 4 * jax.random.normal(
        jax.random.PRNGKey(9), (128, cfg.hidden_size))).astype(
            params["tok_emb"].dtype)
    uncut = {**params, "tok_emb": jnp.concatenate([params["tok_emb"],
                                                   other])}
    ids = jax.random.randint(jax.random.PRNGKey(6), (1, 8), 0, 128)
    want = jax.jit(family.reference_logits(whole))(uncut, ids)
    assert want.shape[-1] == 256
    with jax.default_matmul_precision("highest"):
        low = gh.granite_hybrid_forward(params, cfg, ids, dtype=jnp.float32)
        # the other chip's head: rows 128-255 over the same trunk
        high = gh.granite_hybrid_forward(uncut, whole, ids,
                                         dtype=jnp.float32)[..., 128:]
    assert low.shape[-1] == 128 == high.shape[-1]
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate([low, high], -1)), np.asarray(want),
        atol=1e-4)


def test_the_parameter_arithmetic_at_published_sizes_and_at_the_cut():
    """ISSUE 41's numbers, from the configuration file through the
    family."""
    with open(os.path.join(BENCH, "configs",
                           "granite-4.0-h-small.json")) as f:
        config = json.load(f)
    cfg = family.serve_model_of(config)
    mamba, soft, around, expert, table = gh.granite_hybrid_param_count(cfg)
    assert (mamba, soft, around, expert) == (
        102_286_976, 41_943_040, 19_177_472, 9_437_184)
    # W_in 4,096 -> 8,192 + 8,448 + 128
    assert cfg.d_inner + cfg.conv_channels + cfg.mamba_n_heads == 16_768
    # the whole model at the published sizes: 32.2B, which bears out
    # reading `intermediate_size` 768 as ONE expert's width
    pub = config["published"]
    whole = (36 * mamba + 4 * soft + pub["num_hidden_layers"] * (
        around + pub["num_local_experts"] * expert)
        + pub["vocab_size"] * 4096 + 4096)
    assert pub["vocab_size"] * 4096 == 411_041_792
    assert 72 * expert == 679_477_248
    assert 32.1e9 < whole < 32.3e9
    # the cut: 9 x 461.2M + 400.9M + 205.5M = 4,757M
    layer = around + 36 * expert
    assert round((mamba + layer) / 1e6, 1) == 461.2
    assert round((soft + layer) / 1e6, 1) == 400.9
    assert table == 50_176 * 4096 + 4096
    assert round(family.param_count(cfg) / 1e6) == 4757
    shapes = jax.eval_shape(
        lambda: gh.init_granite_hybrid_params(cfg, jax.random.PRNGKey(0)))
    leaves = jax.tree_util.tree_leaves(shapes)
    assert sum(int(np.prod(a.shape)) for a in leaves) == \
        family.param_count(cfg)
    held = sum(a.size * a.dtype.itemsize for a in leaves)
    assert 9.50e9 < held < 9.53e9               # bfloat16 as held
    # a slot's state: 9 x 128 x 64 x 128 x 4 B + 9 x 3 x 8,448 x 2 B
    row = family.describe_served(cfg)
    assert row["state_bytes_per_slot"] == 37_748_736 + 456_192
    assert row["ssd_tail_bytes_per_layer"] == 3 * 8_448 * 2
    assert row["kv_bytes_per_token"] == 4096


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
    """The same list as `SolarOpen2Config`'s
    (`_refuse_what_state_cannot_follow`)."""
    cfg, params, _ = model
    with pytest.raises(ValueError, match="recurrent state"):
        InferenceEngine(cfg, params, {**INFERENCE, **REFUSED[feature]})


def test_the_state_pool_has_unequal_widths_and_the_one_convolutions_tail(
        model):
    cfg, params, _ = model
    engine = InferenceEngine(cfg, params, INFERENCE)
    kc, vc, state, tails = engine._cache
    assert kc.shape[0] == 1 == engine.paged_spec.num_layers
    rows = INFERENCE["max_batch_size"] + 1
    assert state.shape == (3, rows, 8, 16, 128) and \
        state.dtype == jnp.float32
    # x, B and C together: 8 x 16 + 2 x 128, not 3 x width
    assert tails.shape == (3, rows, 3, 8 * 16 + 2 * 128)
    assert state_pool_bytes(engine.state_spec) == state.nbytes + tails.nbytes
    for call in (lambda: engine.export_request(0), engine.warm_migration):
        with pytest.raises(NotImplementedError, match="recurrent state"):
            call()
    engine.close()


def test_the_new_scopes_are_registered_and_traced(model):
    cfg, params, _ = model
    assert {"ssd_proj", "ssd_scan", "ssd_state"} <= set(spans.DEVICE_SCOPES)
    ids = jnp.zeros((1, 16), jnp.int32)
    text = jax.jit(lambda p: gh.granite_hybrid_forward(p, cfg, ids)).lower(
        params).as_text(debug_info=True)
    for name in ("ssd_proj", "ssd_scan", "attn_proj", "attn_core",
                 "moe_route", "moe_experts", "moe_shared", "lm_head"):
        assert name in text, name
