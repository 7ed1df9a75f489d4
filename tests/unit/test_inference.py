"""Inference serving engine (deepspeed_tpu/inference/): bucketed
prefill/decode with KV cache, continuous batching, checkpoint bridge,
serving telemetry.

Tier-1 acceptance pins (ISSUE 5):
- greedy ``generate()`` exactly matches a one-shot full-sequence
  forward argmax loop on CPU for BOTH model families;
- steady-state decode performs ZERO recompiles after bucket warmup
  (CompileTracker-counted);
- scheduler admission/eviction/slot-reuse semantics and deterministic
  per-request sampling with fixed keys.
"""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def tiny_gpt2():
    from deepspeed_tpu.models.gpt2 import GPT2Config, init_gpt2_params
    cfg = GPT2Config(vocab_size=61, max_position_embeddings=32,
                     hidden_size=32, num_layers=2, num_heads=4,
                     embd_dropout=0.0, attn_dropout=0.0,
                     resid_dropout=0.0)
    return cfg, init_gpt2_params(cfg, jax.random.PRNGKey(3))


def tiny_llama():
    from deepspeed_tpu.models.llama import LlamaConfig, init_llama_params
    cfg = LlamaConfig(vocab_size=61, hidden_size=32, num_layers=2,
                      num_heads=4, num_kv_heads=2,
                      max_position_embeddings=32)
    return cfg, init_llama_params(cfg, jax.random.PRNGKey(4))


TINY_INF = {"max_batch_size": 3, "prompt_buckets": [4, 8],
            "batch_buckets": [1, 2], "max_seq_len": 32,
            "max_new_tokens": 4}


def greedy_reference(forward, params, cfg, prompt, n):
    """No-cache argmax loop: one full forward per generated token."""
    ids = jnp.asarray([prompt], jnp.int32)
    for _ in range(n):
        logits = forward(params, cfg, ids, dtype=jnp.float32)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        ids = jnp.concatenate([ids, nxt[:, None]], axis=1)
    return np.asarray(ids)[0].tolist()


# --------------------------------------------------------------------- #
# buckets
# --------------------------------------------------------------------- #
class TestBuckets:
    def test_pick_bucket(self):
        from deepspeed_tpu.inference.buckets import pick_bucket
        assert pick_bucket(1, (4, 8)) == 4
        assert pick_bucket(4, (4, 8)) == 4
        assert pick_bucket(5, (4, 8)) == 8
        with pytest.raises(ValueError, match="exceeds the largest"):
            pick_bucket(9, (4, 8))

    def test_validate_buckets(self):
        from deepspeed_tpu.inference.buckets import validate_buckets
        assert validate_buckets([4, 8], "b") == (4, 8)
        for bad in ([], [0, 4], [8, 4], [4, 4]):
            with pytest.raises(ValueError):
                validate_buckets(bad, "b")

    def test_pad_prompts(self):
        from deepspeed_tpu.inference.buckets import pad_prompts
        ids, lengths = pad_prompts([[1, 2], [3, 4, 5]], 4, 3)
        assert ids.shape == (3, 4)
        np.testing.assert_array_equal(lengths, [2, 3, 1])  # pad row len 1
        np.testing.assert_array_equal(ids[0], [1, 2, 0, 0])
        np.testing.assert_array_equal(ids[2], [0, 0, 0, 0])
        with pytest.raises(ValueError):
            pad_prompts([[1] * 5], 4, 1)          # prompt > bucket
        with pytest.raises(ValueError):
            pad_prompts([[1], [2]], 4, 1)         # batch > bucket


# --------------------------------------------------------------------- #
# scheduler (pure host-side: no jax)
# --------------------------------------------------------------------- #
def test_host_side_scheduling_modules_stay_jax_free():
    """scheduler.py advertises "nothing here imports jax, so scheduler
    policy is unit-testable in microseconds" — pin that at the source
    level for the whole host-side chain it pulls in (scheduler ->
    paging, buckets), so a convenience import can't quietly drag jax
    back into admission policy.

    ISSUE 8 extension: the same modules must also stay KERNEL-AGNOSTIC
    — scheduling/paging policy must not know (or care) whether decode
    attention runs the fused Pallas paged kernel or the gather
    fallback, so no import from ops.attention (or any ops/ module) and
    no kernel-path strings may appear. The engine owns the path choice;
    the scheduler only ever produces block tables."""
    import ast
    import pathlib

    import deepspeed_tpu.inference as inf
    root = pathlib.Path(inf.__file__).parent
    for mod in ("scheduler.py", "paging.py", "buckets.py", "tracing.py",
                "draft.py", "disagg.py", "fleet.py", "rpc.py"):
        src = (root / mod).read_text()
        for node in ast.walk(ast.parse(src)):
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                names = [node.module or ""]
            else:
                continue
            for n in names:
                assert n != "jax" and not n.startswith("jax."), \
                    f"{mod} imports {n}"
                assert ".ops" not in n and not n.startswith("ops"), \
                    f"{mod} imports kernel code: {n}"
        assert "pallas" not in src.lower(), \
            f"{mod} mentions a kernel path — scheduling must stay " \
            f"kernel-agnostic"


class TestScheduler:
    def _sched(self, slots=3, clock=None):
        from deepspeed_tpu.inference.scheduler import Scheduler
        kw = {"clock": clock} if clock else {}
        return Scheduler(slots, (4, 8), (1, 2), 32, **kw)

    def test_submit_validation(self):
        # ISSUE 19: unservable shapes are a graceful submit-time
        # rejection (pinned reason "reject_too_long"), never a crash
        from deepspeed_tpu.inference.scheduler import Request
        s = self._sched()
        uid = s.submit(Request(prompt=list(range(1, 10))))  # > bucket 8
        uid2 = s.submit(Request(prompt=[1, 2, 3], max_new_tokens=30))
        rejects = s.drain_rejects()
        assert [r.uid for r in rejects] == [uid, uid2]
        for r in rejects:
            assert r.finish_reason == "reject_too_long"
            assert r.tokens == [] and r.ttft_ms is None
        assert s.drain_rejects() == []          # one-shot drain
        assert not s.queue                      # never queued
        with pytest.raises(ValueError, match="empty"):
            Request(prompt=[])

    def test_a_steps_decode_tokens_reach_the_tracer_in_one_call(self):
        """``record_token_runs`` hands the tracer a call's decode tokens
        at once, AFTER every first token of the call and BEFORE any of
        its requests is finished there (a finishing request's last
        interval is in its finish row)."""
        from deepspeed_tpu.inference.scheduler import Request, Scheduler
        calls = []

        class Tracer:
            def __getattr__(self, name):
                return lambda *a, **k: calls.append((name, a))
        s = Scheduler(3, (4, 8), (1, 2), 32, tracer=Tracer())
        ra = Request(prompt=[1, 2, 3], max_new_tokens=2)
        rb = Request(prompt=[4, 5], max_new_tokens=3)
        s.submit(ra), s.submit(rb)
        (batch,) = s.admit()
        a, b = batch.slot_ids
        s.record_tokens({a: 7, b: 8})
        del calls[:]
        done = s.record_token_runs({a: [9], b: [10]})
        assert [f.uid for f in done] == [ra.uid]
        assert [c[0] for c in calls] == ["on_tokens", "on_finish"]
        assert list(calls[0][1][0]) == [ra.uid, rb.uid]
        assert calls[1][1][0].tokens == [7, 9]

    def test_admission_groups_by_bucket_fifo(self):
        from deepspeed_tpu.inference.scheduler import Request
        s = self._sched(slots=3)
        r1 = Request(prompt=[1, 2, 3], max_new_tokens=4)        # bucket 4
        r2 = Request(prompt=[1] * 7, max_new_tokens=4)          # bucket 8
        r3 = Request(prompt=[4, 5], max_new_tokens=4)           # bucket 4
        for r in (r1, r2, r3):
            s.submit(r)
        batches = s.admit()
        # head (r1) fixes bucket 4; r3 rides along; r2 admits second
        assert len(batches) == 2
        assert batches[0].prompt_bucket == 4
        assert [r.uid for r in batches[0].requests] == [r1.uid, r3.uid]
        assert batches[0].batch_bucket == 2
        assert batches[1].prompt_bucket == 8
        assert [r.uid for r in batches[1].requests] == [r2.uid]
        assert batches[1].batch_bucket == 1
        assert s.queue_depth == 0 and s.occupancy == 1.0

    def test_eviction_and_slot_reuse(self):
        from deepspeed_tpu.inference.scheduler import Request
        s = self._sched(slots=1)
        a = Request(prompt=[1, 2], max_new_tokens=2)
        b = Request(prompt=[3], max_new_tokens=1, eos_id=9)
        s.submit(a)
        s.submit(b)
        (batch,) = s.admit()
        assert [r.uid for r in batch.requests] == [a.uid]
        sid = batch.slot_ids[0]
        assert s.record_tokens({sid: 5}) == []        # 1/2 tokens
        assert s.admit() == []                        # slot still busy
        done = s.record_tokens({sid: 6})
        assert [f.uid for f in done] == [a.uid]
        assert done[0].tokens == [5, 6]
        assert done[0].finish_reason == "length"
        # slot freed -> b admitted into the SAME slot
        (batch2,) = s.admit()
        assert batch2.slot_ids == [sid]
        done = s.record_tokens({sid: 9})              # eos on first token
        assert done[0].finish_reason == "eos"
        assert s.idle()

    def test_decode_state_bookkeeping(self):
        from deepspeed_tpu.inference.scheduler import Request
        s = self._sched(slots=2)
        s.submit(Request(prompt=[1, 2, 3], max_new_tokens=3,
                         temperature=0.7, seed=42))
        (batch,) = s.admit()
        sid = batch.slot_ids[0]
        assert s.decode_state()[0] == []        # first token still pending
        s.record_tokens({sid: 7})               # prefill's first token
        sids, toks, poss, temps, seeds = s.decode_state()
        assert sids == [sid] and toks == [7]
        assert poss == [3]                      # prompt tokens in cache
        assert temps == [0.7] and seeds == [42]
        s.record_tokens({sid: 8})               # decode wrote tok 7 at 3
        assert s.decode_state()[2] == [4]

    def test_ttft_drain(self):
        from deepspeed_tpu.inference.scheduler import Request
        t = [0.0]
        s = self._sched(slots=1, clock=lambda: t[0])
        s.submit(Request(prompt=[1], max_new_tokens=2))
        (batch,) = s.admit()
        t[0] = 0.25
        s.record_tokens({batch.slot_ids[0]: 1})
        assert s.drain_ttfts() == [250.0]
        assert s.drain_ttfts() == []


# --------------------------------------------------------------------- #
# model-level cached forward (satellite: training signature unchanged)
# --------------------------------------------------------------------- #
class TestCachedForward:
    def test_causal_cache_mask(self):
        from deepspeed_tpu.ops.attention.page_pool import \
            causal_cache_mask
        m = np.asarray(causal_cache_mask(jnp.asarray([0, 2]), 2, 5))
        assert m.shape == (2, 1, 2, 5)
        # row 0 at offset 0: query j attends k <= j
        np.testing.assert_array_equal(m[0, 0, 0], [1, 0, 0, 0, 0])
        np.testing.assert_array_equal(m[0, 0, 1], [1, 1, 0, 0, 0])
        # row 1 at offset 2: query 0 sits at absolute position 2
        np.testing.assert_array_equal(m[1, 0, 0], [1, 1, 1, 0, 0])
        np.testing.assert_array_equal(m[1, 0, 1], [1, 1, 1, 1, 0])

    @pytest.mark.parametrize("family", ["gpt2", "llama"])
    def test_chunked_cached_forward_matches_oneshot(self, family):
        if family == "gpt2":
            from deepspeed_tpu.models.gpt2 import gpt2_forward as fwd
            cfg, params = tiny_gpt2()
            heads = cfg.num_heads
        else:
            from deepspeed_tpu.models.llama import llama_forward as fwd
            cfg, params = tiny_llama()
            heads = cfg.kv_heads      # GQA cache stays kv_heads-sized
        hd = cfg.hidden_size // cfg.num_heads
        B, S, max_len = 2, 7, 16
        ids = jnp.asarray(np.random.RandomState(0).randint(0, 61, (B, S)),
                          jnp.int32)
        ref = fwd(params, cfg, ids, dtype=jnp.float32)
        cache = tuple(jnp.zeros((cfg.num_layers, B, heads, max_len, hd),
                                jnp.float32) for _ in range(2))
        # prefill 4 tokens into the cache, then decode 3 one by one
        lg, cache = fwd(params, cfg, ids[:, :4], dtype=jnp.float32,
                        kv_cache=cache)
        outs = [lg]
        for t in range(4, S):
            lg, cache = fwd(params, cfg, ids[:, t:t + 1],
                            dtype=jnp.float32, kv_cache=cache,
                            cache_position=jnp.full((B,), t, jnp.int32))
            outs.append(lg)
        got = jnp.concatenate(outs, axis=1)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-4)


# --------------------------------------------------------------------- #
# the serving engine
# --------------------------------------------------------------------- #
class TestInferenceEngine:
    @pytest.mark.parametrize("family", ["gpt2", "llama"])
    def test_greedy_generate_parity(self, family):
        """ISSUE 5 acceptance: token-by-token greedy parity with the
        one-shot full-forward argmax loop, under continuous batching
        (6 mixed-length requests over 3 slots -> slot reuse on the
        real path)."""
        from deepspeed_tpu.inference import InferenceEngine
        if family == "gpt2":
            from deepspeed_tpu.models.gpt2 import gpt2_forward as fwd
            cfg, params = tiny_gpt2()
        else:
            from deepspeed_tpu.models.llama import llama_forward as fwd
            cfg, params = tiny_llama()
        engine = InferenceEngine(cfg, params, TINY_INF,
                                 dtype=jnp.float32)
        rng = np.random.RandomState(1)
        prompts = [rng.randint(1, 61, (n,)).tolist()
                   for n in (3, 5, 7, 2, 8, 4)]
        outs = engine.generate(prompts, max_new_tokens=4, temperature=0.0)
        for prompt, out in zip(prompts, outs):
            assert out == greedy_reference(fwd, params, cfg, prompt, 4)

    def test_zero_steady_state_recompiles_after_warmup(self):
        """ISSUE 5 acceptance: warmup compiles exactly
        len(batch_buckets) x len(prompt_buckets) prefill programs + 1
        decode program; serving traffic that stays inside the bucket
        table compiles NOTHING more (CompileTracker-exact)."""
        from deepspeed_tpu.inference import InferenceEngine
        cfg, params = tiny_gpt2()
        engine = InferenceEngine(cfg, params, TINY_INF,
                                 dtype=jnp.float32)
        assert engine.steady_state_recompiles == -1   # before warmup
        programs = engine.warmup()
        # (the merge of first tokens into the device's: a batch bucket)
        assert programs == 2 * 2 + 1 + 2
        assert engine.compile_tracker.counts == {"prefill": 4,
                                                 "decode": 1,
                                                 "merge_tokens": 2}
        rng = np.random.RandomState(2)
        prompts = [rng.randint(1, 61, (n,)).tolist()
                   for n in (1, 4, 5, 8, 3, 6, 2, 7)]
        engine.generate(prompts, max_new_tokens=3)
        engine.generate(prompts[:2], max_new_tokens=5, temperature=0.5)
        assert engine.steady_state_recompiles == 0
        assert engine.compile_tracker.total_compiles == programs

    def test_sampling_deterministic_per_request_keys(self):
        """Same seeds -> identical streams regardless of runs; seeds are
        per-request, so a request's stream does not depend on what else
        shares the batch."""
        from deepspeed_tpu.inference import InferenceEngine
        cfg, params = tiny_gpt2()
        engine = InferenceEngine(cfg, params, TINY_INF,
                                 dtype=jnp.float32)
        prompts = [[1, 2, 3], [4, 5]]
        a = engine.generate(prompts, max_new_tokens=6, temperature=0.8,
                            seeds=[7, 8])
        b = engine.generate(prompts, max_new_tokens=6, temperature=0.8,
                            seeds=[7, 8])
        assert a == b
        c = engine.generate(prompts, max_new_tokens=6, temperature=0.8,
                            seeds=[70, 80])
        assert a != c
        # request 0 alone samples the same stream as batched with 1
        solo = engine.generate([prompts[0]], max_new_tokens=6,
                               temperature=0.8, seeds=[7])
        assert solo[0] == a[0]
        assert all(0 <= t < 61 for out in a for t in out)

    def test_eos_stops_generation(self):
        from deepspeed_tpu.inference import InferenceEngine
        cfg, params = tiny_gpt2()
        engine = InferenceEngine(cfg, params, TINY_INF,
                                 dtype=jnp.float32)
        prompt = [1, 2, 3]
        full = engine.generate([prompt], max_new_tokens=6,
                               temperature=0.0)[0]
        gen = full[len(prompt):]
        # declare a token greedy decoding is known to emit as EOS: the
        # rerun must stop at its FIRST occurrence, inclusive
        eos = gen[1]
        stop = gen.index(eos)
        stopped = engine.generate([prompt], max_new_tokens=6,
                                  temperature=0.0, eos_id=eos)[0]
        assert stopped == full[:len(prompt) + stop + 1]

    def test_serving_telemetry_and_report(self, tmp_path):
        """Serve/* scalars + serve events land in events.jsonl; the
        obs_report serving section renders them (function AND CLI —
        the tier-1 serving-report smoke)."""
        from deepspeed_tpu.inference import InferenceEngine
        cfg, params = tiny_gpt2()
        icfg = dict(TINY_INF, events_dir=str(tmp_path))
        engine = InferenceEngine(cfg, params, icfg, dtype=jnp.float32)
        engine.warmup()
        prompts = [[1, 2, 3], [4, 5], [6, 7, 8, 9], [10]]
        engine.generate(prompts, max_new_tokens=4)
        engine.close()

        rows = [json.loads(line)
                for line in open(tmp_path / "events.jsonl")]
        tags = {r["tag"] for r in rows if "tag" in r}
        # tag schema pinned (utils/monitor.write_serving_metrics)
        assert {"Serve/ttft_ms", "Serve/token_latency_ms",
                "Serve/tokens_per_sec", "Serve/queue_depth",
                "Serve/batch_occupancy"} <= tags
        events = {r["event"] for r in rows if "event" in r}
        assert {"serve_warmup", "serve_finish", "compile"} <= events
        assert sum(1 for r in rows
                   if r.get("tag") == "Serve/ttft_ms") == len(prompts)

        obs_report = _load_tool("obs_report")
        s = obs_report.summarize(str(tmp_path))
        sv = s["serving"]
        assert sv["requests"] == len(prompts)
        assert sv["decode_steps"] >= 1
        assert sv["ttft_ms"]["p50"] is not None
        assert sv["ttft_ms"]["p95"] >= sv["ttft_ms"]["p50"]
        assert sv["token_latency_ms"]["p95"] is not None
        assert sv["tokens_per_sec"]["last"] > 0
        assert 0 < sv["batch_occupancy_mean"] <= 1
        text = obs_report.render(s)
        assert "serving" in text and "ttft_ms" in text
        assert obs_report.main([str(tmp_path)]) == 0
        assert obs_report.main([str(tmp_path), "--json"]) == 0

    def test_serve_tag_registry_in_sync(self):
        """One tag, three homes: the monitor (canonical writer), the
        profiling registry (re-export), and stdlib-only obs_report
        (mirrored strings) must agree."""
        from deepspeed_tpu import profiling as prof
        from deepspeed_tpu.utils import monitor as m
        obs_report = _load_tool("obs_report")
        assert m.TAG_SERVE_TTFT == prof.TAG_SERVE_TTFT == \
            obs_report.T_TTFT
        assert m.TAG_SERVE_TOKEN_LATENCY == \
            prof.TAG_SERVE_TOKEN_LATENCY == obs_report.T_TOK_LAT
        assert m.TAG_SERVE_TPS == prof.TAG_SERVE_TPS == obs_report.T_TPS
        assert m.TAG_SERVE_QUEUE_DEPTH == prof.TAG_SERVE_QUEUE_DEPTH == \
            obs_report.T_QDEPTH
        assert m.TAG_SERVE_OCCUPANCY == prof.TAG_SERVE_OCCUPANCY == \
            obs_report.T_OCC
        assert m.TAG_SERVE_KV_PAGES == prof.TAG_SERVE_KV_PAGES == \
            obs_report.T_KV_PAGES
        assert m.TAG_SERVE_TOKENS_IN_FLIGHT == \
            prof.TAG_SERVE_TOKENS_IN_FLIGHT == obs_report.T_TOKENS_IN_FLIGHT
        # ISSUE 9: the request-granular plane's tags (queue wait, TBT,
        # SLO attainment, goodput) live in the same three homes
        assert m.TAG_SERVE_QUEUE_WAIT == prof.TAG_SERVE_QUEUE_WAIT == \
            obs_report.T_QUEUE_WAIT
        assert m.TAG_SERVE_TBT == prof.TAG_SERVE_TBT == obs_report.T_TBT
        assert m.TAG_SERVE_SLO == prof.TAG_SERVE_SLO == obs_report.T_SLO
        assert m.TAG_SERVE_GOODPUT == prof.TAG_SERVE_GOODPUT == \
            obs_report.T_GOODPUT
        assert m.TAG_SERVE_PREFIX_HIT == prof.TAG_SERVE_PREFIX_HIT == \
            obs_report.T_PREFIX_HIT
        # ISSUE 13: speculation + disaggregation scalars
        assert m.TAG_SERVE_SPEC_ACCEPT == prof.TAG_SERVE_SPEC_ACCEPT == \
            obs_report.T_SPEC_ACCEPT == "Serve/spec_accept_rate"
        assert m.TAG_SERVE_HANDOFF == prof.TAG_SERVE_HANDOFF == \
            obs_report.T_HANDOFF == "Serve/handoff_ms"
        # ISSUE 17: quantized-serving scalars
        assert m.TAG_SERVE_KV_POOL_BPT == prof.TAG_SERVE_KV_POOL_BPT \
            == obs_report.T_KV_POOL_BPT == "Serve/kv_pool_bytes_per_token"
        assert m.TAG_SERVE_QUANT_LOGIT_ERR == \
            prof.TAG_SERVE_QUANT_LOGIT_ERR == \
            obs_report.T_QUANT_LOGIT_ERR == "Serve/quant_logit_err"
        # ISSUE 19: chunked-prefill scalars
        assert m.TAG_SERVE_CHUNK_DISPATCHES == \
            prof.TAG_SERVE_CHUNK_DISPATCHES == \
            obs_report.T_CHUNK_DISPATCHES == "Serve/chunk_dispatches"
        assert m.TAG_SERVE_TBT_MAX == prof.TAG_SERVE_TBT_MAX == \
            obs_report.T_TBT_MAX == "Serve/tbt_max_ms"

    def test_rejects_unservable_config(self):
        from deepspeed_tpu.inference import InferenceEngine
        cfg, params = tiny_gpt2()
        with pytest.raises(ValueError, match="prompt_buckets"):
            # buckets exceed the model's position table after clamping
            InferenceEngine(cfg, params,
                            dict(TINY_INF, prompt_buckets=[4, 64],
                                 max_seq_len=1024))


# --------------------------------------------------------------------- #
# checkpoint -> serving bridge
# --------------------------------------------------------------------- #
class TestFromCheckpoint:
    def _save_training_checkpoint(self, tmp_path, cfg, params):
        import deepspeed_tpu
        from deepspeed_tpu.models.gpt2 import gpt2_loss_fn
        engine, *_ = deepspeed_tpu.initialize(
            model=gpt2_loss_fn(cfg, dtype=jnp.float32,
                               deterministic=True),
            model_parameters=params,
            config={"train_micro_batch_size_per_gpu": 1,
                    "gradient_accumulation_steps": 1,
                    "steps_per_print": 10**9,
                    "optimizer": {"type": "Adam",
                                  "params": {"lr": 1e-3}}})
        return engine.save_checkpoint(str(tmp_path))

    def test_params_only_load_and_parity(self, tmp_path):
        """A committed PR-1 training checkpoint serves: params-only load
        (no optimizer state touched), greedy outputs identical to an
        engine built from the in-memory params."""
        from deepspeed_tpu.inference import InferenceEngine
        from deepspeed_tpu.runtime import checkpoint as ckpt
        cfg, params = tiny_gpt2()
        self._save_training_checkpoint(tmp_path, cfg, params)

        groups = ckpt.state_groups(
            os.path.join(str(tmp_path), ckpt.read_latest(str(tmp_path))))
        assert groups["model_states"] == "sharded"
        assert groups["optim_states"] == "sharded"
        assert groups["meta"]

        served = InferenceEngine.from_checkpoint(
            str(tmp_path), cfg, inference_config=TINY_INF,
            dtype=jnp.float32)
        direct = InferenceEngine(cfg, params, TINY_INF,
                                 dtype=jnp.float32)
        prompts = [[1, 2, 3], [4, 5, 6, 7]]
        assert served.generate(prompts, max_new_tokens=4) == \
            direct.generate(prompts, max_new_tokens=4)

    def test_params_only_checkpoint_is_servable(self, tmp_path):
        """A tag carrying ONLY model_states (no optimizer group at all)
        loads — proof the bridge never requires training state."""
        from deepspeed_tpu.inference import InferenceEngine
        from deepspeed_tpu.runtime import checkpoint as ckpt
        cfg, params = tiny_gpt2()
        tag_dir = tmp_path / "weights_only"
        tag_dir.mkdir()
        ckpt.save_tree_sharded(str(tag_dir), "model_states", params)
        ckpt.write_meta(str(tag_dir), {"global_step": 0})
        ckpt.write_commit_marker(str(tag_dir))
        ckpt.write_latest(str(tmp_path), "weights_only")
        groups = ckpt.state_groups(str(tag_dir))
        assert groups["model_states"] == "sharded"
        assert groups["optim_states"] is None
        engine = InferenceEngine.from_checkpoint(
            str(tmp_path), cfg, inference_config=TINY_INF,
            dtype=jnp.float32)
        out = engine.generate([[1, 2, 3]], max_new_tokens=2)[0]
        assert len(out) == 5

    def test_qwz_quantized_weight_path(self, tmp_path):
        """quantize_weights=True ships params through the qwZ int8
        block format: the engine still serves, and greedy outputs stay
        close to the fp32 weights' (identical at this size — int8
        block quantization error is far below the logit gaps)."""
        from deepspeed_tpu.inference import InferenceEngine
        cfg, params = tiny_gpt2()
        self._save_training_checkpoint(tmp_path, cfg, params)
        q = InferenceEngine.from_checkpoint(
            str(tmp_path), cfg, inference_config=TINY_INF,
            dtype=jnp.float32, quantize_weights=True)
        # weights really were roundtripped through int8 blocks
        assert not np.allclose(np.asarray(q.params["wte"]),
                               np.asarray(params["wte"]))
        out = q.generate([[1, 2, 3]], max_new_tokens=3)[0]
        assert len(out) == 6 and all(0 <= t < 61 for t in out)

    def test_verify_checkpoint_cli_reports_state_groups(self, tmp_path,
                                                        capsys):
        """tools/verify_checkpoint.py names the state groups a committed
        tag contains (the satellite's reporting requirement)."""
        cfg, params = tiny_gpt2()
        self._save_training_checkpoint(tmp_path, cfg, params)
        vc = _load_tool("verify_checkpoint")
        assert vc.main([str(tmp_path)]) == 0
        out = capsys.readouterr().out
        assert "state groups:" in out
        assert "model_states(sharded)" in out
        assert "optim_states(sharded)" in out

    def test_from_checkpoint_rejects_corrupt(self, tmp_path):
        from deepspeed_tpu.inference import InferenceEngine
        cfg, _ = tiny_gpt2()
        with pytest.raises(FileNotFoundError):
            InferenceEngine.from_checkpoint(
                str(tmp_path), cfg, inference_config=TINY_INF)


# --------------------------------------------------------------------- #
# config section
# --------------------------------------------------------------------- #
class TestInferenceConfigSection:
    def test_defaults_parse(self):
        from deepspeed_tpu.runtime.config import get_inference_config
        cfg = get_inference_config({})
        assert cfg["max_batch_size"] == 8
        assert cfg["prompt_buckets"] == [64, 256]
        assert cfg["batch_buckets"] == [1, 8]
        assert cfg["temperature"] == 0.0 and cfg["top_k"] == 0

    def test_validation(self):
        from deepspeed_tpu.runtime.config import (DeepSpeedConfigError,
                                                  get_inference_config)
        with pytest.raises(DeepSpeedConfigError):
            get_inference_config(
                {"inference": {"prompt_buckets": [8, 4]}})
        with pytest.raises(DeepSpeedConfigError):
            get_inference_config(
                {"inference": {"batch_buckets": [16],
                               "max_batch_size": 8}})
        with pytest.raises(DeepSpeedConfigError):
            get_inference_config(
                {"inference": {"prompt_buckets": [2048],
                               "max_seq_len": 1024}})

    def test_rides_deepspeed_config(self):
        from deepspeed_tpu.runtime.config import DeepSpeedConfig
        cfg = DeepSpeedConfig({"train_micro_batch_size_per_gpu": 1,
                               "inference": {"max_batch_size": 2,
                                             "prompt_buckets": [16],
                                             "batch_buckets": [2],
                                             "max_seq_len": 64}},
                              world_size=1)
        assert cfg.inference_config["max_batch_size"] == 2
        assert cfg.inference_config["prompt_buckets"] == [16]


# --------------------------------------------------------------------- #
# paged KV cache (ISSUE 7 tentpole): page pool + block tables + prefix
# caching; occupancy bounded by tokens in flight, not slots x max_len
# --------------------------------------------------------------------- #
class TestPageAllocator:
    def _alloc(self, pages=9, ps=4, prefix=True):
        from deepspeed_tpu.inference.kv_cache import PageAllocator
        return PageAllocator(pages, ps, prefix_cache=prefix)

    def test_alloc_free_refcount(self):
        al = self._alloc()
        assert al.free_pages == 8 and al.pages_in_use == 0
        a = al.alloc(3)
        assert len(a) == 3 and al.pages_in_use == 3
        assert all(al.refcount(p) == 1 for p in a)
        assert al.alloc(6) is None          # partial grabs never happen
        assert al.free_pages == 5
        al.free(a)
        assert al.free_pages == 8 and al.pages_in_use == 0
        with pytest.raises(ValueError, match="unowned"):
            al.free(a[:1])

    def test_prefix_survives_until_last_reader_evicts(self):
        al = self._alloc()
        prompt = list(range(10))            # 2 full pages of 4 + tail
        owner = al.alloc(3)
        al.register_prefix(prompt, owner)
        shared, reused = al.match_prefix(prompt)
        assert shared == owner[:2] and reused == 8
        # a reader takes references on the shared pages
        al.incref(shared)
        assert al.refcount(owner[0]) == 2
        # owner evicts: shared pages SURVIVE (reader still holds them)
        al.free(owner)
        assert al.refcount(owner[0]) == 1
        assert al.match_prefix(prompt)[0] == owner[:2]
        # last reader evicts: pages return AND the prefix entry drops
        al.free(shared)
        assert al.free_pages == 8
        assert al.match_prefix(prompt) == ([], 0)

    def test_prefix_disabled(self):
        al = self._alloc(prefix=False)
        pages = al.alloc(2)
        al.register_prefix(list(range(8)), pages)
        assert al.match_prefix(list(range(8))) == ([], 0)

    def test_prefix_hit_verifies_content_not_just_hash(self):
        """A chain-hash collision (builtin tuple hashing is predictable,
        so craftable) must NOT hand one request another prompt's KV
        pages: hits verify the stored page's tokens."""
        al = self._alloc()
        prompt = list(range(8))
        owner = al.alloc(2)
        al.register_prefix(prompt, owner)
        other = [99] * 8
        # simulate the collision: point other's chain hash at owner's page
        h_other = next(al._chain_hashes(other))
        al._prefix[h_other] = owner[0]
        assert al.match_prefix(other) == ([], 0)     # content rejects
        assert al.match_prefix(prompt)[1] == 8       # genuine hit holds

    def test_prefix_hit_verifies_parent_chain_not_just_chunk(self):
        """Deep-layer K/V of page i depends on the WHOLE prefix before
        it, not just page i's own tokens — so a colliding entry whose
        chunk MATCHES but whose registered context differs must still be
        rejected. The parent-link check pins this: a hit at page i
        requires the candidate's registered predecessor to be the exact
        physical page matched at i-1."""
        al = self._alloc(pages=9, ps=4)
        attacker = [7, 7, 7, 7] + [1, 2, 3, 4]   # context A + chunk C
        ap = al.alloc(2)
        al.register_prefix(attacker, ap)
        victim = [0, 1, 2, 3] + [1, 2, 3, 4]     # context V + same chunk C
        vp = al.alloc(1)
        al.register_prefix(victim[:4], vp)        # page 0 registered honestly
        # simulate a chain-hash collision at the victim's page 1: the
        # index hands back the attacker's page, whose own chunk equals
        # the victim's — the old content-only check would accept it
        h_victim = list(al._chain_hashes(victim))[1]
        al._prefix[h_victim] = ap[1]
        got, n = al.match_prefix(victim)
        assert got == vp and n == 4      # page 1 rejected: wrong parent
        assert al.match_prefix(attacker)[0] == ap    # honest chain holds

    def test_divergent_prompts_share_only_common_pages(self):
        al = self._alloc(pages=17)
        a = list(range(12))
        b = list(range(8)) + [99, 98, 97, 96]    # diverges at page 2
        pa = al.alloc(3)
        al.register_prefix(a, pa)
        shared, reused = al.match_prefix(b)
        assert shared == pa[:2] and reused == 8

    def test_shared_duplicate_tokens(self):
        """Per-reader context sums count shared prefix pages once per
        reader; the allocator reports the exact overcount so
        ``tokens_in_flight`` can deduplicate."""
        al = self._alloc()
        owner = al.alloc(2)                      # 2 full shared pages
        al.register_prefix(list(range(8)), owner)
        assert al.shared_duplicate_tokens == 0   # one owner, no dupes
        al.incref(owner)                         # reader 1
        al.incref(owner)                         # reader 2
        assert al.shared_duplicate_tokens == 2 * 2 * 4
        al.free(owner)                           # one reference drops
        assert al.shared_duplicate_tokens == 2 * 4
        al.free(owner)
        al.free(owner)
        assert al.shared_duplicate_tokens == 0

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_shared_duplicate_tokens_is_kept_not_walked(self, seed):
        """The count is kept as references come and go (the scheduler
        reads it three times a step and a walk over a pool of 45,056
        pages was a quarter of the host's serial section, PR 43): after
        any run of alloc / incref / free, a refused incref and a refused
        free among them, it equals the walk over the refcounts."""
        rs = np.random.RandomState(seed)
        al = self._alloc(pages=65)
        held = []                                # one entry a reference

        def walked():
            return sum((c - 1) * al.page_size
                       for c in al._ref.values() if c > 1)

        for _ in range(400):
            op = rs.randint(4)
            if op == 0:
                pages = al.alloc(int(rs.randint(1, 5)))
                if pages is not None:
                    held.append(pages)
            elif op == 1 and held:
                pages = held[rs.randint(len(held))]
                al.incref(pages)
                held.append(list(pages))
            elif op == 2 and held:
                al.free(held.pop(rs.randint(len(held))))
            elif op == 3:
                free_page = next((p for p in range(1, al.num_pages)
                                  if not al.refcount(p)), None)
                if free_page is not None:
                    owned = held[0][:1] if held else []
                    with pytest.raises(ValueError):
                        al.incref(owned + [free_page])
                    al.free(owned)           # undo the half that landed
                    with pytest.raises(ValueError):
                        al.free([free_page])
            assert al.shared_duplicate_tokens == walked()
        for pages in held:
            al.free(pages)
        assert al.shared_duplicate_tokens == 0 and not al._ref


class TestPagedServing:
    @pytest.mark.parametrize("family", ["gpt2", "llama"])
    def test_paged_vs_dense_generate_parity_small_pool(self, family):
        """ISSUE 7 acceptance: a mixed-length workload whose DENSE
        footprint exceeds the page pool (6 live requests x max_len 32 =
        192 token-slots dense; the pool holds 44) serves with greedy
        outputs EXACTLY matching the dense path, for both families,
        under continuous batching."""
        from deepspeed_tpu.inference import InferenceEngine
        cfg, params = tiny_gpt2() if family == "gpt2" else tiny_llama()
        rng = np.random.RandomState(7)
        prompts = [rng.randint(1, 61, (n,)).tolist()
                   for n in (3, 5, 7, 2, 8, 4, 6, 1)]
        dense = InferenceEngine(
            cfg, params, dict(TINY_INF, paged_kv={"enabled": False}),
            dtype=jnp.float32)
        ref = dense.generate(prompts, max_new_tokens=4, temperature=0.0)
        paged = InferenceEngine(
            cfg, params,
            dict(TINY_INF, paged_kv={"page_size": 4, "num_pages": 12}),
            dtype=jnp.float32)
        assert paged.paged and paged.scheduler.allocator is not None
        got = paged.generate(prompts, max_new_tokens=4, temperature=0.0)
        assert got == ref
        # every page returned once the workload drained
        al = paged.scheduler.allocator
        assert al.pages_in_use == 0 and al.free_pages == 11
        assert paged.scheduler.peak_tokens_in_flight > 0

    @pytest.mark.parametrize("versus", ["dense", "int8_pool"])
    def test_tokens_in_flight_per_cache_byte(self, versus):
        """What the page pool buys, as scheduler counts at an EQUAL
        cache budget: against the dense slot x max_len cache the pool
        holds at least twice the live tokens per byte on a mixed-length
        workload (the dense geometry charges every slot max_len up
        front); against a float pool of the same page geometry the int8
        pool packs the same peak concurrency into fewer bytes. Zero
        steady-state recompiles under the churn either way."""
        from deepspeed_tpu.inference import (InferenceEngine,
                                             kv_cache_bytes,
                                             paged_kv_bytes)
        cfg, params = tiny_gpt2()
        ps, max_len, dense_slots = 4, 32, 3
        # equal budget: dense (slots + 1) rows x max_len == the pool
        num_pages = (dense_slots + 1) * (max_len // ps)
        rng = np.random.RandomState(0)
        prompts = [rng.randint(1, 61, (n,)).tolist()
                   for n in (5, 8, 3, 7, 2, 6, 4, 8, 3, 5, 7, 2)]
        wide = dict(TINY_INF, max_batch_size=12, batch_buckets=[1, 4, 12])

        def serve(icfg):
            eng = InferenceEngine(cfg, params, icfg, dtype=jnp.float32)
            eng.warmup()
            outs = eng.generate(prompts, max_new_tokens=4,
                                temperature=0.0)
            assert eng.steady_state_recompiles == 0
            nbytes = paged_kv_bytes(eng.paged_spec) if eng.paged \
                else kv_cache_bytes(eng.cache_spec)
            return outs, eng.scheduler.peak_tokens_in_flight, nbytes

        pool = {"page_size": ps, "num_pages": num_pages}
        outs, peak, nbytes = serve(dict(wide, paged_kv=pool))
        if versus == "dense":
            ref_outs, ref_peak, ref_bytes = serve(
                dict(TINY_INF, paged_kv={"enabled": False}))
            assert ref_outs == outs
            assert nbytes <= ref_bytes
            assert peak / nbytes >= 2.0 * ref_peak / ref_bytes
        else:
            _, q_peak, q_bytes = serve(dict(
                wide, paged_kv=dict(pool, kv_dtype="int8")))
            assert q_peak == peak and q_bytes < nbytes

    def test_paged_sampling_parity_with_dense(self):
        """Temperature sampling keys are position-based: the paged path
        must reproduce the dense stream exactly (same fold_in schedule
        even when a prefix offset splits prefill)."""
        from deepspeed_tpu.inference import InferenceEngine
        cfg, params = tiny_gpt2()
        prompts = [[1, 2, 3], [4, 5], [6, 7, 8, 9]]
        dense = InferenceEngine(
            cfg, params, dict(TINY_INF, paged_kv={"enabled": False}),
            dtype=jnp.float32)
        paged = InferenceEngine(
            cfg, params,
            dict(TINY_INF, paged_kv={"page_size": 4, "num_pages": 16}),
            dtype=jnp.float32)
        kw = dict(max_new_tokens=5, temperature=0.8, seeds=[7, 8, 9])
        assert paged.generate(prompts, **kw) == dense.generate(prompts,
                                                               **kw)

    def test_prefix_cache_shares_pages_with_parity(self):
        """Repeated system prompts prefill once: later requests reuse
        the registered pages (hit tokens > 0), outputs stay exactly the
        dense path's, and the shared pages free only after the last
        reader evicts."""
        from deepspeed_tpu.inference import InferenceEngine
        cfg, params = tiny_gpt2()
        sys_prompt = list(range(1, 9))          # 2 full pages of 4
        prompts = [sys_prompt + [10], sys_prompt + [20, 21],
                   sys_prompt[:]]
        icfg = dict(TINY_INF, prompt_buckets=[4, 16], max_seq_len=32,
                    paged_kv={"page_size": 4, "num_pages": 20})
        dense = InferenceEngine(
            cfg, params, dict(icfg, paged_kv={"enabled": False}),
            dtype=jnp.float32)
        ref = dense.generate(prompts, max_new_tokens=3, temperature=0.0)
        paged = InferenceEngine(cfg, params, icfg, dtype=jnp.float32)
        got = paged.generate(prompts, max_new_tokens=3, temperature=0.0)
        assert got == ref
        al = paged.scheduler.allocator
        assert al.prefix_hit_tokens >= 8        # later prompts reused
        assert al.pages_in_use == 0             # all returned at drain

    def test_prefix_cache_off_still_serves(self):
        from deepspeed_tpu.inference import InferenceEngine
        cfg, params = tiny_gpt2()
        paged = InferenceEngine(
            cfg, params,
            dict(TINY_INF, paged_kv={"page_size": 4, "num_pages": 20,
                                     "prefix_cache": False}),
            dtype=jnp.float32)
        outs = paged.generate([[1, 2, 3], [1, 2, 3]], max_new_tokens=3)
        assert outs[0] == outs[1]
        assert paged.scheduler.allocator.prefix_hit_tokens == 0

    def test_warmup_program_count_and_zero_recompiles_under_churn(self):
        """ISSUE 7 CI satellite: with paging enabled, warmup compiles
        EXACTLY len(batch_buckets) x len(prompt_buckets) prefill
        programs + the one paged decode program; a mixed-length churn
        workload (page alloc/free + prefix reuse + slot turnover) then
        compiles NOTHING more (CompileTracker-exact)."""
        from deepspeed_tpu.inference import InferenceEngine
        cfg, params = tiny_gpt2()
        engine = InferenceEngine(
            cfg, params,
            dict(TINY_INF, paged_kv={"page_size": 4, "num_pages": 14}),
            dtype=jnp.float32)
        programs = engine.warmup()
        # (the merge of first tokens into the device's: a batch bucket)
        assert programs == 2 * 2 + 1 + 2
        assert engine.compile_tracker.counts == {"prefill": 4,
                                                 "decode": 1,
                                                 "merge_tokens": 2}
        rng = np.random.RandomState(5)
        sys_prompt = rng.randint(1, 61, (4,)).tolist()
        churn = [rng.randint(1, 61, (n,)).tolist()
                 for n in (1, 4, 5, 8, 3, 6, 2, 7)]
        churn += [sys_prompt + [int(t)] for t in rng.randint(1, 61, (4,))]
        engine.generate(churn, max_new_tokens=3)
        engine.generate(churn[:3], max_new_tokens=5, temperature=0.5)
        assert engine.steady_state_recompiles == 0
        assert engine.compile_tracker.total_compiles == programs

    def test_paged_telemetry_lands_in_events(self, tmp_path):
        from deepspeed_tpu.inference import InferenceEngine
        cfg, params = tiny_gpt2()
        icfg = dict(TINY_INF, events_dir=str(tmp_path),
                    paged_kv={"page_size": 4, "num_pages": 20})
        engine = InferenceEngine(cfg, params, icfg, dtype=jnp.float32)
        engine.warmup()
        engine.generate([[1, 2, 3], [1, 2, 3], [4, 5]],
                        max_new_tokens=4)
        engine.close()
        rows = [json.loads(line)
                for line in open(tmp_path / "events.jsonl")]
        tags = {r["tag"] for r in rows if "tag" in r}
        assert {"Serve/kv_pages_in_use", "Serve/tokens_in_flight",
                "Serve/prefix_hit_rate"} <= tags
        pages = [r["value"] for r in rows
                 if r.get("tag") == "Serve/kv_pages_in_use"]
        assert max(pages) > 0
        obs_report = _load_tool("obs_report")
        s = obs_report.summarize(str(tmp_path))
        pk = s["serving"]["paged_kv"]
        assert pk["pages_in_use_peak"] > 0
        assert pk["tokens_in_flight_peak"] > 0
        assert "paged_kv" in obs_report.render(s)

    def test_submit_rejects_request_larger_than_pool(self):
        # ISSUE 19: graceful rejection — the caller sees an ordinary
        # FinishedRequest with the pinned reason from the next step
        from deepspeed_tpu.inference import InferenceEngine
        from deepspeed_tpu.inference.scheduler import Request
        cfg, params = tiny_gpt2()
        engine = InferenceEngine(
            cfg, params,
            dict(TINY_INF, paged_kv={"page_size": 4, "num_pages": 3}),
            dtype=jnp.float32)
        uid = engine.submit(Request(prompt=[1, 2, 3], max_new_tokens=8))
        fins = engine.step()
        assert [f.uid for f in fins] == [uid]
        assert fins[0].finish_reason == "reject_too_long"


class TestLookaheadAdmission:
    """ISSUE 7 satellite: bounded-lookahead admission — a head request
    that doesn't fit the free pages must not stall the whole queue."""

    def _sched(self, lookahead, pages=10, ps=4, occupy=True):
        """Scheduler with 9 usable pages; ``occupy`` admits a resident
        8-page request so only ONE page stays free — a later 8-page head
        fits the pool in principle (submit accepts it) but not the
        current free pages (admission must look past it)."""
        from deepspeed_tpu.inference.kv_cache import PageAllocator
        from deepspeed_tpu.inference.scheduler import Request, Scheduler
        s = Scheduler(3, (4, 16), (1, 2), 32,
                      allocator=PageAllocator(pages, ps),
                      lookahead=lookahead)
        if occupy:
            resident = Request(prompt=[9] * 16, max_new_tokens=16)
            s.submit(resident)
            (batch,) = s.admit()
            s.record_tokens({batch.slot_ids[0]: 1})   # mid-decode
            assert s.allocator.free_pages == pages - 1 - 8
        return s

    def test_small_request_behind_big_head_lands(self):
        from deepspeed_tpu.inference.scheduler import Request
        s = self._sched(lookahead=4)
        big = Request(prompt=[1] * 16, max_new_tokens=16)   # 8 pages
        small = Request(prompt=[2, 3], max_new_tokens=2)    # 1 page
        s.submit(big)
        s.submit(small)
        (batch,) = s.admit()
        assert [r.uid for r in batch.requests] == [small.uid]
        assert s.queue_depth == 1                # big still waiting
        # small finishes -> its page frees -> big still blocked (needs
        # 8, 2 free): the queue drains only when capacity appears
        sid = batch.slot_ids[0]
        s.record_tokens({sid: 1})
        s.record_tokens({sid: 2})
        assert s.admit() == []

    def test_strict_fifo_blocks_without_lookahead(self):
        from deepspeed_tpu.inference.scheduler import Request
        s = self._sched(lookahead=0)
        s.submit(Request(prompt=[1] * 16, max_new_tokens=16))
        s.submit(Request(prompt=[2, 3], max_new_tokens=2))
        assert s.admit() == []                   # head-of-line blocked

    def test_lookahead_window_is_bounded(self):
        from deepspeed_tpu.inference.scheduler import Request
        s = self._sched(lookahead=1)
        s.submit(Request(prompt=[1] * 16, max_new_tokens=16))
        s.submit(Request(prompt=[3] * 16, max_new_tokens=16))
        fits = Request(prompt=[2, 3], max_new_tokens=2)
        s.submit(fits)                           # position 2 > window
        assert s.admit() == []
        s2 = self._sched(lookahead=2)
        s2.submit(Request(prompt=[1] * 16, max_new_tokens=16))
        s2.submit(Request(prompt=[3] * 16, max_new_tokens=16))
        fits2 = Request(prompt=[2, 3], max_new_tokens=2)
        s2.submit(fits2)
        (batch,) = s2.admit()
        assert [r.uid for r in batch.requests] == [fits2.uid]

    def test_fifo_order_restored_when_head_fits(self):
        from deepspeed_tpu.inference.scheduler import Request
        s = self._sched(lookahead=4, pages=20)
        a = Request(prompt=[1, 2], max_new_tokens=2)
        b = Request(prompt=[3, 4], max_new_tokens=2)
        s.submit(a)
        s.submit(b)
        (batch,) = s.admit()
        assert [r.uid for r in batch.requests] == [a.uid, b.uid]


class TestTokensInFlight:
    def test_shared_prefix_counted_once(self):
        """``tokens_in_flight`` reports physical pool occupancy: a
        prefix shared by N readers lands once, not N times."""
        from deepspeed_tpu.inference.kv_cache import PageAllocator
        from deepspeed_tpu.inference.scheduler import Request, Scheduler
        s = Scheduler(3, (4, 16), (1, 2), 32,
                      allocator=PageAllocator(20, 4))
        prompt = [1, 2, 3, 4, 5, 6, 7, 8]
        s.submit(Request(prompt=prompt, max_new_tokens=4))
        s.submit(Request(prompt=prompt, max_new_tokens=4))
        s.admit()
        # reuse caps one token short of the prompt -> the second reader
        # shares exactly the first page (4 of its 8 context tokens)
        assert s.allocator.shared_duplicate_tokens == 4
        assert s.tokens_in_flight == 8 + 8 - 4
        assert s.peak_tokens_in_flight == 12
class TestServingMesh:
    MESH_INF = dict(TINY_INF, mesh={"axes": {"model": 2}})

    @pytest.mark.parametrize("family", ["gpt2", "llama"])
    def test_sharded_decode_parity(self, family):
        """Tensor-parallel serving over a 2-way CPU mesh: greedy outputs
        exactly match the unsharded engine for both families (llama
        exercises the GQA kv_heads split)."""
        from deepspeed_tpu.inference import InferenceEngine
        cfg, params = tiny_gpt2() if family == "gpt2" else tiny_llama()
        rng = np.random.RandomState(3)
        prompts = [rng.randint(1, 61, (n,)).tolist()
                   for n in (3, 5, 7, 2, 8)]
        base = InferenceEngine(cfg, params, TINY_INF, dtype=jnp.float32)
        ref = base.generate(prompts, max_new_tokens=4, temperature=0.0)
        sharded = InferenceEngine(cfg, params, self.MESH_INF,
                                  dtype=jnp.float32)
        assert sharded.mesh is not None
        assert dict(sharded.mesh.shape) == {"model": 2}
        got = sharded.generate(prompts, max_new_tokens=4,
                               temperature=0.0)
        assert got == ref
        # params really live sharded: a column-parallel leaf is split
        from jax.sharding import PartitionSpec as P
        leaf = sharded.params["h_0"]["attn"][
            "qkvw" if family == "gpt2" else "wq"]
        assert leaf.sharding.spec == P(None, "model")

    def test_sharded_zero_steady_state_recompiles(self):
        from deepspeed_tpu.inference import InferenceEngine
        cfg, params = tiny_gpt2()
        engine = InferenceEngine(cfg, params, self.MESH_INF,
                                 dtype=jnp.float32)
        programs = engine.warmup()
        # (the merge of first tokens into the device's: a batch bucket)
        assert programs == 2 * 2 + 1 + 2
        rng = np.random.RandomState(2)
        prompts = [rng.randint(1, 61, (n,)).tolist()
                   for n in (1, 4, 5, 8, 3)]
        engine.generate(prompts, max_new_tokens=3)
        assert engine.steady_state_recompiles == 0

    def test_from_checkpoint_reshards_onto_serving_mesh(self, tmp_path):
        """Train on the default (unsharded) layout, serve on a model=2
        mesh: from_checkpoint materializes the params straight into the
        serving NamedShardings and outputs match the in-memory
        engine."""
        import deepspeed_tpu
        from deepspeed_tpu.inference import InferenceEngine
        from deepspeed_tpu.models.gpt2 import gpt2_loss_fn
        cfg, params = tiny_gpt2()
        engine, *_ = deepspeed_tpu.initialize(
            model=gpt2_loss_fn(cfg, dtype=jnp.float32,
                               deterministic=True),
            model_parameters=params,
            config={"train_micro_batch_size_per_gpu": 1,
                    "gradient_accumulation_steps": 1,
                    "steps_per_print": 10**9,
                    "optimizer": {"type": "Adam",
                                  "params": {"lr": 1e-3}}})
        engine.save_checkpoint(str(tmp_path))
        served = InferenceEngine.from_checkpoint(
            str(tmp_path), cfg, inference_config=self.MESH_INF,
            dtype=jnp.float32)
        assert served.mesh is not None
        from jax.sharding import PartitionSpec as P
        assert served.params["h_0"]["mlp"]["fc_w"].sharding.spec == \
            P(None, "model")
        direct = InferenceEngine(cfg, params, TINY_INF,
                                 dtype=jnp.float32)
        prompts = [[1, 2, 3], [4, 5, 6, 7]]
        assert served.generate(prompts, max_new_tokens=4) == \
            direct.generate(prompts, max_new_tokens=4)

    def test_mesh_rejects_indivisible_heads(self):
        from deepspeed_tpu.inference import InferenceEngine
        cfg, params = tiny_gpt2()          # 4 heads
        with pytest.raises(ValueError, match="divide"):
            InferenceEngine(cfg, params,
                            dict(TINY_INF, mesh={"axes": {"model": 3}}),
                            dtype=jnp.float32)


# --------------------------------------------------------------------- #
# new config keys
# --------------------------------------------------------------------- #
class TestPagedConfigSection:
    def test_defaults(self):
        from deepspeed_tpu.runtime.config import get_inference_config
        cfg = get_inference_config({})
        assert cfg["paged_kv"] == {"enabled": True, "page_size": 16,
                                   "num_pages": 0, "prefix_cache": True,
                                   "attn_kernel": "pallas",
                                   "decode_page_buckets": [],
                                   "kv_dtype": None, "kv_quant_block": 0}
        assert cfg["mesh"] == {"axes": {}}
        assert cfg["admit_lookahead"] == 4

    def test_validation(self):
        from deepspeed_tpu.runtime.config import (DeepSpeedConfigError,
                                                  get_inference_config)
        with pytest.raises(DeepSpeedConfigError, match="page_size"):
            get_inference_config(
                {"inference": {"paged_kv": {"page_size": 0}}})
        with pytest.raises(DeepSpeedConfigError, match="num_pages"):
            get_inference_config(
                {"inference": {"paged_kv": {"num_pages": 1}}})
        with pytest.raises(DeepSpeedConfigError, match="admit_lookahead"):
            get_inference_config({"inference": {"admit_lookahead": -1}})
        with pytest.raises(DeepSpeedConfigError, match="mesh.axes"):
            get_inference_config(
                {"inference": {"mesh": {"axes": {"model": 0}}}})
        # unknown axis names fail HERE with a curated message, not as
        # an opaque jax resource error deep in engine init
        with pytest.raises(DeepSpeedConfigError, match="'model'"):
            get_inference_config(
                {"inference": {"mesh": {"axes": {"tp": 2}}}})

    def test_auto_pool_matches_dense_worst_case(self):
        from deepspeed_tpu.inference import InferenceEngine
        cfg, params = tiny_gpt2()
        engine = InferenceEngine(cfg, params, TINY_INF,
                                 dtype=jnp.float32)
        # max_batch_size 3, max_len 32, page_size 16 -> 3*2 + null
        assert engine.paged_spec.num_pages == 7
        assert engine.paged_spec.pages_per_seq == 2


# --------------------------------------------------------------------- #
# quantized serving (ISSUE 17)
# --------------------------------------------------------------------- #
class TestQuantizedServing:
    """int8-resident weights + int8 KV page pool: the serving bytes
    halve on both levers while greedy decode stays within the pinned
    error budget — and the zero-recompile/continuous-batching pins
    hold with quantization on."""

    # max |logits_fp - logits_quant| budget at the tiny geometry: the
    # measured error is ~0.02; 0.05 leaves slack without ever letting a
    # real regression (e.g. a dropped scale) through
    LOGIT_BUDGET = 0.05

    @pytest.mark.parametrize("family", ["gpt2", "llama"])
    @pytest.mark.parametrize("mode", ["weights", "kv", "both"])
    def test_quant_matrix_greedy_and_zero_recompiles(self, family,
                                                     mode):
        """The quantized-serving matrix: each quantization lever (and
        both together) serves the mixed-length prefix-sharing workload
        under continuous batching with greedy outputs matching the fp
        engine (the quantization error at this scale sits far below
        the logit gaps — the budget itself is pinned by the logit-err
        probe test) and zero steady-state recompiles."""
        from deepspeed_tpu.inference import InferenceEngine
        from deepspeed_tpu.runtime.quantized_params import \
            is_quantized_tree
        cfg, params = tiny_gpt2() if family == "gpt2" else tiny_llama()
        rng = np.random.RandomState(11)
        # 2 full pages of shared system prompt + staggered readers (the
        # admission batches split 2+1, so the later reader reuses the
        # registered prefix pages)
        sys_prompt = rng.randint(1, 61, (8,)).tolist()
        prompts = [rng.randint(1, 61, (n,)).tolist()
                   for n in (3, 6, 2, 7)]
        prompts += [sys_prompt + [10], sys_prompt + [20, 21],
                    sys_prompt[:]]
        base_inf = dict(TINY_INF, prompt_buckets=[4, 16])

        extra = {}
        if mode in ("weights", "both"):
            extra["quantize_weights"] = "int8"
        pk = {"page_size": 4, "num_pages": 20}
        if mode in ("kv", "both"):
            pk["kv_dtype"] = "int8"
            if mode == "both":
                pk["kv_quant_block"] = 4
        ref_eng = InferenceEngine(
            cfg, params, dict(base_inf, paged_kv=dict(
                page_size=4, num_pages=20)), dtype=jnp.float32)
        ref = ref_eng.generate(prompts, max_new_tokens=4,
                               temperature=0.0)
        q_eng = InferenceEngine(
            cfg, params, dict(base_inf, paged_kv=pk, **extra),
            dtype=jnp.float32)
        q_eng.warmup()
        got = q_eng.generate(prompts, max_new_tokens=4,
                             temperature=0.0)
        assert got == ref
        assert q_eng.steady_state_recompiles == 0
        assert is_quantized_tree(q_eng.params) == \
            (mode in ("weights", "both"))
        assert len(q_eng._cache) == (4 if mode in ("kv", "both")
                                     else 2)
        dq = q_eng.debug_state()["quantization"]
        assert dq["weights_resident"] == (
            "int8" if mode in ("weights", "both") else "off")
        assert dq["kv_dtype"] == ("int8" if mode in ("kv", "both")
                                  else "float32")
        if mode in ("weights", "both"):
            assert dq["weight_bytes"] < dq["weight_bytes_dense"]
        # prefix reuse really happened under quantization
        assert q_eng.scheduler.allocator.prefix_hit_tokens >= 4

    @pytest.mark.parametrize("family", ["gpt2", "llama"])
    def test_quant_logit_err_budget_and_probe(self, family, tmp_path):
        """The pinned error budget (NOT bitwise): max logit delta of
        the int8-resident forward vs the fp forward stays under
        LOGIT_BUDGET, and recording it on the engine lands the
        Serve/quant_logit_err scalar + debug_state field + obs_report
        quantization block."""
        from deepspeed_tpu.inference import InferenceEngine
        from deepspeed_tpu.runtime.quantized_params import \
            quantize_param_tree
        if family == "gpt2":
            from deepspeed_tpu.models.gpt2 import gpt2_forward as fwd
            cfg, params = tiny_gpt2()
        else:
            from deepspeed_tpu.models.llama import llama_forward as fwd
            cfg, params = tiny_llama()
        rng = np.random.RandomState(12)
        ids = jnp.asarray(rng.randint(1, 61, (2, 8)), jnp.int32)
        logits_fp = fwd(params, cfg, ids, dtype=jnp.float32)
        logits_q = fwd(quantize_param_tree(params), cfg, ids,
                       dtype=jnp.float32)
        err = float(jnp.max(jnp.abs(logits_fp - logits_q)))
        assert 0.0 < err < self.LOGIT_BUDGET

        icfg = dict(TINY_INF, events_dir=str(tmp_path),
                    quantize_weights="int8",
                    paged_kv={"page_size": 4, "num_pages": 20,
                              "kv_dtype": "int8"})
        eng = InferenceEngine(cfg, params, icfg, dtype=jnp.float32)
        eng.record_quant_logit_err(err)
        eng.generate([[1, 2, 3], [4, 5]], max_new_tokens=3)
        state = eng.debug_state()
        assert state["quantization"]["quant_logit_err"] == err
        assert state["quantization"]["kv_pool_bytes_per_token"] > 0
        eng.close()
        rows = [json.loads(line)
                for line in open(tmp_path / "events.jsonl")]
        tags = {r["tag"] for r in rows if "tag" in r}
        assert {"Serve/quant_logit_err",
                "Serve/kv_pool_bytes_per_token"} <= tags
        obs_report = _load_tool("obs_report")
        s = obs_report.summarize(str(tmp_path))
        qz = s["serving"]["quantization"]
        assert qz["quant_logit_err"] == pytest.approx(err)
        assert qz["kv_pool_bytes_per_token"] > 0

    def test_all_levers_plus_spec_decode_zero_recompiles(self):
        """ISSUE 17 acceptance: quant-weights + quant-KV + spec-decode
        all ON — greedy outputs bitwise match the same quantized
        engine without speculation, steady_state_recompiles == 0, and
        every submitted request finishes exactly once."""
        from deepspeed_tpu.inference import InferenceEngine
        cfg, params = tiny_gpt2()
        quant = {"quantize_weights": "int8",
                 "paged_kv": {"page_size": 4, "num_pages": 20,
                              "kv_dtype": "int8"}}
        # repetitive prompts so the n-gram drafter actually proposes
        prompts = [[1, 2, 3, 1, 2, 3, 1, 2], [4, 5, 4, 5, 4, 5],
                   [7, 8, 9, 7, 8, 9, 7]]
        base = InferenceEngine(cfg, params, dict(TINY_INF, **quant),
                               dtype=jnp.float32)
        base.warmup()
        ref = base.generate(prompts, max_new_tokens=8,
                            temperature=0.0)
        spec = InferenceEngine(
            cfg, params,
            dict(TINY_INF, spec_decode={"enabled": True, "k": 4},
                 **quant), dtype=jnp.float32)
        spec.warmup()
        got = spec.generate(prompts, max_new_tokens=8, temperature=0.0)
        assert got == ref
        assert spec.steady_state_recompiles == 0
        assert base.steady_state_recompiles == 0
        st = spec.debug_state()
        assert st["quantization"]["weights_resident"] == "int8"
        assert st["quantization"]["kv_dtype"] == "int8"

    def test_both_byte_levers_at_a_serving_width(self):
        """Pure accounting at head_dim 128, against bf16 serving at the
        same geometry: the int8-resident weight tree (block 256, 1-D
        leaves left dense) and the int8 page pool with its per-row
        scales each take under 1/1.8 of the bf16 bytes, and a decode
        step's modeled K/V read shrinks by the pool's ratio."""
        from deepspeed_tpu.inference.kv_cache import (paged_kv_bytes,
                                                      paged_spec_for)
        from deepspeed_tpu.models.gpt2 import (GPT2Config,
                                               init_gpt2_params)
        from deepspeed_tpu.ops.attention.paged import decode_read_bytes
        from deepspeed_tpu.runtime.quantized_params import (
            quantize_param_tree, quantized_tree_bytes)
        cfg = GPT2Config(vocab_size=256, max_position_embeddings=512,
                         hidden_size=512, num_layers=2, num_heads=4)
        params = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16),
            init_gpt2_params(cfg, jax.random.PRNGKey(0)))
        resident, dense = quantized_tree_bytes(
            quantize_param_tree(params, 256))
        assert dense / resident >= 1.8

        ps = 16
        bf16 = paged_spec_for(cfg, 144, ps, 256, dtype=jnp.bfloat16)
        int8 = paged_spec_for(cfg, 144, ps, 256, dtype=jnp.int8,
                              kv_quant_block=0)
        assert paged_kv_bytes(bf16) / paged_kv_bytes(int8) >= 1.8
        positions = [n + 8 for n in (5, 9, 14, 3, 16, 7, 12, 4)]
        bf16_step, _ = decode_read_bytes(
            positions, ps, bf16.pages_per_seq, bf16.kv_heads,
            bf16.head_dim, dtype_bytes=2)
        int8_step, _ = decode_read_bytes(
            positions, ps, int8.pages_per_seq, int8.kv_heads,
            int8.head_dim, dtype_bytes=1, scale_blocks=int8.scale_blocks)
        assert bf16_step / int8_step >= 1.8

    def test_quant_config_normalization_and_validation(self):
        from deepspeed_tpu.runtime.config import (DeepSpeedConfigError,
                                                  get_inference_config)
        c = get_inference_config({})
        assert c["quantize_weights"] is False
        assert c["paged_kv"]["kv_dtype"] is None
        assert c["paged_kv"]["kv_quant_block"] == 0
        # legacy boolean means wire-quantize, dequantize to bf16
        c = get_inference_config(
            {"inference": {"quantize_weights": True}})
        assert c["quantize_weights"] == "bf16"
        c = get_inference_config(
            {"inference": {"quantize_weights": "int8",
                           "paged_kv": {"kv_dtype": "int8",
                                        "kv_quant_block": 8}}})
        assert c["quantize_weights"] == "int8"
        assert c["paged_kv"]["kv_dtype"] == "int8"
        assert c["paged_kv"]["kv_quant_block"] == 8
        with pytest.raises(DeepSpeedConfigError,
                           match="quantize_weights"):
            get_inference_config(
                {"inference": {"quantize_weights": "fp8"}})
        with pytest.raises(DeepSpeedConfigError, match="kv_dtype"):
            get_inference_config(
                {"inference": {"paged_kv": {"kv_dtype": "fp4"}}})
        with pytest.raises(DeepSpeedConfigError,
                           match="kv_quant_block"):
            get_inference_config(
                {"inference": {"paged_kv": {"kv_quant_block": 4}}})
