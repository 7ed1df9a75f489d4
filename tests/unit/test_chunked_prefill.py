# Copyright The DeepSpeed-TPU authors. Licensed under Apache 2.0.
"""Chunked prefill + context-parallel long-prompt serving (ISSUE 19).

The acceptance contract, as tests:

- bitwise greedy parity chunked vs whole-prompt prefill for gpt2 AND
  llama, under continuous batching + prefix reuse + spec-decode;
- context-parallel chunks (ring K/V rotation over the serving mesh)
  keep the same bitwise parity while actually engaging the mesh;
- an over-length prompt is a graceful ``reject_too_long`` with
  chunking OFF and SERVES with chunking ON — never a crash, never a
  silent truncation;
- zero steady-state recompiles under mixed long/short churn (the
  prompt-bucket ladder collapse: one chunk width, any prompt length);
- the trail shows the chunk state machine: one ``serve_prefill_chunk``
  row per chunk, cum_ms monotone, and TTFT decomposing into
  ``queue + prefill`` with the chunk legs inside the prefill leg.
"""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest


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


def family(name):
    return tiny_gpt2() if name == "gpt2" else tiny_llama()


# prompts exercising the mix the parity pin demands: one long prompt
# over every short bucket, a short ride-along, a prefix-sharing sibling
# of the long one (prefix cache reuse), and repetition so the n-gram
# spec drafter actually proposes
LONG = [1, 2, 3, 4] * 5                       # 20 tokens
PROMPTS = [LONG, [5, 6, 7], LONG[:8] + [9, 10], [8, 9, 8, 9, 8, 9]]

CHUNKED_INF = {"max_batch_size": 3, "prompt_buckets": [4],
               "batch_buckets": [2], "max_seq_len": 32,
               "max_new_tokens": 6,
               "paged_kv": {"page_size": 4, "num_pages": 24},
               "chunked_prefill": {"enabled": True, "chunk_tokens": 8}}
# the whole-prompt reference: a ladder tall enough to cover LONG
WHOLE_INF = dict(CHUNKED_INF, prompt_buckets=[4, 24],
                 chunked_prefill={"enabled": False})
SPEC = {"spec_decode": {"enabled": True, "k": 4}}


def serve(cfg, params, icfg, prompts, **eng_kw):
    from deepspeed_tpu.inference import InferenceEngine
    eng = InferenceEngine(cfg, params, icfg, dtype=jnp.float32, **eng_kw)
    eng.warmup()
    outs = eng.generate(prompts, max_new_tokens=6, temperature=0.0)
    rc = eng.steady_state_recompiles
    state = eng.debug_state()
    eng.close()
    return outs, rc, state


# one whole-prompt (spec-decode on) reference run per family, shared by
# the chunked and the context-parallel parity tests — the comparison
# target is identical, recomputing it would only re-pay the warmup
_REF = {}


def whole_prompt_ref(name):
    if name not in _REF:
        cfg, params = family(name)
        outs, rc, _ = serve(cfg, params, dict(WHOLE_INF, **SPEC),
                            PROMPTS)
        assert rc == 0
        _REF[name] = outs
    return _REF[name]


class TestChunkedParity:
    @pytest.mark.parametrize("name", ["gpt2", "llama"])
    def test_bitwise_parity_with_prefix_reuse_and_spec(self, name):
        """Chunked prefill vs whole-prompt prefill: greedy outputs
        bitwise equal for both model families, with the prefix cache
        live and spec-decode verifying drafts on both engines."""
        cfg, params = family(name)
        got, ck_rc, state = serve(cfg, params,
                                  dict(CHUNKED_INF, **SPEC), PROMPTS)
        assert got == whole_prompt_ref(name)
        assert ck_rc == 0
        ck = state["chunked_prefill"]
        assert ck["chunk_tokens"] == 8
        assert ck["dispatches"] > 0          # LONG really went chunked

    @pytest.mark.parametrize("name", ["gpt2", "llama"])
    def test_context_parallel_parity_on_mesh(self, name):
        """CP chunks (ring K/V rotation, 2-way over the conftest's
        virtual 8-device CPU backend) match the unsharded whole-prompt
        engine bitwise — spec-decode still on — and really engaged the
        mesh (no silent fallback)."""
        cfg, params = family(name)
        icfg = dict(CHUNKED_INF, mesh={"axes": {"model": 2}},
                    chunked_prefill={"enabled": True, "chunk_tokens": 8,
                                     "cp_threshold_tokens": 8}, **SPEC)
        got, rc, state = serve(cfg, params, icfg, PROMPTS)
        assert got == whole_prompt_ref(name)
        assert rc == 0
        ck = state["chunked_prefill"]
        assert ck["cp_shards"] == 2
        assert ck["cp_reason"].startswith("ring prefill")
        assert ck["dispatches"] > 0


class TestOverLengthPrompt:
    def test_rejected_gracefully_when_chunking_off(self):
        """A prompt over the largest bucket (or over max_len -
        max_new_tokens) must come back as a FinishedRequest with the
        pinned reason — generate() returns the prompt unextended."""
        from deepspeed_tpu.inference import InferenceEngine, Request
        from deepspeed_tpu.inference.tracing import SHED_REASONS
        assert "reject_too_long" in SHED_REASONS
        cfg, params = tiny_gpt2()
        eng = InferenceEngine(cfg, params, WHOLE_INF, dtype=jnp.float32)
        eng.warmup()
        over = list(range(1, 27))             # 26 > bucket 24
        uid = eng.submit(Request(prompt=over, max_new_tokens=6,
                                 temperature=0.0, seed=0))
        fins = eng.run()
        mine = [f for f in fins if f.uid == uid]
        assert len(mine) == 1
        assert mine[0].finish_reason == "reject_too_long"
        assert mine[0].tokens == [] and mine[0].ttft_ms is None
        # generate() surfaces it as the prompt unextended, not a crash
        outs = eng.generate([over, [5, 6, 7]], max_new_tokens=6,
                            temperature=0.0)
        assert outs[0] == over
        assert len(outs[1]) == 3 + 6
        eng.close()

    def test_served_when_chunking_on(self):
        """The same over-bucket prompt SERVES once chunking is on —
        the ladder ceiling is gone; only max_len and the page pool
        bound admission."""
        cfg, params = tiny_gpt2()
        over = list(range(1, 27))             # 26 tokens, bucket max 4
        outs, rc, state = serve(cfg, params, CHUNKED_INF, [over])
        assert outs[0][:26] == over and len(outs[0]) == 26 + 6
        assert rc == 0
        ck = state["chunked_prefill"]
        assert ck["dispatches"] == math.ceil(26 / 8)
        assert ck["chunking_slots"] == 0      # drained
        assert ck["cp_shards"] == 1           # no mesh configured

    def test_beyond_max_len_rejected_even_with_chunking(self):
        cfg, params = tiny_gpt2()
        from deepspeed_tpu.inference import InferenceEngine, Request
        eng = InferenceEngine(cfg, params, CHUNKED_INF,
                              dtype=jnp.float32)
        uid = eng.submit(Request(prompt=list(range(1, 31)),
                                 max_new_tokens=6))   # 30 + 6 > 32
        fins = eng.step()
        assert [f.uid for f in fins] == [uid]
        assert fins[0].finish_reason == "reject_too_long"
        eng.close()


class TestSteadyState:
    def test_zero_recompiles_under_mixed_churn(self):
        """Waves of long and short prompts landing while earlier ones
        still decode: after warmup, not one new program — prompt length
        is no longer a compile axis."""
        from deepspeed_tpu.inference import InferenceEngine, Request
        cfg, params = tiny_gpt2()
        eng = InferenceEngine(cfg, params, CHUNKED_INF,
                              dtype=jnp.float32)
        eng.warmup()
        rng = np.random.RandomState(9)
        waves = [[rng.randint(1, 61, (n,)).tolist() for n in lens]
                 for lens in ((20, 3), (11, 2, 17), (26,), (5, 22))]
        finished = 0
        pending = list(waves)
        while pending or not eng.scheduler.idle():
            if pending:
                for p in pending.pop(0):
                    eng.submit(Request(prompt=p, max_new_tokens=4,
                                       temperature=0.0, seed=0))
            finished += len(eng.step())
        assert finished == sum(len(w) for w in waves)
        assert eng.steady_state_recompiles == 0
        eng.close()


    def test_a_long_prompt_costs_a_decode_step_one_chunk(self):
        """The inter-token bound as pure dispatch ordering: a long
        prompt landing while short requests decode adds, to any one
        engine step, at most ONE chunk dispatch, and every decode
        dispatch of that step runs before it — so the worst gap between
        two tokens is one decode plus one chunk, whatever the prompt's
        length."""
        from deepspeed_tpu.inference import InferenceEngine, Request
        cfg, params = tiny_gpt2()
        eng = InferenceEngine(cfg, params, CHUNKED_INF,
                              dtype=jnp.float32)
        eng.warmup()
        assert eng._dispatch_trace is not None

        def submit(p):
            eng.submit(Request(prompt=p, max_new_tokens=6,
                               temperature=0.0, seed=0))
        for p in ([5, 6, 7], [8, 9]):
            submit(p)
        for _ in range(2):           # the shorts are decoding...
            eng.step()
        submit(LONG + [11, 12, 13, 14])   # ...when 24 tokens land
        while not eng.scheduler.idle():
            eng.step()
        by_step = {}
        for step, kind in eng._dispatch_trace.rows():
            by_step.setdefault(step, []).append(kind)
        chunk_steps = [k for k in by_step.values() if "chunk" in k]
        assert len(chunk_steps) >= 3            # 24 tokens, 8 a chunk
        assert any("decode" in k for k in chunk_steps)
        for kinds in chunk_steps:
            assert kinds.count("chunk") == 1, kinds
            assert "decode" not in kinds[kinds.index("chunk"):], kinds
        eng.close()


class TestChunkTrail:
    def test_chunk_rows_and_ttft_decomposition(self, tmp_path):
        """One serve_prefill_chunk row per chunk (ceil(prompt/chunk)),
        ordinals 0..k-1, cum_ms monotone and summing the walls; the
        finish row carries the chunk count; TTFT = queue_wait +
        prefill with every chunk leg inside the prefill leg."""
        from deepspeed_tpu.inference import InferenceEngine, Request
        cfg, params = tiny_gpt2()
        icfg = dict(CHUNKED_INF, events_dir=str(tmp_path))
        eng = InferenceEngine(
            cfg, params, icfg, dtype=jnp.float32,
            observability_config={"serve": {"sample_rate": 1.0}})
        eng.warmup()
        uid = eng.submit(Request(prompt=LONG, max_new_tokens=4,
                                 temperature=0.0, seed=0))
        eng.run()
        eng.close()
        rows = []
        for fn in sorted(os.listdir(tmp_path)):
            if fn.startswith("events"):
                with open(os.path.join(tmp_path, fn)) as fh:
                    rows += [json.loads(line) for line in fh]
        chunks = [r for r in rows
                  if r.get("event") == "serve_prefill_chunk"
                  and r.get("uid") == uid]
        k = math.ceil(len(LONG) / 8)
        assert [c["chunk"] for c in chunks] == list(range(k))
        assert sum(c["tokens"] for c in chunks) == len(LONG)
        cums = [c["cum_ms"] for c in chunks]
        assert cums == sorted(cums)
        assert cums[-1] == pytest.approx(
            sum(c["wall_ms"] for c in chunks), rel=0.05)
        fin = next(r for r in rows if r.get("event") == "serve_finish"
                   and r.get("uid") == uid)
        assert fin["chunks"] == k
        ft = next(r for r in rows
                  if r.get("event") == "serve_first_token"
                  and r.get("uid") == uid)
        # the pinned decomposition: prefill leg = ttft - queue_wait,
        # and the k chunk dispatches all ran inside it
        adm = next(r for r in rows if r.get("event") == "serve_admit"
                   and r.get("uid") == uid)
        assert ft["prefill_ms"] == pytest.approx(
            ft["ttft_ms"] - adm["queue_wait_ms"], abs=0.05)
        assert cums[-1] <= ft["prefill_ms"] + 0.05

    def test_chunk_warmup_plan(self):
        from deepspeed_tpu.inference.buckets import chunk_warmup_plan
        assert chunk_warmup_plan([1, 2], 8) == [(1, 8), (2, 8)]
        assert chunk_warmup_plan([1, 2], 0) == []
