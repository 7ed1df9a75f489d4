"""The program's own names in a trace (profiling/spans.py registry):
device scopes inside the compiled programs, host phase spans with their
counters as arguments inside the two step loops.

Pins, on the CPU at a tiny size:
- the registry refuses an unknown scope, and every span's parent is
  registered before it;
- the GPT-2 train step and the paged prefill and decode programs carry
  the scopes they should in their lowered text, and open no
  ``jax.named_scope`` outside the registry;
- a short ``jax.profiler`` trace of three ``engine.step()`` and two
  ``train_batch`` calls holds the spans in their nesting and order, with
  arguments equal to what the scheduler reports;
- the scopes change neither the compiled program set nor the
  zero-recompile contract.
"""

import contextlib
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.inference import InferenceEngine
from deepspeed_tpu.inference.scheduler import Request
from deepspeed_tpu.models.gpt2 import (GPT2Config, gpt2_loss_fn,
                                       init_gpt2_params)
from deepspeed_tpu.ops.attention.paged import block_pages
from deepspeed_tpu.profiling import spans
from deepspeed_tpu.profiling.spans import (DEVICE_SCOPES, HOST_SPANS,
                                           ChromeTraceRecorder, scope,
                                           trace_span)

CFG = GPT2Config(vocab_size=61, max_position_embeddings=32, hidden_size=32,
                 num_layers=2, num_heads=4, embd_dropout=0.0,
                 attn_dropout=0.0, resid_dropout=0.0)
INF = {"max_batch_size": 3, "prompt_buckets": [4, 8],
       "batch_buckets": [1, 2], "max_seq_len": 32, "max_new_tokens": 4,
       "paged_kv": {"attn_kernel": "gather"}}
PROMPTS = [[5, 6, 7], [8, 9, 10, 11, 12], [13, 14]]


def _params():
    return init_gpt2_params(CFG, jax.random.PRNGKey(3))


def _train_engine():
    ds = {"train_micro_batch_size_per_gpu": 2,
          "gradient_accumulation_steps": 1,
          "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
          "bf16": {"enabled": True}, "zero_optimization": {"stage": 2},
          "gradient_clipping": 1.0, "steps_per_print": 1000,
          "mesh": {"axes": {"data": 1}}}
    engine, *_ = deepspeed_tpu.initialize(
        model=gpt2_loss_fn(CFG, deterministic=True),
        model_parameters=_params(), config=ds)
    return engine


def _batches(rows=2):
    rs = np.random.RandomState(0)
    while True:
        yield {"input_ids": rs.randint(0, 61, (rows, 17)).astype(np.int32)}


def _serve_engine(dtype=jnp.float32, attn_kernel="gather"):
    inf = dict(INF, paged_kv=dict(INF["paged_kv"], attn_kernel=attn_kernel))
    return InferenceEngine(CFG, _params(), inf, dtype=dtype)


def _scopes_in(text):
    """Registered scopes among the name-stack components of a lowered
    program's locations (the last component is the primitive)."""
    found = set()
    for path in re.findall(r'loc\("([^"]+)"', text):
        for part in path.split("/")[:-1]:
            found.update(w for w in re.findall(r"[A-Za-z0-9_]+", part)
                         if w in DEVICE_SCOPES)
    return found


@contextlib.contextmanager
def _opened_scopes(monkeypatch):
    """Every name handed to ``jax.named_scope`` while the body traces."""
    opened, real = [], jax.named_scope

    def spy(name):
        opened.append(name)
        return real(name)

    monkeypatch.setattr(jax, "named_scope", spy)
    yield opened
    monkeypatch.setattr(jax, "named_scope", real)


# ------------------------------------------------------------- registry
def test_registry_refuses_unknown_scope_and_orders_spans():
    with pytest.raises(ValueError, match="attn_kernel"):
        scope("attn_kernel")
    with scope("mlp"):
        pass
    assert len(set(DEVICE_SCOPES)) == len(DEVICE_SCOPES)
    assert len(set(HOST_SPANS)) == len(HOST_SPANS)
    for i, name in enumerate(HOST_SPANS):
        parent = name.rsplit("/", 1)[0]
        if parent.startswith(("serve/", "setup/")) and parent != name:
            assert parent in HOST_SPANS[:i], name
    # set-up's spans (profiling/recompile.py ``setup_span``): ten at most
    setup = [name for name in HOST_SPANS if name.startswith("setup/")]
    assert {"setup/import", "setup/engine", "setup/warmup",
            "setup/program"} <= set(setup) and len(setup) <= 10
    # the names tests and docs have pinned since PR 18
    for name in ("train_batch", "data", "serve/prefill", "serve/chunk",
                 "serve/verify", "serve/decode"):
        assert name in HOST_SPANS


def test_trace_span_hands_arguments_to_both_sinks(monkeypatch):
    seen = []

    class Annotation(contextlib.nullcontext):
        def __init__(self, name, **kwargs):
            super().__init__()
            seen.append((name, kwargs))

    monkeypatch.setattr(spans, "_annotation", Annotation)
    with trace_span("serve/decode", live_tokens=3, rows=5):
        pass
    assert seen == [("serve/decode", {"live_tokens": 3, "rows": 5})]
    rec = ChromeTraceRecorder()
    with trace_span("serve/prefill", recorder=rec, batch=2):
        pass
    assert seen[-1] == ("serve/prefill", {"batch": 2})
    assert [(e["name"], e["args"]) for e in rec.events] == \
        [("serve/prefill", {"batch": 2})]


# -------------------------------------------------------- device scopes
def test_train_step_carries_its_scopes_and_none_outside(monkeypatch):
    engine = _train_engine()
    batch = engine._put_micro_batch(next(_batches()))
    with _opened_scopes(monkeypatch) as opened:
        text = engine._get_compiled_micro_step().lower(
            engine.state, batch).as_text(debug_info=True)
    assert set(opened) <= set(DEVICE_SCOPES), set(opened) - set(DEVICE_SCOPES)
    want = {"embed", "ln", "attn_proj", "attn_core", "mlp", "weight_cast",
            "loss_head", "loss_scale", "grad_clip", "opt_update"}
    assert want <= _scopes_in(text), want - _scopes_in(text)
    # forward and backward of one scope are told apart by autodiff's own
    # wrappers in the path
    assert re.search(r'loc\("[^"]*/jvp\(mlp\)/', text)
    assert re.search(r'loc\("[^"]*/transpose\(jvp\(mlp\)\)/', text)
    # nothing of serving is traced into the train step
    assert not {"kv_write", "kv_gather", "attn_cached", "lm_head",
                "sample"} & _scopes_in(text)
    engine.close()


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_paged_serving_programs_carry_their_scopes(monkeypatch, program):
    engine = _serve_engine(jnp.bfloat16)      # fp32 weights really cast
    assert engine.paged and engine._decode_attn_path == "gather"
    rows, pps = engine._rows, engine.paged_spec.pages_per_seq
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)      # noqa: E731
    keys = jnp.zeros((rows, 2), jnp.uint32)
    temps = jnp.zeros((rows,), jnp.float32)
    if program == "decode":
        fn, args = engine._decode_paged_impl, (
            i32(rows), i32(rows), i32(rows, pps), keys, temps)
    else:
        fn, args = engine._prefill_paged_impl, (
            i32(rows, 8), i32(rows) + 1, i32(rows),
            i32(rows, engine._prefill_pps), keys, temps)
    with _opened_scopes(monkeypatch) as opened:
        text = jax.jit(fn).lower(engine.params, engine._cache,
                                 *args).as_text(debug_info=True)
    assert set(opened) <= set(DEVICE_SCOPES)
    want = {"embed", "ln", "attn_proj", "kv_write", "kv_gather",
            "attn_cached", "mlp", "weight_cast", "lm_head", "sample"}
    assert want <= _scopes_in(text), want - _scopes_in(text)
    # innermost wins: the gather sits inside the block's attention scope
    assert re.search(r'loc\("[^"]*/attn_core/kv_gather/', text)
    assert not {"loss_head", "opt_update", "grad_clip"} & _scopes_in(text)
    engine.close()


@pytest.mark.parametrize("program", ["chunk", "decode"])
def test_a_chunked_hybrids_programs_carry_their_scopes(monkeypatch, program):
    """models/kimi_linear.py: a chunk carries the delta-rule scan, the
    own rows' `attn_core` and the prefix's `mla_prefix`; decode the
    state update and the absorbed reader; neither opens a name outside
    the registry."""
    from deepspeed_tpu.models import kimi_linear as kl
    cfg = kl.KimiLinearConfig(
        vocab_size=64, hidden_size=32, num_layers=3, latent_layers=(1,),
        num_heads=2, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=8, v_head_dim=8, kda_num_heads=2, kda_head_dim=8,
        kda_gate_rank=4, intermediate_size=48, moe_intermediate_size=16,
        num_experts=4, experts_per_token=2, max_position_embeddings=64)
    engine = InferenceEngine(
        cfg, kl.init_kimi_linear_params(cfg, jax.random.PRNGKey(0)),
        {"max_batch_size": 2, "prompt_buckets": [16], "batch_buckets": [1],
         "max_seq_len": 48, "paged_kv": {"prefix_cache": False},
         "chunked_prefill": {"enabled": True, "chunk_tokens": 16}})
    rows, pps = engine._rows, engine.paged_spec.pages_per_seq
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)      # noqa: E731
    keys = lambda n: jnp.zeros((n, 2), jnp.uint32)        # noqa: E731
    temps = lambda n: jnp.zeros((n,), jnp.float32)        # noqa: E731
    if program == "decode":
        fn, args = engine._decode_paged_impl, (
            i32(rows), i32(rows), i32(rows, pps), keys(rows), temps(rows))
        want = {"kda_proj", "kda_state", "mla_q", "mla_latent",
                "mla_absorb", "attn_core", "mla_out", "moe_route",
                "moe_experts", "moe_shared", "mlp", "lm_head", "sample"}
        never = {"kda_scan", "mla_prefix", "mla_expand"}
    else:
        fn, args = engine._prefill_state_impl, (
            i32(1, 16), i32(1) + 1, i32(1) + 16, i32(1, pps), keys(1),
            temps(1), i32(1))
        want = {"kda_proj", "kda_scan", "kda_state", "mla_q", "mla_latent",
                "mla_expand", "attn_core", "mla_prefix", "mla_out",
                "moe_route", "moe_experts", "moe_shared", "mlp", "lm_head"}
        never = {"mla_absorb"}
    with _opened_scopes(monkeypatch) as opened:
        text = jax.jit(fn).lower(engine.params, engine._cache,
                                 *args).as_text(debug_info=True)
    assert set(opened) <= set(DEVICE_SCOPES)
    assert want <= _scopes_in(text), want - _scopes_in(text)
    assert not never & _scopes_in(text)
    engine.close()


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_a_short_convolution_hybrids_programs_carry_their_scopes(
        monkeypatch, program):
    """models/lfm2.py: both programs carry the convolution's two scopes
    and, in an attention layer, the norm a head and the rotation between
    `attn_proj` and the reader; NO `moe_shared` (the family has no shared
    expert); neither opens a name outside the registry."""
    from deepspeed_tpu.models import lfm2
    cfg = lfm2.LFM2Config(
        vocab_size=64, hidden_size=32, num_layers=4, num_heads=2,
        num_kv_heads=1, intermediate_size=48, moe_intermediate_size=16,
        num_dense_layers=1, num_experts=4, experts_per_token=2,
        max_position_embeddings=64)
    engine = InferenceEngine(
        cfg, lfm2.init_lfm2_params(cfg, jax.random.PRNGKey(0)),
        {"max_batch_size": 2, "prompt_buckets": [16], "batch_buckets": [1],
         "max_seq_len": 48, "paged_kv": {"prefix_cache": False}})
    rows, pps = engine._rows, engine.paged_spec.pages_per_seq
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)      # noqa: E731
    keys = lambda n: jnp.zeros((n, 2), jnp.uint32)        # noqa: E731
    temps = lambda n: jnp.zeros((n,), jnp.float32)        # noqa: E731
    want = {"conv_proj", "conv_core", "attn_proj", "attn_norm_rope",
            "kv_write", "moe_route", "moe_dispatch", "moe_experts", "mlp",
            "lm_head"}
    if program == "decode":
        fn, args = engine._decode_paged_impl, (
            i32(rows), i32(rows), i32(rows, pps), keys(rows), temps(rows))
        want |= {"attn_cached", "sample"}
    else:
        fn, args = engine._prefill_state_impl, (
            i32(1, 16), i32(1) + 9, i32(1), i32(1, pps), keys(1),
            temps(1), i32(1))
    with _opened_scopes(monkeypatch) as opened:
        text = jax.jit(fn).lower(engine.params, engine._cache,
                                 *args).as_text(debug_info=True)
    assert set(opened) <= set(DEVICE_SCOPES)
    assert want <= _scopes_in(text), want - _scopes_in(text)
    assert not {"moe_shared", "kda_state", "ssd_state"} & _scopes_in(text)
    engine.close()


@pytest.mark.parametrize("program", ["chunk", "decode"])
def test_a_learned_selections_programs_carry_their_scopes(monkeypatch,
                                                           program):
    """models/keye_vl2.py: both programs carry the indexer's scores, the
    exact choice and the softmax over the chosen (`indexer`, `select`,
    `sparse_attn`), a chunk also the prefix's part (`sparse_prefix`);
    NO `moe_shared` and no reader of another family; neither opens a
    name outside the registry."""
    from deepspeed_tpu.models import keye_vl2 as kv2
    cfg = kv2.KeyeVL2Config(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
        num_kv_heads=1, head_dim=16, moe_intermediate_size=16,
        num_experts=4, experts_per_token=2, mrope_section=(2, 3, 3),
        indexer_num_heads=2, indexer_head_dim=8, indexer_topk=6,
        max_position_embeddings=64)
    engine = InferenceEngine(
        cfg, kv2.init_keye_vl2_params(cfg, jax.random.PRNGKey(0)),
        {"max_batch_size": 2, "prompt_buckets": [16], "batch_buckets": [1],
         "max_seq_len": 48,
         "chunked_prefill": {"enabled": True, "chunk_tokens": 16},
         "paged_kv": {"prefix_cache": False}})
    rows, pps = engine._rows, engine.paged_spec.pages_per_seq
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)      # noqa: E731
    keys = lambda n: jnp.zeros((n, 2), jnp.uint32)        # noqa: E731
    temps = lambda n: jnp.zeros((n,), jnp.float32)        # noqa: E731
    want = {"attn_proj", "attn_norm_rope", "indexer", "select",
            "sparse_attn", "kv_write", "moe_route", "moe_dispatch",
            "moe_experts", "lm_head"}
    if program == "decode":
        fn, args = engine._decode_paged_impl, (
            i32(rows), i32(rows), i32(rows, pps), keys(rows), temps(rows))
        want |= {"sample"}
    else:
        fn, args = engine._prefill_state_impl, (
            i32(1, 16), i32(1) + 9, i32(1) + 16, i32(1, pps), keys(1),
            temps(1), i32(1))
        want |= {"sparse_prefix"}
    with _opened_scopes(monkeypatch) as opened:
        text = jax.jit(fn).lower(engine.params, engine._cache,
                                 *args).as_text(debug_info=True)
    assert set(opened) <= set(DEVICE_SCOPES)
    assert want <= _scopes_in(text), want - _scopes_in(text)
    assert not {"moe_shared", "attn_cached", "attn_core", "kv_gather",
                "mla_prefix"} & _scopes_in(text)
    engine.close()


def test_scopes_leave_the_program_set_and_recompiles_alone(monkeypatch):
    def programs():
        engine = _serve_engine()
        warm = engine.warmup()
        out = engine.generate(PROMPTS, max_new_tokens=4)
        counts = dict(engine.compile_tracker.counts)
        recompiles = engine.steady_state_recompiles
        engine.close()
        return warm, counts, recompiles, out

    scoped = programs()
    from deepspeed_tpu.inference import engine as serve_engine
    from deepspeed_tpu.models import gpt2
    from deepspeed_tpu.ops.attention import page_pool
    for module in (gpt2, page_pool, serve_engine):
        monkeypatch.setattr(module, "scope",
                            lambda name: contextlib.nullcontext())
    bare = programs()
    assert scoped[:3] == bare[:3]
    assert scoped[2] == 0
    assert scoped[3] == bare[3]           # and the same tokens


# ----------------------------------------------------------- host spans
def _traced(tmp_path, body):
    """Run `body` under a CPU profiler trace; the host events whose name
    is registered, as (name, start, end, arguments), by start."""
    from jax.profiler import ProfileData
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                         / "*.xplane.pb"))[-1]
    events = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in HOST_SPANS:
                    events.append((e.name, e.start_ns,
                                   e.start_ns + e.duration_ns,
                                   dict(e.stats)))
    return sorted(events, key=lambda ev: (ev[1], -ev[2]))


def _children(events, parent):
    return [ev[0] for ev in events
            if ev is not parent and parent[1] <= ev[1] and ev[2] <= parent[2]]


@pytest.mark.parametrize("attn_kernel", ["gather", "pallas"])
def test_serving_trace_holds_spans_in_order_with_scheduler_counters(
        tmp_path, attn_kernel):
    engine = _serve_engine(attn_kernel=attn_kernel)
    engine.warmup()
    sched = engine.scheduler
    for i, p in enumerate(PROMPTS):
        engine.submit(Request(prompt=p, max_new_tokens=6, temperature=0.0,
                              seed=i, eos_id=None))
    reported = []                   # what the scheduler says at each build
    real_span = engine._span
    ps = engine.paged_spec.page_size
    per_turn = block_pages(ps)      # pages a loop turn of the Pallas reader

    def spy(name, **args):
        if name == "serve/decode/build":
            # live tokens, and what the Pallas reader walks: each
            # decoding row's live pages, the null page once for a row
            # that is not decoding, a block of pages a loop turn
            walked = [p // ps + 1 for p in sched.decode_state()[2]]
            walked += [1] * (engine._rows - len(walked))
            reported.append((sched.tokens_in_flight, sum(walked),
                             sum(-(-w // per_turn) for w in walked)))
        return real_span(name, **args)

    engine._span = spy
    # a dispatch's span opens where its tokens are read, after the next
    # dispatch has been issued: the third decode's by `debug_state()`
    events = _traced(tmp_path, lambda: [engine.step() for _ in range(3)]
                     + [engine.debug_state()])
    engine.close()

    decodes = [ev for ev in events if ev[0] == "serve/decode"]
    prefills = [ev for ev in events if ev[0] == "serve/prefill"]
    assert len(decodes) == 3 and len(prefills) >= 1
    assert engine._decode_attn_path == attn_kernel
    pallas = attn_kernel == "pallas"
    # the host worked on another dispatch under every program but the
    # last, whose read nothing was issued behind
    assert [ev[3]["deferred"] for ev in decodes] == [1, 1, 0]
    assert all(ev[3]["deferred"] == 1 for ev in prefills)
    for ev, (live, walked, turns) in zip(decodes, reported):
        # every argument has a reader (decode_stripe_live_share.sat,
        # decode_read_live_share.sat, decode_block_fill_share.sat,
        # decode_deferred_share.sat, decode_run_turn_share.sat)
        # (..., and the dispatch ledger's row: test_dispatch_ledger.py)
        assert set(ev[3]) == {"seq", "step", "deferred", "rows",
                              "table_pages", "page_size", "live_tokens",
                              "read_pages", "read_turns", "run_turns",
                              "block_tokens"}
        assert ev[3]["live_tokens"] == live
        # the gather reader walks no page list: it reads the table
        assert ev[3]["read_pages"] == (walked if pallas else 0)
        assert ev[3]["read_turns"] == (turns if pallas else 0)
        # a fresh pool gives every request its pages as one extent
        # and what is left over out of one more: every turn is a run
        assert ev[3]["run_turns"] == ev[3]["read_turns"]
        assert ev[3]["block_tokens"] == (per_turn * ps if pallas else 0)
        assert live <= walked * ps <= turns * per_turn * ps
        assert engine._rows <= turns <= walked
        assert ev[3]["rows"] == engine._rows
        assert ev[3]["page_size"] == ps
        assert ev[3]["table_pages"] == engine.paged_spec.pages_per_seq
        assert _children(events, ev) == ["serve/decode/wait"]
    # every prompt token is real, the rest of the buckets is padding
    assert sum(ev[3]["real_tokens"] for ev in prefills) == \
        sum(len(p) for p in PROMPTS)
    for ev in prefills:
        assert set(ev[3]) == {"seq", "step", "deferred", "batch", "prompt",
                              "real_tokens", "own_key_tokens",
                              "page_write_tokens"}
        # no prompt here rides a shared prefix: every row starts at
        # cache position 0 (prefill_own_keys_share.sat)
        assert ev[3]["own_key_tokens"] == ev[3]["real_tokens"]
        # and the pool write lands a page an index where the bucket is
        # whole pages (prefill_page_write_share.sat), a row an index in
        # a narrower one
        assert ev[3]["page_write_tokens"] == (
            0 if ev[3]["prompt"] % ps
            else ev[3]["real_tokens"])
        assert ev[3]["batch"] in INF["batch_buckets"]
        assert ev[3]["prompt"] in INF["prompt_buckets"]
        assert ev[3]["real_tokens"] <= ev[3]["batch"] * ev[3]["prompt"]
        assert _children(events, ev) == ["serve/prefill/wait"]
    # a dispatch's build and call come before its span, and between
    # them and it the NEXT dispatch's build and call: the spans' seq is
    # the order issued
    for kind, parents in (("decode", decodes), ("prefill", prefills)):
        builds = [ev for ev in events if ev[0] == f"serve/{kind}/build"]
        calls = [ev for ev in events if ev[0] == f"serve/{kind}/dispatch"]
        assert len(builds) == len(calls) == len(parents)
        assert all(b[2] <= c[1] and c[2] <= p[1]
                   for b, c, p in zip(builds, calls, parents))
    both = sorted(decodes + prefills, key=lambda ev: ev[1])
    assert [ev[3]["seq"] for ev in both] == list(range(len(both)))
    issued = sorted((ev for ev in events if ev[0] in (
        "serve/decode/dispatch", "serve/prefill/dispatch")),
        key=lambda ev: ev[1])
    for this, nxt, span in zip(issued, issued[1:], both):
        assert span[0] + "/dispatch" == this[0]
        assert nxt[2] <= span[1]
    # one step, in order: admission, the prefill issued, the decode
    # issued, then the prefill's span with its bookkeeping (the decode's
    # comes in the step after, once that step's prefill is issued)
    top = [ev[0] for ev in events
           if ev[0].count("/") == 1 and ev[0] != "serve/plan"]
    # (the walk over the slots that makes the decode dispatch's rows and
    # its span's counters comes right before its build, under its own
    # name)
    plans = [ev for ev in events if ev[0] == "serve/plan"]
    builds = [ev for ev in events if ev[0] == "serve/decode/build"]
    assert len(plans) == 2 * len(decodes)
    assert all(p[2] <= b[1] for p, b in zip(plans[1::2], builds))
    assert top[:4] == ["serve/admit", "serve/prefill", "serve/record",
                       "serve/metrics"]
    for i, name in enumerate(top):
        if name in ("serve/decode", "serve/prefill"):
            assert top[i + 1:i + 3] == ["serve/record", "serve/metrics"]


def test_training_trace_holds_dispatch_and_tail_spans(tmp_path):
    engine = _train_engine()
    it = _batches()
    engine.train_batch(it)                            # compiles
    events = _traced(
        tmp_path, lambda: [engine.train_batch(it) for _ in range(2)])
    engine.close()
    steps = [ev for ev in events if ev[0] == "train_batch"]
    tails = [ev for ev in events if ev[0] == "train/tail"]
    assert len(steps) == 2 and len(tails) == 2
    for step, tail in zip(steps, tails):
        assert _children(events, step) == ["data", "train/dispatch"]
        assert tail[1] >= step[2]                     # after, not inside
    assert tails[0][2] <= steps[1][1]


# ------------------------------------------------------- set-up's spans
def _ledger_since(mark):
    """The compile ledger's program rows and spans that began after
    ``mark`` (a ``time.perf_counter()`` stamp)."""
    from deepspeed_tpu.profiling.spans import compile_ledger
    table = compile_ledger().table()
    return ([r for r in table["programs"] if r["t_begin"] >= mark],
            [s for s in table["spans"] if s["t0"] >= mark])


def _assert_phases_nest(programs, spans):
    """Every span lies inside a span of its parent's name, a parent's
    children do not overlap and never add up to more than it, and every
    program row lies inside a span of the phase it names."""
    for s in spans:
        assert s["t1"] >= s["t0"]
        if s["parent"] is not None:
            assert [p for p in spans if p["name"] == s["parent"]
                    and p["t0"] <= s["t0"] and s["t1"] <= p["t1"]], s
    for p in spans:
        children = sorted(
            (s for s in spans if s is not p and s["parent"] == p["name"]
             and p["t0"] <= s["t0"] and s["t1"] <= p["t1"]),
            key=lambda s: s["t0"])
        for a, b in zip(children, children[1:]):
            assert a["t1"] <= b["t0"]
        assert sum(s["t1"] - s["t0"] for s in children) <= p["t1"] - p["t0"]
    for r in programs:
        if r["phase"] != "steady":
            assert [s for s in spans if s["name"] == r["phase"]
                    and s["t0"] <= r["t_begin"] and r["t_end"] <= s["t1"]], r


def test_warmup_leaves_a_row_a_warmed_program_inside_its_spans():
    import time
    mark = time.perf_counter()
    engine = _serve_engine()
    warm = engine.warmup()
    engine.generate(PROMPTS, max_new_tokens=3)
    assert engine.steady_state_recompiles == 0
    engine.close()
    programs, spans = _ledger_since(mark)
    _assert_phases_nest(programs, spans)
    # one engine, one warm-up, a setup/program a warmed call
    names = [s["name"] for s in spans]
    assert names.count("setup/engine") == names.count("setup/warmup") == 1
    assert {"setup/engine/params", "setup/engine/state",
            "setup/engine/programs"} <= set(names)
    warmed = [s for s in spans if s["name"] == "setup/program"]
    assert all(s["parent"] == "setup/warmup" for s in warmed)
    # a row a program the tracker counted, each with the wrap's name, the
    # dispatch ledger's class and JAX's three durations
    tracked = [r for r in programs if r["name"] is not None]
    assert len(tracked) == warm == engine.compile_tracker.total_compiles
    assert all(r["phase"] == "setup/program" for r in tracked)
    assert all(r["trace_s"] > 0 and r["lower_s"] > 0 and r["backend_s"] > 0
               and r["call_s"] > 0 for r in tracked)
    by_class = {r["cls"]: r["name"] for r in tracked}
    assert {cls: name for cls, name in by_class.items()
            if name != "merge_tokens"} == {
        **{("prefill", bb, pb): "prefill"
           for bb in INF["batch_buckets"] for pb in INF["prompt_buckets"]},
        ("decode", 2): "decode"}
    assert {cls for cls, name in by_class.items()
            if name == "merge_tokens"} == {("merge_tokens", 1),
                                           ("merge_tokens", 2)}
    # the same tuples the dispatch ledger wrote as the programs ran
    ran = set(engine.dispatch_ledger.table()["cls"])
    assert ran <= set(by_class)
    # a program the engine built as it was constructed (a pool's zeros,
    # where this process had not built them yet) is a row by JAX's name
    assert all(r["name"] is None for r in programs
               if r["phase"].startswith("setup/engine"))


def test_first_train_batch_is_a_setup_program_and_the_step_has_its_row():
    """The training engine wraps nothing unless observability is on: its
    step program still has its row, by JAX's name and the span around
    the first ``train_batch``'s dispatches; the second opens none."""
    import time
    mark = time.perf_counter()
    engine = _train_engine()
    it = _batches()
    engine.train_batch(it)
    engine.train_batch(it)
    engine.close()
    programs, spans = _ledger_since(mark)
    _assert_phases_nest(programs, spans)
    first = [s for s in spans if s["name"] == "setup/program"]
    assert len(first) == 1 and first[0]["cls"] == ("train_batch",)
    assert first[0]["parent"] is None
    (step,) = [r for r in programs if r["fun_name"] == "jit(_micro_step)"]
    assert step["phase"] == "setup/program" and step["name"] is None
    assert step["cls"] == ("train_batch",) and step["call_s"] is None
    assert step["trace_s"] > 0 and step["lower_s"] > 0
    assert step["backend_s"] > 0
    (built,) = [s for s in spans if s["name"] == "setup/engine"]
    assert {s["name"] for s in spans if s["parent"] == "setup/engine"} == {
        "setup/engine/params", "setup/engine/state"}
    assert built["t1"] <= first[0]["t0"]


def test_the_first_batchs_span_adds_no_frame_and_nothing_to_the_instance():
    """``setup/program`` is opened in ``train_batch``'s own frame (one
    more Python frame under the step program's trace cost GPT-2 345M
    2.5 s of set-up on the benchmark's host: PERF.md §6, PR 53), and the
    engine's public methods stay the class's: a ``train_batch`` held
    from before the first step is callable for good."""
    import sys
    import time
    mark = time.perf_counter()
    engine = _train_engine()
    assert "train_batch" not in vars(engine)
    depths = []
    traced = engine._micro_step

    def micro_step(state, batch):       # runs as the program is traced
        frame, depth = sys._getframe(), 0
        while frame.f_code is not type(engine).train_batch.__code__:
            frame, depth = frame.f_back, depth + 1
        depths.append((depth, frame.f_back.f_code.co_name))
        return traced(state, batch)
    engine._micro_step = micro_step
    step, it = engine.train_batch, _batches()
    losses = [float(step(it)) for _ in range(3)]
    engine.close()
    assert all(np.isfinite(losses)) and engine.global_steps == 3
    # traced once, and train_batch was called by THIS test, directly
    ((_, caller),) = depths
    assert caller == \
        "test_the_first_batchs_span_adds_no_frame_and_nothing_to_the_instance"
    programs, spans = _ledger_since(mark)
    assert len([s for s in spans if s["name"] == "setup/program"]) == 1
    assert len([r for r in programs if r["phase"] == "setup/program"
                and r["trace_s"] > 0 and r["lower_s"] > 0]) == 1


def test_the_packages_import_is_a_span_of_the_ledger():
    from deepspeed_tpu.profiling.spans import compile_ledger
    spans = [s for s in compile_ledger().table()["spans"]
             if s["name"] == "setup/import"]
    assert len(spans) == 1 and spans[0]["parent"] is None
    assert spans[0]["t0"] == deepspeed_tpu._T_IMPORT < spans[0]["t1"]


def test_a_trace_over_setup_shows_its_spans_on_the_profilers_clock(tmp_path):
    def body():
        engine = _serve_engine()
        engine.warmup()
        engine.close()
    events = [ev for ev in _traced(tmp_path, body)
              if ev[0].startswith("setup/")]
    (built,) = [ev for ev in events if ev[0] == "setup/engine"]
    (warm,) = [ev for ev in events if ev[0] == "setup/warmup"]
    assert built[2] <= warm[1]
    assert set(_children(events, built)) == {
        "setup/engine/params", "setup/engine/state",
        "setup/engine/programs"}
    warmed = [ev for ev in events if ev[0] == "setup/program"]
    assert warmed and set(_children(events, warm)) == {"setup/program"}
    # a warmed call's class rides as the annotation's argument
    assert {ev[3]["cls"] for ev in warmed} >= {"prefill 1 4", "decode 2"}
