"""The serving loop reads a dispatch's tokens AFTER it has issued the next
dispatch (docs/inference.md "The order of a step"): the last tokens stay
on the device, the scheduler advances by count when a dispatch is issued
and fills by value when its tokens arrive, and every end is seen one
dispatch late. Held here, at tiny sizes on the CPU: every request's
tokens and finish reason are those of the same engine reading every
dispatch at once (``_read_depth`` 0, the depth an engine with a drafter
or a handoff queue runs at: no option chooses it), a slot is released
one step later and nothing else of the schedule moves, the scheduler's
count equals what its slots and the returned requests hold after EVERY
step, and a call from outside a step settles what is pending first."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu  # noqa: F401
from deepspeed_tpu.inference import InferenceEngine
from deepspeed_tpu.inference.scheduler import Request
from deepspeed_tpu.models import solar_open2 as so
from deepspeed_tpu.models.gpt2 import GPT2Config, init_gpt2_params

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks")

GPT2 = GPT2Config(vocab_size=61, max_position_embeddings=32,
                  hidden_size=32, num_layers=2, num_heads=4)
GPT2_INFERENCE = {"max_batch_size": 3, "prompt_buckets": [4, 8],
                  "batch_buckets": [1, 2], "max_seq_len": 32,
                  "paged_kv": {"page_size": 4, "num_pages": 40}}
SOLAR = so.SolarOpen2Config(
    vocab_size=512, hidden_size=64, num_layers=4, num_heads=4,
    num_kv_heads=2, head_dim=32, gqa_layers=(0, 4), kda_num_heads=4,
    kda_head_dim=32, kda_gate_rank=16, moe_intermediate_size=32,
    num_experts=16, experts_per_token=4, max_position_embeddings=256,
    initializer_range=0.2, experts_held=(0, 4), vocab_held=(0, 128))
SOLAR_INFERENCE = {"max_batch_size": 3, "batch_buckets": [1, 2],
                   "prompt_buckets": [16, 32], "max_seq_len": 64,
                   "paged_kv": {"num_pages": 14, "prefix_cache": False}}


def _gpt2():
    return (GPT2, init_gpt2_params(GPT2, jax.random.PRNGKey(3)),
            GPT2_INFERENCE, 61, (2, 8))


def _solar():
    return (SOLAR, so.init_solar_open2_params(SOLAR, jax.random.PRNGKey(3)),
            SOLAR_INFERENCE, 128, (3, 30))


def _tiny_cell(name, prompts):
    """A benchmark configuration at its ``tiny`` sizes, through the
    benchmark's own family module (as the CPU rehearsals build it)."""
    for path in (REPO, BENCH):
        if path not in sys.path:
            sys.path.insert(0, path)
    from loader import load_module
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        cfg = json.load(f)
    # wider weights than the published 0.02, which at hidden 64 leaves
    # every logit within 0.01 of every other
    cfg = {**cfg, **cfg["tiny"], "initializer_range": 0.2}
    family = load_module("families", cfg["family"])
    model = family.serve_model_of(cfg)
    return (model, family.init_params(model, jax.random.PRNGKey(0)),
            cfg["serve"]["inference"], model.vocab_size, prompts)


# name -> (model, params, inference, vocabulary, (shortest, longest prompt))
FAMILIES = {
    "gpt2_paged": _gpt2,
    "solar_state_pool": _solar,
    # every prompt over 16 goes in chunks that carry the state
    "kimi_tiny_chunked": lambda: _tiny_cell("kimi-linear-48b-a3b", (5, 50)),
    "lfm2_tiny": lambda: _tiny_cell("lfm2-24b-a2b", (3, 30)),
}


@pytest.fixture(scope="module", params=list(FAMILIES))
def family(request):
    return request.param, FAMILIES[request.param]()


def _requests(vocab, span, n=12, eos_id=None, new=(3, 8)):
    """``n`` requests, greedy and sampled rows mixed, a seed each."""
    rs = np.random.RandomState(52)
    return [Request(
        prompt=[int(t) for t in rs.randint(1, vocab, rs.randint(*span))],
        max_new_tokens=int(rs.randint(*new)),
        temperature=0.8 if i % 3 == 2 else 0.0, seed=7000 + i,
        eos_id=eos_id, uid=10_000 + i) for i in range(n)]


def _served(case, requests, depth=None):
    """Serve ``requests`` step by step. Returns {uid: FinishedRequest},
    {uid: (the step it was admitted in, the step its slot was
    released in)} and the ledger's (step, class) rows."""
    model, params, inference = case[:3]
    engine = InferenceEngine(model, params, inference, dtype=jnp.float32)
    if depth is not None:
        engine._read_depth = depth
    sched = engine.scheduler
    admitted, released, returned = {}, {}, []
    plain_release = sched._release

    def release(slot):
        released[slot.request.uid] = engine._steps
        return plain_release(slot)

    sched._release = release
    plain_admit = sched.admit

    def admit():
        batches = plain_admit()
        for batch in batches:
            for r in batch.requests:
                admitted[r.uid] = engine._steps
        return batches

    sched.admit = admit
    for r in requests:
        engine.submit(r)
    while not sched.idle():
        step = engine._steps
        returned += engine.step()
        for slot in sched.slots:
            if slot is not None:        # (a prompt admitted in chunks)
                admitted.setdefault(slot.request.uid, step)
        # the benchmark's check (kinds/serve_backlog.py), after EVERY
        # step: nothing is in neither a slot nor a request that was
        # returned
        held = sum(len(s.tokens) for s in sched.slots if s is not None)
        assert sched.total_tokens == held + sum(
            len(f.tokens) for f in returned), step
    ledger = engine.dispatch_ledger
    engine.close()
    assert engine.steady_state_recompiles <= 0      # never warmed: -1
    assert sorted(f.uid for f in returned) == sorted(
        r.uid for r in requests)
    assert sum(ledger.table()["tokens"]) == sched.total_tokens == sum(
        len(f.tokens) for f in returned)
    steps = {u: (admitted[u], released[u]) for u in admitted}
    return {f.uid: f for f in returned}, steps, ledger.table()


def test_tokens_are_those_of_the_loop_that_reads_at_once(family):
    """Every request's tokens and finish reason are IDENTICAL with the
    read deferred and taken at once; the deferred run holds every slot
    one step longer (a chunked engine, which reads its decode before it
    admits wherever a chunk follows, none or one) and its ledger holds
    the same dispatches of the same requests otherwise."""
    name, case = family
    requests = _requests(case[3], case[4])
    at_once, steps_0, table_0 = _served(case, requests, depth=0)
    deferred, steps_1, table_1 = _served(case, requests)
    for r in requests:
        a, d = at_once[r.uid], deferred[r.uid]
        assert (d.tokens, d.finish_reason) == (a.tokens, a.finish_reason)
        assert d.finish_reason == "length"
        assert len(d.tokens) == r.max_new_tokens
        # a slot's life in steps: one longer, for the value of its last
        # token arrives a dispatch after the dispatch that sampled it
        life_0 = steps_0[r.uid][1] - steps_0[r.uid][0]
        life_1 = steps_1[r.uid][1] - steps_1[r.uid][0]
        if name == "kimi_tiny_chunked":
            assert life_1 - life_0 in (0, 1), r.uid
        else:
            assert life_1 - life_0 == 1, r.uid
    # the same kinds of dispatch, the same tokens in both ledgers
    assert set(table_0["kind"]) == set(table_1["kind"])
    assert sum(table_0["tokens"]) == sum(table_1["tokens"])
    # and the first wave is admitted in the same step in both
    first = min(s for s, _ in steps_0.values())
    assert {u for u, (s, _) in steps_0.items() if s == first} == \
        {u for u, (s, _) in steps_1.items() if s == first}


def test_a_freed_slots_prefill_comes_one_step_later():
    """ONE slot, two requests: the ledgers of the two loops, row by
    row. The second request's prefill comes one step later, and the
    step between issues nothing (the slot waits for its last value)."""
    case = _gpt2()
    case = (case[0], case[1], {**case[2], "max_batch_size": 1,
                               "batch_buckets": [1]}) + case[3:]
    requests = [Request(prompt=[5, 6, 7], max_new_tokens=3, seed=1,
                        uid=20_001),
                Request(prompt=[8, 9], max_new_tokens=2, seed=2,
                        uid=20_002)]

    def rows(table):
        return list(zip(table["step"], table["kind"]))

    at_once, _, table_0 = _served(case, requests, depth=0)
    deferred, _, table_1 = _served(case, requests)
    assert rows(table_0) == [(0, "prefill"), (0, "decode"), (1, "decode"),
                             (2, "prefill"), (2, "decode")]
    assert rows(table_1) == [(0, "prefill"), (0, "decode"), (1, "decode"),
                             (3, "prefill"), (3, "decode")]
    assert {u: f.tokens for u, f in at_once.items()} == \
        {u: f.tokens for u, f in deferred.items()}


def test_an_eos_mid_stream_gives_the_same_tokens_one_step_late():
    """A row that hits its ``eos_id`` has been issued into the next
    decode already: that token is dropped, the request's tokens are
    those of the loop that reads at once, its slot is freed one step
    later, and nothing arrives in the slot's next request."""
    case = _gpt2()
    plain, _, _ = _served(case, _requests(case[3], case[4], new=(6, 9)),
                          depth=0)
    # a token that some request samples in the middle of its output
    eos = next(f.tokens[2] for f in plain.values()
               if f.tokens[2] not in f.tokens[:2])
    requests = _requests(case[3], case[4], new=(6, 9), eos_id=eos)
    at_once, steps_0, _ = _served(case, requests, depth=0)
    deferred, steps_1, _ = _served(case, requests)
    stopped = [u for u, f in at_once.items() if f.finish_reason == "eos"]
    assert stopped and any(
        len(at_once[u].tokens) < r.max_new_tokens
        for u, r in ((r.uid, r) for r in requests) if u in stopped)
    for r in requests:
        a, d = at_once[r.uid], deferred[r.uid]
        assert (d.tokens, d.finish_reason) == (a.tokens, a.finish_reason)
        if a.finish_reason == "eos":
            assert a.tokens[-1] == eos and eos not in a.tokens[:-1]
        life_0 = steps_0[r.uid][1] - steps_0[r.uid][0]
        life_1 = steps_1[r.uid][1] - steps_1[r.uid][0]
        # (a FIRST token is read in its own step: the step's decode is
        # issued behind the prefill)
        assert life_1 - life_0 == (len(a.tokens) > 1), (
            r.uid, a.finish_reason)


# ------------------------------------------------------------------ #
# a call from outside a step settles what is pending first
# ------------------------------------------------------------------ #
@pytest.fixture
def mid_run():
    """A GPT-2 engine three steps into a backlog: a decode's read is
    waiting, every slot mid-decode."""
    model, params, inference = _gpt2()[:3]
    engine = InferenceEngine(model, params, inference, dtype=jnp.float32)
    engine.warmup()
    for r in _requests(61, (2, 8), new=(6, 9)):
        engine.submit(r)
    returned = []
    for _ in range(3):
        returned += engine.step()
    assert len(engine._pending) == 1
    assert engine._pending[0].name == "serve/decode"
    yield engine, returned
    engine.close()


def _accounted(engine, returned):
    sched = engine.scheduler
    held = sum(len(s.tokens) for s in sched.slots if s is not None)
    return sched.total_tokens == held + sum(
        len(f.tokens) for f in returned + sched.undelivered)


def _training_checkpoint(tmp_path, cfg, params):
    from deepspeed_tpu.models.gpt2 import gpt2_loss_fn
    trainer, *_ = deepspeed_tpu.initialize(
        model=gpt2_loss_fn(cfg, dtype=jnp.float32, deterministic=True),
        model_parameters=params,
        config={"train_micro_batch_size_per_gpu": 1,
                "gradient_accumulation_steps": 1,
                "steps_per_print": 10**9,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}})
    trainer.save_checkpoint(str(tmp_path))
    return str(tmp_path)


@pytest.mark.parametrize("call", ["cancel", "evict_queued", "export_request",
                                  "swap_params", "debug_state", "close"])
def test_a_call_from_outside_a_step_settles_the_pending_read(
        call, mid_run, tmp_path):
    engine, returned = mid_run
    sched = engine.scheduler
    before = sched.total_tokens
    in_flight = [s.request.uid for s in sched.slots if s is not None]
    generated = {s.request.uid: len(s.tokens) for s in sched.slots
                 if s is not None}
    if call == "cancel":
        fin = engine.cancel(in_flight[0])
        # it leaves with the token the device had made for it
        assert fin is not None and fin.finish_reason == "evicted"
        assert len(fin.tokens) == generated[in_flight[0]] + 1
        returned = returned + [fin]
    elif call == "evict_queued":
        fin = engine.cancel(sched.queue[-1].uid)
        assert fin is not None and fin.tokens == []
        returned = returned + [fin]
    elif call == "export_request":
        engine.warm_migration()
        assert engine._pending                  # warming reads nothing
        rec = engine.export_request(in_flight[1])
        assert rec is not None
        assert len(rec.tokens) == generated[in_flight[1]] + 1
        assert rec.pending_tok == rec.tokens[-1]
        assert rec.position == len(rec.prompt) + len(rec.tokens) - 1
        returned = returned + [sched.finished[-1]]      # its "migrate" row
    elif call == "swap_params":
        load_dir = _training_checkpoint(tmp_path, GPT2, engine.params)
        assert engine.swap_params(load_dir) != "initial"
        assert engine.steady_state_recompiles == 0
    elif call == "debug_state":
        state = engine.debug_state()
        assert {s["uid"]: s["generated"] for s in state["slots"]} == {
            s.request.uid: len(s.tokens) for s in sched.slots
            if s is not None}
    elif call == "close":
        engine.close()
    assert not engine._pending
    assert sched.total_tokens == before + len(in_flight)
    assert _accounted(engine, returned)
    if call != "close":
        # and serving goes on: every request is answered, once
        while not sched.idle():
            returned += engine.step()
        assert _accounted(engine, returned)
        assert engine.steady_state_recompiles == 0
        assert len({f.uid for f in returned}) == len(returned) == 12


def test_run_and_generate_return_every_request():
    model, params, inference = _gpt2()[:3]
    engine = InferenceEngine(model, params, inference, dtype=jnp.float32)
    requests = _requests(61, (2, 8))
    for r in requests:
        engine.submit(r)
    finished = engine.run()
    assert sorted(f.uid for f in finished) == sorted(r.uid for r in requests)
    assert not engine._pending and engine.scheduler.idle()
    outs = engine.generate([r.prompt for r in requests[:5]],
                           max_new_tokens=4, temperature=0.0)
    assert [len(o) for o in outs] == [len(r.prompt) + 4
                                      for r in requests[:5]]
    assert not engine._pending
    engine.close()


# ------------------------------------------------------------------ #
# where the values are needed before the next issue: depth 0
# ------------------------------------------------------------------ #
SPEC = {"max_batch_size": 3, "prompt_buckets": [4, 8],
        "batch_buckets": [1, 2], "max_seq_len": 32,
        "paged_kv": {"page_size": 4, "num_pages": 40},
        "spec_decode": {"enabled": True, "k": 3}}
DISAGG = {"max_batch_size": 3, "prompt_buckets": [4, 8],
          "batch_buckets": [1, 2], "max_seq_len": 32,
          "disagg": {"enabled": True, "separate_pools": True}}


PROMPTS = [[(i + j) % 3 + 1 for j in range(n)]      # repeats: drafts
           for i, n in enumerate((3, 7, 5, 8, 2, 6))]


@pytest.fixture(scope="module")
def plain_outs():
    params = init_gpt2_params(GPT2, jax.random.PRNGKey(3))
    engine = InferenceEngine(GPT2, params, GPT2_INFERENCE,
                             dtype=jnp.float32)
    outs = engine.generate(PROMPTS, max_new_tokens=6, temperature=0.0)
    engine.close()
    return outs


@pytest.mark.parametrize("inference, kinds", [
    (GPT2_INFERENCE, {"serve/prefill", "serve/decode"}),
    (SPEC, {"serve/prefill", "serve/decode", "serve/verify"}),
    (DISAGG, {"serve/prefill", "serve/decode"}),
], ids=["plain", "spec_decode", "disagg"])
def test_the_depth_follows_what_the_engine_is(inference, kinds,
                                              plain_outs):
    """An engine whose drafter proposes from values, or whose handoff
    record carries the first token, reads every dispatch at once and
    says so on its spans (``deferred`` 0); the plain engine defers every
    read but those nothing was issued behind. The same tokens all
    three."""
    params = init_gpt2_params(GPT2, jax.random.PRNGKey(3))
    engine = InferenceEngine(GPT2, params, inference, dtype=jnp.float32)
    seen = []
    plain = engine._span

    def spy(name, **args):
        if name in kinds | {"serve/verify", "serve/chunk"}:
            seen.append((name, args["deferred"], len(engine._pending)))
        return plain(name, **args)

    engine._span = spy
    outs = engine.generate(PROMPTS, max_new_tokens=6, temperature=0.0)
    engine.close()
    assert {name for name, _, _ in seen} == kinds
    deferred = [d for _, d, _ in seen]
    if inference is GPT2_INFERENCE:
        assert engine._read_depth == 1
        # but for a step's last read when the next step issues nothing
        assert sum(deferred) >= len(deferred) - 3 and min(deferred) == 0
    else:
        assert engine._read_depth == 0
        assert set(deferred) == {0}
        # and nothing waits while such an engine opens a span
        assert {waiting for _, _, waiting in seen} == {0}
    assert outs == plain_outs
