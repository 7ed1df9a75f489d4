"""What a dispatch's host section may touch (docs/inference.md): a
request's sampling key is two words the host writes, held to the
library's once at engine construction, and every dispatch hands its
host arrays to the program as they are, and the decode program the
device's own array of last tokens, each the kind warm-up passed, so a
program keeps ONE entry in its jit cache. Tiny sizes on the CPU."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu  # noqa: F401
from deepspeed_tpu.inference import InferenceEngine
from deepspeed_tpu.inference import engine as engine_mod
from deepspeed_tpu.inference.scheduler import Request
from deepspeed_tpu.models import solar_open2 as so
from deepspeed_tpu.models.gpt2 import (GPT2Config, gpt2_forward,
                                       init_gpt2_params)
from deepspeed_tpu.profiling.recompile import TrackedFunction

GPT2 = GPT2Config(vocab_size=61, max_position_embeddings=32,
                  hidden_size=32, num_layers=2, num_heads=4)
SOLAR = so.SolarOpen2Config(
    vocab_size=512, hidden_size=64, num_layers=4, num_heads=4,
    num_kv_heads=2, head_dim=32, gqa_layers=(0, 4), kda_num_heads=4,
    kda_head_dim=32, kda_gate_rank=16, moe_intermediate_size=32,
    num_experts=16, experts_per_token=4, max_position_embeddings=256,
    initializer_range=0.2, experts_held=(0, 4), vocab_held=(0, 128))

PAGED = {"max_batch_size": 3, "prompt_buckets": [4, 8],
         "batch_buckets": [1, 2], "max_seq_len": 32, "max_new_tokens": 4}
DENSE = dict(PAGED, paged_kv={"enabled": False})
# a long prompt goes in chunks of 8, repeated tokens make the n-gram
# drafter propose: one engine dispatches prefill, chunk, decode, verify
CHUNKED_SPEC = {"max_batch_size": 3, "prompt_buckets": [4, 8],
                "batch_buckets": [1, 2], "max_seq_len": 32,
                "max_new_tokens": 6,
                "paged_kv": {"page_size": 4, "num_pages": 24},
                "chunked_prefill": {"enabled": True, "chunk_tokens": 8},
                "spec_decode": {"enabled": True, "k": 4}}
SEPARATE = dict(PAGED, disagg={"enabled": True, "separate_pools": True})
STATE = {"max_batch_size": 3, "batch_buckets": [1, 2],
         "prompt_buckets": [16, 32], "max_seq_len": 64,
         "paged_kv": {"num_pages": 14, "prefix_cache": False}}


@pytest.fixture(scope="module")
def gpt2_params():
    return init_gpt2_params(GPT2, jax.random.PRNGKey(3))


@pytest.fixture(scope="module")
def solar_params():
    return so.init_solar_open2_params(SOLAR, jax.random.PRNGKey(3))


# ------------------------------------------------------------------ #
# the key of a seed
# ------------------------------------------------------------------ #
_DRAWN = np.random.RandomState(45).randint(0, 2 ** 62, 2).tolist()
SEEDS = [0, 1, 2 ** 31 - 1, 2 ** 31, 2 ** 32 - 1, 2 ** 32 + 5, -1,
         2 ** 62 + 12345, 12_345_678_901, -2 ** 40 - 3] + _DRAWN


@pytest.mark.parametrize("x64", [False, True], ids=["x32", "x64"])
@pytest.mark.parametrize("seed", SEEDS)
def test_the_host_key_is_the_librarys(seed, x64):
    """Bit for bit, dtype and shape: with 64-bit types off the library
    narrows the seed first and the high word is 0; with them on it is
    the seed's high half."""
    with jax.enable_x64(x64):
        want = np.asarray(jax.random.PRNGKey(seed))
        got = engine_mod._keys_for([seed])[0]
    assert got.dtype == want.dtype == np.uint32
    assert got.shape == want.shape == (2,)
    assert got.tolist() == want.tolist()
    if not x64:
        assert got.tolist() == [0, seed & 0xFFFFFFFF]


@pytest.mark.parametrize("seed", [np.int32(-5), np.uint32(2 ** 32 - 1),
                                  np.int64(2 ** 40 + 1)],
                         ids=["int32", "uint32", "int64"])
def test_the_host_key_of_a_numpy_seed_is_the_librarys(seed):
    assert engine_mod._keys_for([seed])[0].tolist() == \
        np.asarray(jax.random.PRNGKey(seed)).tolist()


def test_a_dispatchs_keys_at_once_are_each_seeds():
    keys = engine_mod._keys_for(SEEDS)
    assert keys.shape == (len(SEEDS), 2) and keys.dtype == np.uint32
    assert keys.tolist() == [np.asarray(jax.random.PRNGKey(s)).tolist()
                             for s in SEEDS]
    assert engine_mod._keys_for([]).shape == (0, 2)


def _another_default_prng(monkeypatch):
    # the library's side changes: a key of four words
    return jax.default_prng_impl("rbg")


def _another_seeding(monkeypatch):
    # the host's side is wrong (as if the library's seeding had moved)
    monkeypatch.setattr(
        engine_mod, "_keys_for",
        lambda seeds: np.array([[0, s & 0xFFFF] for s in seeds], np.uint32))
    return jax.default_prng_impl("threefry2x32")


@pytest.mark.parametrize("disagree", [_another_default_prng,
                                      _another_seeding])
def test_an_engine_is_refused_where_the_two_forms_disagree(
        disagree, gpt2_params, monkeypatch):
    with disagree(monkeypatch):
        with pytest.raises(RuntimeError, match="PRNGKey.*disagrees"):
            InferenceEngine(GPT2, gpt2_params, PAGED, dtype=jnp.float32)
    monkeypatch.undo()
    InferenceEngine(GPT2, gpt2_params, PAGED, dtype=jnp.float32).close()


# ------------------------------------------------------------------ #
# the sampled tokens are the reference's
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("inference", [PAGED, DENSE],
                         ids=["paged", "dense"])
def test_a_sampled_request_draws_what_the_librarys_key_draws(
        inference, gpt2_params):
    """Temperature 1.0: the token at absolute position p is
    ``categorical(fold_in(PRNGKey(seed), p), logits[p - 1] / T)`` on
    the plain forward's logits, prefill's first token and every
    decoded one, for seeds with the high bit set and past 32 bits."""
    engine = InferenceEngine(GPT2, gpt2_params, inference,
                             dtype=jnp.float32)
    prompts = [[1, 2, 3], [4, 5, 6, 7, 8], [9, 10]]
    seeds = [7, 2 ** 31 + 11, 2 ** 32 + 5]
    outs = engine.generate(prompts, max_new_tokens=6, temperature=1.0,
                           seeds=seeds)
    engine.close()
    for prompt, seed, out in zip(prompts, seeds, outs):
        key = jax.random.PRNGKey(seed)
        ids = list(prompt)
        for p in range(len(prompt), len(prompt) + 6):
            logits = gpt2_forward(gpt2_params, GPT2,
                                  jnp.asarray([ids], jnp.int32),
                                  dtype=jnp.float32)[0, -1]
            ids.append(int(jax.random.categorical(
                jax.random.fold_in(key, p), logits / 1.0)))
        assert out == ids, seed


# ------------------------------------------------------------------ #
# host arrays in: one entry a program, nothing of the device's touched
# ------------------------------------------------------------------ #
def _programs(engine):
    """Every jitted program the engine holds, by attribute."""
    return {name: prog for name, prog in vars(engine).items()
            if isinstance(prog, TrackedFunction)}


def _tokens(n, first):
    """``n`` tokens of a repeating run (what the n-gram drafter
    proposes from) that starts at ``first``: two prompts with other
    first tokens share no page."""
    return [(first + i) % 4 + 1 for i in range(n)]


def _phases(engine, longest):
    """Groups of requests served one after the other: one and two
    prompts of each prompt bucket (every (batch, prompt) program of
    batch buckets (1, 2)), then a mix with the longest prompt, sampled
    and greedy rows together, every request a seed of its own."""
    small, large = engine.config["prompt_buckets"]
    groups = [[_tokens(small - 1, 0)],
              [_tokens(small - 1, 1), _tokens(small - 1, 2)],
              [_tokens(large - 1, 0)],
              [_tokens(large - 1, 1), _tokens(large - 1, 2)],
              [_tokens(longest, 3), _tokens(small - 1, 3),
               _tokens(large - 1, 3), _tokens(2, 0), _tokens(longest, 2)]]
    seeds = iter(range(10 ** 9, 10 ** 9 + 100))
    return [[Request(prompt=p, max_new_tokens=5,
                     temperature=0.7 if i % 2 else 0.0, seed=next(seeds),
                     eos_id=None) for i, p in enumerate(group)]
            for group in groups]


def _serve(engine, phases):
    finished = []
    for group in phases:
        for r in group:
            engine.submit(r)
        while not engine.scheduler.idle():
            finished.extend(engine.step())
    return finished


# model, inference, the longest prompt served
CASES = {
    "paged": (GPT2, PAGED, 8),
    "dense": (GPT2, DENSE, 8),
    "chunked_spec": (GPT2, CHUNKED_SPEC, 20),
    "separate_pools": (GPT2, SEPARATE, 8),
    "state_pool": (SOLAR, STATE, 32),
}


@pytest.fixture
def case(request, gpt2_params, solar_params):
    model, inference, longest = CASES[request.param]
    params = solar_params if model is SOLAR else gpt2_params
    engine = InferenceEngine(model, params, inference, dtype=jnp.float32)
    yield request.param, engine, _phases(engine, longest)
    engine.close()


@pytest.mark.parametrize("case", list(CASES), indirect=True)
def test_serving_leaves_every_jit_cache_as_warmup_left_it(case):
    """A device array where warm-up passed a host array (or the other
    way) is a second entry in a program's jit cache, and counted a
    recompile: after warm-up a run through every prefill bucket (and
    chunk, verify and handoff where the engine has them) adds none."""
    name, engine, phases = case
    engine.warmup()
    programs = _programs(engine)
    assert {"_prefill", "_decode", "_merge"} <= set(programs)
    warm = {n: p._cache_size() for n, p in programs.items()}
    finished = _serve(engine, phases)
    assert len(finished) == sum(len(g) for g in phases)
    assert {n: p._cache_size() for n, p in programs.items()} == warm
    assert engine.steady_state_recompiles == 0
    ran = set(engine.dispatch_ledger.table()["cls"])
    assert {c for c in ran if c[0] == "prefill"} == {
        ("prefill", bb, pb) for bb in (1, 2)
        for pb in engine.config["prompt_buckets"]}
    assert "decode" in {c[0] for c in ran}
    if name == "chunked_spec":
        # the longest prompt went in chunks, the repeats were verified
        assert {"chunk", "verify"} <= {c[0] for c in ran}
        assert warm["_verify"] > 0
    if name == "separate_pools":
        assert ("handoff",) in ran
        assert warm["_export"] == warm["_import"] == 1


def _kinds(args):
    """("device" | "host" | "tree") an argument of a program call."""
    return tuple("device" if isinstance(a, jax.Array)
                 else "host" if isinstance(a, np.ndarray) else "tree"
                 for a in args)


@pytest.mark.parametrize("case", list(CASES), indirect=True)
def test_the_loop_passes_the_kinds_of_argument_warmup_passed(case):
    """To a jit a NumPy argument and a device array are two cache
    entries: every program is called in the loop with the kinds (and
    shapes) of argument warm-up called it with. The decode program's
    tokens are a DEVICE array (the one the decode before returned, first
    tokens merged in), never an upload of what the host read back; the
    merge program takes the device's tokens, a program's result as it
    returned it, and the host's slots."""
    name, engine, phases = case
    calls = {}

    def watch(attr):
        prog = getattr(engine, attr)

        def watched(*args):
            shapes = tuple(getattr(a, "shape", None) for a in args)
            calls.setdefault(attr, set()).add((_kinds(args), shapes))
            return prog(*args)
        watched._cache_size = prog._cache_size
        setattr(engine, attr, watched)

    for attr in _programs(engine):
        watch(attr)
    engine.warmup()
    warmed = {attr: set(seen) for attr, seen in calls.items()}
    finished = _serve(engine, phases)
    assert len(finished) == sum(len(g) for g in phases)
    assert engine.steady_state_recompiles == 0
    for attr, seen in calls.items():
        assert seen <= warmed[attr], (attr, seen - warmed[attr])
    rows = engine._rows
    for kinds, shapes in calls["_decode"]:
        # params, cache, tokens, then host arrays
        assert kinds[:3] == ("tree", "tree", "device")
        assert set(kinds[3:]) == {"host"} and shapes[2] == (rows,)
    for kinds, shapes in calls["_merge"]:
        assert kinds[0] == "device" and kinds[2] == "host"
        assert shapes[0] == (rows,) and shapes[1][0] >= shapes[2][0]
    host_values = {k for k, _ in calls["_merge"] if k[1] == "host"}
    # values the HOST chose reach the device only where an engine reads
    # every dispatch at once: a verify run's last kept tokens, a claimed
    # handoff's first token
    assert bool(host_values) == (name in ("chunked_spec",
                                          "separate_pools"))


class _NoAsarray(types.ModuleType):
    """``jax.numpy`` as the engine module sees it, ``asarray`` refused."""

    def __getattr__(self, name):
        if name == "asarray":
            raise AssertionError("jnp.asarray in a dispatch's host section")
        return getattr(jnp, name)


def _refuse(what):
    def refused(*args, **kwargs):
        raise AssertionError(f"{what} in a dispatch's host section")
    return refused


@pytest.mark.parametrize("case", ["paged", "dense", "chunked_spec",
                                  "state_pool"], indirect=True)
def test_a_batch_is_served_with_the_device_calls_refused(case,
                                                        monkeypatch):
    """Warm-up and every step run with ``jax.random.PRNGKey``,
    ``jax.device_put`` and the engine module's ``jnp.asarray`` raising:
    the only device call of a dispatch is the program's own."""
    name, engine, phases = case
    monkeypatch.setattr(jax.random, "PRNGKey", _refuse("PRNGKey"))
    monkeypatch.setattr(jax, "device_put", _refuse("device_put"))
    monkeypatch.setattr(engine_mod, "jnp", _NoAsarray("jnp"))
    engine.warmup()
    finished = _serve(engine, phases)
    assert len(finished) == sum(len(g) for g in phases)
    assert all(len(f.tokens) == 5 for f in finished)
    assert engine.steady_state_recompiles == 0


def test_distinct_seeds_leave_no_state_on_the_engine(gpt2_params):
    """A service whose clients send their own seeds: 5,000 distinct
    ones leave no container on the engine or its scheduler larger (a
    memo of their keys had to be bounded, and its clear-all was a
    stall)."""
    inference = {"max_batch_size": 16, "prompt_buckets": [4],
                 "batch_buckets": [16], "max_seq_len": 16,
                 "paged_kv": {"prefix_cache": False}}
    engine = InferenceEngine(GPT2, gpt2_params, inference,
                             dtype=jnp.float32)

    def held(obj):
        return {n: len(v) for n, v in vars(obj).items()
                if isinstance(v, (dict, list, set, tuple))}

    def grown(after, before):
        return {n for n in after if after[n] > before[n] + 16}

    engine.generate([[1, 2, 3]] * 16, max_new_tokens=2, temperature=0.9,
                    seeds=range(16))
    before = held(engine), held(engine.scheduler)
    seeds = [7_000_000_000 + 3 * i for i in range(5000)]
    outs = engine.generate([[1, 2, 3]] * 5000, max_new_tokens=2,
                           temperature=0.9, seeds=seeds)
    assert len(outs) == 5000 and len({tuple(o) for o in outs}) > 30
    assert grown(held(engine), before[0]) == set()
    # the scheduler's log of finished requests is a request's, not a
    # seed's
    assert grown(held(engine.scheduler), before[1]) <= {"finished"}
    engine.close()
