#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py              # one TPU chip: device, kernels,
                                      # train, serve
    python chip_smoke.py --chips 4    # one four-chip host: only the
                                      # sharded paths and what they are
                                      # compared with

One process (a chip belongs to one process at a time), no network, no
files besides the checkout: weights and tokens are made from ``--seed``.
It drives the two main paths through the entry points a user calls, at
the published widths of GPT-2 345M:

- *device*  — fails at once unless JAX's first device is a TPU;
- *kernels* — each Pallas kernel of the main path, compiled
  (``interpret=False``), against its ``jnp`` oracle at real widths, and
  the delta-rule chunk scan of the served hybrids (float32 products in
  Mosaic) against the sequential recurrence;
- *train*   — ``deepspeed_tpu.initialize`` with
  ``examples/megatron_gpt2/ds_config_zero2.json`` (bf16, ZeRO-2,
  micro-batch 8 x 1024 tokens), a few ``train_batch`` steps on one
  repeated batch: finite losses that start near ln(vocab) and fall, and
  a compiled step that contains the attention kernel;
- *serve*   — ``InferenceEngine`` on ``GPT2_MEDIUM`` through the default
  paged-KV path: ``warmup()``, greedy ``generate`` on prompts of mixed
  lengths, checked against the plain (no-cache) forward, with zero
  recompiles after warmup and the decode reader named.

Any phase that raises ends the run with a non-zero exit code. Everything
it prints before the last line is smoke output, not a benchmark: the
last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

import argparse
import gc
import importlib.metadata
import itertools
import json
import math
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)        # the package is not installed

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import deepspeed_tpu  # noqa: E402
from deepspeed_tpu.inference import InferenceEngine  # noqa: E402
from deepspeed_tpu.models.gpt2 import (GPT2_MEDIUM, count_params,  # noqa
                                       gpt2_forward, gpt2_loss_fn,
                                       init_gpt2_params)
from deepspeed_tpu.ops.attention.flash import (attention_reference,  # noqa
                                               flash_attention)
from deepspeed_tpu.ops.attention.paged import (paged_decode_attention,  # noqa
                                               paged_decode_reference,
                                               paged_decode_supported)
from deepspeed_tpu.profiling.flops import peak_flops_per_device  # noqa: E402
from deepspeed_tpu.utils.platform import enable_compile_cache  # noqa: E402

# GPT-2 345M as the training example builds it (GPT2_345M in
# examples/megatron_gpt2/train.py): GPT2_MEDIUM's widths with the
# Megatron-padded vocabulary, dropout off (the example trains
# deterministically)
TRAIN_MODEL = GPT2_MEDIUM._replace(vocab_size=50304, embd_dropout=0.0,
                                   attn_dropout=0.0, resid_dropout=0.0)
TRAIN_CONFIG = os.path.join(REPO, "examples", "megatron_gpt2",
                            "ds_config_zero2.json")
TRAIN_SEQ = 1024
# (batch, heads, seq, head_dim): the train step's attention shape, and
# the same token count at the head width the paged-decode kernel needs
KERNEL_SHAPES = ((8, 16, 1024, 64), (4, 16, 1024, 128))
# bf16 tolerances. Kernel outputs are bf16 (8 mantissa bits) of O(1)
# values against an fp32 oracle. Logits are fp32 sums of bf16 products
# of magnitude ~2-4, where one bf16 step of the hidden state moves a
# logit by ~2^-6: two paths that round in a different order may differ
# by a few such steps.
KERNEL_TOL = 2e-2
LOGIT_TOL = 0.08


def log(phase, msg):
    print(f"[{phase}] {msg}", flush=True)


# ------------------------------------------------------------------ device
def phase_device(chips):
    """Fail at once unless JAX's devices are TPUs, ``chips`` of them."""
    devices = jax.devices()
    d0 = devices[0]
    versions = " ".join(f"{p}={importlib.metadata.version(p)}"
                        for p in ("jax", "jaxlib", "libtpu"))
    log("device", f"platform={d0.platform} kind={d0.device_kind!r} "
                  f"count={len(devices)} {versions}")
    if d0.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: [device] FAILED: JAX's first device is "
            f"{d0.platform!r} ({d0.device_kind!r}), not a TPU. This smoke "
            "proves the chip path and never falls back to the CPU; run "
            "the tests (JAX_PLATFORMS=cpu python -m pytest tests/) here "
            "instead.")
    if len(devices) < chips:
        raise SystemExit(
            f"chip_smoke: [device] FAILED: --chips {chips} needs {chips} "
            f"TPU devices, JAX reports {len(devices)}")
    peak, label = peak_flops_per_device(d0)    # unknown kind raises
    log("device", f"peak table entry for {label!r}: "
                  f"{peak / 1e12:.0f} TFLOP/s bf16")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices)}


class CompileCacheCounter:
    """Counts JAX's persistent-cache hits and misses in this process, so
    a second run in the same tree can show that it compiled less."""

    def __init__(self):
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


# ----------------------------------------------------------------- kernels
def random_qkv(batch, heads, seq, head_dim, seed=0):
    key = jax.random.PRNGKey(seed)
    return tuple(jax.random.normal(jax.random.fold_in(key, i),
                                   (batch, heads, seq, head_dim),
                                   jnp.bfloat16) for i in range(3))


def assert_close(a, b, msg, atol=KERNEL_TOL, rtol=KERNEL_TOL):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32),
                               atol=atol, rtol=rtol, err_msg=msg)


def grad_pair(fn_a, fn_b, args):
    """Gradients of sum(fn(*args)) w.r.t. every arg, for both fns."""
    def grads(fn):
        return jax.jit(jax.grad(
            lambda *xs: jnp.sum(fn(*xs).astype(jnp.float32)),
            argnums=tuple(range(len(args)))))(*args)
    return grads(fn_a), grads(fn_b)


def check_flash_causal(shape, seed):
    """The default training attention, forward and backward, against
    the fp32 ``attention_reference``."""
    q, k, v = random_qkv(*shape, seed=seed)

    def kernel(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False)

    def oracle(q, k, v):
        return attention_reference(q, k, v, causal=True)

    assert_close(jax.jit(kernel)(q, k, v), jax.jit(oracle)(q, k, v),
                 f"fwd {shape}")
    for a, b, name in zip(*grad_pair(kernel, oracle, (q, k, v)), "qkv"):
        assert_close(a, b, f"d{name} {shape}")


def check_flash_dropout(shape, seed, rate=0.1):
    """Attention dropout runs inside the kernel: the forward must equal
    the oracle under the same hash mask, and the gradients (which
    regenerate the mask twice more) must be finite and of the inputs'
    shapes."""
    q, k, v = random_qkv(*shape, seed=seed)
    rng = jax.random.PRNGKey(seed + 1)

    def attend(q, k, v, reference):
        return flash_attention(q, k, v, causal=True, dropout_rate=rate,
                               dropout_rng=rng, interpret=False,
                               force_reference=reference)

    out = jax.jit(lambda *a: attend(*a, False))(q, k, v)
    assert out.shape == q.shape and bool(jnp.isfinite(out).all()), shape
    assert_close(out, jax.jit(lambda *a: attend(*a, True))(q, k, v),
                 f"dropout fwd {shape}")
    grads = jax.jit(jax.grad(
        lambda *a: jnp.sum(attend(*a, False).astype(jnp.float32)),
        argnums=(0, 1, 2)))(q, k, v)
    for g, x in zip(grads, (q, k, v)):
        assert g.shape == x.shape and bool(jnp.isfinite(g).all()), shape


def check_paged_decode(seed, head_dim=128, page_size=16):
    """The paged-decode kernel against the gather reader, rows at mixed
    cache positions over shuffled pages."""
    batch, heads, num_pages, pages_per_seq = 8, 16, 160, 16
    ok, why = paged_decode_supported(page_size, head_dim, jnp.bfloat16,
                                     kv_heads=heads)
    assert ok, why
    rs = np.random.RandomState(seed)
    key = jax.random.PRNGKey(seed)
    q = jax.random.normal(key, (batch, heads, head_dim), jnp.bfloat16)
    # the stacked pool's layout: one token a row, heads side by side
    layers, layer = 2, 1
    kpool, vpool = (
        jax.random.normal(jax.random.fold_in(key, i),
                          (layers, num_pages, page_size, heads * head_dim),
                          jnp.bfloat16) for i in (1, 2))
    # page 0 is the null page: every row owns distinct pages >= 1
    tables = 1 + rs.permutation(num_pages - 1)[:batch * pages_per_seq]
    tables = jnp.asarray(tables.reshape(batch, pages_per_seq), jnp.int32)
    positions = jnp.asarray(
        rs.randint(0, page_size * pages_per_seq, (batch,)), jnp.int32)
    got = paged_decode_attention(q, kpool, vpool, tables, positions,
                                 interpret=False, layer=layer)
    want = paged_decode_reference(q, kpool, vpool, tables, positions,
                                  layer=layer)
    assert_close(got, want, f"paged decode hd={head_dim} ps={page_size}")


def check_delta_rule_scan(seed, shape=(2, 2048, 32, 128), tol=1e-4):
    """``ops/kda.kda_chunk_scan`` (one Mosaic kernel: the products at
    float32, the solve by substitution) against ``kda_sequential`` from
    a random state at a chunk dispatch's shape: steps up to 2 under
    mild decays, under decays no exp(-G) could hold (a chunk of 64 by
    e^-290), and the step 2 at EVERY position under those (the solve at
    an eigenvalue of -1). Interpret-mode equality says nothing of the
    chip's float32 products; the worst differences are logged."""
    from deepspeed_tpu.ops import kda
    B, S, H, D = shape
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(keys[0], shape)) * D ** -0.5
    k = unit(jax.random.normal(keys[1], shape))
    v = jax.random.normal(keys[2], shape)
    state = jax.random.normal(keys[5], (B, H, D, D))
    sequential, scan = jax.jit(kda.kda_sequential), jax.jit(kda.kda_chunk_scan)
    worst = [0.0, 0.0]
    for strong, every in ((False, False), (True, False), (True, True)):
        g = -jnp.exp(jax.random.uniform(
            keys[3], shape, minval=-7.0, maxval=1.5 if strong else -1.0))
        beta = 2.0 * jax.nn.sigmoid(jax.random.normal(keys[4], (B, S, H)))
        if every:
            beta = jnp.full_like(beta, 2.0)
        want_o, want_s = sequential(q, k, v, g, beta, state)
        got_o, got_s = scan(q, k, v, g, beta, state)
        gaps = [float(jnp.max(jnp.abs(a - b)))
                for a, b in ((got_o, want_o), (got_s, want_s))]
        assert gaps[0] <= tol and gaps[1] <= tol, (strong, every, gaps)
        worst = [max(w, x) for w, x in zip(worst, gaps)]
    log("kernels", f"delta-rule chunk scan {shape}: worst |o - sequential| "
                   f"{worst[0]:.3g}, worst |state - sequential| "
                   f"{worst[1]:.3g} (limit {tol})")
    return worst


def phase_kernels(seed):
    checks = [(f"masked flash causal fwd+bwd {s}",
               lambda s=s: check_flash_causal(s, seed))
              for s in KERNEL_SHAPES]
    checks += [
        (f"masked flash causal + dropout {KERNEL_SHAPES[0]}",
         lambda: check_flash_dropout(KERNEL_SHAPES[0], seed)),
        ("paged decode bf16 head_dim=128 vs gather reader",
         lambda: check_paged_decode(seed)),
        # GPT-2's head width: the kernel streams whole pool rows, so
        # it is the row (16 x 64 lanes) that has to be whole lane tiles
        ("paged decode bf16 head_dim=64 vs gather reader",
         lambda: check_paged_decode(seed, head_dim=64)),
        ("delta-rule chunk scan float32 vs the sequential recurrence",
         lambda: check_delta_rule_scan(seed)),
    ]
    for name, check in checks:
        t0 = time.perf_counter()
        check()
        log("kernels", f"ok  {name}  ({time.perf_counter() - t0:.1f} s, "
                       "compile included)")


# ------------------------------------------------------------------- train
def load_train_config(warmup_steps=10):
    """The example's ZeRO-2 config, with its 2000-step warmup shortened
    in this copy to the length of the run: at step 8 of 2000 nothing
    would move, and with no ramp at all the first Adam steps of a fresh
    345M model make the loss jump about."""
    with open(TRAIN_CONFIG) as f:
        config = json.load(f)
    config["scheduler"]["params"]["warmup_num_steps"] = warmup_steps
    return config


def compiled_train_step(engine, batch):
    """The program ``train_batch`` dispatches, lowered and compiled
    ahead of time through the engine's own jit wrapper (the technique
    of tests/unit/test_hlo_collectives.py): its memory analysis says
    whether the job fits, its text whether the kernel is in it."""
    step = (engine._get_compiled_batch_step() if engine._batch_path()
            else engine._get_compiled_micro_step())
    return step.lower(engine.state, batch).compile()


def train_losses(model, config, seq, steps, seed, require_kernel=True,
                 tag="train"):
    """``steps`` x ``train_batch`` on one repeated batch of synthetic
    tokens; returns (engine, losses)."""
    params = init_gpt2_params(model, jax.random.PRNGKey(seed))
    n_params = count_params(params)
    engine, *_ = deepspeed_tpu.initialize(
        model=gpt2_loss_fn(model, deterministic=True),
        model_parameters=params, config=config)
    del params                      # the engine holds its own copy
    rows = engine.train_batch_size() // engine.gradient_accumulation_steps
    batch = {"input_ids": np.random.RandomState(seed).randint(
        0, model.vocab_size, (rows, seq + 1)).astype(np.int32)}
    log(tag, f"{n_params / 1e6:.0f}M parameters, mesh "
             f"{dict(engine.mesh.shape)}, ZeRO stage {engine.zero_stage}, "
             f"micro-batch {engine.train_micro_batch_size_per_gpu()} x "
             f"{seq} tokens a device, global batch {rows}")

    t0 = time.perf_counter()
    compiled = compiled_train_step(engine, batch)
    aot_s = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    kernel_calls = compiled.as_text().count("tpu_custom_call")
    del compiled
    gib = 2.0 ** 30
    log(tag, f"step program: ahead-of-time compile {aot_s:.1f} s; per "
             f"device arguments {mem.argument_size_in_bytes / gib:.2f} GiB "
             f"(aliased to outputs {mem.alias_size_in_bytes / gib:.2f}), "
             f"temporaries {mem.temp_size_in_bytes / gib:.2f} GiB, code "
             f"{mem.generated_code_size_in_bytes / gib:.2f} GiB; "
             f"{kernel_calls} Pallas kernel calls (tpu_custom_call)")
    if require_kernel:
        assert kernel_calls > 0, \
            "the compiled train step contains no Pallas attention kernel"

    batches = itertools.repeat(batch)
    t0 = time.perf_counter()
    losses = [engine.train_batch(batches)]
    jax.block_until_ready(losses[0])
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(steps - 1):
        losses.append(engine.train_batch(batches))
    jax.block_until_ready(losses[-1])
    step_ms = (time.perf_counter() - t0) / max(steps - 1, 1) * 1e3
    losses = [float(x) for x in losses]
    stats = jax.local_devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    log(tag, f"first train_batch (dispatch compile + step) {first_s:.1f} s;"
             f" the next {steps - 1} steps {step_ms:.1f} ms each (the first "
             "steps after a compile run slow); device 0 peak_bytes_in_use "
             + (f"{peak / gib:.2f} GiB" if peak else "not reported")
             + " (smoke output, not a benchmark)")
    log(tag, "losses " + " ".join(f"{x:.4f}" for x in losses))
    return engine, losses


def check_losses(losses, vocab_size, min_drop):
    assert all(math.isfinite(x) for x in losses), losses
    assert abs(losses[0] - math.log(vocab_size)) < 0.5, \
        (losses[0], math.log(vocab_size))
    assert losses[-1] < losses[0] - min_drop, \
        f"loss did not fall by {min_drop}: {losses[0]} -> {losses[-1]}"


def phase_train(model, config, seq, steps, seed, min_drop,
                require_kernel=True):
    engine, losses = train_losses(model, config, seq, steps, seed,
                                  require_kernel)
    engine.close()
    del engine
    gc.collect()                    # the state leaves the device now
    check_losses(losses, model.vocab_size, min_drop)
    log("train", f"ok  {len(losses)} finite losses, first {losses[0]:.3f} "
                 f"(ln vocab {math.log(model.vocab_size):.3f}), last "
                 f"{losses[-1]:.3f}")
    return losses


# ------------------------------------------------------------------- serve
def make_prompts(lengths, vocab_size, seed):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, vocab_size, (n,)).tolist() for n in lengths]


def reference_logits(model, params, sequences):
    """The plain forward (no KV cache — the training forward, what the
    tests' greedy reference loops over), teacher-forced once over each
    served sequence: row ``i, t`` holds the logits for token ``t + 1``
    of sequence ``i`` given its first ``t + 1`` tokens."""
    width = -(-max(len(s) for s in sequences) // 128) * 128
    ids = np.zeros((len(sequences), width), np.int32)
    for i, s in enumerate(sequences):
        ids[i, :len(s)] = s
    fwd = jax.jit(lambda p, x: gpt2_forward(p, model, x))
    return np.asarray(fwd(params, jnp.asarray(ids)), np.float32)


def engine_first_logits(engine, prompts):
    """First-position logits through the engine's own cached forward —
    the body of its paged prefill program (same forward, same page pool
    layout, same reader) without the sampling, on pages 1.. of a pool
    left untouched (the updated pool is dropped)."""
    bucket = max(engine.config["prompt_buckets"])
    pages = engine.paged_spec.pages_per_seq
    tables = jnp.arange(1, pages + 1, dtype=jnp.int32)[None]

    @jax.jit
    def first(params, cache, ids, last):
        logits, _ = engine._forward(
            params, engine.model_config, ids, dtype=engine.dtype,
            kv_cache=cache, cache_position=jnp.zeros((1,), jnp.int32),
            block_tables=tables,
            paged_attn_kernel=engine._decode_attn_path)
        return logits[0, last]

    rows = []
    for prompt in prompts:
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :len(prompt)] = prompt
        rows.append(np.asarray(first(engine.params, engine._cache,
                                     jnp.asarray(ids), len(prompt) - 1),
                               np.float32))
    return rows


def check_served(tag, model, params, prompts, outputs, tol):
    """Every served token must be one the plain forward would pick, or
    within ``tol`` of its pick where random weights leave a near-tie."""
    ref = reference_logits(model, params, outputs)
    exact = total = 0
    for i, (prompt, out) in enumerate(zip(prompts, outputs)):
        assert out[:len(prompt)] == prompt and len(out) > len(prompt), i
        for t in range(len(prompt), len(out)):
            row = ref[i, t - 1, :model.vocab_size]
            best = int(row.argmax())
            gap = float(row[best] - row[out[t]])
            total += 1
            exact += out[t] == best
            if out[t] != best:
                second = float(np.partition(row, -2)[-2])
                log(tag, f"near-tie: request {i} position {t}: served "
                         f"{out[t]}, plain forward {best}, logit gap "
                         f"{gap:.4f}, reference top-two margin "
                         f"{float(row[best]) - second:.4f}")
            assert gap <= tol, \
                (f"request {i} position {t}: served token {out[t]} is "
                 f"{gap:.4f} below the plain forward's {best} "
                 f"(tolerance {tol})")
    log(tag, f"ok  {exact}/{total} served tokens are the plain forward's "
             f"argmax, the rest within {tol} of it")
    return ref


def serve(tag, model, params, inference_config, prompts, max_new_tokens,
          tol):
    """``InferenceEngine`` -> ``warmup()`` -> greedy ``generate``, with
    the outputs held to the plain forward and the zero-recompile
    contract; returns (engine, outputs, reference logits)."""
    engine = InferenceEngine(model, params, inference_config)
    t0 = time.perf_counter()
    programs = engine.warmup()
    log(tag, f"warmup compiled {programs} programs in "
             f"{time.perf_counter() - t0:.1f} s; decode reader: "
             f"{engine._decode_attn_path} ({engine._decode_attn_reason}) — "
             f"head width {model.hidden_size // model.num_heads}")
    t0 = time.perf_counter()
    outputs = engine.generate(prompts, max_new_tokens=max_new_tokens,
                              temperature=0.0, eos_id=None)
    log(tag, f"{len(prompts)} requests (prompt lengths "
             f"{[len(p) for p in prompts]}, {max_new_tokens} new tokens "
             f"each) in {time.perf_counter() - t0:.2f} s")
    recompiles = engine.steady_state_recompiles
    assert recompiles == 0, f"{recompiles} recompiles after warmup"
    ref = check_served(tag, model, params, prompts, outputs, tol)
    return engine, outputs, ref


def phase_serve(model, inference_config, prompt_lengths, max_new_tokens,
                seed, tol=LOGIT_TOL):
    params = init_gpt2_params(model, jax.random.PRNGKey(seed))
    prompts = make_prompts(prompt_lengths, model.vocab_size, seed)
    engine, outputs, ref = serve("serve", model, params, inference_config,
                                 prompts, max_new_tokens, tol)
    worst = max(
        float(np.abs(got - ref[i, len(prompt) - 1]).max())
        for i, (prompt, got) in enumerate(
            zip(prompts, engine_first_logits(engine, prompts))))
    assert worst <= tol, \
        f"first-position logits differ by {worst:.4f} (tolerance {tol})"
    log("serve", f"ok  first-position logits within {worst:.4f} of the "
                 f"plain forward's (tolerance {tol}); 0 recompiles after "
                 "warmup")
    engine.close()
    return outputs


# ------------------------------------------------- four chips (--chips 4)
def placement(tree, min_size=1 << 16):
    """Where the large leaves of ``tree`` live: the devices that hold a
    piece, the largest fraction of one leaf on one device, and the
    fullest device's share of all their bytes."""
    held, worst, total = {}, 0.0, 0
    for leaf in jax.tree_util.tree_leaves(tree):
        if getattr(leaf, "size", 0) < min_size:
            continue
        total += leaf.nbytes
        for shard in leaf.addressable_shards:
            held[shard.device] = held.get(shard.device, 0) + \
                shard.data.nbytes
            worst = max(worst, shard.data.size / leaf.size)
    return sorted(d.id for d in held), worst, max(held.values()) / total


def phase_sharded_train(model, seq, steps, seed, loss_tol=0.05):
    """ZeRO-2 over four chips against the same global batch on a
    one-device mesh — the one-chip job's own micro-batch, so both fit."""
    runs = {}
    for chips, micro in ((4, 2), (1, 8)):
        config = load_train_config()
        config["train_micro_batch_size_per_gpu"] = micro
        config["mesh"] = {"axes": {"data": chips}}
        engine, losses = train_losses(model, config, seq, steps, seed,
                                      tag=f"train dp={chips}")
        # build_mesh takes jax.devices()[:size]: check where the state
        # landed, do not assume it
        state = (engine.state.params, engine.state.opt_state)
        devices, worst, share = placement(state)
        log(f"train dp={chips}",
            f"fp32 master + Adam moments on devices {devices}: largest "
            f"piece {worst:.3f} of a leaf, fullest device holds "
            f"{share:.3f} of the bytes")
        assert len(devices) == chips, (chips, devices)
        assert max(abs(worst - 1.0 / chips),
                   abs(share - 1.0 / chips)) < 1e-6, (chips, worst, share)
        engine.close()
        del engine, state
        gc.collect()
        runs[chips] = losses
    diffs = [abs(a - b) for a, b in zip(runs[4], runs[1])]
    assert max(diffs) <= loss_tol, \
        f"dp=4 losses differ from one device by {max(diffs)} (> {loss_tol})"
    check_losses(runs[4], model.vocab_size, min_drop=0.1)
    log("train dp=4", f"ok  losses within {max(diffs):.4f} of the "
                      f"one-device run (tolerance {loss_tol})")


def phase_sharded_serve(model, prompt_lengths, max_new_tokens, seed,
                        tol=LOGIT_TOL):
    """Tensor-parallel serving over four chips against the one-chip
    engine, both held to the plain forward."""
    params = init_gpt2_params(model, jax.random.PRNGKey(seed))
    prompts = make_prompts(prompt_lengths, model.vocab_size, seed)
    outputs = {}
    for chips in (4, 1):
        config = {"mesh": {"axes": {"model": chips}}} if chips > 1 else {}
        engine, outputs[chips], _ = serve(
            f"serve tp={chips}", model, params, config, prompts,
            max_new_tokens, tol)
        # build_mesh takes jax.devices()[:size]: check, do not assume
        devices, _, share = placement(engine.params)
        log(f"serve tp={chips}",
            f"weights on devices {devices}, fullest device holds "
            f"{share:.3f} of the bytes (embeddings stay replicated)")
        assert len(devices) == chips and share < 1.0 / chips + 0.2, \
            (chips, devices, share)
        engine.close()
        del engine
        gc.collect()
    same = sum(a == b for a, b in zip(outputs[4], outputs[1]))
    log("serve tp=4", f"ok  {same}/{len(prompts)} sequences token-equal to "
                      "the one-chip engine's; every token of both within "
                      f"{tol} of the plain forward's pick")


# -------------------------------------------------------------------- main
def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded paths and what they "
                         "are compared with (default: 1)")
    ap.add_argument("--seed", type=int, default=0,
                    help="weights, tokens and prompts are made from it")
    args = ap.parse_args()

    t_start = time.perf_counter()
    device = phase_device(args.chips)
    cache = CompileCacheCounter()
    log("device", f"persistent compile cache at {enable_compile_cache()}")
    serve_prompts = (5, 23, 64, 100, 200)
    if args.chips == 1:
        phase_kernels(args.seed)
        phase_train(TRAIN_MODEL, load_train_config(), TRAIN_SEQ, steps=10,
                    seed=args.seed, min_drop=0.3)
        phase_serve(GPT2_MEDIUM, {}, serve_prompts, max_new_tokens=16,
                    seed=args.seed)
    else:
        phase_sharded_train(TRAIN_MODEL, TRAIN_SEQ, steps=6,
                            seed=args.seed)
        phase_sharded_serve(GPT2_MEDIUM, serve_prompts, max_new_tokens=16,
                            seed=args.seed)
    log("done", f"{time.perf_counter() - t_start:.0f} s; persistent "
                f"compile cache: {cache.hits} hits, {cache.misses} misses")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
