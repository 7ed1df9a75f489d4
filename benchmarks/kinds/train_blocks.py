"""Traffic kind `train_blocks`: `deepspeed_tpu.initialize` + `train_batch`
over a stream of distinct seeded batches, measured as consecutive blocks
of a fixed number of steps.

Each block is ended by one `block_until_ready` on its last loss, with no
sync inside it and the input iterator running. `train_tokens_per_s` is
every token of the window over the window's wall time, per chip: a stall
on the host is in it, as a user's bill is. Beside it, for the traced
run, go the rate from the MEDIAN block time (`core/stats.median_block`),
which one stall does not move, and the stall share that tells the two
apart. Warm-up runs whole blocks inside set-up until two in a row agree,
so the slow steps that follow a compile never reach the window.

Traffic parameters (`traffic/<name>.json`): `seq`, `micro_batch_per_chip`,
`steps_per_block`, `warm_blocks_max`, `warm_agree`, `warm_steps_min` (the
engine's every-`steps_per_print` report builds a few scalar programs the
first time: it has to fall inside warm-up), `trace_blocks`,
`zipf_exponent`, `successor_share`, `loss_tolerance`.
"""

import copy
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import gpt2_loss_fn, init_gpt2_params
from deepspeed_tpu.parallel.mesh import build_mesh
from deepspeed_tpu.runtime.zero.sharding import zero_shardings

from core import draws, flops, stats
from core.gpt2_model import model_of
from reference import gpt2_reference


def make_params(model, seed, mesh_axes, stage):
    """fp32 weights on the device(s) in ONE jitted call from the seed,
    already laid out as the engine's ZeRO stage will hold them, so that
    no chip ever holds the whole of a model it could not."""
    mesh = build_mesh(mesh_axes)
    key = jax.random.PRNGKey(draws.seed32(seed, 31) % (2 ** 31))
    init = lambda k: init_gpt2_params(model, k)
    shardings = zero_shardings(jax.eval_shape(init, key), mesh,
                               stage=stage, axis_name="data")
    return jax.jit(init, out_shardings=shardings)(key)


def run(ctx):
    cfg, tr = ctx.config, ctx.traffic
    chips = ctx.cell["chips"]
    train = cfg["train"]
    model = model_of(cfg, train["vocab_size"], dropout=0.0)
    seq, micro = tr["seq"], tr["micro_batch_per_chip"]
    ds_config = copy.deepcopy(train["ds_config"])
    ds_config["train_micro_batch_size_per_gpu"] = micro
    ds_config["mesh"] = {"axes": {"data": chips}}
    stage = ds_config["zero_optimization"]["stage"]

    t0 = time.perf_counter()
    params = make_params(model, ctx.seed, ds_config["mesh"]["axes"], stage)
    engine, *_ = deepspeed_tpu.initialize(
        model=gpt2_loss_fn(model, deterministic=True),
        model_parameters=params, config=ds_config)
    del params
    rows = engine.train_batch_size() // engine.gradient_accumulation_steps
    tokens_per_step = rows * seq
    n_params = flops.gpt2_param_count(
        model.vocab_size, model.max_position_embeddings, model.hidden_size,
        model.num_layers, model.inter)
    ctx.log(f"engine up in {time.perf_counter() - t0:.1f} s: "
            f"{n_params / 1e6:.1f}M parameters, mesh "
            f"{dict(engine.mesh.shape)}, ZeRO stage {engine.zero_stage}, "
            f"micro-batch {micro} x {seq} a chip, global batch {rows}")

    stream = draws.TokenStream(ctx.seed, model.vocab_size, rows, seq,
                               tr["zipf_exponent"], tr["successor_share"])

    # ---- correctness, before any step: the plain reference's loss on
    # the first batch under the very weights the engine starts from
    first = next(stream)
    t0 = time.perf_counter()
    ref_loss = reference_loss(engine.state.params, first["input_ids"],
                              model)
    ctx.log(f"reference loss on the first batch {ref_loss:.5f} "
            f"({time.perf_counter() - t0:.1f} s)")
    batches = iter(stream)
    t0 = time.perf_counter()
    with ctx.span("train_batch"):
        loss0 = float(engine.train_batch(iter([first])))
    ctx.log(f"first train_batch (compile or cache load + step) "
            f"{time.perf_counter() - t0:.1f} s, loss {loss0:.5f}")

    steps = tr["steps_per_block"]

    def block(collect):
        t = time.perf_counter()
        for _ in range(steps):
            with ctx.span("train_batch"):
                loss = engine.train_batch(batches)
            collect.append(loss)
        with ctx.span("block_sync"):
            jax.block_until_ready(loss)
        return time.perf_counter() - t

    # ---- warm-up, inside set-up: whole blocks until two in a row agree
    warm, warm_losses = [], []
    for _ in range(tr["warm_blocks_max"]):
        warm.append(block(warm_losses))
        if len(warm) >= 2 and len(warm) * steps >= tr["warm_steps_min"] \
                and abs(warm[-1] - warm[-2]) <= tr["warm_agree"] * warm[-1]:
            break
    ctx.log("warm-up blocks (s): " + " ".join(f"{b:.4f}" for b in warm))
    del warm_losses
    gc.collect()
    gc.freeze()             # nothing of set-up is scanned in the window

    # ---- the window
    compiles_before = ctx.compiles.compiles
    times, losses = [], []
    ctx.setup_done()
    t_open = time.perf_counter()
    traced = None
    while time.perf_counter() - t_open < ctx.seconds:
        if ctx.trace and traced is None and len(times) == 1:
            ctx.start_trace()
            traced = [len(times), None]
        times.append(block(losses))
        if traced and traced[1] is None and \
                len(times) - traced[0] >= tr["trace_blocks"]:
            ctx.stop_trace()
            traced[1] = len(times)
    ctx.stop_trace()
    wall = time.perf_counter() - t_open
    compiles_in_window = ctx.compiles.compiles - compiles_before

    losses = [float(x) for x in losses]
    est = stats.median_block(times, steps, tokens_per_step, chips)
    rate = len(losses) * tokens_per_step / wall / chips
    ctx.log(f"{est['blocks']} blocks of {steps} steps in {wall:.3f} s: "
            f"{rate:.1f} tokens/s/chip over the whole window; block "
            f"seconds min {min(times):.4f} median "
            f"{est['median_block_s']:.4f} max {max(times):.4f}; from the "
            f"median block {est['tokens_per_s_per_chip']:.1f} "
            f"tokens/s/chip, step {est['step_ms']:.3f} ms, stall share "
            f"{est['stall_share_pct']:.3f}%")
    third = max(len(losses) // 3, 1)
    early = float(np.mean(losses[:third]))
    late = float(np.mean(losses[-third:]))
    ctx.log(f"loss: first step {loss0:.4f}, window early third "
            f"{early:.4f}, late third {late:.4f}")

    why_not = []
    # bf16 compute against a float32 reference: the loss is a mean of
    # 8,192 log-probabilities of magnitude ~11 whose bf16 rounding errors
    # (2^-8 relative, partly systematic through 24 layers) average out
    # to 2e-4..9e-4 on the chip (13 runs at 345M, PR 23). The tolerance
    # is three times the largest of those. At random weights the loss
    # sits near ln(vocab) whatever the network does, so only a tight
    # tolerance tells a precision or mask fault from rounding; the loss
    # having to fall through the window (below) catches what it cannot.
    if not abs(loss0 - ref_loss) <= tr["loss_tolerance"]:
        why_not.append(f"first-step loss {loss0} differs from the "
                       f"reference's {ref_loss} by more than "
                       f"{tr['loss_tolerance']}")
    if not all(np.isfinite(losses)):
        why_not.append("a loss in the window is not finite")
    if not late < early:
        why_not.append(f"loss did not fall in the window: {early} -> "
                       f"{late}")
    if compiles_in_window:
        why_not.append(f"{compiles_in_window} compilations inside the "
                       "window")
    engine.close()

    traced_blocks = times[traced[0]:traced[1]] if traced else []
    facts = {
        "kind": "train_blocks", "block_seconds": times,
        "steps_per_block": steps, "tokens_per_step": tokens_per_step,
        "chips": chips, "window_wall_s": wall,
        "whole_window_tokens_per_s_per_chip": rate, "estimate": est,
        "traced_steps": len(traced_blocks) * steps,
        "n_params": n_params, "compiles_in_window": compiles_in_window,
        "model": {"layers": model.num_layers, "hidden": model.hidden_size,
                  "heads": model.num_heads, "seq": seq,
                  "micro_batch_per_chip": micro},
    }
    return {"correct": not why_not, "why_not": why_not,
            "attempted": len(losses), "failed": 0,
            "end_to_end": {"train_tokens_per_s": rate},
            "facts": facts}


def reference_loss(params, ids, model):
    """The plain float32 loss, one row at a time (what a chip holds
    beside the engine), under the engine's own parameter placement: the
    compiler gathers what a row needs."""
    fn = jax.jit(lambda p, row: gpt2_reference.next_token_loss(
        p, row, model.num_layers, model.num_heads))
    rows = [fn(params, jnp.asarray(ids[i:i + 1]))
            for i in range(ids.shape[0])]
    return float(np.mean([float(r) for r in rows]))
