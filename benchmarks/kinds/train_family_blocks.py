"""Traffic kind `train_family_blocks`: `kinds/train_blocks.py`'s way of
measuring (`deepspeed_tpu.initialize` + `train_batch` over a stream of
distinct seeded batches, consecutive blocks of a fixed number of steps,
each ended by one `block_until_ready` with no sync inside, warm-up blocks
inside set-up until two in a row agree, `train_tokens_per_s` every token
of the window over its wall time) for ANY architecture: the model, its
initialiser, its loss, its plain reference and its forward comparison
come from `families/<family>.py`, named by the configuration's `family`.
The next architecture adds that module, a reference and data; not a kind.

`correct`: (a) the first-step loss against the reference's within
`loss_tolerance`; (b) the family's forward comparison (for `smallthinker`:
logits at seeded positions of the first row, the router's choices first)
and its backward comparison (the expert layer's hand-written gradients
against `jax.grad` of the reference's); (c) finite losses, the late third
below the early third, no compilation in the window.

What the loss fn returns beside its loss (`engine.last_aux`: the step's
own counters, outputs of the compiled step) is kept as device arrays
through a block and read on the host at the block's end only.

Traffic parameters: `train_blocks`' (`seq`, `micro_batch_per_chip`,
`steps_per_block`, `warm_blocks_max`, `warm_agree`, `warm_steps_min`,
`trace_blocks`, `zipf_exponent`, `successor_share`, `loss_tolerance`)
and the family's own (`check_positions`, `logit_tolerance`,
`route_epsilon`, `expert_grad_tolerance`).
"""

import copy
import gc
import time

import jax
import numpy as np

import deepspeed_tpu
from deepspeed_tpu.parallel.mesh import build_mesh
from deepspeed_tpu.runtime.zero.sharding import zero_shardings

from core import draws, stats
from loader import load_module


def make_params(family, model, seed, mesh_axes, stage):
    """fp32 weights on the device(s) in ONE jitted call from the seed,
    already laid out as the engine's ZeRO stage will hold them."""
    mesh = build_mesh(mesh_axes)
    key = jax.random.PRNGKey(draws.seed32(seed, 31) % (2 ** 31))
    init = lambda k: family.init_params(model, k)
    shardings = zero_shardings(jax.eval_shape(init, key), mesh,
                               stage=stage, axis_name="data")
    return jax.jit(init, out_shardings=shardings)(key)


def run(ctx):
    cfg, tr = ctx.config, ctx.traffic
    chips = ctx.cell["chips"]
    family = load_module("families", cfg["family"])
    model = family.model_of(cfg)
    seq, micro = tr["seq"], tr["micro_batch_per_chip"]
    ds_config = copy.deepcopy(cfg["train"]["ds_config"])
    ds_config["train_micro_batch_size_per_gpu"] = micro
    ds_config["mesh"] = {"axes": {"data": chips}}
    stage = ds_config["zero_optimization"]["stage"]

    t0 = time.perf_counter()
    params = make_params(family, model, ctx.seed, ds_config["mesh"]["axes"],
                         stage)
    engine, *_ = deepspeed_tpu.initialize(
        model=family.loss_fn(model), model_parameters=params,
        config=ds_config)
    del params
    rows = engine.train_batch_size() // engine.gradient_accumulation_steps
    tokens_per_step = rows * seq
    shape = family.describe(model, seq, micro)
    ctx.log(f"engine up in {time.perf_counter() - t0:.1f} s: "
            f"{shape['n_params'] / 1e6:.1f}M parameters, mesh "
            f"{dict(engine.mesh.shape)}, ZeRO stage {engine.zero_stage}, "
            f"micro-batch {micro} x {seq} a chip, global batch {rows}")

    stream = draws.TokenStream(ctx.seed, family.id_vocab(cfg), rows, seq,
                               tr["zipf_exponent"], tr["successor_share"])

    # ---- correctness, before any step, under the very weights the
    # engine starts from: the reference's loss on the first batch, and
    # the program's forward against the reference's
    first = next(stream)
    t0 = time.perf_counter()
    ref = family.reference_readings(ctx, engine.state.params,
                                    first["input_ids"], model, tr)
    ref_loss = ref["loss"]
    ctx.log(f"reference loss on the first batch {ref_loss:.5f} "
            f"({time.perf_counter() - t0:.1f} s)")
    why_not, forward_facts = family.compare_forward(
        ctx, engine.state.params, first["input_ids"], model, tr, ref)
    more, backward_facts = family.compare_backward(
        ctx, engine.state.params, first["input_ids"], model, tr)
    why_not += more
    batches = iter(stream)
    t0 = time.perf_counter()
    with ctx.span("train_batch"):
        loss0 = float(engine.train_batch(iter([first])))
    ctx.log(f"first train_batch (compile or cache load + step) "
            f"{time.perf_counter() - t0:.1f} s, loss {loss0:.5f}")

    steps = tr["steps_per_block"]

    def block(collect, counters):
        t = time.perf_counter()
        held = []
        for _ in range(steps):
            with ctx.span("train_batch"):
                loss = engine.train_batch(batches)
            collect.append(loss)
            held.append(engine.last_aux)
        with ctx.span("block_sync"):
            jax.block_until_ready(loss)
        seconds = time.perf_counter() - t
        counters.extend(family.step_counters(aux) for aux in held)
        return seconds

    # ---- warm-up, inside set-up: whole blocks until two in a row agree
    warm, warm_losses = [], []
    for _ in range(tr["warm_blocks_max"]):
        warm.append(block(warm_losses, []))
        if len(warm) >= 2 and len(warm) * steps >= tr["warm_steps_min"] \
                and abs(warm[-1] - warm[-2]) <= tr["warm_agree"] * warm[-1]:
            break
    ctx.log("warm-up blocks (s): " + " ".join(f"{b:.4f}" for b in warm))
    del warm_losses
    gc.collect()
    gc.freeze()             # nothing of set-up is scanned in the window

    # ---- the window
    compiles_before = ctx.compiles.compiles
    times, losses, counters = [], [], []
    ctx.setup_done()
    t_open = time.perf_counter()
    traced = None
    while time.perf_counter() - t_open < ctx.seconds:
        if ctx.trace and traced is None and len(times) == 1:
            ctx.start_trace()
            traced = [len(times), None]
        times.append(block(losses, counters))
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

    traced_steps = [b * steps for b in traced] if traced else None
    experts = family.counter_facts(counters, model, tokens_per_step,
                                   traced_steps)
    ctx.log(f"first-step loss differs from the reference's by "
            f"{abs(loss0 - ref_loss):.6f}; counters of the window: "
            f"{experts}; block seconds "
            + " ".join(f"{b:.3f}" for b in times)
            + "; landed share by block "
            + " ".join(f"{family.held_share(counters[i:i + steps], model, tokens_per_step):.4f}"
                       for i in range(0, len(counters), steps)))
    facts = {
        "kind": "train_family_blocks", "block_seconds": times,
        "steps_per_block": steps, "tokens_per_step": tokens_per_step,
        "chips": chips, "window_wall_s": wall,
        "whole_window_tokens_per_s_per_chip": rate, "estimate": est,
        "traced_steps": (traced_steps[1] - traced_steps[0])
        if traced else 0,
        "n_params": shape["n_params"],
        "compiles_in_window": compiles_in_window,
        "model": shape, "experts": experts, "forward": forward_facts,
        "backward": backward_facts,
        "loss_error": abs(loss0 - ref_loss),
    }
    return {"correct": not why_not, "why_not": why_not,
            "attempted": len(losses), "failed": 0,
            "end_to_end": {"train_tokens_per_s": rate},
            "facts": facts}
