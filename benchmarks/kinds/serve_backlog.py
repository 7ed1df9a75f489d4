"""Traffic kind `serve_backlog`: `InferenceEngine.submit/step` driven from
one thread, every request queued before the first step and the queue
never empty: offline batch generation. Measured is the output tokens
emitted inside the window over the window.

The engine runs `ramp_s` before the window opens (set-up the traffic
needs: the slots have left their common start and the page pool is at
its steady fill). Lengths come from `core/draws.backlog_lengths`: the
stated laws by inverse CDF over a stratified grid, queued as epochs of
`epoch_requests` that all hold the same lengths, each epoch shuffled by
itself from the traffic file's `order_seed`, NOT from `--seed`: every
run of the cell queues the same lengths in the same order, so the same
requests meet in the same prefill buckets in the same steps, and
`--seed` makes the weights and every prompt's ids. (Under the seed's
own shuffle the order was a third to a half of the spread between runs:
PERF.md §6, PR 36.)

The model comes from `families/<family>.py`, named by the
configuration's `family` (as `kinds/train_family_blocks.py` finds its
own): the program's model at the file's sizes, its initialiser, the
plain float32 reference, what a token and a slot hold in the engine's
pools, the parameter count. This file names no architecture.

Traffic parameters (`traffic/<name>.json`): `prompt`, `output` (the two
laws), `backlog_requests`, `epoch_requests`, `order_seed`,
`ramp_s`, `trace_s` (the traced seconds, the window's last),
`check_requests`, `check_pad_to`, `logit_tolerance`. The engine's
settings are the configuration's `serve.inference`.

Correctness, outside the window: a seeded sample of finished requests is
teacher-forced through the plain float32 reference, and every served
token (prefill's first and each decoded one, through the paged cache)
must be the reference's argmax or within `logit_tolerance` of it; no
program may compile inside the window, and the backlog may not run dry.
"""

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference import InferenceEngine
from deepspeed_tpu.inference.scheduler import Request

from core import draws
from loader import load_module


def run(ctx):
    cfg, tr = ctx.config, ctx.traffic
    family = load_module("families", cfg["family"])
    model = family.serve_model_of(cfg)
    inference = cfg["serve"]["inference"]
    ramp = tr["ramp_s"]

    t0 = time.perf_counter()
    key = jax.random.PRNGKey(draws.seed32(ctx.seed, 31) % (2 ** 31))
    params = jax.jit(lambda k: family.init_params(model, k))(key)
    engine = InferenceEngine(model, params, inference)
    programs = engine.warmup()
    spec = engine.paged_spec
    held = family.cache_bytes(model, engine)
    kv_token, kv_slot = held["per_token"], held["per_slot"]
    pool_tokens = (spec.num_pages - 1) * spec.page_size
    ctx.log(f"engine up and {programs} programs warm in "
            f"{time.perf_counter() - t0:.1f} s; {engine.num_slots} slots of "
            f"at most {inference['max_seq_len']} positions, decode reader "
            f"{engine._decode_attn_path}; page pool {spec.num_pages - 1} "
            f"pages x {spec.page_size} tokens = "
            f"{pool_tokens * kv_token / 1e9:.3f} GB")

    n = int(tr["backlog_requests"])
    plen, olen = draws.backlog_lengths(
        n, int(tr["epoch_requests"]), tr["order_seed"], tr["prompt"],
        tr["output"])
    prompts = draws.prompt_tokens(plen, model.vocab_size, ctx.seed)
    requests = [Request(prompt=prompts[i], max_new_tokens=int(olen[i]),
                        temperature=0.0, seed=i, eos_id=None)
                for i in range(n)]
    index_of = {r.uid: i for i, r in enumerate(requests)}
    ctx.log(f"{n} requests queued at once in epochs of "
            f"{tr['epoch_requests']} ordered by the traffic file's "
            f"order_seed {tr['order_seed']}, {int(olen.sum())} output "
            f"tokens in all; prompts "
            f"{int(plen.min())}-{int(plen.max())} (mean {plen.mean():.1f}), "
            f"outputs {int(olen.min())}-{int(olen.max())} (mean "
            f"{olen.mean():.1f})")

    done_at, finished = {}, {}
    step_log = []     # (t_end, tokens emitted so far, active slots, live
    #                    tokens, reserved pages)
    sched = engine.scheduler
    with ctx.span("submit"):
        for r in requests:
            engine.submit(r)
    gc.collect()
    gc.freeze()

    t_open = t_close = None
    tokens_open = tokens_close = compiles_open = compiles_close = 0
    traced = False
    t_start = time.perf_counter()
    while not sched.idle():
        now = time.perf_counter() - t_start
        if t_open is None and now >= ramp:
            # the window opens on a step boundary
            t_open, tokens_open = now, sched.total_tokens
            compiles_open = ctx.compiles.compiles
            ctx.setup_done()
        if t_open is not None and now >= t_open + ctx.seconds:
            t_close, tokens_close = now, sched.total_tokens
            compiles_close = ctx.compiles.compiles
            break
        if ctx.trace and not traced and t_open is not None \
                and now >= t_open + ctx.seconds - tr["trace_s"]:
            # the profiler holds the host for seconds as it stops: the
            # traced seconds are the window's last, so that this falls
            # after it
            ctx.start_trace()
            traced = True
        with ctx.span("engine_step"):
            out = engine.step()
        t = time.perf_counter() - t_start
        with ctx.span("observe"):
            # the scheduler's own count of every token it recorded (a
            # prefill's first and each decoded one), and one pass over
            # the slots for what they hold
            held_by = [s.position for s in sched.slots if s is not None]
            for f in out:
                i = index_of[f.uid]
                done_at[i], finished[i] = t, f
            step_log.append((t, sched.total_tokens, len(held_by),
                             sum(held_by), sched.allocator.pages_in_use))
    ctx.stop_trace()
    if t_close is None:
        raise SystemExit("benchmarks: the backlog ran out before the "
                         "window closed (too small for this system: a "
                         "new traffic file with more backlog_requests)")
    window = t_close - t_open
    compiles_in_window = compiles_close - compiles_open
    recompiles = engine.steady_state_recompiles
    # columns: t_end, tokens so far, active, live, pages
    log = np.asarray(step_log, np.float64)
    ends = log[:, 0]
    in_window = log[(ends > t_open) & (ends <= t_close)]
    tokens_in_window = tokens_close - tokens_open
    rate = tokens_in_window / window
    active_slots = float(in_window[:, 2].mean())
    live_tokens = float(in_window[:, 3].mean())
    reserved_tokens = float(in_window[:, 4].mean()) * spec.page_size
    ctx.log(f"ramp {t_open:.3f} s, window {window:.3f} s, "
            f"{len(in_window)} steps in it, {tokens_in_window} tokens "
            f"emitted in it ({rate:.1f} tokens/s); longest step "
            f"{np.diff(in_window[:, 0]).max():.3f} s")
    ctx.log(f"page pool over the window's steps: live keys and values "
            f"{live_tokens:.0f} tokens = {live_tokens * kv_token / 1e9:.3f} "
            f"GB ({100 * live_tokens / pool_tokens:.1f}% of the pool), "
            f"reserved by admitted requests {reserved_tokens:.0f} tokens "
            f"({100 * reserved_tokens / pool_tokens:.1f}%)")

    why_not = []
    facts = {
        "kind": "serve_backlog", "window_s": window,
        "tokens_in_window": tokens_in_window,
        "steps_in_window": len(in_window),
        "mean_active_slots": active_slots,
        "mean_live_tokens": live_tokens,
        "mean_reserved_tokens": reserved_tokens,
        "pool_tokens": pool_tokens, "kv_bytes_per_token": kv_token,
        "num_slots": engine.num_slots,
        "compiles_in_window": compiles_in_window,
        "n_params": family.param_count(model),
        "model": family.describe_served(model),
    }
    in_win = [i for i, t in done_at.items() if t_open < t <= t_close]
    failed = sum(1 for i in in_win if finished[i].finish_reason != "length")
    sample_from = [i for i in in_win
                   if finished[i].finish_reason == "length"]
    ctx.log(f"{len(in_win)} requests finished inside the window, {failed} "
            f"failed; queue depth at its end {sched.queue_depth}; mean "
            f"active slots {facts['mean_active_slots']:.2f} of "
            f"{engine.num_slots}")
    if sched.queue_depth == 0:
        why_not.append("the backlog ran dry inside the window")
    in_requests = sum(len(f.tokens) for f in finished.values()) + sum(
        len(s.tokens) for s in sched.slots if s is not None)
    if in_requests != tokens_close:
        why_not.append(f"the scheduler counted {tokens_close} tokens where "
                       f"its requests hold {in_requests}")

    # ---- correctness, outside the window
    rs = np.random.RandomState(draws.seed32(ctx.seed, 41))
    sample = [sample_from[j] for j in rs.permutation(len(sample_from))[
        :tr["check_requests"]]]
    worst = None
    if not sample:
        why_not.append("no finished request to check")
    else:
        worst, exact, total = check_served(
            family.reference_logits(model), engine.params,
            [finished[i] for i in sample], tr["check_pad_to"])
        ctx.log(f"checked {len(sample)} requests against the plain "
                f"float32 forward: {exact}/{total} served tokens are its "
                f"argmax, the worst {worst:.4f} below it (tolerance "
                f"{tr['logit_tolerance']})")
        # logits here are fp32 sums of bf16 products of magnitude 2-4,
        # where one bf16 step of the hidden state moves a logit by about
        # 2^-6: two paths that round in a different order differ by a few
        # such steps, so a served token may sit up to 0.08 under the
        # reference's pick in a near-tie, and not further. A float32
        # reference would not be matched closer by any bf16 engine.
        if worst > tr["logit_tolerance"]:
            why_not.append(f"a served token is {worst} below the "
                           "reference's pick")
    if compiles_in_window or recompiles:
        why_not.append(f"{compiles_in_window} compilations inside the "
                       f"window, {recompiles} recompiles since warm-up")
    engine.close()
    compared = {
        "served_logit_gap": {"value": worst,
                             "limit": tr["logit_tolerance"]},
        "compiles_in_window": {"value": compiles_in_window, "limit": 0},
        "recompiles_since_warmup": {"value": recompiles, "limit": 0},
        "tokens_counted_less_held": {"value": tokens_close - in_requests,
                                     "limit": 0}}
    return {"correct": not why_not, "why_not": why_not,
            "compared": compared,
            "attempted": len(in_win), "failed": failed,
            "end_to_end": {"serve_tokens_per_s": rate},
            "device_also": {
                "kv_pool_bytes": int(pool_tokens * kv_token
                                     + engine.num_slots * kv_slot),
                "kv_live_bytes_mean": int(live_tokens * kv_token
                                          + active_slots * kv_slot),
                "kv_reserved_bytes_mean": int(reserved_tokens * kv_token
                                              + active_slots * kv_slot)},
            "facts": facts}


def check_served(reference_logits, params, finished, pad_to):
    """(worst gap, exact, total): each served sequence teacher-forced
    once through the family's plain forward; row t holds the logits for
    token t + 1 given the first t + 1."""
    fn = jax.jit(reference_logits)
    worst, exact, total = 0.0, 0, 0
    for f in finished:
        seq = list(f.prompt) + list(f.tokens)
        width = -(-len(seq) // pad_to) * pad_to
        ids = np.zeros((1, width), np.int32)
        ids[0, :len(seq)] = seq
        ref = np.asarray(fn(params, jnp.asarray(ids)))[0]
        for t in range(len(f.prompt), len(seq)):
            row = ref[t - 1]
            gap = float(row.max() - row[seq[t]])
            worst = max(worst, gap)
            exact += gap == 0.0
            total += 1
    return worst, exact, total
