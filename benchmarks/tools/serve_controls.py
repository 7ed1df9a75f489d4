#!/usr/bin/env python3
"""The controls that a served cell's `logit_tolerance` lies between the
readings of: the family's plain reference as the cell's `correct`
compares with it (has to pass) and at the nearest precision BELOW the
one the configuration states (has to fail), both put against what the
ENGINE served at the cell's own sizes.

    python3 benchmarks/tools/serve_controls.py --workload solar-open2-250b.serve-rollout-saturated --seed 7

Weights from the seed as the cell makes them; the first `--requests` of
the cell's own backlog served to their end through `submit` / `step`;
each finished request teacher-forced through `kinds/serve_backlog.
check_served`'s comparison under every reference the family offers
(`families/<family>.py` `reference_logits(model, **lower)`). The
configuration states bfloat16 products over a float32 state, so:

- `reference` (float32): has to pass;
- `products_float8_e5m2` (both operands of every product with a weight
  table rounded to float8, the arithmetic float32): the nearest
  precision below the stated one; has to FAIL;
- `products_bfloat16`, `state_bfloat16`, `recurrence_bfloat16`: shown,
  and required of nothing. The first is the stated precision itself in
  the reference's own arithmetic: how far IT moves the logits is the
  noise any bfloat16 engine carries, and a state held in bfloat16 or a
  recurrence on bfloat16 operands moves them less than that (PERF.md
  section 6, PR 37), so no comparison of served tokens can refuse those
  two and pass the engine.

Prints every reading beside the limit and exits 1 unless every required
control came out as it has to. `--rehearse-cpu` runs the `tiny` sizes.
"""

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for path in (ROOT, BENCH_DIR):
    if path not in sys.path:
        sys.path.insert(0, path)

from loader import load_module  # noqa: E402
import run as bench_run  # noqa: E402

# what: (the family's `lower` arguments, has to be refused / None: shown)
LOWER = {"products_float8_e5m2": ({"products": "float8_e5m2"}, True),
         "products_bfloat16": ({"products": "bfloat16"}, None),
         "state_bfloat16": ({"state_dtype": "bfloat16"}, None),
         "recurrence_bfloat16": ({"round_to": "bfloat16"}, None)}


def controls(workload, seed, requests=8, rehearse_cpu=False,
             log=bench_run.log):
    """{what: (worst gap, has to be refused (None: shown only), was
    refused, rms distance of its logits from the plain reference's)}."""
    import jax
    import jax.numpy as jnp
    from core import device as dev, draws
    from deepspeed_tpu.inference import InferenceEngine
    from deepspeed_tpu.inference.scheduler import Request
    import numpy as np

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell, entry = bench_run.find_cell(bench, workload)
    cfg = bench_run.load_json(os.path.relpath(
        os.path.join(ROOT, entry["file"]), BENCH_DIR))
    tr = bench_run.load_json("traffic", cell["traffic"] + ".json")
    if rehearse_cpu:
        cfg, tr = {**cfg, **cfg.get("tiny", {})}, {**tr, **tr.get("tiny", {})}
    dev.require_chips(cell["chips"], rehearse_cpu)
    dev.enable_compile_cache()
    family = load_module("families", cfg["family"])
    model = family.serve_model_of(cfg)
    key = jax.random.PRNGKey(draws.seed32(seed, 31) % (2 ** 31))
    params = jax.jit(lambda k: family.init_params(model, k))(key)
    engine = InferenceEngine(model, params, cfg["serve"]["inference"])
    engine.warmup()
    plen, olen = draws.backlog_lengths(
        requests, int(tr["epoch_requests"]), tr["order_seed"],
        tr["prompt"], tr["output"])
    prompts = draws.prompt_tokens(plen, model.vocab_size, seed)
    for i in range(requests):
        engine.submit(Request(prompt=prompts[i],
                              max_new_tokens=int(olen[i]),
                              temperature=0.0, seed=i, eos_id=None))
    finished = engine.run()
    log(f"{len(finished)} requests served to their end: prompts "
        f"{[len(f.prompt) for f in finished]}, outputs "
        f"{[len(f.tokens) for f in finished]}")
    limit = tr["logit_tolerance"]
    out, plain = {}, None
    for what, (lower, has_to) in [("reference", ({}, False))] + list(
            LOWER.items()):
        lower = {k: jnp.dtype(v) for k, v in lower.items()}
        rows = served_rows(family.reference_logits(model, **lower),
                           engine.params, finished, tr["check_pad_to"])
        if plain is None:
            plain = rows
        # the cell's own comparison (kinds/serve_backlog.check_served):
        # every served token under the reference's pick
        gaps = np.concatenate([r.max(-1) - r[np.arange(len(t)), t]
                               for r, t in rows])
        worst = float(gaps.max())
        # and how far this reference's logits lie from the plain one's
        moved = np.sqrt(np.mean(np.concatenate(
            [(r - p) ** 2 for (r, _), (p, _) in zip(rows, plain)])))
        refused = worst > limit
        out[what] = (worst, has_to, refused, float(moved))
        must = {True: "has to be NOT correct", False: "has to be correct",
                None: "shown only"}[has_to]
        log(f"control {what}: {int((gaps == 0).sum())}/{len(gaps)} served "
            f"tokens are its argmax, the worst {worst:.4f} below it (limit "
            f"{limit}): {'NOT correct' if refused else 'correct'}, {must}; "
            f"its logits lie "
            f"{moved:.5f} (rms) from the plain reference's, whose rms is "
            f"{float(np.sqrt(np.mean(np.concatenate([p for p, _ in plain]) ** 2))):.4f}")
    engine.close()
    return out


def served_rows(reference_logits, params, finished, pad_to):
    """[(logits rows that produced each served token, the served
    tokens)] a request: each sequence teacher-forced once, as
    `kinds/serve_backlog.check_served` does it."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    fn = jax.jit(reference_logits)
    out = []
    for f in finished:
        seq = list(f.prompt) + list(f.tokens)
        width = -(-len(seq) // pad_to) * pad_to
        ids = np.zeros((1, width), np.int32)
        ids[0, :len(seq)] = seq
        ref = np.asarray(fn(params, jnp.asarray(ids)))[0]
        at = np.arange(len(f.prompt), len(seq))
        out.append((ref[at - 1], np.asarray(seq)[at]))
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    out = controls(args.workload, args.seed, args.requests,
                   args.rehearse_cpu)
    print(json.dumps({k: {"worst_gap": v[0], "has_to_be_refused": v[1],
                          "refused": v[2], "rms_from_reference": v[3]}
                      for k, v in out.items()}),
          flush=True)
    raise SystemExit(0 if all(v[1] in (None, v[2])
                              for v in out.values()) else 1)


if __name__ == "__main__":
    main()
