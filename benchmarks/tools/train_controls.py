#!/usr/bin/env python3
"""The controls that a `train_family_blocks` cell's limits lie between:
the family's plain reference, changed, put in the program's place and
judged by the cell's own comparison at the cell's own sizes (weights and
first batch made from the seed as the cell makes them; no engine).

    python3 benchmarks/tools/train_controls.py --workload smallthinker-21b-a3b.train-8k --seed 7

The reference at the program's precision has to come out correct; at the
nearest precision below it, and with each fault the family plants, NOT
correct, by one of the cell's limits at least. Prints every reading
beside its limit and exits 1 unless every control came out as it has to.
`--rehearse-cpu` runs the cell's `tiny` sizes on whatever JAX finds.
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


def controls(workload, seed, rehearse_cpu=False, log=bench_run.log):
    """{what: (has to be refused, why_not)} for every control."""
    import jax
    from core import device as dev, draws

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
    model = family.model_of(cfg)
    ctx = bench_run.Context(cell=cell, seed=seed, log=log)
    key = jax.random.PRNGKey(draws.seed32(seed, 31) % (2 ** 31))
    params = jax.jit(lambda k: family.init_params(model, k))(key)
    ids = next(draws.TokenStream(
        seed, family.id_vocab(cfg), tr["micro_batch_per_chip"], tr["seq"],
        tr["zipf_exponent"], tr["successor_share"]))["input_ids"]
    ref = family.reference_readings(ctx, params, ids, model, tr)
    grads = family.reference_expert_gradients(params, ids, model, seed)
    out = {}
    for what, kw, refused in family.controls():
        log(f"control, the reference with {what}:")
        why_not = family.judge_control(ctx, params, ids, model, tr, ref,
                                       grads, **kw)
        verdict = "REFUSED" if why_not else "correct"
        fine = bool(why_not) == refused
        log(f"control, the reference with {what}: {verdict} by the cell's "
            f"limits, as it has to be" if fine else
            f"control, the reference with {what}: {verdict}, and it has "
            f"NOT to be")
        for why in why_not[:3]:
            log("   " + why[:300])
        out[what] = (refused, why_not)
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    out = controls(args.workload, args.seed, args.rehearse_cpu)
    sys.exit(0 if all(bool(why) == refused
                      for refused, why in out.values()) else 1)


if __name__ == "__main__":
    main()
