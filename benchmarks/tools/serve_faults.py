#!/usr/bin/env python3
"""`tools/serve_controls.py` with the family's PLANTED FAULTS beside its
precisions: a served cell's `logit_tolerance` has to refuse a reference
that computes another function (a wrong scale, a position left out, a
cache row rounded to float8), not only one at a lower precision.

    python3 benchmarks/tools/serve_faults.py --workload ax-k1.serve-reason-saturated --seed 7 --requests 4

`serve_controls.controls` builds the engine, serves the first
`--requests` of the cell's backlog ONCE and reads its own controls; this
tool keeps what it served (through `serve_controls.served_rows`, the one
seam between its serving and its comparison) and puts the same tokens,
through the same comparison, against every entry of the family's
`PLANTED` (`families/<family>.py`: name -> the `lower` arguments of its
`reference_logits`), each of which has to FAIL, and of its `SHOWN`,
required of nothing. A family with neither has only `serve_controls`'
readings. Prints every reading beside the limit and exits 1 unless every
required control came out as it has to. `--rehearse-cpu` runs the `tiny`
sizes.
"""

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for path in (ROOT, BENCH_DIR, os.path.join(BENCH_DIR, "tools")):
    if path not in sys.path:
        sys.path.insert(0, path)

from loader import load_module  # noqa: E402
import run as bench_run  # noqa: E402
import serve_controls  # noqa: E402

_DTYPES = ("products", "state_dtype", "round_to")


def controls(workload, seed, requests=4, rehearse_cpu=False,
             log=bench_run.log):
    """{what: (worst gap, has to be refused (None: shown only), was
    refused, rms distance of its logits from the plain reference's)}."""
    import jax.numpy as jnp
    import numpy as np

    served, rows_of = {}, serve_controls.served_rows

    def keep(reference_logits, params, finished, pad_to):
        rows = rows_of(reference_logits, params, finished, pad_to)
        served.setdefault("plain", rows)        # its first is the plain one
        served.update(params=params, finished=finished, pad_to=pad_to)
        return rows

    serve_controls.served_rows = keep
    try:
        out = serve_controls.controls(workload, seed, requests,
                                      rehearse_cpu, log)
    finally:
        serve_controls.served_rows = rows_of

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell, entry = bench_run.find_cell(json.load(f), workload)
    cfg = bench_run.load_json(os.path.relpath(
        os.path.join(ROOT, entry["file"]), BENCH_DIR))
    tr = bench_run.load_json("traffic", cell["traffic"] + ".json")
    if rehearse_cpu:
        cfg, tr = {**cfg, **cfg.get("tiny", {})}, {**tr, **tr.get("tiny", {})}
    family = load_module("families", cfg["family"])
    model, limit, plain = (family.serve_model_of(cfg),
                           tr["logit_tolerance"], served["plain"])
    rms = float(np.sqrt(np.mean(np.concatenate([p for p, _ in plain]) ** 2)))
    wanted = [(what, lower, True)
              for what, lower in getattr(family, "PLANTED", {}).items()] + [
        (what, lower, None)
        for what, lower in getattr(family, "SHOWN", {}).items()]
    for what, lower, has_to in wanted:
        lower = {k: jnp.dtype(v) if k in _DTYPES else v
                 for k, v in lower.items()}
        rows = rows_of(family.reference_logits(model, **lower),
                       served["params"], served["finished"],
                       served["pad_to"])
        gaps = np.concatenate([r.max(-1) - r[np.arange(len(t)), t]
                               for r, t in rows])
        worst = float(gaps.max())
        moved = float(np.sqrt(np.mean(np.concatenate(
            [(r - p) ** 2 for (r, _), (p, _) in zip(rows, plain)]))))
        out[what] = (worst, has_to, worst > limit, moved)
        must = "shown only" if has_to is None else "has to be NOT correct"
        log(f"control {what}: {int((gaps == 0).sum())}/{len(gaps)} served "
            f"tokens are its argmax, the worst {worst:.4f} below it (limit "
            f"{limit}): {'NOT correct' if worst > limit else 'correct'}, "
            f"{must}; its logits lie {moved:.5f} (rms) from the plain "
            f"reference's, whose rms is {rms:.4f}")
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--requests", type=int, default=4)
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
