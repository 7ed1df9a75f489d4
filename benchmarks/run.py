#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process. Fails, printing no result, unless JAX's first device is a
TPU and the cell's chips are there. Finds the cell's configuration
(`configs/<config>.json`), traffic (`traffic/<traffic>.json`, whose
`kind` names the module under `kinds/` that drives it) and, with
`--trace 1`, its per-layer metrics (`metrics/<name>.json`, whose `reader`
names the module under `readers/`) by the names in BENCHMARK.json: a new
cell, traffic kind, metric or reader is a new file, never a branch here.
Prints earlier lines freely and the contract's one JSON line last.

`--rehearse-cpu` runs the cell's `tiny` sizes on whatever JAX finds, to
prove paths and control flow. It prints no result line and exits 3.
"""

import time
T_PROCESS = time.perf_counter()          # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
for path in (ROOT, BENCH_DIR):           # the package is not installed
    if path not in sys.path:
        sys.path.insert(0, path)


from loader import load_module  # noqa: E402


def log(msg):
    print(f"[bench] {msg}", flush=True)


def load_json(*parts):
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def find_cell(bench, workload):
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"benchmarks: no workload {workload!r} in "
                         f"BENCHMARK.json (have {sorted(cells)})")
    cell = cells[workload]
    config = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return cell, config


def metrics_of(bench, group, workload):
    return [m for m in bench[group]
            if "workloads" not in m or workload in m["workloads"]]


class Context:
    """What a traffic kind gets: the cell, its files, the run's
    arguments, the devices, and the tracer's switch."""

    def __init__(self, **kw):
        self.__dict__.update(kw)
        self.trace_dir = os.path.join(
            ROOT, ".bench_trace", self.cell["name"])
        self._tracing = False

    def start_trace(self):
        import jax
        t0 = time.perf_counter()
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0      # the host's own cost down
        options.host_tracer_level = 2        # TraceAnnotations kept
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        self._tracing = True
        self._trace_cost = [time.perf_counter() - t0]
        self._window = self.span("trace_window")
        self._window.__enter__()

    def stop_trace(self):
        import jax
        if self._tracing:
            self._window.__exit__(None, None, None)
            t0 = time.perf_counter()
            jax.profiler.stop_trace()
            self._tracing = False
            self._trace_cost.append(time.perf_counter() - t0)
            self.log("the profiler held the host for {:.2f} s as it started "
                     "and {:.2f} s as it stopped".format(*self._trace_cost))

    def warm_tracer(self):
        """One throwaway trace in set-up, so that the profiler's own
        start-up is not paid inside the window."""
        self.start_trace()
        self.stop_trace()

    @staticmethod
    def span(name):
        """A host span on the profiler's clock, around a call into the
        program: shows in the trace only while one is being taken."""
        import jax
        return jax.profiler.TraceAnnotation("bench/" + name)

    def setup_done(self):
        """Called by the kind as its measured window opens."""
        self.setup_s = time.perf_counter() - T_PROCESS
        return self.setup_s


def read_per_layer(ctx, bench, facts):
    """Every per-layer metric of this cell through its own reader. A
    reader that finds nothing returns None and the metric is left out."""
    from core import trace as tr
    view = None
    if os.path.isdir(ctx.trace_dir):
        view = tr.load_xplane(tr.find_xplane(ctx.trace_dir))
        if os.environ.get("BENCH_TRACE_DESCRIBE"):
            out = os.path.join(ROOT, "chiprun_out")
            os.makedirs(out, exist_ok=True)
            with open(os.path.join(
                    out, f"trace_{ctx.cell['name']}.txt"), "w") as f:
                f.write(tr.describe(view))
            with open(os.path.join(
                    out, f"trace_cut_{ctx.cell['name']}.json"), "w") as f:
                json.dump(tr.cut(view), f)
    out = {}
    for metric in metrics_of(bench, "per_layer", ctx.cell["name"]):
        spec = load_json("metrics", metric["name"] + ".json")
        reader = load_module("readers", spec["reader"])
        value = reader.read(view=view, facts=facts, ctx=ctx,
                            **spec.get("params", {}))
        if value is not None:
            out[metric["name"]] = {"value": float(value),
                                   "unit": metric["unit"]}
        else:
            log(f"per-layer metric {metric['name']}: nothing to read")
    return out, view


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny sizes on any backend; no result line")
    args = ap.parse_args()

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell, config_entry = find_cell(bench, args.workload)
    config = load_json(os.path.relpath(
        os.path.join(ROOT, config_entry["file"]), BENCH_DIR))
    traffic = load_json("traffic", cell["traffic"] + ".json")
    if args.rehearse_cpu:
        config = {**config, **config.get("tiny", {})}
        traffic = {**traffic, **traffic.get("tiny", {})}
    seconds = args.seconds if args.seconds is not None \
        else bench["run_seconds"]

    from core import device as dev
    devices = dev.require_chips(cell["chips"], args.rehearse_cpu)
    d0 = devices[0]
    peaks = dev.peaks_for(d0.device_kind, args.rehearse_cpu)
    cache_dir = dev.enable_compile_cache()
    compiles = dev.CompileCounter()
    log(f"cell {cell['name']}: {cell['chips']} x {d0.device_kind!r}, seed "
        f"{args.seed}, window {seconds} s, trace {args.trace}, compile "
        f"cache {cache_dir}")

    ctx = Context(cell=cell, config=config, traffic=traffic,
                  seed=args.seed, seconds=seconds, trace=bool(args.trace),
                  devices=devices, peaks=peaks, compiles=compiles,
                  log=log, setup_s=None)
    kind = load_module("kinds", traffic["kind"])
    if ctx.trace:
        ctx.warm_tracer()
    try:
        result = kind.run(ctx)
    finally:
        ctx.stop_trace()
    facts = result["facts"]
    log(f"set-up {ctx.setup_s:.1f} s; programs built or loaded in this "
        f"process {compiles.compiles} ({compiles.cache_hits} from the "
        f"cache), {facts.get('compiles_in_window', 0)} inside the window")

    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices),
              "memory_peak_bytes": dev.memory_peak_bytes(devices)}
    # what of that peak the traffic fills, where the kind can say (the
    # driver reads the keys above and ignores these)
    device.update(result.get("device_also", {}))
    line = {"correct": bool(result["correct"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"]), "device": device}
    end_to_end = dict(result["end_to_end"], setup_s=ctx.setup_s)
    if args.trace:
        from core import trace as tr
        line["metrics"], view = read_per_layer(ctx, bench, facts)
        if view is not None:
            busy, window = tr.busy_seconds(view)
            device["busy_s"], device["window_s"] = busy, window
            log(f"device busy {busy:.4f} s of a traced {window:.4f} s: "
                f"idle share {1 - busy / max(window, 1e-12):.4f}")
            breakdown = tr.breakdown(view)
            if breakdown:
                line["breakdown"] = breakdown
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        log("end-to-end in this traced run (not reported; tracing slows "
            "the host): " + json.dumps(end_to_end))
    else:
        units = {m["name"]: m["unit"]
                 for m in metrics_of(bench, "end_to_end", cell["name"])}
        missing = set(units) - set(end_to_end)
        if missing:
            raise SystemExit(f"benchmarks: the kind gave no {missing}")
        line["metrics"] = {name: {"value": float(end_to_end[name]),
                                  "unit": unit}
                           for name, unit in units.items()}
    if not line["correct"]:
        log("NOT CORRECT: " + "; ".join(result.get("why_not", [])))
    if "compared" in result:
        # each number compared beside its limit: the last key of the
        # line, and the last lines on standard error
        line["compared"] = result["compared"]
        for name, c in result["compared"].items():
            print(f"compared {name}: {c['value']} (limit {c['limit']})",
                  file=sys.stderr, flush=True)
    if args.rehearse_cpu:
        log("rehearsal on " + d0.platform + " (not a measurement, no "
            "result line): " + json.dumps(line))
        raise SystemExit(3)
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
