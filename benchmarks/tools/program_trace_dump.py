"""A by-hand look at the program's view of a traced run, and the cut of
it that is kept with the tests. `core/program_trace.load` calls `dump`
when `BENCH_TRACE_DESCRIBE` is set, as `run.py` does for its own view:
the trace itself is removed once the readers have run.

    BENCH_TRACE_DESCRIBE=1 python3 benchmarks/run.py --workload <cell> \\
        --seed <n> --trace 1

writes `chiprun_out/program_trace_<cell>.txt` (per program, device time
by scope and direction; the operations by base name and scope; the
unscoped and the inherited ones by path; the host spans) and
`chiprun_out/program_cut_<cell>.json`. `record` turns the cuts of the
cells and the result lines of the same runs into
`tests/recorded_program_trace.json`:

    python3 benchmarks/tools/program_trace_dump.py "<what run>" \\
        <cell> <program_cut json> <result line json> [<cell> ...]
"""

import bisect
import json
import os
import re
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

from core import program_trace as pt  # noqa: E402
from core import trace as tr  # noqa: E402

OUT = os.path.join(os.path.dirname(BENCH_DIR), "chiprun_out")


def dump(view, scopes, cell):
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, f"program_trace_{cell}.txt"), "w") as f:
        f.write(describe(view, scopes))
    with open(os.path.join(OUT, f"program_cut_{cell}.json"), "w") as f:
        json.dump(cut(view), f)


def describe(view, scopes, top=40):
    """A by-hand look: per program, device time by scope, direction and
    the operations' base names."""
    lines = []
    for plane in view["devices"]:
        programs = sorted({re.sub(r"\(\d+\)$", "", m[0])
                           for m in plane["modules"]})
        for prog in programs:
            got = pt.program_scopes({"devices": [plane]},
                                 "^" + re.escape(prog) + r"(\(|$)", scopes)
            if got is None:
                continue
            totals, module_ns, runs = got
            lines.append(f"PROGRAM {prog} on {plane['name']}: {runs} runs, "
                         f"{module_ns / 1e6:.3f} ms each")
            by = {}
            for (scope, bwd, _, _), ns in totals.items():
                k = (scope, "bwd" if bwd else "fwd")
                by[k] = by.get(k, 0.0) + ns
            for (scope, way), ns in sorted(by.items(),
                                           key=lambda kv: -kv[1])[:top]:
                lines.append(f"  {ns / 1e6:10.3f} ms  {scope:12s} {way}")
        own = {}
        ops = sorted(plane["ops"], key=lambda ev: (ev[1], -ev[2]))
        for (name, _, _, path, _), ns in zip(ops, pt.self_ns(ops)):
            k = (tr.base_name(name),
                 pt.scope_of(path, scopes) or pt.UNSCOPED)
            own[k] = own.get(k, 0.0) + ns
        lines.append(f"OPERATIONS of {plane['name']} by base name and scope")
        for (base, scope), ns in sorted(own.items(),
                                        key=lambda kv: -kv[1])[:top]:
            lines.append(f"  {ns / 1e6:10.3f} ms  {base:40s} {scope}")
        lines.append("UNSCOPED operations by base name and path, and those "
                     "charged by inheritance (~)")
        odd = {}
        for (name, _, _, path, _), ns in zip(ops, pt.self_ns(ops)):
            if path.startswith(pt.INHERITED) or not pt.scope_of(path, scopes):
                k = (tr.base_name(name), path)
                odd[k] = odd.get(k, 0.0) + ns
        for (base, path), ns in sorted(odd.items(),
                                       key=lambda kv: -kv[1])[:top]:
            lines.append(f"  {ns / 1e6:10.3f} ms  {base:36s} {path}")
    names = {}
    for name, _, dur, args in view["host"]:
        c = names.setdefault(name, [0, 0.0, args])
        c[0] += 1
        c[1] += dur
    lines.append("HOST SPANS")
    for name, (n, dur, args) in sorted(names.items()):
        lines.append(f"  {dur / 1e6:10.3f} ms {n:6d}x  {name}  "
                     f"last arguments {args}")
    return "\n".join(lines) + "\n"


def cut(view, steps=2):
    """A trace small enough to keep with the tests: on each device the
    first `steps` executions of every program with the operations inside
    them, and the host spans up to the last of those."""
    devices, until = [], 0.0
    for plane in view["devices"]:
        seen, keep = {}, []
        for m in sorted(plane["modules"], key=lambda ev: ev[1]):
            base = re.sub(r"\(\d+\)$", "", m[0])
            seen[base] = seen.get(base, 0) + 1
            if seen[base] <= steps:
                keep.append(m)
        inside = tr.union([[m[1], m[1] + m[2]] for m in keep])
        starts = [s for s, _ in inside]
        ops = []
        for ev in plane["ops"]:
            j = bisect.bisect_right(starts, ev[1]) - 1
            if j >= 0 and ev[1] < inside[j][1]:
                ops.append(ev)
        until = max([until] + [m[1] + m[2] for m in keep])
        devices.append({"name": plane["name"], "modules": keep, "ops": ops})
    return {"devices": devices,
            "host": [ev for ev in view["host"] if ev[1] <= until]}


# the programs of a cell that the recording keeps one execution of
RECORDED = {"gpt2-345m.train-1k": r"micro_step",
            "gpt2-345m.serve-saturated": r"decode|prefill"}


def record(what, runs, out_path):
    """The recording the tests read: per cell the first execution of
    each kind of program in `RECORDED` (every prefill bucket is one
    kind) with the operations inside it and the program spans up to its
    end, scope paths interned, beside what the run itself reported."""
    out = {"about": f"cut from {what} by benchmarks/tools/"
           "program_trace_dump.py: the first execution of the train step; "
           "the first decode program and the first prefill program of the "
           "serving cell; every program span up to their end. Scope paths "
           "are interned: an operation's fourth field is an index into "
           "`paths`. `reported` holds what that run's own result line said "
           "for the metrics the test recomputes.", "reported": {}}
    for cell, cut_path, line_path in runs:
        with open(cut_path) as f:
            view = json.load(f)
        with open(line_path) as f:
            line = json.load(f)
        rx = re.compile(RECORDED[cell])
        until, devices = 0.0, []
        for plane in view["devices"]:
            seen, mods = set(), []
            for m in sorted(plane["modules"], key=lambda ev: ev[1]):
                kind = "prefill" if "prefill" in m[0] \
                    else re.sub(r"\(\d+\)$", "", m[0])
                if rx.search(m[0]) and kind not in seen:
                    seen.add(kind)
                    mods.append(m)
            inside = [(m[1], m[1] + m[2]) for m in mods]
            ops = [ev for ev in plane["ops"]
                   if any(a <= ev[1] < b for a, b in inside)]
            until = max([until] + [b for _, b in inside])
            devices.append({"name": plane["name"], "modules": mods,
                            "ops": ops})
        paths = sorted({ev[3] for d in devices for ev in d["ops"]})
        index = {p: i for i, p in enumerate(paths)}
        for d in devices:
            d["ops"] = [[ev[0], round(ev[1], 3), round(ev[2], 3),
                         index[ev[3]], ev[4]] for ev in d["ops"]]
        out[cell] = {"paths": paths, "devices": devices,
                     "host": [ev for ev in view["host"] if ev[1] <= until]}
        out["reported"][cell] = {
            k: v["value"] for k, v in line["metrics"].items()
            if os.path.isfile(os.path.join(BENCH_DIR, "metrics",
                                           k + ".json"))}
    with open(out_path, "w") as f:
        json.dump(out, f, separators=(",", ":"))


if __name__ == "__main__":
    record(sys.argv[1], list(zip(*[iter(sys.argv[2:])] * 3)),
           os.path.join(BENCH_DIR, "tests", "recorded_program_trace.json"))
