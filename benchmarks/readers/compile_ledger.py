"""The program's compile ledger over SET-UP: `value` names one number of
`summarize` below.

The ledger (`deepspeed_tpu/profiling/recompile.py` `CompileLedger`, found
through `profiling.spans.compile_ledger()`: one for the process) holds a
row a program built or loaded (JAX's own trace, lowering and backend
seconds, whether the persistent cache answered, the `setup/*` span it
was built in) and a row a `setup/*` span, all on `time.perf_counter()`.
Set-up on that clock is the harness's own: `run.py`'s `T_PROCESS` to
`T_PROCESS` + `ctx.setup_s`, the window's opening. Rows and spans count
where they END before the opening.

- `programs_built`: the rows.
- `trace_lower_s`: their `trace_s` + `lower_s`, paid cache or no cache.
- `backend_s`: their `backend_s`: the compile, or the load from the cache.
- `cache_hit_share_pct`: 100 x `hit` rows over rows (a `not_asked` row
  counts against it): whether the run was warm.
- `engine_s`: the `setup/engine` spans less the programs built inside
  them: weights, pools, optimizer state.
- `outside_startup_s`: the seconds of set-up under no `setup/*` span and
  no program row: the harness's own (draws, queueing, warm-up blocks, the
  ramp's steps), which no change to the program's start can shorten.

It also logs one line a costly program, every `steady` row with what
changed, and ONE PARTITION of set-up (`partition`) in which every second
is counted once. A program without the ledger gives None and the metric
is left out.
"""

import sys

from core import trace as tr
from loader import load_module

PARTS = ("before_import", "import", "engine", "programs_trace_lower",
         "programs_backend", "programs_rest", "warmup_rest",
         "programs_outside_spans", "ramp", "remainder")
STEADY = "steady"
GROUPS = 16         # lines of the log for the programs past the costliest


def ledger_of_process():
    try:
        from deepspeed_tpu.profiling.spans import compile_ledger
    except ImportError:
        return None
    return compile_ledger()


def _inside(rows):
    """The rows no earlier row's interval holds (a program built inside
    another's trace is that one's time)."""
    out, hi = [], float("-inf")
    for row in sorted(rows, key=lambda r: (r["t_begin"], -r["t_end"])):
        if row["t_end"] > hi:
            out.append(row)
            hi = row["t_end"]
    return out


def partition(table, t_process, t_open, dispatch_intervals=()):
    """{part: seconds} of [t_process, t_open], every second in exactly
    one part (they add up to `t_open - t_process`), the first that
    holds it of: the programs built inside a `setup/*` span (their
    trace + lowering, their backend, the rest of the calls that built
    them), the programs built outside every span (the harness's own
    jits), `setup/import`, `setup/engine`, `setup/warmup` and any
    `setup/program` outside it (the first `train_batch`), the dispatch
    ledger's intervals (the ramp), what lies before the import, and the
    remainder. `dispatch_intervals`: [start, end] a dispatch."""
    whole = [[t_process, t_open]]
    rows = [r for r in table["programs"] if r["t_end"] <= t_open]
    spans = [s for s in table["spans"] if s["t1"] <= t_open]

    def region(intervals):
        return tr.clip(tr.union(intervals), t_process, t_open)

    def named(*names):
        return region([[s["t0"], s["t1"]] for s in spans
                       if s["name"] in names])

    inside = _inside([r for r in rows if r["phase"] != STEADY])
    p_in = region([[r["t_begin"], r["t_end"]] for r in inside])
    p_out = tr.subtract(region([[r["t_begin"], r["t_end"]] for r in rows
                                if r["phase"] == STEADY]), p_in)
    out = {"programs_outside_spans": tr.length(p_out)}
    trace_lower = sum(r["trace_s"] + r["lower_s"] for r in inside)
    backend = sum(r["backend_s"] for r in inside)
    out["programs_trace_lower"] = trace_lower
    out["programs_backend"] = backend
    out["programs_rest"] = tr.length(p_in) - trace_lower - backend
    taken = tr.union(p_in + p_out)
    imports = named("setup/import")
    for part, mine in (
            ("import", imports),
            ("engine", named("setup/engine")),
            ("warmup_rest", named("setup/warmup", "setup/program")),
            ("ramp", region(dispatch_intervals)),
            ("before_import", [[t_process, imports[0][0]]]
             if imports else []),
            ("remainder", whole)):
        mine = tr.subtract(mine, taken)
        out[part] = tr.length(mine)
        taken = tr.union(taken + mine)
    return out


def summarize(table, t_process, t_open, dispatch_intervals=()):
    rows = [r for r in table["programs"] if r["t_end"] <= t_open]
    if not rows and not table["spans"]:
        return None
    parts = partition(table, t_process, t_open, dispatch_intervals)
    hits = sum(r["cache"] == "hit" for r in rows)
    return {
        "programs_built": len(rows),
        "trace_lower_s": sum(r["trace_s"] + r["lower_s"] for r in rows),
        "backend_s": sum(r["backend_s"] for r in rows),
        "cache_hit_share_pct": 100.0 * hits / len(rows) if rows else None,
        "engine_s": parts["engine"],
        "outside_startup_s": (parts["before_import"] + parts["ramp"]
                              + parts["remainder"]),
        "parts": parts, "setup_s": t_open - t_process}


# ------------------------------------------------------------------ log
def _cost(row):
    return row["t_end"] - row["t_begin"]


def _name(row):
    name = row["name"] or row["fun_name"]
    if row["cls"]:
        name += " [" + " ".join(str(c) for c in row["cls"]) + "]"
    return name


def _phase(row):
    """Where a program of set-up was built, for the log: the ledger's
    `steady` is every row outside the `setup/*` spans, and before the
    window only a tracked one (an engine's own program, built again) is
    a steady-state build; the others are the harness's own jits."""
    if row["phase"] == STEADY and row["name"] is None:
        return "outside spans"
    return row["phase"]


def _cache(row):
    if row["cache"] == "hit":
        return (f"hit (loaded in {row['retrieval_s'] or 0.0:.3f} s, saved "
                f"{row['saved_s'] or 0.0:.3f})")
    if row["cache"] == "miss" and not row.get("written"):
        return "miss, not kept"     # it will miss in the next process too
    return row["cache"]


def _log_programs(ctx, rows, dispatched, top=10):
    """The `top` costliest of set-up one a line, the others a line a
    name."""
    rows = sorted(rows, key=_cost, reverse=True)
    for r in rows[:top]:
        rest = _cost(r) - r["trace_s"] - r["lower_s"] - r["backend_s"]
        in_window = dispatched.get(tuple(r["cls"] or ()))
        ctx.log(f"  program {_name(r)} in {_phase(r)}: trace "
                f"{r['trace_s']:.3f} lower {r['lower_s']:.3f} backend "
                f"{r['backend_s']:.3f} rest {rest:.3f} s, cache "
                f"{_cache(r)}"
                + (f", {in_window} dispatches of its class in the window"
                   if in_window is not None else ""))
    others = {}
    for r in rows[top:]:
        key = (r["name"] or r["fun_name"], _phase(r))
        n, seconds, hits, lost = others.get(key, (0, 0.0, 0, 0))
        others[key] = (n + 1, seconds + _cost(r),
                       hits + (r["cache"] == "hit"),
                       lost + (_cache(r) == "miss, not kept"))
    groups = sorted(others.items(), key=lambda kv: -kv[1][1])
    for (name, phase), (n, seconds, hits, lost) in groups[:GROUPS]:
        ctx.log(f"  and {n} x {name} in {phase}: {seconds:.3f} s in all, "
                f"{hits} from the cache"
                + (f", {lost} missed and not kept" if lost else ""))
    if groups[GROUPS:]:
        ctx.log(f"  and {sum(g[1][0] for g in groups[GROUPS:])} more of "
                f"{len(groups[GROUPS:])} names: "
                f"{sum(g[1][1] for g in groups[GROUPS:]):.3f} s in all")


def _log(ctx, table, summary, t_open, dispatched, ledger):
    rows = [r for r in table["programs"] if r["t_end"] <= t_open]
    later = len(table["programs"]) - len(rows)
    counted = getattr(getattr(ctx, "compiles", None), "compiles", None)
    ctx.log(f"compile ledger: {len(rows)} programs built or loaded before "
            f"the window opened, {later} after; {ledger.total} rows since "
            f"the package was imported, dropped {ledger.dropped}"
            + ("" if counted is None else
               f"; the harness counted {counted}"
               + ("" if counted == ledger.total else
                  f": {counted - ledger.total} built before the package "
                  f"was imported, where no listener of the ledger's was "
                  f"on yet")))
    _log_programs(ctx, rows, dispatched)
    for r in table["programs"]:
        if r["phase"] == STEADY and r["name"] is not None:
            ctx.log(f"  steady build: {_name(r)} at step {r['step']}, "
                    f"{_cost(r):.3f} s; changed: " + ("; ".join(
                        f"{path}: {was} -> {now}"
                        for path, was, now in r["changed"] or [])
                        or "nothing the ledger can see"))
    for s in table["spans"]:
        if s["t1"] <= t_open and s["name"] != "setup/program":
            ctx.log(f"  span {s['name']}: {s['t1'] - s['t0']:.3f} s")
    parts = summary["parts"]
    ctx.log("compile ledger: set-up by part, s: " + ", ".join(
        f"{k} {parts[k]:.3f}" for k in PARTS)
        + f"; sum {sum(parts[k] for k in PARTS):.3f} beside setup_s "
        f"{summary['setup_s']:.3f}")
    ctx.log("compile ledger: " + ", ".join(
        f"{k} {v:.4f}" for k, v in summary.items()
        if isinstance(v, float) and k != "setup_s"))


def _dispatches(facts, t_open):
    """([start, end] a dispatch before the opening, {class: dispatches
    inside the window}) from the serving engine's dispatch ledger."""
    dl = load_module("readers", "dispatch_ledger")
    ledger = dl.ledger_of_process()
    if ledger is None or not ledger.total:
        return [], {}
    table = ledger.table()
    done = table["t_done"]
    starts = [table["t_begin"][0]] + done[:-1]
    before = [[s, e] for s, e in zip(starts, done) if e <= t_open]
    counts = {}
    for cls, e in zip(table["cls"], done):
        if t_open < e <= t_open + facts.get("window_s", 0.0):
            counts[cls] = counts.get(cls, 0) + 1
    return before, counts


def summary_of(facts, ctx):
    """Set-up's summary, made (and logged) once a run."""
    if "_compile_ledger" in facts:
        return facts["_compile_ledger"]
    summary = None
    ledger = ledger_of_process()
    t_process = getattr(sys.modules.get("__main__"), "T_PROCESS", None)
    if ledger is not None and t_process is not None \
            and ctx.setup_s is not None:
        table = ledger.table()
        t_open = t_process + ctx.setup_s
        before, dispatched = _dispatches(facts, t_open)
        summary = summarize(table, t_process, t_open, before)
        if summary is not None:
            _log(ctx, table, summary, t_open, dispatched, ledger)
    facts["_compile_ledger"] = summary
    return summary


def read(view, facts, ctx, value):
    summary = summary_of(facts, ctx)
    return None if summary is None else summary[value]
