"""The serving engine's dispatch ledger over the WHOLE measured window:
`value` names one number of `summarize` below.

The ledger (`deepspeed_tpu/inference/disagg.py` `DispatchTrace`, found
through `profiling.spans.last_dispatch_ledger()`: the engine this
process built or closed last) holds one row a device dispatch with four stamps
of `time.perf_counter()`. The window on that clock is the harness's own:
`run.py`'s `T_PROCESS` + `ctx.setup_s` is its opening,
`facts["window_s"]` its length. A row belongs to the window in which it
was finished (`t_done`); its interval runs from the row before's
`t_done` (the window's opening for the first) to its own, in three
legs: before (to `t_issued`), wait (to `t_ready`), after.

- `stall_share_pct`: 100 x (the intervals' sum over the sum of each
  interval's CLASS median, less one). A class (one compiled program)
  with fewer than `MIN_CLASS` dispatches in the window is its own
  median. `train_stall_share`'s twin: a stall moves it, a run that is
  uniformly slower does not.
- `tokens_per_s_median_step`: the ledger's tokens in the window over the
  sum of the class medians: a uniform shift moves it, one stall does not.
- `host_serial_ms`: the median before + after leg of a dispatch.
- `decode_wait_ms`: the median wait leg of the decode dispatches.
- `prefill_wait_ms`: the prefill classes' median wait, weighted by each
  class's count in the window.

With a trace it also joins the two clocks (a `serve/*` span carries its
row's `seq` and `step`; the row's `t_ready` is stamped as the span's
`wait` child closes) and logs the traced seconds' device idle time by leg. A program
without the ledger gives None and the metric is left out.
"""

import bisect
import sys

import numpy as np

from core import program_trace as pt
from core import trace as tr

LEGS = ("before", "wait", "after")
MIN_CLASS = 8
DISPATCH_SPANS = ("serve/prefill", "serve/decode", "serve/chunk",
                  "serve/verify")


def ledger_of_process():
    try:
        from deepspeed_tpu.profiling.spans import last_dispatch_ledger
    except ImportError:
        return None
    return last_dispatch_ledger()


def window_rows(table, t_open, t_close):
    """The rows finished inside (t_open, t_close], a numpy array a
    column, with each row's interval and its three legs in seconds."""
    done = np.asarray(table["t_done"], np.float64)
    if not len(done):
        return None
    # the first kept row has no row before it: its own t_begin
    prev = np.concatenate([[table["t_begin"][0]], done[:-1]])
    keep = (done > t_open) & (done <= t_close)
    if not keep.any():
        return None
    rows = {k: np.asarray(table[k])[keep]
            for k in ("seq", "step", "class_id", "t_begin", "t_issued",
                      "t_ready", "t_done", "tokens")}
    rows["kind"] = np.asarray(table["kind"])[keep]
    rows["cls"] = [c for c, k in zip(table["cls"], keep) if k]
    start = np.maximum(prev[keep], t_open)
    rows["start"] = start
    rows["before"] = np.maximum(rows["t_issued"] - start, 0.0)
    rows["wait"] = rows["t_ready"] - rows["t_issued"]
    rows["after"] = rows["t_done"] - rows["t_ready"]
    rows["interval"] = rows["before"] + rows["wait"] + rows["after"]
    return rows


def class_table(rows):
    """{class: {count, interval_ms, <leg>_ms: (median, p99)}} over the
    window's rows, most dispatched first."""
    out = {}
    for k in np.unique(rows["class_id"]):
        mine = rows["class_id"] == k
        cls = rows["cls"][int(np.flatnonzero(mine)[0])]
        entry = {"count": int(mine.sum())}
        for leg in ("interval",) + LEGS:
            v = rows[leg][mine] * 1e3
            entry[leg + "_ms"] = (float(np.median(v)),
                                  float(np.percentile(v, 99)))
        out[cls] = entry
    return dict(sorted(out.items(), key=lambda kv: -kv[1]["count"]))


def expected_intervals(rows):
    """Each row's interval as its class's median would have it."""
    expected = rows["interval"].copy()
    for k in np.unique(rows["class_id"]):
        mine = rows["class_id"] == k
        if mine.sum() >= MIN_CLASS:
            expected[mine] = np.median(rows["interval"][mine])
    return expected


def summarize(rows):
    expected = expected_intervals(rows)
    total, tokens = rows["interval"].sum(), int(rows["tokens"].sum())
    out = {"stall_share_pct": 100.0 * (total / expected.sum() - 1.0),
           "tokens_per_s_median_step": tokens / expected.sum(),
           "host_serial_ms":
               float(np.median(rows["before"] + rows["after"])) * 1e3,
           "decode_wait_ms": None, "prefill_wait_ms": None,
           "intervals_s": float(total), "tokens": tokens,
           "dispatches": len(expected)}
    decode = rows["kind"] == "decode"
    if decode.any():
        out["decode_wait_ms"] = float(np.median(rows["wait"][decode])) * 1e3
    prefill = rows["kind"] == "prefill"
    if prefill.any():
        ids = rows["class_id"][prefill]
        waits = rows["wait"][prefill]
        out["prefill_wait_ms"] = sum(
            (ids == k).sum() * np.median(waits[ids == k])
            for k in np.unique(ids)) / len(ids) * 1e3
    return out


def longest(rows, top=10):
    """The `top` intervals furthest over their class's median: (seq,
    step, class, interval ms, excess ms, the leg that holds most of the
    excess, that leg's excess ms)."""
    expected = expected_intervals(rows)
    leg_median = {leg: rows[leg].copy() for leg in LEGS}
    for k in np.unique(rows["class_id"]):
        mine = rows["class_id"] == k
        for leg in LEGS:
            leg_median[leg][mine] = np.median(rows[leg][mine])
    excess = rows["interval"] - expected
    out = []
    for i in np.argsort(-excess)[:top]:
        by_leg = {leg: rows[leg][i] - leg_median[leg][i] for leg in LEGS}
        leg = max(by_leg, key=by_leg.get)
        out.append((int(rows["seq"][i]), int(rows["step"][i]),
                    rows["cls"][i], rows["interval"][i] * 1e3,
                    excess[i] * 1e3, leg, by_leg[leg] * 1e3))
    return out


# ------------------------------------------------------- the two clocks
def clock_offsets(program, table):
    """ns to add to a `perf_counter()` stamp x 1e9 to reach the trace's
    clock, one a traced dispatch: the end of the `wait` child of the
    span that carries a row's `seq`, less the row's `t_ready`."""
    row_of = {(seq, step): t for seq, step, t in zip(
        table["seq"], table["step"], table["t_ready"])}
    out = []
    for name in DISPATCH_SPANS:
        waits = pt.spans_named(program, name + "/wait")
        starts = [ev[1] for ev in waits]
        for _, start, dur, args in pt.spans_named(program, name):
            # seq AND step: a second engine's spans name other rows
            ready = row_of.get((args.get("seq"), args.get("step")))
            j = bisect.bisect_left(starts, start)
            if ready is None or j >= len(waits) \
                    or waits[j][1] > start + dur:
                continue
            out.append(waits[j][1] + waits[j][2] - ready * 1e9)
    return np.asarray(out)


def idle_by_leg(view, program, table, offset_ns):
    """{leg: ns} of the first device's idle time inside the traced
    window, split at the legs' edges; `outside` is what lies before the
    first or after the last row of the ledger."""
    window = tr.window_of(view)
    if window is None or not program["devices"]:
        return None
    ops = program["devices"][0]["ops"]
    busy = tr.clip(tr.union([[ev[1], ev[1] + ev[2]] for ev in ops]),
                   *window)
    idle = tr.subtract([list(window)], busy)
    at = {k: np.asarray(table[k], np.float64) * 1e9 + offset_ns
          for k in ("t_issued", "t_ready", "t_done")}
    start = np.concatenate([[table["t_begin"][0] * 1e9 + offset_ns],
                            at["t_done"][:-1]])
    edges = {"before": (start, at["t_issued"]),
             "wait": (at["t_issued"], at["t_ready"]),
             "after": (at["t_ready"], at["t_done"])}
    out, left = {}, tr.length(idle)
    for leg, (lo, hi) in edges.items():
        segments = tr.clip([[a, b] for a, b in zip(lo, hi) if b > a],
                           *window)
        out[leg] = tr.length(idle) - tr.length(tr.subtract(idle, segments))
        left -= out[leg]
    out["outside"] = left
    return out


# ----------------------------------------------------------------- read
def _log(ctx, facts, ledger, rows, summary):
    ctx.log(f"dispatch ledger: {summary['dispatches']} dispatches in the "
            f"window, intervals {summary['intervals_s']:.4f} s beside a "
            f"window of {facts['window_s']:.4f} s, {summary['tokens']} "
            f"tokens beside {facts['tokens_in_window']}; {ledger.total} "
            f"rows since the engine was built, dropped {ledger.dropped}")
    for cls, e in class_table(rows).items():
        ctx.log("  class " + " ".join(str(c) for c in cls)
                + f": {e['count']} dispatches; median / p99 ms: "
                + ", ".join(f"{leg} {e[leg + '_ms'][0]:.3f} / "
                            f"{e[leg + '_ms'][1]:.3f}"
                            for leg in ("interval",) + LEGS))
    for seq, step, cls, ms, excess, leg, leg_ms in longest(rows):
        ctx.log(f"  long interval: seq {seq} step {step} class "
                + " ".join(str(c) for c in cls)
                + f" {ms:.3f} ms, {excess:.3f} over its class's median, "
                f"{leg_ms:.3f} of it in {leg}")
    ctx.log("dispatch ledger: " + ", ".join(
        f"{k} {v:.4f}" for k, v in summary.items()
        if isinstance(v, float)))


def _log_join(ctx, view, table):
    program = pt.load(ctx.trace_dir)
    if view is None or program is None:
        return
    offsets = clock_offsets(program, table)
    if not len(offsets):
        ctx.log("dispatch ledger: no traced span carries a seq")
        return
    offset = float(np.median(offsets))
    q1, q3 = np.percentile(offsets, [25, 75])
    ctx.log(f"dispatch ledger: {len(offsets)} traced dispatches joined by "
            f"seq; trace clock less perf_counter {offset / 1e9:.6f} s, "
            f"spread between quartiles {(q3 - q1) / 1e6:.4f} ms, widest "
            f"{np.abs(offsets - offset).max() / 1e6:.4f} ms")
    idle = idle_by_leg(view, program, table, offset)
    if idle:
        ctx.log("dispatch ledger: device idle in the traced seconds by "
                "leg, ms: " + ", ".join(f"{k} {v / 1e6:.3f}"
                                        for k, v in idle.items()))


def summary_of(facts, ctx, view):
    """The window's summary, made (and logged) once a run."""
    if "_dispatch_ledger" in facts:
        return facts["_dispatch_ledger"]
    summary = None
    ledger = ledger_of_process()
    t_process = getattr(sys.modules.get("__main__"), "T_PROCESS", None)
    if ledger is not None and t_process is not None \
            and ctx.setup_s is not None:
        table = ledger.table()
        t_open = t_process + ctx.setup_s
        rows = window_rows(table, t_open, t_open + facts["window_s"])
        if rows is not None:
            summary = summarize(rows)
            _log(ctx, facts, ledger, rows, summary)
            _log_join(ctx, view, table)
    facts["_dispatch_ledger"] = summary
    return summary


def read(view, facts, ctx, value):
    summary = summary_of(facts, ctx, view)
    return None if summary is None else summary[value]
