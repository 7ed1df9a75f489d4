"""Reduction of a `jax.profiler` trace to numbers. The trace is first
cut down to a plain dict (`load_xplane`), which is also the form of the
small recorded trace under `benchmarks/tests/`, so the arithmetic below
is tested without a chip:

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, dur_ns], ...]}]}]}

Device planes are those named `/device:TPU:<n>`; on each, the line
`XLA Ops` holds one event per executed operation (a Pallas kernel is one
such event) and `XLA Modules` one per executed program. Host planes keep
only the benchmark's own `bench/...` annotations.
"""

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench/"
CONTAINERS = ("while", "conditional", "call")


def find_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load_xplane(path):
    """The .xplane.pb as the plain dict above (needs only JAX)."""
    from jax.profiler import ProfileData
    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        if not device and not plane.name.startswith("/host:CPU"):
            continue
        lines = []
        for line in plane.lines:
            events = [[e.name, float(e.start_ns), float(e.duration_ns)]
                      for e in line.events
                      if device or e.name.startswith(HOST_PREFIX)]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


# ------------------------------------------------------------ intervals
def union(intervals):
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def length(merged):
    return sum(e - s for s, e in merged)


def clip(merged, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in merged
            if min(e, hi) > max(s, lo)]


def subtract(a, b):
    """The part of merged `a` that no interval of merged `b` covers."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def spans(events):
    return [[s, s + d] for _, s, d in events]


# ---------------------------------------------------------------- views
def device_planes(view):
    return sorted((p for p in view["planes"]
                   if DEVICE_PLANE.match(p["name"])),
                  key=lambda p: int(p["name"].rsplit(":", 1)[1]))


def line_events(plane, line_name):
    for line in plane["lines"]:
        if line["name"] == line_name:
            return line["events"]
    return []


def host_spans(view):
    """Every `bench/...` annotation of the host planes, by name."""
    out = {}
    for plane in view["planes"]:
        if DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                out.setdefault(name, []).append([start, start + dur])
    return out


def base_name(name):
    """`%fusion.123 = ...` and `fusion.123` both become `fusion`."""
    name = op_name(name)
    return re.sub(r"[.\d]+$", "", name) or name


def window_of(view, marker=HOST_PREFIX + "trace_window"):
    """[start, end) of the traced window in trace time: the benchmark's
    own marker span where the device's events lie inside it (one clock),
    else the extent of the device's events."""
    ops = [sp for p in device_planes(view)
           for sp in spans(line_events(p, OPS_LINE))]
    if not ops:
        return None
    extent = [min(s for s, _ in ops), max(e for _, e in ops)]
    mark = host_spans(view).get(marker)
    if mark:
        lo, hi = mark[0]
        inside = length(clip(union(ops), lo, hi))
        if inside >= 0.9 * length(union(ops)):
            return [lo, hi]
    return extent


def busy_seconds(view, window=None):
    """Seconds in which an operation ran on the device, averaged over
    the device planes, and the window's length."""
    window = window or window_of(view)
    planes = device_planes(view)
    if window is None or not planes:
        return 0.0, 0.0
    busy = [length(clip(union(spans(line_events(p, OPS_LINE))), *window))
            for p in planes]
    return sum(busy) / len(busy) / 1e9, (window[1] - window[0]) / 1e9


def idle_share(view, window=None):
    busy, win = busy_seconds(view, window)
    return 1.0 - busy / win if win else None


def sum_by_name(events, normalise=base_name):
    out = {}
    for name, _, dur in events:
        key = normalise(name)
        out[key] = out.get(key, 0.0) + dur
    return out


def matching(events, pattern):
    rx = re.compile(pattern)
    return [ev for ev in events if rx.search(ev[0])]


def op_name(name):
    """The operation's own name: `%jvp__.3 = bf16[..] custom-call(%x)` is
    `jvp__.3` (the rest of the text names its operands too)."""
    return name.split(" = ")[0].lstrip("%")


def matching_ops(events, pattern):
    """Events whose own name (not their operands') matches."""
    rx = re.compile(pattern)
    return [ev for ev in events if rx.search(op_name(ev[0]))]


def module_events(plane, pattern):
    return sorted(matching(line_events(plane, MODULES_LINE), pattern),
                  key=lambda ev: ev[1])


def gaps_before(plane, pattern):
    """For each program matching `pattern` but the first, the idle time
    on this device between the end of whatever program ran before it and
    its own start (ns): what the host took to dispatch it."""
    mods = sorted(line_events(plane, MODULES_LINE), key=lambda ev: ev[1])
    rx, out, prev_end = re.compile(pattern), [], None
    for name, start, dur in mods:
        if rx.search(name) and prev_end is not None:
            out.append(max(start - prev_end, 0.0))
        prev_end = start + dur if prev_end is None \
            else max(prev_end, start + dur)
    return out


def breakdown(view, top=10):
    """The contract's `breakdown`: device operations that took most time
    (summed over chips, by base name) and the longest idle gaps of the
    first device by what the host was doing (the innermost `bench/` span
    over the gap's middle)."""
    window = window_of(view)
    planes = device_planes(view)
    if window is None or not planes:
        return None
    totals = {}
    for p in planes:
        for k, v in sum_by_name(line_events(p, OPS_LINE)).items():
            if k not in CONTAINERS:     # their bodies are listed too
                totals[k] = totals.get(k, 0.0) + v
    device_ops = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    busy = clip(union(spans(line_events(planes[0], OPS_LINE))), *window)
    idle = subtract([list(window)], busy)
    host = [(name, s, e) for name, sps in host_spans(view).items()
            if name != HOST_PREFIX + "trace_window" for s, e in sps]
    by_host = {}
    for s, e in idle:
        mid = (s + e) / 2
        cover = [(he - hs, name) for name, hs, he in host if hs <= mid < he]
        name = min(cover)[1][len(HOST_PREFIX):] if cover else "outside_spans"
        by_host[name] = by_host.get(name, 0.0) + (e - s)
    gaps = sorted(by_host.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v / 1e9] for k, v in device_ops],
            "idle_gaps": [[k, v / 1e9] for k, v in gaps]}


def describe(view, top=25):
    """A by-hand look at a trace: planes, lines, counts, top names."""
    lines = []
    for p in view["planes"]:
        lines.append(f"PLANE {p['name']}")
        for ln in p["lines"]:
            evs = ln["events"]
            lines.append(f"  LINE {ln['name']!r}: {len(evs)} events, "
                         f"{sum(d for _, _, d in evs) / 1e6:.2f} ms")
            raw = {}
            for name, _, dur in evs:
                c = raw.setdefault(name[:120], [0, 0.0])
                c[0] += 1
                c[1] += dur
            for name, (n, dur) in sorted(raw.items(),
                                         key=lambda kv: -kv[1][1])[:top]:
                lines.append(f"      {dur / 1e6:10.3f} ms {n:6d}x  {name}")
    return "\n".join(lines)


def cut(view, ops=300):
    """A trace small enough to keep with the tests: every program event
    and host span, and the first `ops` operations of each device."""
    planes = []
    for p in view["planes"]:
        lines = []
        for ln in p["lines"]:
            evs = ln["events"]
            if ln["name"] == OPS_LINE:
                evs = sorted(evs, key=lambda ev: ev[1])[:ops]
            elif DEVICE_PLANE.match(p["name"]) and ln["name"] != MODULES_LINE:
                continue
            lines.append({"name": ln["name"], "events": [
                [name[:160], start, dur] for name, start, dur in evs]})
        planes.append({"name": p["name"], "lines": lines})
    return {"planes": planes}
