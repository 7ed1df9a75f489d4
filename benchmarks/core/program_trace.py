"""The program's own names in the benchmark's one `jax.profiler` trace.

`core/trace.py` reduces the trace to what the compiler and the harness
call things. This module keeps what the PROGRAM calls them, by the
registry in `deepspeed_tpu/profiling/spans.py`:

- for every device operation its scope path: the `op_name` the
  operation got from the `jax.named_scope`s it was traced under
  (`jit(_micro_step)/transpose(jvp(mlp))/dot_general`). On the chip the
  profiler files it as the stat `tf_op` of the operation's event
  METADATA in the device plane. `jax.profiler.ProfileData` gives an
  event's own stats and not its metadata's, so the device planes are read
  from the `.xplane.pb` by the small protobuf wire reader below (field
  numbers of tsl's `xplane.proto`). A fusion carries ONE `op_name`, its
  root's: a scope's time is the time of the operations whose root was
  traced under it, which includes what the compiler fused into them from
  next door. An operation the compiler made itself has NO `op_name` (a
  layout `copy`, a `convert` it moved, the in-place write-back of a
  stacked result, an async `copy-done`): it is charged to the operation
  whose result it reads, else to one that reads its result, found by the
  operand names in the instruction's text within the same program, and
  its path is marked with a leading `~`.
- for the host planes every event whose name is in `HOST_SPANS`, with
  its arguments (through `ProfileData`, whose clock the raw reader
  matches: a line's `timestamp_ns` plus the event's `offset_ps`).

The plain form, also that of the recorded trace under `tests/`:

    {"devices": [{"name": "/device:TPU:0",
                  "modules": [[name, start_ns, dur_ns], ...],
                  "ops": [[own name, start_ns, dur_ns, scope path,
                           opcode], ...]}],
     "host": [[name, start_ns, dur_ns, {argument: value}], ...]}

Where the program under test has no registry (a commit from before the
names went in), `registry()` is None and every reader built on this
module returns None: the metric is left out of the line.
"""

import bisect
import functools
import os
import re

from core import trace as tr

UNSCOPED = "unscoped"


def registry():
    """(device scopes, host spans) of the program under test, or None."""
    try:
        from deepspeed_tpu.profiling.spans import DEVICE_SCOPES, HOST_SPANS
    except ImportError:
        return None
    return tuple(DEVICE_SCOPES), tuple(HOST_SPANS)


# ------------------------------------------------- protobuf wire reader
def _varint(buf, i):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _signed(v):
    return v - (1 << 64) if v >= 1 << 63 else v


def _fields(buf, i, end):
    """(field number, value, end) of one message in buf[i:end]: for a
    length-delimited field `value` is where its bytes start and `end`
    where they stop; for a varint `end` is None; fixed-width fields
    (doubles) are skipped."""
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield key >> 3, value, None
        elif wire == 2:
            n, i = _varint(buf, i)
            yield key >> 3, i, i + n
            i += n
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")


def _text(buf, a, b):
    return bytes(buf[a:b]).decode("utf-8", "replace")


def _map_entries(buf, spans):
    """{key: (start, end) of the value message} of a map<int64, Message>."""
    out = {}
    for a, b in spans:
        key, value = 0, None
        for f, v, e in _fields(buf, a, b):
            if f == 1 and e is None:
                key = _signed(v)
            elif f == 2 and e is not None:
                value = (v, e)
        if value is not None:
            out[key] = value
    return out


_OPCODE = re.compile(r" ([a-z][a-z0-9-]*)\(")


def opcode_of(text):
    """`custom-call` of `%x.3 = (bf16[8]{0:T(8)}, f32[8]) custom-call(...)`:
    the first lower-case word before a parenthesis after the shape."""
    m = _OPCODE.search(text.split(" = ", 1)[-1])
    return m.group(1) if m else ""


_OPERAND = re.compile(r"%([\w.-]+)")
INHERITED = "~"


def charge_nameless(metadata):
    """{key: (text, path, program)} with every operation that has no
    `op_name` given the path, marked `~`, of the operation of the same
    program whose result it reads (its first operand that has one),
    else of the first that reads its result; chains (a `copy-done` of a
    `copy-start` of ...) are followed until nothing changes. Within a
    program an instruction is its name: the lines of a plane may each
    hold an entry of their own for it."""
    programs = {}
    for key, (text, _, prog) in metadata.items():
        programs.setdefault(prog, {}).setdefault(
            tr.op_name(text), []).append(key)
    out = dict(metadata)
    for names in programs.values():
        text = {n: metadata[keys[0]][0] for n, keys in names.items()}
        path = {}
        for n, keys in names.items():
            named = [metadata[k][1] for k in keys if metadata[k][1]]
            if named:
                path[n] = named[0]
        reads = {n: [o for o in _OPERAND.findall(t.split(" = ", 1)[-1])
                     if o in names and o != n] for n, t in text.items()}
        read_by = {}
        for n, producers in reads.items():
            for producer in producers:
                read_by.setdefault(producer, []).append(n)
        nameless = [n for n in names if n not in path]
        for neighbours in (reads, read_by, reads):
            changed = True
            while changed:
                changed = False
                for n in nameless:
                    if n in path:
                        continue
                    for other in neighbours.get(n, ()):
                        if other in path:
                            path[n] = INHERITED + path[other].lstrip(
                                INHERITED)
                            changed = True
                            break
        for n in nameless:
            for k in names[n] if n in path else ():
                out[k] = (metadata[k][0], path[n], metadata[k][2])
    return out


def _event_metadata(buf, events_md, stats_md):
    """{metadata id: (instruction text, op_name path, program id)} of a
    device plane, from its XEventMetadata and XStatMetadata maps."""
    stat_names = {}
    for key, (v, e) in _map_entries(buf, stats_md).items():
        for f, x, y in _fields(buf, v, e):          # XStatMetadata.name
            if f == 2 and y is not None:
                stat_names[key] = _text(buf, x, y)
    wanted = {k: n for k, n in stat_names.items()
              if n in ("tf_op", "program_id")}
    metadata = {}
    for key, (v, e) in _map_entries(buf, events_md).items():
        text, path, prog = "", "", 0
        for f, x, y in _fields(buf, v, e):          # XEventMetadata
            if y is None:
                continue
            if f == 2:
                text = _text(buf, x, y)
            elif f == 5:                            # .stats: one XStat
                stat_id, number, string, ref = None, 0, None, None
                for g, p, q in _fields(buf, x, y):
                    if g == 1 and q is None:
                        stat_id = _signed(p)
                    elif g in (3, 4) and q is None:  # uint64 / int64
                        number = p
                    elif g == 5 and q is not None:   # str_value
                        string = _text(buf, p, q)
                    elif g == 7 and q is None:       # ref_value
                        ref = p
                if wanted.get(stat_id) == "tf_op":
                    path = string if string is not None \
                        else stat_names.get(ref, "")
                elif wanted.get(stat_id) == "program_id":
                    prog = number
        metadata[key] = (text, path.rstrip(":"), prog)
    return metadata


def _line_events(buf, a, b):
    """(line name, [(metadata id, start_ns, dur_ns)]) of one XLine."""
    name, t0, spans = "", 0, []
    for f, v, e in _fields(buf, a, b):
        if f == 2 and e is not None:
            name = _text(buf, v, e)
        elif f == 3 and e is None:
            t0 = v                                  # timestamp_ns
        elif f == 4 and e is not None:
            spans.append((v, e))
    events = []
    for v, e in spans:
        md, offset, dur = 0, 0, 0
        for f, p, q in _fields(buf, v, e):          # XEvent
            if q is not None:
                continue
            if f == 1:
                md = _signed(p)
            elif f == 2:
                offset = p                          # offset_ps
            elif f == 3:
                dur = p                             # duration_ps
        events.append((md, t0 + offset / 1e3, dur / 1e3))
    return name, events


def _raw_device_planes(path):
    """Every `/device:TPU:<n>` plane of the file: its `XLA Modules` and
    `XLA Ops` events with the metadata's name and `tf_op`."""
    with open(path, "rb") as f:
        buf = f.read()
    planes = []
    for f1, a, b in _fields(buf, 0, len(buf)):
        if f1 != 1 or b is None:                    # XSpace.planes
            continue
        name, lines, events_md, stats_md = "", [], [], []
        for f2, v, e in _fields(buf, a, b):         # XPlane
            if e is None:
                continue
            if f2 == 2:
                name = _text(buf, v, e)
            elif f2 == 3:
                lines.append((v, e))
            elif f2 == 4:
                events_md.append((v, e))
            elif f2 == 5:
                stats_md.append((v, e))
        if not tr.DEVICE_PLANE.match(name):
            continue
        metadata = charge_nameless(_event_metadata(buf, events_md, stats_md))
        plane = {"name": name, "modules": [], "ops": []}
        for a3, b3 in lines:
            line_name, events = _line_events(buf, a3, b3)
            for md, start, dur in events:
                text, path_ = metadata.get(md, ("", ""))[:2]
                if line_name == tr.MODULES_LINE:
                    plane["modules"].append([text, start, dur])
                elif line_name == tr.OPS_LINE:
                    plane["ops"].append([tr.op_name(text), start, dur,
                                         path_, opcode_of(text)])
        planes.append(plane)
    return sorted(planes, key=lambda p: int(p["name"].rsplit(":", 1)[1]))


def _host_spans(path, names):
    """Every host event whose name is in `names`, with its arguments."""
    from jax.profiler import ProfileData
    names, out = set(names), []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in names:
                    out.append([e.name, float(e.start_ns),
                                float(e.duration_ns),
                                {k: v for k, v in e.stats
                                 if isinstance(v, (int, float))}])
    return sorted(out, key=lambda ev: ev[1])


@functools.lru_cache(maxsize=2)
def load(trace_dir):
    """The program's view of the trace under `trace_dir`, read once for
    all the metrics of a run; None where there is no trace or the
    program has no registry."""
    names = registry()
    if names is None or not os.path.isdir(trace_dir):
        return None
    path = tr.find_xplane(trace_dir)
    view = {"devices": _raw_device_planes(path),
            "host": _host_spans(path, names[1])}
    if os.environ.get("BENCH_TRACE_DESCRIBE"):      # as run.py's own dump
        from loader import load_module
        load_module("tools", "program_trace_dump").dump(
            view, names[0], os.path.basename(os.path.normpath(trace_dir)))
    return view


# ------------------------------------------------------------ reductions
_WORD = re.compile(r"[A-Za-z0-9_]+")


def scope_of(path, scopes):
    """The innermost registered scope of an `op_name` path, or None:
    `jit(f)/transpose(jvp(attn_core))/kv_gather/gather` is `kv_gather`.
    The last component is the primitive's own name and is not looked at."""
    for part in reversed(path.split("/")[:-1]):
        for word in reversed(_WORD.findall(part)):
            if word in scopes:
                return word
    return None


def backward(path):
    return "transpose(" in path


def self_ns(ops):
    """For each operation (sorted by start) its own time: its duration
    less that of the operations nested inside it (a `while`'s body runs
    as events of the same line, inside the `while`'s own event)."""
    own = [ev[2] for ev in ops]
    stack = []                                      # (end, index)
    for i, (_, start, dur, *_rest) in enumerate(ops):
        while stack and stack[-1][0] <= start:
            stack.pop()
        if stack:
            own[stack[-1][1]] -= dur
        stack.append((start + dur, i))
    return own


def program_scopes(view, step_pattern, scopes):
    """Per execution of the programs matching `step_pattern`, over the
    chips: ({(scope or UNSCOPED, backward?, opcode, charged by
    inheritance?): ns}, the program's device time in ns, executions on
    one chip)."""
    key = ("program_scopes", step_pattern)
    cache = view.setdefault("_cache", {})
    if key in cache:
        return cache[key]
    rx = re.compile(step_pattern)
    totals, module_ns, runs, of_path = {}, 0.0, 0, {}
    for plane in view["devices"]:
        mods = sorted(plane["modules"], key=lambda ev: ev[1])
        starts = [m[1] for m in mods]
        ops = sorted(plane["ops"], key=lambda ev: (ev[1], -ev[2]))
        for (_, start, _, path, opcode), own in zip(ops, self_ns(ops)):
            j = bisect.bisect_right(starts, start) - 1
            if j < 0 or start >= mods[j][1] + mods[j][2] \
                    or not rx.search(mods[j][0]):
                continue
            if path not in of_path:     # a few thousand distinct paths
                of_path[path] = (scope_of(path, scopes) or UNSCOPED,
                                 backward(path), path.startswith(INHERITED))
            scope, bwd, inherited = of_path[path]
            k = (scope, bwd, opcode, inherited)
            totals[k] = totals.get(k, 0.0) + own
        mine = [m for m in mods if rx.search(m[0])]
        module_ns += sum(m[2] for m in mine)
        runs += len(mine)
    if not runs:
        out = None
    else:
        out = ({k: v / runs for k, v in totals.items()}, module_ns / runs,
               runs // len(view["devices"]))
    cache[key] = out
    return out


def spans_named(view, name):
    return [ev for ev in view["host"] if ev[0] == name]
