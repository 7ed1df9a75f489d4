"""The share (%) of the first device's idle time inside the traced
window that falls to the program's host span `span`: each gap between
operations goes to the innermost program span (`HOST_SPANS`) that
covers the gap's middle. `span` null reads the gaps that no program
span covers. With `per` (a span's name: one `train_batch` is one step)
the value is that idle time in ms per occurrence of `per` in place of
the share. A host that waits for the device inside a span collects the
small gaps between the operations of the running program there too.
The whole table goes to the log."""

from core import program_trace as pt
from core import trace as tr

NO_SPAN = "no_program_span"


def by_span(view, program):
    """{span name or NO_SPAN: idle ns} of the first device."""
    window = tr.window_of(view)
    if window is None or not program["devices"]:
        return None
    ops = program["devices"][0]["ops"]
    busy = tr.clip(tr.union([[ev[1], ev[1] + ev[2]] for ev in ops]),
                   *window)
    out, covering, nxt = {}, [], 0
    spans = sorted(program["host"], key=lambda ev: ev[1])
    for s, e in tr.subtract([list(window)], busy):      # in time order
        mid = (s + e) / 2
        while nxt < len(spans) and spans[nxt][1] <= mid:
            covering.append(spans[nxt])
            nxt += 1
        covering = [ev for ev in covering if mid < ev[1] + ev[2]]
        name = min(covering, key=lambda ev: ev[2])[0] if covering \
            else NO_SPAN
        out[name] = out.get(name, 0.0) + (e - s)
    return out


def read(view, facts, ctx, span=None, per=None):
    program = pt.load(ctx.trace_dir)
    if view is None or program is None:
        return None
    gaps = by_span(view, program)
    if not gaps:
        return None
    idle = sum(gaps.values())
    ctx.log(f"device idle {idle / 1e6:.3f} ms of the window by the "
            "program's innermost span: " + ", ".join(
                f"{k} {v / 1e6:.3f}"
                for k, v in sorted(gaps.items(), key=lambda kv: -kv[1])))
    mine = gaps.get(span or NO_SPAN, 0.0)
    if per is None:
        return 100.0 * mine / idle
    window = tr.window_of(view)
    steps = sum(window[0] <= ev[1] < window[1]
                for ev in pt.spans_named(program, per))
    return mine / steps / 1e6 if steps else None
