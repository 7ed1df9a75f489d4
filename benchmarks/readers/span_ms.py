"""Host time (ms) spent in the program's phase spans named in `spans`
per occurrence of the span `per` (one `train_batch`, or one
`serve/decode`, is one step), on the profiler's clock
(`core/program_trace`)."""

from core import program_trace as pt


def read(view, facts, ctx, spans, per):
    program = pt.load(ctx.trace_dir)
    if program is None:
        return None
    steps = len(pt.spans_named(program, per))
    found = [ev for name in spans for ev in pt.spans_named(program, name)]
    if not steps or not found:
        return None
    return sum(ev[2] for ev in found) / steps / 1e6
