"""A ratio (%) of sums of the arguments the program's span `span`
carried, over the traced dispatches (`core/program_trace`): the sum of
the products of the arguments in `of` over the sum of the products of
those in `over`; `complement` gives 100 less that."""

import math

from core import program_trace as pt


def _product(args, keys):
    return math.prod(args[k] for k in keys)


def read(view, facts, ctx, span, of, over, complement=False):
    program = pt.load(ctx.trace_dir)
    if program is None:
        return None
    events = [ev[3] for ev in pt.spans_named(program, span)
              if all(k in ev[3] for k in of + over)]
    base = sum(_product(a, over) for a in events)
    if not base:
        return None
    share = 100.0 * sum(_product(a, of) for a in events) / base
    return 100.0 - share if complement else share
