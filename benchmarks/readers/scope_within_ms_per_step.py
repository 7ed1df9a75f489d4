"""Device time (ms) of the operations anywhere under the scope `within`
(not only those it is the innermost scope of) per execution of the
program matching `step_pattern`, over the chips; `opcode` keeps one HLO
opcode (`custom-call`: the Pallas kernels alone). `core/scope_within`."""

from core import program_trace as pt
from core import scope_within


def read(view, facts, ctx, within, step_pattern, opcode=None):
    names = pt.registry()
    program = pt.load(ctx.trace_dir)
    if names is None or program is None or within not in names[0]:
        return None
    ns = scope_within.within_ns(program, step_pattern, within, opcode)
    return None if ns is None else ns / 1e6
