"""Device time (ms) per step of the operations whose own name (the text
before ` = `, not their operands) matches `op_pattern`, over the chips; a step is one execution of the program
matching `step_pattern`."""

from core import trace as tr


def per_step_ns(view, op_pattern, step_pattern):
    vals = []
    for plane in tr.device_planes(view):
        steps = len(tr.module_events(plane, step_pattern))
        ops = tr.matching_ops(tr.line_events(plane, tr.OPS_LINE), op_pattern)
        if steps and ops:
            vals.append(sum(d for _, _, d in ops) / steps)
    return sum(vals) / len(vals) if vals else None


def read(view, facts, ctx, op_pattern, step_pattern):
    if view is None:
        return None
    ns = per_step_ns(view, op_pattern, step_pattern)
    return None if ns is None else ns / 1e6
