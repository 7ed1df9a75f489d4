"""Device time of the operations traced ANYWHERE under a registered
scope, per execution of a program (`core/program_trace` gives each
operation to its INNERMOST scope only: `attn_core` inside `attn_window`
is `attn_core` there). A scope that wraps another one, such as the kind
of layer around `attn_core`, is read here."""

import bisect
import re

from core import program_trace as pt


def under(path, name):
    """Is `name` one of the scopes of an `op_name` path? The last
    component is the primitive's own name and is not looked at."""
    return any(name in pt._WORD.findall(part)
               for part in path.split("/")[:-1])


def within_ns(view, step_pattern, name, opcode=None):
    """ns per execution of the programs matching `step_pattern`, over
    the chips, of the operations under scope `name` (own time: a
    `while` without its body), optionally of one HLO opcode only; None
    where no such program ran."""
    rx = re.compile(step_pattern)
    total, runs = 0.0, 0
    for plane in view["devices"]:
        mods = sorted(plane["modules"], key=lambda ev: ev[1])
        starts = [m[1] for m in mods]
        ops = sorted(plane["ops"], key=lambda ev: (ev[1], -ev[2]))
        for (_, start, _, path, code), own in zip(ops, pt.self_ns(ops)):
            j = bisect.bisect_right(starts, start) - 1
            if j < 0 or start >= mods[j][1] + mods[j][2] \
                    or not rx.search(mods[j][0]):
                continue
            if opcode in (None, code) and under(path, name):
                total += own
        runs += sum(1 for m in mods if rx.search(m[0]))
    return total / runs if runs else None
