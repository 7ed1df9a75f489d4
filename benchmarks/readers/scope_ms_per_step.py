"""Device time (ms) of the program's own scopes per execution of the
program matching `step_pattern`, over the chips (`core/program_trace`).

`scopes` lists registered device scopes whose times are summed;
`"unscoped"` among them is what no registered scope covers, and `scopes`
null is every operation of the program. An operation counts under the
innermost scope of its `op_name`, a fusion under its root's, a `while`
for its own time only. An operation the compiler made with no `op_name`
counts under the scope of what it reads from, else of what reads it
(`core/program_trace.charge_nameless`): which neighbour that is can
change with the compiler's fusion and layout choices, so `inherited`
true keeps only what was charged that way (the `*_inherited_share`
metrics: how much of a program's scope times rests on the neighbour
rule and not on a name), and false only what carries a name of its own.
`opcode` keeps only operations of that HLO opcode (`custom-call`: the
Pallas kernels alone, without the copies the compiler puts around them
under the same `op_name`). `share` gives the sum as a percentage of the
program's device time. The whole table, forward and backward apart and
the inherited part of each scope beside it, and its sum beside the
program's device time go to the log once a program.
"""

from core import program_trace as pt


def table(ctx, view, step_pattern, scopes):
    got = pt.program_scopes(view, step_pattern, scopes)
    if got is None:
        return None
    totals, module_ns, runs = got
    logged = view.setdefault("_cache", {}).setdefault("logged", set())
    if step_pattern not in logged:      # once a program, not a metric
        logged.add(step_pattern)
        by = {}
        for (scope, bwd, _, inherited), ns in totals.items():
            row = by.setdefault(scope, [0.0, 0.0, 0.0])
            row[bwd] += ns
            row[2] += ns * inherited
        ops_ns = sum(totals.values())
        inherited = sum(row[2] for row in by.values())
        ctx.log(f"scopes of {step_pattern!r}, ms a run over {runs} runs "
                "(forward + backward; of which inherited): " + ", ".join(
                    f"{s} {(f + b) / 1e6:.3f} ({f / 1e6:.3f} + "
                    f"{b / 1e6:.3f}; {i / 1e6:.3f})"
                    for s, (f, b, i) in sorted(
                        by.items(), key=lambda kv: -sum(kv[1][:2]))))
        ctx.log(f"scopes and unscoped add up to {ops_ns / 1e6:.3f} ms of "
                f"the program's {module_ns / 1e6:.3f} ms on the device "
                f"({100 * (1 - ops_ns / module_ns):.3f}% of it no operation "
                f"runs); {inherited / 1e6:.3f} ms are operations with no "
                "op_name of their own, charged to what they read from or "
                "what reads them")
    return totals, module_ns


def read(view, facts, ctx, scopes, step_pattern, opcode=None, share=False,
         inherited=None):
    names = pt.registry()
    program = pt.load(ctx.trace_dir)
    if names is None or program is None:
        return None
    got = table(ctx, program, step_pattern, names[0])
    if got is None:
        return None
    totals, module_ns = got
    ns = sum(v for (scope, _, op, charged), v in totals.items()
             if (scopes is None or scope in scopes)
             and opcode in (None, op) and inherited in (None, charged))
    return 100.0 * ns / module_ns if share else ns / 1e6
