"""Mean idle time (ms) on the device just before each execution of a
program matching `pattern`: from the end of whatever program ran before
it to its own start. It is what the host took between dispatches."""

from core import trace as tr


def read(view, facts, ctx, pattern):
    if view is None:
        return None
    vals = []
    for plane in tr.device_planes(view):
        gaps = tr.gaps_before(plane, pattern)
        if gaps:
            vals.append(sum(gaps) / len(gaps) / 1e6)
    return sum(vals) / len(vals) if vals else None
