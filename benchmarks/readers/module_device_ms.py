"""Mean device time (ms) of one execution of the programs whose XLA
module name matches `pattern`, over the chips."""

from core import trace as tr


def read(view, facts, ctx, pattern):
    if view is None:
        return None

    def one(plane):
        evs = tr.module_events(plane, pattern)
        return sum(d for _, _, d in evs) / len(evs) / 1e6 if evs else None

    vals = [v for v in map(one, tr.device_planes(view)) if v is not None]
    return sum(vals) / len(vals) if vals else None
