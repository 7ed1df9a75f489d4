"""A number the run already holds (`facts`), optionally as a share of
another: `key` may be dotted, `over` names the denominator, `scale`
multiplies."""


def _get(facts, key):
    cur = facts
    for part in key.split("."):
        if not isinstance(cur, dict) or part not in cur:
            return None
        cur = cur[part]
    return cur


def read(view, facts, ctx, key, over=None, scale=1.0):
    value = _get(facts, key)
    if value is None:
        return None
    if over is not None:
        base = _get(facts, over)
        if not base:
            return None
        value = value / base
    return value * scale
