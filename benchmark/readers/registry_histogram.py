"""Mean of one of the program's timer families over the window, in
milliseconds: (sum after - sum before) / (count after - count before) of
the registry's snapshots.  The registry is on in the traced run only."""


def _totals(snap, family):
    vals = (snap or {}).get(family, {}).get("values", [])
    return (sum(v.get("sum", 0.0) for v in vals),
            sum(v.get("count", 0) for v in vals))


def read(ctx, family):
    before, after = ctx.get("registry") or (None, None)
    if after is None:
        return None
    s0, c0 = _totals(before, family)
    s1, c1 = _totals(after, family)
    if c1 - c0 <= 0:
        return None
    return 1e3 * (s1 - s0) / (c1 - c0)
