"""Growth of one of the program's counter families over the window."""


def _total(snap, family):
    vals = (snap or {}).get(family, {}).get("values", [])
    return sum(v.get("value", 0.0) for v in vals)


def read(ctx, family):
    before, after = ctx.get("registry") or (None, None)
    if after is None or family not in after:
        return None
    return _total(after, family) - _total(before, family)
