"""A program counter family as set-up left it: the sum of its values,
those whose labels match `labels` alone, in the registry's snapshot
taken at the window's opening (the traced serving run enables the
registry before the build, so that snapshot holds all of set-up).  No
registry, no such family, or no value with those labels: nothing to
read (a program without the family, as a parent may be)."""


def read(ctx, family, labels=None):
    snap = (ctx.get("registry") or (None, None))[0]
    if not snap or family not in snap:
        return None
    vals = [v.get("value", 0.0) for v in snap[family].get("values", [])
            if all(v.get("labels", {}).get(k) == x
                   for k, x in (labels or {}).items())]
    return sum(vals) if vals else None
