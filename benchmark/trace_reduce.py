"""`.xplane.pb` -> device busy union, idle share, per-op self time, idle
gaps by what the host was doing, exposed collective time.

Reads the profile with `jax.profiler.ProfileData` alone.  What a v5e
trace looks like (looked at by hand, PR 26): one plane `/device:TPU:<n>`
per chip with the lines `XLA Ops` (one event per HLO instruction run, its
name the instruction's text, `%dl4tpu_flash_fwd.3 = ... custom-call(...)`
for a Pallas kernel; a `while` covers the events of its body), `XLA
Modules` and `Steps`; one plane `/host:CPU` with a line per host thread,
on which a `jax.profiler.TraceAnnotation` is an event of its own name.
Both are on one clock, in nanoseconds.

The window is the interval of the host annotation `WINDOW` that the
benchmark puts round the traced part of its measured window.  Checked
against `testdata/small.xplane.pb` by `tests/test_trace_reduce.py`.
"""

from __future__ import annotations

import re

WINDOW = "bench/window"
SPAN_PREFIX = "bench/"
OPS_LINE = "XLA Ops"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all", "collective-broadcast")


def short_name(text: str) -> str:
    """`%fusion.314 = bf16[...] fusion(...)` -> `fusion.314`."""
    m = re.match(r"%?([^\s=]+)", text)
    return m.group(1) if m else text


def category(text: str) -> str:
    """Instruction name without its number: `fusion.314` -> `fusion`."""
    return re.sub(r"(\.\d+)+$", "", short_name(text))


def union(intervals):
    """Merged, sorted list of (start, end)."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def length(merged):
    return sum(e - s for s, e in merged)


def subtract(a, b):
    """Parts of merged list `a` not covered by merged list `b`."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def self_times(events):
    """events: list of (start, end, name), possibly nested -> list of
    (name, self_ns, start, end): an event's time less what the events
    inside it cover."""
    evs = sorted(events, key=lambda t: (t[0], -(t[1] - t[0])))
    out, stack = [], []

    def close(upto):
        while stack and stack[-1][1] <= upto:
            s, e, name, child = stack.pop()
            out.append((name, max(0, (e - s) - child), s, e))
            if stack:
                stack[-1][3] += e - s

    for s, e, name in evs:
        close(s)
        stack.append([s, e, name, 0])
    close(float("inf"))
    return out


def _events(line):
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for e in line.events]


def load(path):
    """-> {"devices": {plane name: [(start, end, text)]},
           "spans": [(start, end, name)] of host annotations}."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in pd.planes:
        if re.match(r"/device:(TPU|GPU):\d+$", plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = _events(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [ev for ev in _events(line)
                          if ev[2].startswith(SPAN_PREFIX)]
    return {"devices": devices, "spans": spans}


def reduce(path, top=10):
    """The reduction.  Returns None where the trace has no device plane
    or no window annotation (a CPU rehearsal has no device plane)."""
    data = load(path)
    wins = [s for s in data["spans"] if s[2] == WINDOW]
    if not data["devices"] or not wins:
        return None
    lo, hi = min(w[0] for w in wins), max(w[1] for w in wins)
    inner = [s for s in data["spans"] if s[2] != WINDOW]
    n = len(data["devices"])
    busy = 0.0
    ops, kernels, gaps = {}, {}, {}
    coll_exposed = coll_total = 0.0
    for evs in data["devices"].values():
        evs = [(max(s, lo), min(e, hi), t) for s, e, t in evs
               if min(e, hi) > max(s, lo)]
        merged = union((s, e) for s, e, _ in evs)
        busy += length(merged)
        leaf = self_times(evs)
        for text, self_ns, _, _ in leaf:
            ops[category(text)] = ops.get(category(text), 0) + self_ns
            kernels[short_name(text)] = kernels.get(short_name(text), 0) + self_ns
        for s, e in subtract([(lo, hi)], merged):
            mid = (s + e) / 2
            open_ = [sp for sp in inner if sp[0] <= mid < sp[1]]
            name = (max(open_, key=lambda sp: sp[0])[2] if open_
                    else "unattributed")
            gaps[name] = gaps.get(name, 0) + (e - s)
        is_coll = lambda t: category(t).replace("-start", "").replace(  # noqa: E731
            "-done", "") in COLLECTIVES
        coll = union((s, e) for s, e, t in evs if is_coll(t))
        comp = union((s, e) for t, self_ns, s, e in leaf
                     if not is_coll(t) and self_ns >= 0.5 * (e - s))
        coll_total += length(coll)
        coll_exposed += length(subtract(coll, comp))

    def rank(d):
        return [[k, v / n / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy / n / 1e9,
        "n_devices": n,
        "device_ops": rank(ops),
        "idle_gaps": rank(gaps),
        "self_s_by_op": {k: v / n / 1e9 for k, v in kernels.items()},
        "collective_s": coll_total / n / 1e9,
        "exposed_collective_s": coll_exposed / n / 1e9,
    }


def kernel_seconds(reduced, pattern: str):
    """Seconds (a chip's mean) of the ops whose instruction name matches
    `pattern` from its start; None where none ran."""
    rx = re.compile(pattern)
    hit = [v for k, v in reduced["self_s_by_op"].items() if rx.match(k)]
    return sum(hit) if hit else None
