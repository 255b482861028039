"""Arithmetic of the yardstick: the table of peaks, the roofline, and a
count of a jaxpr's matrix operations.

`count_math_flops` is copied from `deeplearning4j_tpu/bench.py::
_count_math_flops` and `roofline` from `monitor/xprof.py::roofline`
(PERF.md lists the originals for a later PR to delete): the benchmark
keeps its own so that no PR that claims a gain can change the yardstick.
"""

from __future__ import annotations

import json
import os

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def device_peaks(device_kind: str) -> dict:
    """Published peaks of `device_kind`; an unknown kind is an error,
    never a default."""
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r} in "
            f"benchmark/peaks.json (known: {sorted(table)}); add the row "
            f"with its source before measuring on it")
    return table[device_kind]


def roofline_seconds(flops: float, bytes_moved: float, peaks: dict) -> dict:
    """The least time the chip could take: the larger of operations over
    peak FLOP/s and bytes over peak bytes/s, and which of the two it is."""
    t_c = float(flops) / peaks["bf16_flops_per_s"]
    t_m = float(bytes_moved) / peaks["hbm_bytes_per_s"]
    return {"seconds": max(t_c, t_m),
            "bound": "compute" if t_c >= t_m else "memory"}


def count_math_flops(jaxpr) -> float:
    """2 FLOPs per multiply-accumulate over every `conv_general_dilated`
    and `dot_general` of a jaxpr, sub-jaxprs included (a scan's body is
    counted once: multiply by its length yourself)."""
    total = 0.0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "conv_general_dilated":
            out = eqn.outvars[0].aval.shape
            rhs = eqn.invars[1].aval.shape
            dn = eqn.params["dimension_numbers"]
            kspatial = 1
            for d in dn.rhs_spec[2:]:
                kspatial *= rhs[d]
            cin = rhs[dn.rhs_spec[1]]
            nout = 1
            for s in out:
                nout *= s
            total += 2.0 * nout * kspatial * cin
        elif name == "dot_general":
            a = eqn.invars[0].aval.shape
            b = eqn.invars[1].aval.shape
            (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
            m = n = k = bsz = 1
            for i, s in enumerate(a):
                if i not in lc and i not in lb:
                    m *= s
            for i, s in enumerate(b):
                if i not in rc and i not in rb:
                    n *= s
            for i in lc:
                k *= a[i]
            for i in lb:
                bsz *= a[i]
            total += 2.0 * bsz * m * n * k
        mult = eqn.params.get("length", 1) if name == "scan" else 1
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else (p,)):
                inner = getattr(sub, "jaxpr", None)
                if inner is not None and hasattr(inner, "eqns"):
                    total += mult * count_math_flops(inner)
                elif hasattr(sub, "eqns"):
                    total += mult * count_math_flops(sub)
    return total
