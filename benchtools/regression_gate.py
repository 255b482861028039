"""Bench regression gate CLI — exits nonzero on an unexplained
throughput drop.

Wraps `deeplearning4j_tpu.bench.compare_bench`: a structural,
per-metric-tolerance comparison of a fresh BENCH JSON against a
baseline record the caller names. Records from another platform and
first runs (an empty baseline) are explained outcomes and exit 0 with a
distinct status; only a genuine regression exits 1.

Usage::

    python -m benchtools.regression_gate FRESH.json BASELINE.json
        [--tolerance 0.10]

Either file may be a raw BENCH record, a driver round wrapper
(``{"parsed": {...}}``), or a log whose LAST line is the record (what
``python bench.py | tee`` leaves behind).

Exit codes: 0 pass / explained (incomparable, no baseline),
1 regression, 2 usage or unreadable input.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from deeplearning4j_tpu import bench  # noqa: E402

_EXPLAINED = ("pass", "incomparable_platform", "no_baseline",
              "no_measurement")


def load_record(path: str) -> dict:
    """Accept a raw record, a driver round wrapper, or a JSONL log whose
    last parseable line is the record."""
    with open(path) as f:
        text = f.read()
    try:
        rec = json.loads(text)
    except ValueError:
        rec = None
        for line in reversed(text.strip().splitlines()):
            line = line.strip()
            if not line.startswith("{"):
                continue
            try:
                rec = json.loads(line)
                break
            except ValueError:
                continue
        if rec is None:
            raise ValueError(f"no JSON record found in {path}")
    if isinstance(rec, dict) and isinstance(rec.get("parsed"), dict):
        rec = rec["parsed"]          # driver round wrapper
    if not isinstance(rec, dict):
        raise ValueError(f"{path} is not a JSON object")
    return rec


def run_gate(fresh: dict, baseline: dict, *, tolerance=None) -> dict:
    """The gate verdict for two loaded records (library seam the tests
    drive)."""
    kw = {}
    if tolerance is not None:
        kw["default_tolerance"] = tolerance
    return bench.compare_bench(fresh, baseline, **kw)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="benchtools.regression_gate")
    ap.add_argument("fresh", help="fresh BENCH JSON (record, driver "
                                  "wrapper, or log w/ last-line JSON)")
    ap.add_argument("baseline", help="baseline record, same formats")
    ap.add_argument("--tolerance", type=float, default=None,
                    help="override the default relative-drop tolerance "
                         f"(default {bench.GATE_DEFAULT_TOLERANCE})")
    ap.add_argument("--quiet", action="store_true",
                    help="suppress the JSON report (status line only)")
    args = ap.parse_args(argv)
    try:
        fresh = load_record(args.fresh)
        baseline = load_record(args.baseline)
    except (OSError, ValueError) as e:
        print(f"regression-gate: cannot load input: {e}", file=sys.stderr)
        return 2
    report = run_gate(fresh, baseline, tolerance=args.tolerance)
    status = report.get("status", "regression")
    if not args.quiet:
        print(json.dumps(report, indent=1, default=str))
    nreg = len(report.get("regressions", []) or [])
    print(f"regression-gate: {status}"
          + (f" ({nreg} metric(s) past tolerance)" if nreg else ""))
    return 0 if status in _EXPLAINED else 1


if __name__ == "__main__":
    sys.exit(main())
