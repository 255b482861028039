"""Test config: the CPU backend with 8 virtual devices, so mesh /
sharding tests run without TPU hardware (the Spark `local[N]` idea from
the reference test suite, SURVEY.md §4).
"""

import os
import sys

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Persistent XLA compilation cache (nd/cache.py — one rule for where it
# lives): the suite is compile-dominated, cold it does not fit the
# tier-1 window and warm it does. Programs are keyed by HLO — code
# changes recompile exactly what they touch. Under the in-checkout root
# the suite keeps a per-machine namespace: sandbox sessions migrate
# between machine types, and XLA:CPU AOT results compiled for another
# machine load with "may SIGILL" warnings. The tag is a function of the
# machine and the toolchain, never of the run.
from deeplearning4j_tpu.nd import enable_compilation_cache  # noqa: E402


def _machine_tag():
    import hashlib
    import platform
    try:
        with open("/proc/cpuinfo") as f:
            flags = next((line for line in f if line.startswith("flags")), "")
    except OSError:
        flags = ""
    # fingerprint the full toolchain, not just the CPU: entries AOT-
    # compiled by another jaxlib build load "successfully" and then
    # corrupt the heap mid-suite (observed: malloc_consolidate abort
    # from a cache dir written by a previous sandbox image) — a version
    # change must land in a fresh namespace
    import jaxlib
    versions = jax.__version__ + jaxlib.__version__ + platform.python_version()
    return hashlib.sha256(
        (platform.machine() + flags + versions).encode()).hexdigest()[:10]


# Threshold 0: the suite compiles ~5,000 programs and most take under
# 0.2 s each — at that threshold only ~800 were cached and a "warm" run
# recompiled the rest (PR 21: 973 s warm vs 1273 s cold; one file 108 s
# against 51 s with everything cached). ~50 MB on disk.
if not os.environ.get("DL4J_DISABLE_XLA_CACHE"):
    enable_compilation_cache(f"tests-{_machine_tag()}",
                             min_compile_time_secs=0.0)


# ---------------------------------------------------- suite budget report
# Per-file duration accounting for the tier-1 gate: the suite runs in a
# single hard window (driver: 600 s; ROADMAP timeout -k 10 870), and at
# ~8% headroom a silent overflow loses the whole round's verification.
# These hooks ride INSIDE the verbatim ROADMAP command (they are repo
# conftest code, not extra flags) and leave a JSON report that
# scripts/verify.sh turns into a top-offenders table + a soft-budget
# warning above 480 s.
import collections as _collections
import json as _json

_FILE_DURATIONS = _collections.defaultdict(float)
_DURATIONS_OUT = os.environ.get("DL4J_SUITE_DURATIONS",
                                "/tmp/_t1_durations.json")
SUITE_BUDGET_SOFT_S = 480.0
SUITE_BUDGET_HARD_S = 600.0


def pytest_runtest_logreport(report):
    # setup + call + teardown all charged to the test's file
    _FILE_DURATIONS[report.location[0]] += getattr(report, "duration",
                                                   0.0) or 0.0


def pytest_sessionfinish(session, exitstatus):
    if not _FILE_DURATIONS:
        return
    total = sum(_FILE_DURATIONS.values())
    files = sorted(({"file": f, "seconds": round(s, 2)}
                    for f, s in _FILE_DURATIONS.items()),
                   key=lambda r: -r["seconds"])
    try:
        with open(_DURATIONS_OUT, "w") as f:
            _json.dump({"total_seconds": round(total, 2),
                        "budget_soft_seconds": SUITE_BUDGET_SOFT_S,
                        "budget_hard_seconds": SUITE_BUDGET_HARD_S,
                        "files": files}, f, indent=1)
            f.write("\n")
    except OSError:
        pass
