"""`trace_reduce.py` against the small trace recorded on a v5e (PR 26):
four steps of a d256/L2/T1024 `TransformerLM` under `fit`, the window
annotated `bench/fit`, batches built under `bench/etl`."""

import os

import pytest

import trace_reduce as tr

TRACE = os.path.join(os.path.dirname(os.path.dirname(__file__)), "testdata",
                     "small.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    old, tr.WINDOW = tr.WINDOW, "bench/fit"
    try:
        return tr.reduce(TRACE)
    finally:
        tr.WINDOW = old


def test_interval_arithmetic():
    assert tr.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5), (7, 10)]
    assert tr.length(tr.union([(0, 2), (1, 3)])) == 3
    st = {n: s for n, s, _, _ in tr.self_times(
        [(0, 10, "while"), (1, 4, "a"), (5, 9, "b"), (6, 7, "c")])}
    assert st == {"while": 3, "a": 3, "b": 3, "c": 1}
    assert tr.category("%fusion.314 = bf16[2]{0} fusion(...)") == "fusion"
    assert tr.short_name("%dl4tpu_flash_fwd.3 = (...) custom-call()") == \
        "dl4tpu_flash_fwd.3"


def test_recorded_trace(reduced):
    assert reduced["n_devices"] == 1
    assert 0.0237 < reduced["window_s"] < 0.0239
    assert 0.0030 < reduced["busy_s"] < 0.0031
    # self times add up to the busy union: nothing is counted twice
    assert abs(sum(reduced["self_s_by_op"].values()) - reduced["busy_s"]) \
        < 1e-6
    assert len(reduced["device_ops"]) == 10 and len(reduced["idle_gaps"]) <= 10
    gaps = dict(reduced["idle_gaps"])
    assert gaps["bench/etl"] > 0.004
    assert abs(sum(gaps.values()) + reduced["busy_s"] - reduced["window_s"]) \
        < 1e-6
    assert reduced["collective_s"] == 0.0


def test_kernels_are_found_by_name(reduced):
    fwd = tr.kernel_seconds(reduced, r"dl4tpu_flash_fwd")
    bwd = tr.kernel_seconds(reduced, r"dl4tpu_flash_bwd_(dq|dkv)")
    assert 0.00029 < fwd < 0.00030 and 0.00070 < bwd < 0.00071
    assert tr.kernel_seconds(reduced, r"dl4tpu_fused_adam") > 0
    assert tr.kernel_seconds(reduced, r"no_such_kernel") is None


def test_no_window_no_result():
    assert tr.reduce(TRACE) is None      # the recorded trace has no bench/window
