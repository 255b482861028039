"""`run.py --rehearse-cpu` end to end, once for each cell, and the
faults a cell can have planted under the timed path: `correct` has to
come out false for each."""

import os

import pytest

import harness

BENCH = harness.load_json(os.path.join(harness.REPO, "BENCHMARK.json"))
CELLS = [w["name"] for w in BENCH["workloads"]]
TRAIN = [w["name"] for w in BENCH["workloads"]
         if harness.load_cell(w["name"])[1]["kind"] == "train"]
SERVE = [c for c in CELLS if c not in TRAIN]


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_end_to_end(run_cell, cell, trace):
    res, err = run_cell(cell, "--trace", str(trace))
    assert res["rehearsal"] is True and res["device"]["platform"] == "cpu"
    assert res["correct"] is True, err
    assert list(res)[-1] == "compared"
    group = "per_layer" if trace else "end_to_end"
    known = {m["name"]: m["unit"] for m in
             harness.cell_metrics(BENCH, cell, group)}
    assert res["metrics"], "a run reports at least one metric"
    for name, m in res["metrics"].items():
        assert known[name] == m["unit"]
    if not trace:
        assert set(res["metrics"]) == set(known)
        assert all(m["value"] > 0 for m in res["metrics"].values())
    # no share of a peak or a roofline is ever reported from a CPU
    assert not any("mfu" in n or "roofline" in n for n in res["metrics"])
    for line in err.strip().splitlines()[-len(res["compared"]):]:
        assert line.startswith("compared ")


def _wrap_step(net, wrapper):
    net._jit_train_step = wrapper(net._make_train_step(tbptt=False))


def unchanged(real):
    def step(params, upd, state, *rest):
        out = real(params, upd, state, *rest)
        return (params, upd, state) + tuple(out[3:])
    return step


def half_batch(real):
    def step(params, upd, state, it, x, y, *rest):
        n = x.shape[0] // 2
        return real(params, upd, state, it, x[:n], y[:n], *rest)
    return step


@pytest.mark.parametrize("fault", [unchanged, half_batch])
@pytest.mark.parametrize("cell", TRAIN)
def test_training_fault_is_not_correct(run_cell, cell, fault):
    res, err = run_cell(cell, hooks={"net": lambda n: _wrap_step(n, fault)})
    assert res["correct"] is False, err
    failed = [k for k, v in res["compared"].items() if not v["ok"]]
    assert failed, res["compared"]


@pytest.mark.parametrize("cell", SERVE)
def test_altered_token_is_not_correct(run_cell, cell):
    def alter(server):
        real = server.generate_async

        def submit(*a, **kw):
            s = real(*a, **kw)
            emit = s._emit_many

            def emit_altered(toks, now):
                toks = list(toks)
                if len(s.tokens) <= 2 < len(s.tokens) + len(toks):
                    j = 2 - len(s.tokens)
                    toks[j] = (int(toks[j]) + 7) % 251
                emit(toks, now)
            s._emit_many = emit_altered
            return s
        server.generate_async = submit

    res, err = run_cell(cell, hooks={"server": alter})
    assert res["correct"] is False, err
    assert res["compared"]["served_logit_gap"]["ok"] is False
