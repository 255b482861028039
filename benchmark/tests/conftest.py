"""The benchmark's own tests run on the CPU, in one process, at the tiny
sizes each file keeps under `rehearsal`.  `python -m pytest benchmark/tests -q`."""

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))

import pytest  # noqa: E402


@pytest.fixture
def run_cell(capsys):
    """Drive `run.main` in this process (it skips nothing but the look
    for a chip: `--rehearse-cpu`) and return the result line."""
    import run

    def go(workload, *extra, hooks=None, seed=2147483659):
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--rehearse-cpu", *extra], hooks=hooks)
        assert rc == 0
        out = capsys.readouterr()
        last = [ln for ln in out.out.splitlines() if ln.strip()][-1]
        return json.loads(last), out.err

    return go
