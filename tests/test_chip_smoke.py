"""`chip_smoke.py` cannot rot between chip runs.

The real run needs a TPU (the driver and the builder run it through the
chip tool). Here: the script refuses anything but a TPU, refuses to run
from outside its checkout, and its explicit CPU rehearsal drives the
very same phases — train, publish/resolve, serve vs generate(), the
four-device section — at a tiny size, without ever reading as a pass.
"""

import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")


def _run(args, *, cwd=ROOT, script=SMOKE, xla_flags=None, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env.pop("PYTHONPATH", None)
    if xla_flags:
        env["XLA_FLAGS"] = xla_flags
    return subprocess.run([sys.executable, script, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


def _json_lines(stdout):
    return [json.loads(ln) for ln in stdout.splitlines()
            if ln.startswith("{")]


def test_without_a_tpu_it_fails_and_names_what_it_found():
    proc = _run([])
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert "platform='cpu'" in proc.stderr
    # prints no result: nothing on stdout parses as a verdict
    assert not _json_lines(proc.stdout)


def test_alone_without_the_package_it_fails(tmp_path):
    alone = shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    proc = _run(["--rehearse-cpu"], cwd=tmp_path, script=str(alone))
    assert proc.returncode != 0
    assert not _json_lines(proc.stdout)


def test_cpu_rehearsal_drives_every_phase_and_is_never_a_pass():
    # four virtual devices so the four-chip section runs too
    proc = _run(["--rehearse-cpu"],
                xla_flags="--xla_force_host_platform_device_count=4")
    assert proc.returncode == 0, proc.stderr[-3000:]
    facts, verdict = _json_lines(proc.stdout)[-2:]
    # the last line has the contract's shape, says cpu, and is not ok
    assert proc.stdout.strip().splitlines()[-1] == json.dumps(verdict)
    assert verdict == {"ok": False,
                       "device": {"platform": "cpu", "kind": "cpu",
                                  "count": 4}}
    assert facts["rehearsal"] is True
    # phase 1: loss fell, finite at every readback (else it raised)
    assert facts["train"]["loss_last"] < 0.5 * facts["train"]["loss_first"]
    # no kernel is Mosaic-compiled off the chip, and the facts say so
    assert not any(facts["mosaic_kernels"].values())
    assert facts["donation"]["requested"] is False
    # phase 2-4: served the RESOLVED net, greedy == generate()
    assert facts["registry"]["version"] == 1
    serve = facts["serve"]
    assert serve["greedy_equal_generate"] == f"{serve['greedy']}/" \
                                             f"{serve['greedy']}"
    assert serve["sampled"] > 0 and serve["admitted_into_running_batch"] > 0
    assert serve["compiles_after_warmup"] == 0
    # the four-device section ran and tracked the one-device steps
    four = facts["four_chips"]
    assert four["devices"] == [0, 1, 2, 3]
    assert four["max_rel_diff"] < 0.05
    assert facts["compile"]["xla_compiles"] > 0
    # it claims nothing, last
    assert list(facts)[-1] == "claim" and facts["claim"] is None
    # the registry zip is scratch: gone when the smoke ends
    assert not os.path.exists(os.path.join(
        ROOT, "chiprun_out", "chip_smoke", "registry"))
