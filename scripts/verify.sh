#!/usr/bin/env bash
# The repo's verification gate — what builders and reviewers both run.
#
# 1. Tier-1 tests: the ROADMAP.md command VERBATIM (same timeout, same
#    pass-count accounting), so local runs and the driver's gate can
#    never drift apart.
# 2. Suite duration budget: the conftest hooks leave a per-file
#    duration report; the suite must stay under the driver's single
#    600 s hard window (ROADMAP's own timeout is `-k 10 870`). Above
#    the 480 s soft budget this step WARNS with the top offenders so
#    the ~8%-headroom suite never silently overflows; it does not fail
#    the gate.
# 3. /metrics smoke: boot a UIServer on an ephemeral port after a short
#    fit() and assert the Prometheus exposition parses and contains
#    training counters (the telemetry core's acceptance surface —
#    docs/OBSERVABILITY.md).
# 4. AOT cost smoke: `hlo_cost --all` (reduced batch, scratch dir) must
#    produce every report with the program section's compile_seconds +
#    peak-memory fields — the scan-over-layers/remat observability
#    surface (docs/COMPILE.md) — AND the comm_bytes block (dense-vs-
#    threshold gradient-exchange payload, threshold < dense) AND the
#    comm_overlap block (bucketed exchange: exposed <= total for every
#    report, overlapped_bytes > 0 for the transformer — the
#    comm/compute overlap evidence; docs/COMMS.md). CPU-forced.
# 5. Gradient-sharing smoke: tiny-MLP dense vs threshold loss
#    trajectories must stay within tolerance after 50 sync steps on a
#    4-way mesh (the error-feedback convergence guarantee), and the
#    ZeRO path (dense_rs: reduce-scatter + sharded updater +
#    all-gather) must match bucketed dense BIT-exactly on that mesh.
# 6. Fault-drill smoke: 30-step tiny-MLP run killed (real SIGTERM) at
#    step 15 with async checkpointing every 5, auto-resumed by the
#    drill driver — final params/updater state must be BIT-identical
#    to the uninterrupted run (the preemption-tolerance guarantee,
#    docs/FAULT_TOLERANCE.md).
# 7. Mixed-precision smoke: tiny-MLP bf16-vs-fp32 loss trajectory
#    within the documented tolerance (docs/PRECISION.md), fp32 master
#    params/updater state. The hlo_cost `precision` block (bf16 bytes <
#    fp32 bytes) is asserted in step [4/19] where the reports are
#    already on disk.
# 9. Serving smoke: `scripts/serve_loadtest.py --smoke` — >=64
#    concurrent streams continuously batched over the paged KV pool on
#    a tiny TransformerLM. Hard asserts inside the script: every
#    stream bit-equal to whole-batch `generate()` (greedy decode
#    parity, docs/SERVING.md), aggregate tokens/s beats sequential
#    whole-batch round-trips under the same client harness, p99 TTFT
#    bounded, and the deliberate-overload phase sheds at least one
#    request (SLO admission policy; `serving_shed_total`). The smoke
#    ledger now also carries the mixed-length + int8-quantized phase
#    and the incremental-vs-upfront admission A/B.
# 10. Quantized-serving gate: re-asserts the [9/19] ledger's three
#    perf-lever evidence fields (greedy parity exact fp AND int8,
#    mixed-length wave admission, incremental >= 2x upfront
#    concurrency, weight-byte reduction) and proves compare_bench
#    gates the new serving entries — including the STRUCTURAL
#    near-zero band (a silent fp-weight fallback reports ~1.0x
#    against an int8 baseline and must gate) and the lower-is-better
#    TTFT inversion (docs/SERVING.md).
# 11. Elastic-drill smoke: 4-process gloo run with the membership
#    coordinator; one worker is SIGKILLed at step ~15 (survivors
#    detect the death, re-form a 3-process mesh from the newest valid
#    checkpoint with re-sharded residual/τ, and keep training), then a
#    grow drill re-adds it (4-wide final generation). Asserts loss-
#    trajectory parity vs an uninterrupted 4-replica reference and
#    that `elastic_reconfigurations_total`/`elastic_live_processes`
#    appear on /metrics (docs/FAULT_TOLERANCE.md "Elastic
#    membership").
# 12. Fleet smoke: `scripts/serve_loadtest.py --fleet-smoke` — two
#    tiny models published into a ModelRegistry, deployed behind a
#    FleetServer and driven through the FleetRouter with 128+
#    concurrent streams; MID-RUN the script publishes alpha v2 and
#    hot-swaps it (warmed successor, pointer flip, incumbent drain).
#    Hard asserts inside the script: zero dropped streams, every
#    stream bit-equal to the reference of the version it was SERVED
#    by (old-version parity), post-swap p99 TTFT bounded (no compile
#    cliff), the autoscaler grows the undersized model from the
#    queue-depth gauges, and `fleet_active_models` /
#    `registry_published_total` are live on /metrics
#    (docs/SERVING.md "Fleet").
# 13. Online-learning smoke: `scripts/online_loop.py --smoke` — a
#    TransformerLM continuously fine-tunes from a local firehose
#    (unbounded StreamingDataSetIterator over the offset-addressable
#    LocalLogTransport) while the FleetServer hot-swaps to each
#    published snapshot under live decode traffic. Hard asserts
#    inside the script: >=2 registry publishes (cadence +
#    off-cadence final), >=1 hot-swap with streams in flight at the
#    pointer flip, zero dropped streams, version-tagged greedy
#    parity, the drift gate trips on an injected label-shuffle
#    segment (publishing pauses, training continues) and publishing
#    resumes after recovery, and the streaming_*/online_* families +
#    /train staleness row are live (docs/STREAMING_TRAINING.md).
# 8. Diagnostics smoke: tiny-MLP run with an injected lr spike
#    producing non-finite gradients mid-run — the in-graph watchdog's
#    `skip` policy must keep the trajectory finite (and training must
#    recover), `watchdog_nonfinite_total` must increment on /metrics,
#    `halt` must raise NonFiniteGradientsError naming the offending
#    layers, and the /train overview must serve the real per-layer
#    grad/update/activation stats (docs/OBSERVABILITY.md "Model
#    internals & training health").

set -u
cd "$(dirname "$0")/.."

echo "== [1/19] tier-1 tests (ROADMAP.md verbatim) =="
# stale-report guard: a timeout-killed suite never reaches
# pytest_sessionfinish, and step [2/3] must not read the previous
# run's durations as this run's
rm -f "${DL4J_SUITE_DURATIONS:-/tmp/_t1_durations.json}"
bash -c "set -o pipefail; rm -f /tmp/_t1.log; timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=\${PIPESTATUS[0]}; echo DOTS_PASSED=\$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?\$' /tmp/_t1.log | tr -cd . | wc -c); exit \$rc"
tier1_rc=$?

echo "== [2/19] suite duration budget =="
python - <<'EOF'
import json
import os

path = os.environ.get("DL4J_SUITE_DURATIONS", "/tmp/_t1_durations.json")
try:
    with open(path) as f:
        rep = json.load(f)
except (OSError, ValueError):
    print(f"no duration report at {path} (tier-1 run aborted early?) — "
          "budget unchecked")
    raise SystemExit(0)
total = rep.get("total_seconds", 0.0)
soft = rep.get("budget_soft_seconds", 480.0)
hard = rep.get("budget_hard_seconds", 600.0)
print(f"tier-1 test time: {total:.1f}s "
      f"(soft budget {soft:.0f}s, driver hard window {hard:.0f}s)")
print("slowest files:")
for r in rep.get("files", [])[:10]:
    print(f"  {r['seconds']:8.1f}s  {r['file']}")
if total > soft:
    print(f"WARNING: suite exceeds the {soft:.0f}s soft budget — "
          f"{hard - total:.0f}s of hard-window headroom left. Trim or "
          "mark 'slow' the top offenders above before adding tests.")
EOF

echo "== [3/19] /metrics smoke =="
JAX_PLATFORMS=cpu python - <<'EOF'
import sys
import urllib.request

import numpy as np

from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.ui import UIServer

monitor.enable()
conf = (NeuralNetConfiguration.builder().seed(0).list()
        .layer(DenseLayer(n_in=4, n_out=8))
        .layer(OutputLayer(n_in=8, n_out=3))
        .build())
net = MultiLayerNetwork(conf).init()
x = np.random.default_rng(0).standard_normal((16, 4)).astype(np.float32)
y = np.eye(3, dtype=np.float32)[np.random.default_rng(1).integers(0, 3, 16)]
net.fit(x, y, epochs=1, batch_size=8)

server = UIServer().start()   # port=0 -> ephemeral
try:
    body = urllib.request.urlopen(
        f"http://127.0.0.1:{server.port}/metrics", timeout=10).read().decode()
finally:
    server.stop()

assert "training_iterations_total" in body, body[:400]
for line in body.splitlines():
    if line and not line.startswith("#"):
        name = line.split("{")[0].split(" ")[0]
        assert name and name[0].isalpha() or name[0] == "_", line
nspans = sum(monitor.tracer().span_names().values())
assert nspans >= 3, monitor.tracer().span_names()
print(f"/metrics smoke OK ({len(body.splitlines())} exposition lines, "
      f"{nspans} spans)")
EOF
smoke_rc=$?

echo "== [4/19] AOT cost smoke (hlo_cost --all) =="
hlo_out=$(mktemp -d)
timeout -k 10 840 env JAX_PLATFORMS=cpu \
    python -m benchtools.hlo_cost --all --batch 8 --steps 2 --out "$hlo_out"
hlo_run_rc=$?
JAX_PLATFORMS=cpu HLO_SMOKE_OUT="$hlo_out" python - <<'EOF'
import glob
import json
import os

out = os.environ["HLO_SMOKE_OUT"]
paths = sorted(glob.glob(os.path.join(out, "cost_*.json")))
assert len(paths) >= 4, f"expected 4 headline reports, got {paths}"
for p in paths:
    with open(p) as f:
        rep = json.load(f)
    prog = rep.get("program") or {}
    missing = [k for k in ("compile_seconds", "peak_temp_bytes",
                           "temp_size_in_bytes", "jaxpr_eqn_count")
               if not prog.get(k)]
    assert not missing, f"{p}: program section missing {missing}"
    cb = prog.get("comm_bytes") or {}
    assert cb.get("dense_bytes_per_step") and \
        cb.get("threshold_bytes_per_step"), f"{p}: comm_bytes missing: {cb}"
    assert cb["threshold_bytes_per_step"] < cb["dense_bytes_per_step"], \
        f"{p}: threshold exchange not smaller than dense: {cb}"
    # int8-vs-fp32 stays the 4x wire format; against the REAL dense
    # wire (bf16 grads under the mixed_bf16 headline policy) the
    # honest floor is ~2x
    assert cb.get("reduction_vs_fp32", cb.get("reduction", 0)) >= 3.9, \
        f"{p}: comm reduction below 4x wire format vs fp32: {cb}"
    assert cb.get("reduction", 0) >= 1.9, \
        f"{p}: comm reduction below the real-dtype floor: {cb}"
    prec = rep.get("precision") or {}
    assert "error" not in prec and prec.get("active_policy"), \
        f"{p}: precision block missing: {prec}"
    co = prog.get("comm_overlap") or {}
    assert "error" not in co and co.get("total_bytes"), \
        f"{p}: comm_overlap block missing: {co}"
    for mode, e in co["modes"].items():
        assert e["exposed_bytes"] <= e["total_bytes"] + 1e-6, \
            f"{p}: {mode} exposed > total: {e}"
        assert e["all_at_end_exposed_bytes"] == e["total_bytes"], \
            f"{p}: {mode} single-barrier baseline broken: {e}"
svu = json.load(open(os.path.join(out, "cost_transformer.json")))
co = svu["program"]["comm_overlap"]
assert co["overlapped_bytes"] > 0, \
    f"transformer bucketed exchange hides no bytes: {co}"
assert co["exposed_bytes"] < co["modes"]["dense"]["all_at_end_exposed_bytes"], \
    f"bucketing does not beat the single-barrier baseline: {co}"
# the acceptance bar names BOTH headline shapes: the resnet (graph
# container, conv bucket plan) must beat the single-barrier baseline too
rco = json.load(open(os.path.join(out, "cost_resnet50.json")))[
    "program"]["comm_overlap"]
assert rco["overlapped_bytes"] > 0, \
    f"resnet bucketed exchange hides no bytes: {rco}"
assert rco["exposed_bytes"] < \
    rco["modes"]["dense"]["all_at_end_exposed_bytes"], \
    f"resnet bucketing does not beat the single-barrier baseline: {rco}"
assert svu["scan_vs_unrolled"]["eqn_reduction"] >= 3.0, \
    svu["scan_vs_unrolled"]
assert svu["remat_compare"]["full"]["temp_reduction"] > 1.0, \
    svu["remat_compare"]
# mixed-precision evidence: bf16 activation/wire bytes strictly below
# fp32 on the transformer AND resnet programs (docs/PRECISION.md)
for name in ("cost_transformer.json", "cost_resnet50.json"):
    prec = json.load(open(os.path.join(out, name)))["precision"]
    assert prec["mixed_bf16"]["bytes_per_step"] < \
        prec["float32"]["bytes_per_step"], f"{name}: {prec}"
    assert prec["mixed_bf16"]["wire_bytes_dense"] < \
        prec["float32"]["wire_bytes_dense"], f"{name}: {prec}"
    assert prec["wire_reduction"] >= 1.9, f"{name}: {prec}"
tprec = json.load(open(os.path.join(out, "cost_transformer.json")))[
    "precision"]
print("AOT cost smoke OK "
      f"(eqn_reduction={svu['scan_vs_unrolled']['eqn_reduction']}x, "
      f"remat full temp_reduction="
      f"{svu['remat_compare']['full']['temp_reduction']}x, "
      f"transformer overlapped_bytes={co['overlapped_bytes']:.0f}, "
      f"precision bytes_reduction={tprec['bytes_reduction']}x)")
EOF
hlo_rc=$?
rm -rf "$hlo_out"

echo "== [5/19] gradient-sharing smoke (dense vs threshold) =="
JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=4" \
    timeout -k 10 300 python - <<'PYEOF'
import numpy as np

from deeplearning4j_tpu.common.updaters import Adam
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.parallel.mesh import device_mesh
from deeplearning4j_tpu.parallel.trainer import ParallelTrainer


def build():
    b = NeuralNetConfiguration.builder().seed(7).updater(Adam(0.01)).list()
    for _ in range(4):
        b = b.layer(DenseLayer(n_in=16, n_out=16, activation="tanh"))
    return MultiLayerNetwork(
        (b.layer(OutputLayer(n_in=16, n_out=4, activation="softmax",
                             loss="mcxent"))
          .set_input_type(InputType.feed_forward(16)).build())).init()


rng = np.random.default_rng(0)
B = 32
x = rng.standard_normal((B * 10, 16)).astype(np.float32)
w = rng.standard_normal((16, 4))
y = np.eye(4, dtype=np.float32)[np.argmax(x @ w, axis=1)]
ds = DataSet(x, y)

dense = build()
ParallelTrainer(dense, device_mesh(), mode="sync").fit(
    x, y, epochs=5, batch_size=B)                       # 50 steps
thr = build()
ParallelTrainer(thr, device_mesh(), mode="sync",
                gradient_sharing="threshold").fit(
    x, y, epochs=5, batch_size=B)

d, t = float(dense.score(ds)), float(thr.score(ds))
init = float(build().score(ds))
assert d < init * 0.5, f"dense failed to learn: {init} -> {d}"
assert t < init * 0.5, f"threshold failed to learn: {init} -> {t}"
# error-feedback convergence guarantee: within tolerance of dense
assert abs(t - d) <= 0.35 * init, \
    f"threshold diverged from dense: dense={d} thr={t} init={init}"

# ZeRO smoke: dense_rs (reduce-scatter + data-axis-sharded updater +
# all-gather) must track bucketed dense to fp32 rounding on the 4-way
# mesh (min_shard_elems=1 so the tiny net's 16-wide leaves shard). The
# sums are the same; from the second Adam step XLA:CPU's FMA-contraction
# choice follows the updater's operand SHAPE (full leaf vs shard), a
# <= 1-ulp difference per step (tests/test_gradient_sharing.py pins the
# first step bit-exact) — 50 steps stay far inside 1e-5.
import jax
from deeplearning4j_tpu.parallel.tensor import fsdp_param_specs
rs = build()
ParallelTrainer(rs, device_mesh(), mode="sync",
                gradient_sharing="dense_rs",
                rs_param_specs=fsdp_param_specs(
                    rs, axis_size=4, min_shard_elems=1)).fit(
    x, y, epochs=5, batch_size=B)
drift = max(
    float(np.abs(np.asarray(a) - np.asarray(b)).max())
    for a, b in zip(jax.tree_util.tree_leaves(dense.params),
                    jax.tree_util.tree_leaves(rs.params)))
assert drift < 1e-5, f"dense_rs left bucketed dense: max |dp| {drift:.2e}"
print(f"gradient-sharing smoke OK (init={init:.3f} dense={d:.3f} "
      f"threshold={t:.3f} dense_rs max|dp|={drift:.1e})")
PYEOF
gs_rc=$?

echo "== [6/19] fault-drill smoke (kill@15 + auto-resume, bit parity) =="
# train 30 steps on a tiny MLP in a child process, SIGTERM at step 15
# (async checkpoint every 5, atomic tmp+fsync+rename commits), auto-
# resume from the newest valid checkpoint, and require the final
# params/updater state BIT-identical to an uninterrupted 30-step run
# (docs/FAULT_TOLERANCE.md). CPU-forced; subprocess kills are real.
JAX_PLATFORMS=cpu timeout -k 10 300 python scripts/fault_drill.py --smoke
drill_rc=$?

echo "== [7/19] mixed-precision smoke (bf16 trajectory, fp32 masters) =="
JAX_PLATFORMS=cpu timeout -k 10 300 python - <<'PYEOF'
import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.common.updaters import Adam
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork


def build(policy=None):
    b = NeuralNetConfiguration.builder().seed(7).updater(Adam(0.01))
    if policy is not None:
        b = b.dtype_policy(policy)
    b = b.list()
    for _ in range(4):
        b = b.layer(DenseLayer(n_in=16, n_out=16, activation="tanh"))
    return MultiLayerNetwork(
        (b.layer(OutputLayer(n_in=16, n_out=4, activation="softmax",
                             loss="mcxent"))
          .set_input_type(InputType.feed_forward(16)).build())).init()


rng = np.random.default_rng(0)
x = rng.standard_normal((320, 16)).astype(np.float32)
w = rng.standard_normal((16, 4))
y = np.eye(4, dtype=np.float32)[np.argmax(x @ w, axis=1)]
ds = DataSet(x, y)
init = float(build().score(ds))

fp = build()
fp.fit(x, y, epochs=5, batch_size=32, shuffle=False)
bf = build("mixed_bf16")
bf.fit(x, y, epochs=5, batch_size=32, shuffle=False)
d, b = float(fp.score(ds)), float(bf.score(ds))
assert d < 0.5 * init, f"fp32 failed to learn: {init} -> {d}"
assert b < 0.5 * init, f"bf16 failed to learn: {init} -> {b}"
# documented tolerance band (docs/PRECISION.md): |Δloss| <= 5% of init
assert abs(b - d) <= 0.05 * init, \
    f"bf16 trajectory outside tolerance: init={init} fp32={d} bf16={b}"
# fp32 master contract: params/updater state never leave fp32
for leaf in jax.tree_util.tree_leaves(bf.params):
    assert leaf.dtype == jnp.float32
for leaf in jax.tree_util.tree_leaves(bf.updater_state):
    assert leaf.dtype == jnp.float32

print(f"mixed-precision smoke OK (init={init:.3f} fp32={d:.3f} "
      f"bf16={b:.3f})")
PYEOF
mp_rc=$?

echo "== [8/19] diagnostics smoke (watchdog drill + real UI feed) =="
JAX_PLATFORMS=cpu timeout -k 10 300 python - <<'PYEOF'
import urllib.request

import jax
import numpy as np

from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.common.updaters import Sgd
from deeplearning4j_tpu.monitor.diagnostics import NonFiniteGradientsError
from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.ui import UIServer
from deeplearning4j_tpu.ui.stats import StatsListener
from deeplearning4j_tpu.ui.storage import InMemoryStatsStorage
from deeplearning4j_tpu.common.schedules import MapSchedule

monitor.enable()


def build(watchdog, lr):
    # lr spike at iteration 5: an inf-scale step turns finite
    # gradients into a non-finite update (the silent numeric failure
    # mode arXiv:2606.15870 names; the watchdog's job). `skip` must
    # discard exactly that step and keep training.
    b = (NeuralNetConfiguration.builder().seed(7)
         .updater(Sgd(MapSchedule({0: lr, 5: float("inf"), 6: lr}))))
    lb = b.list()
    for _ in range(3):
        lb = lb.layer(DenseLayer(n_in=16, n_out=16, activation="tanh"))
    return MultiLayerNetwork(
        (lb.layer(OutputLayer(n_in=16, n_out=4, activation="softmax",
                              loss="mcxent"))
           .set_input_type(InputType.feed_forward(16))
           .diagnostics(watchdog).build())).init()


rng = np.random.default_rng(0)
x = rng.standard_normal((320, 16)).astype(np.float32)
w = rng.standard_normal((16, 4))
y = np.eye(4, dtype=np.float32)[np.argmax(x @ w, axis=1)]

from deeplearning4j_tpu.datasets.dataset import DataSet

storage = InMemoryStatsStorage()
net = build("skip", 0.2)
init_score = float(net.score(DataSet(x, y)))
net.set_listeners(StatsListener(storage))
net.fit(x, y, epochs=3, batch_size=32, shuffle=False)   # 30 steps
finite = all(np.isfinite(np.asarray(l)).all()
             for l in jax.tree_util.tree_leaves(net.params))
assert finite, "skip policy let non-finite values into the params"
assert net._diag.skipped_total == 1, \
    f"expected exactly the spike step skipped, got {net._diag.skipped_total}"
final_score = float(net.score(DataSet(x, y)))
assert final_score < 0.7 * init_score, \
    f"training did not recover past the skipped spike: " \
    f"{init_score} -> {final_score}"

reg = monitor.registry()
assert reg.counter("watchdog_nonfinite_total").value >= 1
assert reg.counter("watchdog_skipped_total").value >= 1

# halt must raise a NAMED exception carrying the offending layer keys
try:
    build("halt", 0.2).fit(x, y, epochs=1, batch_size=32, shuffle=False)
    raise SystemExit("halt policy did not raise")
except NonFiniteGradientsError as e:
    assert e.layer_keys, e

server = UIServer().start()
try:
    server.attach(storage)
    base = f"http://127.0.0.1:{server.port}"
    html = urllib.request.urlopen(base + "/train/overview",
                                  timeout=10).read().decode()
    assert "training health" in html and "mean |grad|" in html, html[:400]
    mtext = urllib.request.urlopen(base + "/metrics",
                                   timeout=10).read().decode()
    for fam in ("training_update_ratio", "training_grad_l2",
                "watchdog_nonfinite_total"):
        assert fam in mtext, f"{fam} missing from /metrics"
finally:
    server.stop()
print(f"diagnostics smoke OK (skipped={net._diag.skipped_total}, "
      f"nonfinite={net._diag.nonfinite_total}, halt raised, "
      f"/train + /metrics serve real stats)")
PYEOF
diag_rc=$?

echo "== [9/19] serving smoke (continuous batching, parity + SLO shed) =="
serving_out=$(mktemp /tmp/_serving_smoke_XXXX.json)
# --skip-fleet: the fleet tier gets its own dedicated [12/19] smoke —
# running it twice would double the warmup-grid compile cost
JAX_PLATFORMS=cpu timeout -k 10 420 \
    python scripts/serve_loadtest.py --smoke --skip-fleet \
    --out "$serving_out"
serving_rc=$?

echo "== [10/19] quantized-serving gate (ledger + compare_bench) =="
# the smoke ledger [9/19] just wrote carries the quantized / mixed-
# length / incremental-allocation phase: re-assert the three levers'
# evidence HERE (independent of the loadtest's own exit code) and
# prove compare_bench gates them — including the structural near-zero
# band that catches a silent fp-weight fallback.
SERVING_SMOKE_OUT="$serving_out" JAX_PLATFORMS=cpu python - <<'EOF'
import json
import os

from deeplearning4j_tpu.bench import compare_bench

with open(os.environ["SERVING_SMOKE_OUT"]) as f:
    rec = json.load(f)
q = rec["extras"]["serving_mixed_quantized"]
s = rec["extras"]["serving"]
# greedy parity asserts: fp phase vs generate(), quantized phase vs
# generate(quantize="int8") — both must be exact
assert s["greedy_parity"] == "exact", s
assert q["greedy_parity_vs_quantized_generate"] == "exact", q
# mixed-length wave admission really happened (>= 2 distinct prompt
# lengths through one server)
assert len(set(rec["config"]["mixed_prompt_lens"])) >= 2, rec["config"]
# incremental-grant concurrency: >= 2x the up-front baseline at the
# same pool size (the ISSUE 10 acceptance bar)
assert q["admitted_incremental"] >= 2 * q["admitted_upfront"], q
# int8 weight bytes actually shrank (smoke-model floor 2.5x; the
# committed full-config ledger holds the 3.5x bar)
assert q["weight_bytes_reduction"] >= 2.5, q
# compare_bench gates the new entries: identical record passes...
assert compare_bench(rec, rec)["status"] == "pass"
# ...a silent fp fallback (structural reduction ~1.0) gates
bad = json.loads(json.dumps(rec))
bad["extras"]["serving_mixed_quantized"]["weight_bytes_reduction"] = 1.0
v = compare_bench(bad, rec)
assert v["status"] == "regression" and any(
    r["metric"] == "serving_quantized_weight_bytes_reduction"
    for r in v["regressions"]), v
# ...and a TTFT blow-up gates through the lower-is-better inversion
slow = json.loads(json.dumps(rec))
slow["extras"]["serving_mixed_quantized"]["p50_ttft_ms"] = \
    q["p50_ttft_ms"] * 10.0
v = compare_bench(slow, rec)
assert v["status"] == "regression" and any(
    r["metric"] == "serving_mixed_p50_ttft_ms"
    for r in v["regressions"]), v
# fleet gate wiring (the committed ledger carries the real block; the
# live fleet drill runs in [12/19]): a sustained-concurrency collapse
# gates through the structural band, a swap-window TTFT RISE gates
# through the lower-is-better inversion
fl = {"platform": "cpu-sandbox", "value": 1.0,
      "extras": {"serving_fleet": {"streams_sustained": 10240,
                                   "swap_p99_ttft_ms": 250.0}}}
bad = json.loads(json.dumps(fl))
bad["extras"]["serving_fleet"]["streams_sustained"] = 5000
v = compare_bench(bad, fl)
assert v["status"] == "regression" and any(
    r["metric"] == "fleet_streams_sustained"
    for r in v["regressions"]), v
slow = json.loads(json.dumps(fl))
slow["extras"]["serving_fleet"]["swap_p99_ttft_ms"] = 2500.0
v = compare_bench(slow, fl)
assert v["status"] == "regression" and any(
    r["metric"] == "fleet_swap_p99_ttft_ms"
    for r in v["regressions"]), v
print(f"quantized-serving gate OK (parity exact, "
      f"weight reduction {q['weight_bytes_reduction']}x, "
      f"admits {q['admitted_incremental']} vs "
      f"{q['admitted_upfront']} upfront, "
      f"mixed lens {rec['config']['mixed_prompt_lens']})")
EOF
qgate_rc=$?
rm -f "$serving_out"

echo "== [11/19] elastic-drill smoke (SIGKILL shrink + grow, membership) =="
# 4 gloo worker processes under the membership coordinator; SIGKILL
# one at step ~15 (shrink to a re-formed 3-process mesh, resumed from
# the newest valid checkpoint with re-sharded threshold residual/τ),
# re-add it once the fleet passes step ~20 (grow back to 4). The
# drill's own verdict asserts trajectory parity vs the uninterrupted
# 4-replica reference, >=3 membership generations, cross-worker final-
# param bit-equality, and the elastic_* gauges on /metrics.
JAX_PLATFORMS=cpu timeout -k 10 560 \
    python scripts/fault_drill.py --elastic-smoke
elastic_rc=$?

echo "== [12/19] fleet smoke (registry, hot-swap, router, autoscale) =="
# two tiny models published into the registry, 128+ streams through
# the router, mid-run hot-swap of alpha (warmed successor -> pointer
# flip -> incumbent drain): zero dropped streams, version-tagged
# greedy parity, post-swap p99 TTFT bounded, gauge-driven autoscale of
# the undersized beta, fleet_*/registry_* families on /metrics.
JAX_PLATFORMS=cpu timeout -k 10 560 \
    python scripts/serve_loadtest.py --fleet-smoke
fleet_rc=$?

echo "== [13/19] online-learning smoke (firehose train -> publish -> hot-swap) =="
# TransformerLM continuously fine-tuning from a local firehose
# (StreamingDataSetIterator over LocalLogTransport) while a
# FleetServer hot-swaps to each published snapshot under live decode
# traffic. Hard asserts inside the script: >=2 registry publishes
# (cadence + off-cadence final), >=1 hot-swap with streams in flight
# at the pointer flip, ZERO dropped streams, version-tagged greedy
# parity for every stream, the drift gate trips on the injected
# label-shuffle segment (publishing pauses, training continues) and
# publishing resumes after the held-out score recovers, and the
# streaming_*/online_* families + /train staleness row are live
# (docs/STREAMING_TRAINING.md).
JAX_PLATFORMS=cpu timeout -k 10 560 \
    python scripts/online_loop.py --smoke
online_rc=$?

echo "== [14/19] speculative + shared-prefix CoW smoke (parity, accept, gates) =="
# Draft-accept speculative decoding + copy-on-write shared-prefix
# block reuse (docs/SERVING.md). Hard asserts inside the script:
# speculative greedy BIT-equal to vanilla greedy (the acceptance
# oracle is the target's own argmax), accept rate > 0 with >= 2x
# tok/s over the non-speculative J=1 baseline on the trained-cyclic
# acceptance-friendly workload, shared-prefix streams bit-equal to
# BOTH whole-batch generate() and the private-block run, prefill
# reduction >= 2x, compare_bench gates
# serving_speculative_tokens_per_sec +
# serving_prefix_prefill_reduction (structural band — a silent
# fall-back to private blocks reports ~1.0 and gates), and the
# serving_spec_*/serving_prefix_* families are live on /metrics.
JAX_PLATFORMS=cpu timeout -k 10 420 \
    python scripts/serve_loadtest.py --spec-smoke
spec_rc=$?

echo "== [15/19] trace/observability smoke (request traces, SLO burn, flight dump, federation) =="
# The observability request plane end to end (docs/OBSERVABILITY.md):
# >= 64 routed requests each leaving a finished RequestTrace with
# monotonic queued -> prefill -> decode phase stamps, a two-objective
# SLO fleet driving BOTH the good and bad counters non-zero, a
# mid-run hot-swap captured in a flight-recorder dump, and a
# two-worker federated /metrics scrape carrying worker= labels —
# with every stream still bit-equal to its served version's
# reference (tracing must not perturb tokens).
JAX_PLATFORMS=cpu timeout -k 10 420 \
    python scripts/serve_loadtest.py --trace-smoke
trace_rc=$?

echo "== [16/19] alert + goodput smoke (rule pack, ledger conservation, /alerts) =="
# The alert engine + goodput ledger end to end (docs/OBSERVABILITY.md
# "Alert engine" / "Goodput ledger"): the default rule pack evaluated
# clean against a healthy two-worker aggregator, shed-growth firing
# under a deliberate overload burst and resolving on quiescence,
# worker-vanished firing when a worker drops from the federated
# scrape and resolving on re-publish, every transition in the
# flight-recorder dump, a warmed server's ledger conserved with
# goodput fraction strictly inside (0, 1), the
# serving_tokens_*/serving_goodput_fraction/alert_state families +
# the /alerts route live on one UI server, and compare_bench gating
# an injected goodput regression.
JAX_PLATFORMS=cpu timeout -k 10 420 \
    python scripts/serve_loadtest.py --alert-smoke
alert_rc=$?

echo "== [17/19] sampled-spec + truncated-drafter + radix smoke (chi-square, accept, dedup, gates) =="
# Rejection-sampled speculation + truncated-layer drafter + radix
# prefix cache (docs/SERVING.md). Hard asserts inside the script:
# greedy-subset streams BIT-equal to vanilla generate() under
# spec_sampled=True (the argmax oracle is untouched), sampled-spec
# tok/s >= 1.3x the vanilla sampled baseline at matched
# steps_per_dispatch=1, first-token marginals between the arms pass a
# two-sample chi-square at the 1e-4 critical value (the
# distributional parity contract), the truncated-layer drafter
# accepts > 0 on the run-length-noise workload where the n-gram
# proposer's EWMA collapses, radix auto-dedup reaches >= 2x prefill
# reduction with ZERO register_prefix calls and evicts under pool
# pressure, every phase's goodput ledger conserved, compare_bench
# gates serving_sampled_spec_tokens_per_sec +
# serving_truncated_draft_truncated_accept_rate +
# serving_radix_prefill_reduction (structural band), and the
# serving_radix_* + per-proposer serving_spec_* families are live on
# /metrics.
JAX_PLATFORMS=cpu timeout -k 10 560 \
    python scripts/serve_loadtest.py --sampled-spec-smoke
sspec_rc=$?

echo "== [18/19] replicated-serving smoke (2-process fleet, balance, kill drill, disagg) =="
# Horizontal serving (docs/SERVING.md "Horizontal serving"): a
# 2-subprocess replica fleet registered through the elastic
# coordinator, floods routed by the FleetRouter's least-loaded
# balancing. Hard asserts inside the script: greedy parity vs
# single-process generate() on both arms, aggregate tok/s >= 1.7x
# from 1 -> 2 replicas under the emulated device-step floor (the
# serving plane must not serialize the fleet — see run_replicated's
# sandbox_model note), a hard SIGKILL of one replica mid-flood drops
# ZERO accepted streams (migrated continuations bit-equal, router
# converges to the survivor set), disaggregated prefill->decode DLFP
# handoff bit-equal to the colocated path, and per-replica
# serving_replica_* gauges federated through the coordinator
# heartbeats into one aggregated snapshot.
JAX_PLATFORMS=cpu timeout -k 10 560 \
    python scripts/serve_loadtest.py --replica-smoke
replica_rc=$?

echo "== [19/19] multi-tenant smoke (adapter deltas, shared base, fair-share) =="
# Multi-tenant continuous learning (docs/SERVING.md "Multi-tenant"):
# 3 tenants train LoRA adapters on their own online streams against
# ONE frozen shared base, publish delta-only artifacts (< 5% of the
# full zip) and hot-swap them into a TenantFleet under live traffic.
# Hard asserts inside the script: shared_base_copies == 1, the base
# params bit-identical after all adapter training, zero dropped
# streams across mid-traffic swaps with version-tagged greedy parity
# (>= 2 adapter versions served per tenant), the drifted tenant's
# gate trips + pauses publishes while the others keep publishing, a
# cursor()/seek() membership change mid-consumption loses/replays no
# batch, the 10:1 fair-share flood holds the light tenant's floor
# while the heavy tenant absorbs the shedding, tenant-labeled
# fleet_tenant_* + adapter-publish families live on /metrics, and
# compare_bench gates the tenant_* metrics.
JAX_PLATFORMS=cpu timeout -k 10 560 \
    python scripts/tenant_loadtest.py --smoke --out /tmp/tenant_smoke.json
tenant_rc=$?

echo "tier1_rc=${tier1_rc} metrics_smoke_rc=${smoke_rc} hlo_run_rc=${hlo_run_rc} hlo_smoke_rc=${hlo_rc} gs_rc=${gs_rc} drill_rc=${drill_rc} mp_rc=${mp_rc} diag_rc=${diag_rc} serving_rc=${serving_rc} qgate_rc=${qgate_rc} elastic_rc=${elastic_rc} fleet_rc=${fleet_rc} online_rc=${online_rc} spec_rc=${spec_rc} trace_rc=${trace_rc} alert_rc=${alert_rc} sspec_rc=${sspec_rc} replica_rc=${replica_rc} tenant_rc=${tenant_rc}"
if [ "$tier1_rc" -ne 0 ] || [ "$smoke_rc" -ne 0 ] || [ "$hlo_run_rc" -ne 0 ] || [ "$hlo_rc" -ne 0 ] || [ "$gs_rc" -ne 0 ] || [ "$drill_rc" -ne 0 ] || [ "$mp_rc" -ne 0 ] || [ "$diag_rc" -ne 0 ] || [ "$serving_rc" -ne 0 ] || [ "$qgate_rc" -ne 0 ] || [ "$elastic_rc" -ne 0 ] || [ "$fleet_rc" -ne 0 ] || [ "$online_rc" -ne 0 ] || [ "$spec_rc" -ne 0 ] || [ "$trace_rc" -ne 0 ] || [ "$alert_rc" -ne 0 ] || [ "$sspec_rc" -ne 0 ] || [ "$replica_rc" -ne 0 ] || [ "$tenant_rc" -ne 0 ]; then
    exit 1
fi
echo "VERIFY OK"
