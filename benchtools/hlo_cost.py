"""Device-free AOT HLO cost analysis of the headline bench configs.

Counts, not times: this tool needs no accelerator. It AOT-lowers the
**exact** jitted train-step each headline bench config dispatches
(`net.lower_train_step` — the same `lax.scan`-fused multi-step
`fit(steps_per_execution=k)` and `bench.py` run), then

- runs XLA's cost analysis on the lowered module
  (`jax.stages.Lowered.cost_analysis()` — no backend compile, works on
  any CPU-only host),
- walks the train-step jaxpr primitive-by-primitive for a per-op
  FLOP/byte table (conv/dot counted exactly at 2 FLOPs/MAC — the same
  accounting `bench._count_math_flops` uses for the published MFU —
  everything else estimated at ~1 FLOP/element; `lax.scan` bodies are
  multiplied by their trip count, which XLA's own analysis does NOT do,
  so LSTM-style inner time loops are counted correctly here),
- derives a roofline model (`monitor.xprof.roofline`) against the
  PUBLISHED peaks of the device the prediction is made for
  (`TARGET_DEVICE_KIND`, looked up in the repo's one peaks table,
  `bench.DEVICE_PEAKS`): arithmetic intensity, predicted step time,
  predicted MFU — falsifiable predictions a chip run confirms or
  refutes, never measurements.

Artifacts: ``<out>/cost_<model>.json`` (default ``PROFILE_aot/``), a
``aot_cost_*{model=}`` gauge set on the monitor registry (served by
``/metrics``), and an in-process cost-report store rendered by the
UIServer's ``/profile`` route.

Usage::

    python -m benchtools.hlo_cost --model resnet50          # one config
    python -m benchtools.hlo_cost --all                     # all four
    python -m benchtools.hlo_cost --model lenet --batch 8 --steps 2

Caveats recorded in every artifact: bytes-accessed figures come from
unoptimized HLO (fusion elides intermediate traffic), so the memory
ceiling is an upper bound on step time and the roofline MFU a lower
bound; `mfu_if_compute_bound` is the matching upper bound. Flash
attention only rides the TPU backend, so transformer lowerings on a
CPU host show the XLA attention fallback (same matmul FLOPs, different
memory traffic).
"""

from __future__ import annotations

import argparse
import heapq
import json
import os
import sys
import time
from typing import Dict, List, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# The device these device-free predictions are made FOR: the chip this
# repo measures on. Its published peaks come from the repo's one table
# (bench.DEVICE_PEAKS, keyed by the device_kind JAX reports there); the
# --peak-tflops / --hbm-gbps / --ici-gbps flags override single values.
TARGET_DEVICE_KIND = "TPU v5 lite"

# ------------------------------------------------------ per-eqn cost model
_ZERO_FLOP = frozenset((
    "reshape", "broadcast_in_dim", "transpose", "slice", "squeeze",
    "concatenate", "pad", "rev", "iota", "convert_element_type",
    "bitcast_convert_type", "copy", "stop_gradient", "device_put",
    "gather", "dynamic_slice", "dynamic_update_slice", "split",
    "expand_dims", "real", "imag",
))


def _nelems(shape) -> float:
    n = 1.0
    for s in shape:
        n *= int(s)
    return n


def _aval_nbytes(aval) -> float:
    try:
        return _nelems(aval.shape) * aval.dtype.itemsize
    except (AttributeError, TypeError):
        return 0.0


def _conv_flops(eqn) -> float:
    """2 FLOPs/MAC conv count — same formula as bench._count_math_flops
    (rhs I-dim is already cin/groups, so no group adjustment)."""
    out = eqn.outvars[0].aval.shape
    rhs = eqn.invars[1].aval.shape
    dn = eqn.params["dimension_numbers"]
    kspatial = 1
    for d in dn.rhs_spec[2:]:
        kspatial *= rhs[d]
    cin = rhs[dn.rhs_spec[1]]
    return 2.0 * _nelems(out) * kspatial * cin


def _dot_flops(eqn) -> float:
    a = eqn.invars[0].aval.shape
    b = eqn.invars[1].aval.shape
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    m = 1
    for i, s in enumerate(a):
        if i not in lc and i not in lb:
            m *= s
    n = 1
    for i, s in enumerate(b):
        if i not in rc and i not in rb:
            n *= s
    k = 1
    for i in lc:
        k *= a[i]
    bsz = 1
    for i in lb:
        bsz *= a[i]
    return 2.0 * bsz * m * n * k


def eqn_flops(eqn) -> float:
    """FLOP estimate for one jaxpr equation. conv/dot are exact
    (2 FLOPs/MAC — the accounting the published MFU uses); reductions
    count ~1 FLOP per input element; data movement counts zero;
    everything else (elementwise, transcendentals, RNG) counts ~1 FLOP
    per output element. The estimates are <2% of a conv/matmul net's
    budget — the exact terms dominate."""
    name = eqn.primitive.name
    if name == "conv_general_dilated":
        return _conv_flops(eqn)
    if name == "dot_general":
        return _dot_flops(eqn)
    if name in _ZERO_FLOP:
        return 0.0
    if (name.startswith("reduce_") or name in ("reduce", "argmax", "argmin")
            or name in ("reduce_window", "select_and_scatter_add")):
        return sum(_nelems(v.aval.shape) for v in eqn.invars
                   if hasattr(v.aval, "shape"))
    if name.startswith("scatter"):
        return _nelems(eqn.invars[-1].aval.shape)
    return sum(_nelems(v.aval.shape) for v in eqn.outvars
               if hasattr(v.aval, "shape"))


def eqn_bytes(eqn) -> float:
    """Operand + result bytes of one equation — unfused-HLO traffic,
    an upper bound on what a fusing compiler actually moves."""
    return (sum(_aval_nbytes(v.aval) for v in eqn.invars
                if hasattr(v, "aval"))
            + sum(_aval_nbytes(v.aval) for v in eqn.outvars
                  if hasattr(v, "aval")))


def _sub_jaxprs(eqn):
    subs = []
    for p in eqn.params.values():
        for s in (p if isinstance(p, (list, tuple)) else (p,)):
            inner = getattr(s, "jaxpr", None)
            if inner is not None and hasattr(inner, "eqns"):
                subs.append(inner)
            elif hasattr(s, "eqns"):
                subs.append(s)
    return subs


def _shape_sig(eqn) -> str:
    def one(v):
        aval = getattr(v, "aval", None)
        if aval is None or not hasattr(aval, "shape"):
            return "?"
        dt = getattr(aval.dtype, "name", str(aval.dtype))
        return f"{dt}{list(aval.shape)}"
    ins = ",".join(one(v) for v in eqn.invars[:3])
    if len(eqn.invars) > 3:
        ins += ",..."
    outs = ",".join(one(v) for v in eqn.outvars[:2])
    return f"{ins} -> {outs}"


def _walk(jaxpr, mult: int, by_prim: Dict[str, dict], sites: List[dict],
          flags: Dict[str, bool], comm: Optional[Dict[str, dict]] = None):
    for eqn in jaxpr.eqns:
        subs = _sub_jaxprs(eqn)
        if subs:
            name = eqn.primitive.name
            m = mult
            if name == "scan":
                m = mult * int(eqn.params.get("length", 1) or 1)
            elif name == "while":
                # trip count is data-dependent: body charged once
                flags["while_counted_once"] = True
            elif name == "cond":
                # every branch charged once (only one executes)
                flags["cond_branches_summed"] = True
            for s in subs:
                _walk(s, m, by_prim, sites, flags, comm)
            continue
        f = eqn_flops(eqn) * mult
        b = eqn_bytes(eqn) * mult
        name = eqn.primitive.name
        rec = by_prim.setdefault(
            name, {"op": name, "count": 0, "flops": 0.0, "bytes": 0.0})
        rec["count"] += mult
        rec["flops"] += f
        rec["bytes"] += b
        sites.append({"op": name, "flops": f, "bytes": b,
                      "shape": _shape_sig(eqn)})
        if comm is not None:
            kind = _COLLECTIVE_KINDS.get(name)
            if kind is not None:
                payload = sum(_aval_nbytes(v.aval) for v in eqn.invars
                              if hasattr(v, "aval")) * mult
                crec = comm.setdefault(kind, {"count": 0, "bytes": 0.0})
                crec["count"] += mult
                crec["bytes"] += payload


# jaxpr-level collective primitives → report kind. GSPMD-inserted
# collectives (dense jit paths) never appear in a jaxpr — only programs
# with EXPLICIT collectives (shard_map: the trainers' threshold
# exchange, the gradient_sharing analysis programs) have entries here.
_COLLECTIVE_KINDS = {
    "psum": "all_reduce", "pmin": "all_reduce", "pmax": "all_reduce",
    "all_gather": "all_gather", "all_gather_invariant": "all_gather",
    "psum_scatter": "reduce_scatter", "reduce_scatter": "reduce_scatter",
    "ppermute": "permute", "pshuffle": "permute",
    "all_to_all": "all_to_all",
}


def _walk_collectives(jaxpr, mult: int, acc: Dict[str, dict]):
    for eqn in jaxpr.eqns:
        subs = _sub_jaxprs(eqn)
        if subs:
            m = mult
            if eqn.primitive.name == "scan":
                m = mult * int(eqn.params.get("length", 1) or 1)
            for s in subs:
                _walk_collectives(s, m, acc)
            continue
        kind = _COLLECTIVE_KINDS.get(eqn.primitive.name)
        if kind is None:
            continue
        b = sum(_aval_nbytes(v.aval) for v in eqn.invars
                if hasattr(v, "aval")) * mult
        rec = acc.setdefault(kind, {"count": 0, "bytes": 0.0})
        rec["count"] += mult
        rec["bytes"] += b


def _format_collectives(acc: Dict[str, dict], fused_steps: int) -> dict:
    k = max(1, int(fused_steps))
    by = {kind: {"count": rec["count"] / k,
                 "bytes_per_step": rec["bytes"] / k}
          for kind, rec in sorted(acc.items())}
    return {
        "comm_bytes_per_step": sum(r["bytes_per_step"] for r in by.values()),
        "by_collective": by,
        "note": ("operand bytes of explicit collectives per optimizer "
                 "step; GSPMD-inserted collectives (dense jit paths) "
                 "are not visible at the jaxpr level"),
    }


def collective_table(closed_jaxpr, *, fused_steps: int = 1) -> dict:
    """Per-collective byte accounting of a jaxpr: operand (payload)
    bytes of every all-reduce / all-gather / reduce-scatter / permute /
    all-to-all, scan bodies multiplied by trip count, figures divided
    by `fused_steps` — the communication counterpart of `per_op_table`
    (comm volume measured and gated like FLOPs already are)."""
    acc: Dict[str, dict] = {}
    _walk_collectives(getattr(closed_jaxpr, "jaxpr", closed_jaxpr), 1, acc)
    return _format_collectives(acc, fused_steps)


def comm_bytes_block(net, *, n_workers: int = 8, axis: str = "data") -> dict:
    """Dense-vs-threshold gradient-exchange payload for THIS model's
    parameter tree: both exchange programs
    (`gradient_sharing.exchange_jaxpr`) are traced over an AbstractMesh
    — no devices, no mesh — and their collectives
    counted by `collective_table`. The committed evidence that the
    threshold wire format moves >= 4x fewer bytes per step. The dense
    program is traced with the model's REAL gradient dtype (the dtype
    policy's compute dtype — bf16 grads under mixed_bf16 halve the
    dense wire)."""
    from deeplearning4j_tpu.parallel import gradient_sharing as gs
    grad_dtype = net.dtype.compute_dtype
    out = {"n_workers": n_workers, "axis": axis,
           "grad_dtype": jnp_dtype_name(grad_dtype),
           "note": ("per-replica all-reduce payload of ONE gradient "
                    "exchange, traced over an AbstractMesh "
                    "(device-free); threshold = int8 sign tensor + "
                    "controller scalars, dense = grad-dtype gradients "
                    "(the dtype policy's compute dtype)")}
    for mode in ("dense", "threshold"):
        jx = gs.exchange_jaxpr(net.params, mode, n_workers, axis=axis,
                               grad_dtype=grad_dtype)
        tbl = collective_table(jx)
        out[mode] = tbl
        out[f"{mode}_bytes_per_step"] = tbl["comm_bytes_per_step"]
    if out.get("threshold_bytes_per_step"):
        out["reduction"] = round(out["dense_bytes_per_step"]
                                 / out["threshold_bytes_per_step"], 2)
        # the PR-4 "4x wire format" claim is int8-vs-FP32; under a
        # mixed policy the real dense wire is already bf16 (2x),
        # so both ratios are recorded
        fp32_dense = gs.exchange_wire_bytes(net.params, "dense")
        out["dense_fp32_bytes_per_step"] = fp32_dense
        out["reduction_vs_fp32"] = round(
            fp32_dense / out["threshold_bytes_per_step"], 2)
    return out


def jnp_dtype_name(dt) -> str:
    import jax.numpy as jnp
    return jnp.dtype(dt).name


def resolve_ici_gbps(ici_gbps: Optional[float] = None,
                     device_kind: str = TARGET_DEVICE_KIND) -> dict:
    """ICI-bandwidth ceiling for the overlap accounting: explicit flag
    > DL4J_ICI_GBPS env (a measured all-reduce bandwidth) > the
    published figure for `device_kind` in `bench.DEVICE_PEAKS` (an
    unknown kind raises). Provenance recorded in the report."""
    if ici_gbps is not None:
        return {"ici_gbps": float(ici_gbps), "ici_source": "--ici-gbps flag"}
    env = os.environ.get("DL4J_ICI_GBPS")
    if env:
        return {"ici_gbps": float(env), "ici_source": "DL4J_ICI_GBPS env"}
    from deeplearning4j_tpu import bench
    return {"ici_gbps": bench.device_peaks(device_kind)["ici_gbps"],
            "ici_source": f"bench.DEVICE_PEAKS[{device_kind!r}]"}


def _overlap_timeline(buckets, peak_flops_s: float, ici_bytes_s: float):
    """Serial-ICI timeline of the bucketed exchange: walking buckets in
    backward ISSUE order (last layer first), bucket i's collective can
    start once its VJP finishes (cumulative backward compute time) and
    once the ICI is free; whatever transfer time extends past the end
    of backward compute is EXPOSED. Returns (exposed_seconds,
    backward_seconds, per-bucket issue table)."""
    t = 0.0
    ici_free = 0.0
    table = []
    for key, bwd_flops, payload in buckets:
        t += bwd_flops / peak_flops_s
        start = max(ici_free, t)
        ici_free = start + payload / ici_bytes_s
        table.append({"bucket": key, "payload_bytes": payload,
                      "backward_flops": bwd_flops,
                      "issue_at_seconds": round(t, 9),
                      "done_at_seconds": round(ici_free, 9)})
    return max(0.0, ici_free - t), t, table


def comm_overlap_block(net, *, backward_flops_per_step: float,
                       peak_tflops: float, n_workers: int = 8,
                       axis: str = "data",
                       ici_gbps: Optional[float] = None,
                       device_kind: str = TARGET_DEVICE_KIND,
                       modes=("dense", "threshold", "dense_rs"),
                       bucket_table: bool = True) -> dict:
    """Exposed vs overlapped comm bytes of the bucketed gradient
    exchange (parallel/gradient_sharing.py) for THIS model — the
    roofline-style prediction that per-run bucketing hides collective
    time behind backward compute, computed device-free.

    Model: buckets (``stacked::`` packed runs + singleton layers, from
    `gradient_sharing.bucket_plan`) issue their collectives in backward
    order; each bucket's payload is its share of the mode's wire bytes
    (`exchange_wire_bytes` on the bucket's sub-tree) and each bucket's
    backward compute budget is the step's backward FLOPs attributed
    proportionally to parameter count (exact for homogeneous stacks,
    an estimate across heterogeneous layers — recorded in the note).
    The single-barrier (PR-4) baseline exposes EVERY byte:
    ``all_at_end_exposed_bytes == total_bytes``, so
    ``exposed_bytes < all_at_end_exposed_bytes`` is the committed
    overlap win."""
    import jax

    import numpy as np

    from deeplearning4j_tpu.parallel import gradient_sharing as gs

    ici = resolve_ici_gbps(ici_gbps, device_kind)
    bw = ici["ici_gbps"] * 1e9
    peak_fs = peak_tflops * 1e12
    plan = gs.bucket_plan(net)
    params = net.params
    grad_dtype = net.dtype.compute_dtype
    total_elems = sum(float(np.prod(np.shape(l)))
                      for l in jax.tree_util.tree_leaves(params))
    rs_plan = gs.rs_shard_plan(params, n_workers)

    out = {
        "n_workers": n_workers,
        "axis": axis,
        "buckets": len(plan),
        "backward_flops_per_step": backward_flops_per_step,
        "peak_tflops": peak_tflops,
        **ici,
        "note": ("bucket = stacked:: packed run or singleton layer; "
                 "collectives issued in backward order against a "
                 "serial-ICI timeline; backward FLOPs attributed to "
                 "buckets by parameter count; payloads = "
                 "exchange_wire_bytes per bucket sub-tree; "
                 "all_at_end_exposed_bytes is the PR-4 single-barrier "
                 "baseline (everything exposed)"),
        "modes": {},
    }
    for mode in modes:
        buckets = []
        for key, members in reversed(plan):
            sub = {m: params[m] for m in members}
            sub_elems = sum(float(np.prod(np.shape(l)))
                            for l in jax.tree_util.tree_leaves(sub))
            payload = gs.exchange_wire_bytes(
                sub, mode, n_workers=n_workers,
                rs_plan={m: rs_plan[m] for m in members}
                if mode in gs.RS_MODES else None,
                grad_dtype=grad_dtype)
            bwd = backward_flops_per_step * (sub_elems
                                             / max(total_elems, 1.0))
            buckets.append((key, bwd, payload))
        exposed_s, bwd_s, table = _overlap_timeline(buckets, peak_fs, bw)
        total_bytes = sum(b[2] for b in buckets)
        exposed_bytes = min(total_bytes, exposed_s * bw)
        entry = {
            "total_bytes": total_bytes,
            "exposed_bytes": exposed_bytes,
            "overlapped_bytes": total_bytes - exposed_bytes,
            "exposed_fraction": (exposed_bytes / total_bytes
                                 if total_bytes else 0.0),
            "exposed_seconds": exposed_s,
            "backward_seconds": bwd_s,
            "all_at_end_exposed_bytes": total_bytes,
        }
        if bucket_table:
            entry["bucket_table"] = table
        out["modes"][mode] = entry
    # headline figures = the sync trainers' DEFAULT program (bucketed
    # dense) — what the aot_comm_overlap_* gauges serve
    head = out["modes"].get("dense") or next(iter(out["modes"].values()))
    for k in ("total_bytes", "exposed_bytes", "overlapped_bytes",
              "exposed_fraction"):
        out[k] = head[k]
    return out


def count_jaxpr_eqns(jaxpr) -> int:
    """Total equation count of a (closed) jaxpr including nested
    sub-jaxprs, each counted ONCE (no trip-count multiplication) — the
    program-SIZE measure scan-over-layers compilation is judged by,
    complementing the trip-multiplied FLOP tables above."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    n = 0
    for eqn in jaxpr.eqns:
        n += 1
        for sub in _sub_jaxprs(eqn):
            n += count_jaxpr_eqns(sub)
    return n


def compile_program(lowered) -> dict:
    """XLA-compile a lowered train step and record what the compile
    cost: wall seconds, backend-compile seconds + compile count via the
    telemetry core's `JitCompileCollector` (PR-1), and the executable's
    memory analysis (peak temp = activation working set). CPU-safe —
    this is the seam the compile-time regression test and the
    `scripts/verify.sh` smoke build on."""
    from deeplearning4j_tpu.monitor import JitCompileCollector, MetricsRegistry
    coll = JitCompileCollector(MetricsRegistry()).install()
    out = {}
    t0 = time.perf_counter()
    try:
        compiled = lowered.compile()
        out["compile_seconds"] = round(time.perf_counter() - t0, 3)
        out["xla_backend_compile_seconds"] = round(coll.compile_seconds(), 3)
        out["xla_compiles"] = int(coll.compile_count())
        try:
            mem = compiled.memory_analysis()
            for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                         "temp_size_in_bytes",
                         "generated_code_size_in_bytes"):
                try:
                    out[attr] = int(getattr(mem, attr))
                except (AttributeError, TypeError):
                    pass
            if "temp_size_in_bytes" in out:
                # peak temp == XLA's activation/workspace high-water mark
                out["peak_temp_bytes"] = out["temp_size_in_bytes"]
        except Exception as e:  # noqa: BLE001 — per-backend API surface
            out["memory_analysis_error"] = f"{type(e).__name__}: {e}"[:200]
    except Exception as e:  # noqa: BLE001 — a failed compile still reports
        out["error"] = f"{type(e).__name__}: {e}"[:200]
    finally:
        coll.uninstall()
    return out


# deep-stack config for the committed scan-vs-unrolled / remat evidence:
# >= 12 transformer blocks (the acceptance bar), sized so the UNROLLED
# variant still compiles in well under a minute on a CPU host
_DEEP_LM = dict(n_layers=16, d_model=64, n_heads=4, seq_len=128,
                vocab=128, batch=8, steps=2)


def _deep_lm_net(scan_layers: bool, remat_policy=None):
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.zoo.transformer import TransformerLM
    c = _DEEP_LM
    lm = TransformerLM(vocab_size=c["vocab"], d_model=c["d_model"],
                       n_layers=c["n_layers"], n_heads=c["n_heads"],
                       max_len=c["seq_len"], remat_policy=remat_policy)
    conf = lm.conf()
    conf.scan_layers = scan_layers
    net = MultiLayerNetwork(conf).init(123)
    x = jax.ShapeDtypeStruct((c["batch"], c["seq_len"]), jnp.float32)
    y = jax.ShapeDtypeStruct((c["batch"], c["seq_len"], c["vocab"]),
                             jnp.float32)
    return net, x, y, c["steps"]


def _deep_lm_probe(scan_layers: bool, remat_policy=None) -> dict:
    net, x, y, steps = _deep_lm_net(scan_layers, remat_policy)
    jaxpr = net.train_step_jaxpr(x, y, steps=steps)
    rep = {"jaxpr_eqn_count": count_jaxpr_eqns(jaxpr)}
    rep.update(compile_program(net.lower_train_step(x, y, steps=steps)))
    return rep


# memoized per _DEEP_LM config: the evidence blocks are
# model-independent, so `--all --deep-compare` must not re-run the
# 5-compile battery once per report
_DEEP_MEMO: Dict[tuple, dict] = {}


def _deep_memo_key(name: str) -> tuple:
    return (name,) + tuple(sorted(_DEEP_LM.items()))


def scan_vs_unrolled() -> dict:
    """CPU-measured evidence for scan-over-layers on a deep stack: the
    SAME >=12-block TransformerLM train step lowered both ways. The
    scan path must compile fewer equations into a smaller program in
    less time."""
    key = _deep_memo_key("scan_vs_unrolled")
    if key in _DEEP_MEMO:
        return _DEEP_MEMO[key]
    scan = _deep_lm_probe(scan_layers=True)
    unrolled = _deep_lm_probe(scan_layers=False)
    out = {"config": dict(_DEEP_LM), "scan": scan, "unrolled": unrolled}
    if scan.get("jaxpr_eqn_count") and unrolled.get("jaxpr_eqn_count"):
        out["eqn_reduction"] = round(
            unrolled["jaxpr_eqn_count"] / scan["jaxpr_eqn_count"], 2)
    if scan.get("compile_seconds") and unrolled.get("compile_seconds"):
        out["compile_speedup"] = round(
            unrolled["compile_seconds"] / scan["compile_seconds"], 2)
    _DEEP_MEMO[key] = out
    return out


def remat_compare() -> dict:
    """Peak-temp (activation working set) deltas of the generalized
    remat policies on the same deep stack, scan path: `full` trades ~1
    extra forward of FLOPs for an O(depth)->O(1) activation footprint;
    `dots_saveable` keeps matmul outputs and recomputes the rest."""
    key = _deep_memo_key("remat_compare")
    if key in _DEEP_MEMO:
        return _DEEP_MEMO[key]
    base = _deep_lm_probe(scan_layers=True, remat_policy=None)
    out = {"config": dict(_DEEP_LM),
           "none": {k: base.get(k) for k in ("peak_temp_bytes",
                                             "compile_seconds")}}
    for policy in ("full", "dots_saveable"):
        rep = _deep_lm_probe(scan_layers=True, remat_policy=policy)
        entry = {k: rep.get(k) for k in ("peak_temp_bytes",
                                         "compile_seconds")}
        if rep.get("peak_temp_bytes") and base.get("peak_temp_bytes"):
            entry["temp_reduction"] = round(
                base["peak_temp_bytes"] / rep["peak_temp_bytes"], 2)
        out[policy] = entry
    _DEEP_MEMO[key] = out
    return out


def precision_block(model: str, spec: dict, table: dict, *,
                    batch=None, steps=None) -> dict:
    """fp32-vs-bf16 evidence for one headline config: the SAME model
    traced under both dtype policies, per-op bytes/FLOPs per step from
    the jaxpr walk (no XLA compile — the active policy's program
    section already carries compile evidence), plus the dense-exchange
    wire bytes in each policy's real gradient dtype. The committed
    proof that mixed_bf16 strictly shrinks activation and wire traffic
    (and shifts roofline intensity up) on this program."""
    from deeplearning4j_tpu.parallel import gradient_sharing as gs

    active = spec["net"].dtype.name
    other = "float32" if active != "float32" else "mixed_bf16"

    def policy_entry(pol_name, tbl, net):
        b = tbl["total_bytes_per_step"]
        f = tbl["total_flops_per_step"]
        return {
            "policy": pol_name,
            "bytes_per_step": b,
            "flops_per_step": f,
            "arithmetic_intensity_flop_per_byte": f / max(b, 1.0),
            "wire_bytes_dense": gs.exchange_wire_bytes(
                net.params, "dense", grad_dtype=net.dtype.compute_dtype),
        }

    entries = {active: policy_entry(active, table, spec["net"])}
    spec2 = MODELS[model](batch=batch, steps=steps, policy=other)
    jaxpr2 = spec2["net"].train_step_jaxpr(spec2["x"], spec2["y"],
                                           steps=spec2["steps"])
    table2 = per_op_table(jaxpr2, fused_steps=spec2["steps"], top=1)
    entries[other] = policy_entry(other, table2, spec2["net"])

    fp32 = entries.get("float32")
    bf16 = entries.get("mixed_bf16") or entries.get("custom")
    out = {"active_policy": active, **{k: v for k, v in entries.items()}}
    if fp32 and bf16:
        out["bytes_reduction"] = round(
            fp32["bytes_per_step"] / max(bf16["bytes_per_step"], 1.0), 3)
        out["wire_reduction"] = round(
            fp32["wire_bytes_dense"] / max(bf16["wire_bytes_dense"], 1.0),
            3)
        out["intensity_shift"] = round(
            bf16["arithmetic_intensity_flop_per_byte"]
            / max(fp32["arithmetic_intensity_flop_per_byte"], 1e-12), 3)
    out["note"] = ("per-op jaxpr bytes (unfused operand+result traffic) "
                   "per optimizer step under each dtype policy; wire = "
                   "dense gradient-exchange payload in the policy's "
                   "real grad dtype; bf16 programs must move strictly "
                   "fewer bytes (verify.sh [4/7] asserts)")
    return out


def per_op_table(closed_jaxpr, *, fused_steps: int = 1,
                 top: int = 10) -> dict:
    """Per-op cost table for a (fused) train-step jaxpr. `lax.scan`
    bodies are multiplied by trip count, and the program totals divided
    by `fused_steps` (the top-level steps-per-execution scan), so every
    figure is **per optimizer step** — including inner time loops XLA's
    own cost analysis charges only once."""
    by_prim: Dict[str, dict] = {}
    sites: List[dict] = []
    flags: Dict[str, bool] = {}
    comm_acc: Dict[str, dict] = {}
    _walk(closed_jaxpr.jaxpr, 1, by_prim, sites, flags, comm_acc)
    total_f = sum(r["flops"] for r in by_prim.values())
    total_b = sum(r["bytes"] for r in by_prim.values())
    conv_dot = sum(by_prim.get(k, {}).get("flops", 0.0)
                   for k in ("conv_general_dilated", "dot_general"))
    k = max(1, int(fused_steps))
    top_sites = heapq.nlargest(top, sites, key=lambda s: s["flops"])
    denom = max(total_f, 1.0)

    def per_step(rec):
        # EVERY figure in the tables is per optimizer step (the whole-
        # program totals only appear under total_flops/total_bytes) —
        # so table rows are directly comparable to the *_per_step keys
        out = dict(rec)
        out["flops"] = rec["flops"] / k
        out["bytes"] = rec["bytes"] / k
        if "count" in rec:
            out["count"] = rec["count"] / k
        out["share"] = round(rec["flops"] / denom, 4)
        return out
    return {
        "fused_steps": k,
        "total_flops": total_f,
        "total_bytes": total_b,
        # accumulated in the SAME walk as the FLOP/byte tables (a
        # second full-jaxpr traversal measurably doubled
        # jaxpr_walk_seconds on ResNet-50)
        "collectives": _format_collectives(comm_acc, k),
        "total_flops_per_step": total_f / k,
        "total_bytes_per_step": total_b / k,
        "conv_dot_flops_per_step": conv_dot / k,
        "by_primitive": sorted((per_step(r) for r in by_prim.values()),
                               key=lambda r: -r["flops"]),
        "top10": [per_step(s) for s in top_sites],
        "flags": flags,
        "note": ("per-step figures: scan bodies x trip count, divided by "
                 "fused_steps (tables AND totals_per_step); conv/dot "
                 "exact at 2 FLOPs/MAC, other ops ~1 FLOP/element; bytes "
                 "are unfused operand+result traffic (upper bound)"),
    }


# ------------------------------------------------------------ model builders
def _resolve_builder_policy(policy, default="mixed_bf16"):
    """Builder-level policy resolution: an EXPLICIT `policy=` is a
    measurement seam (the precision_block's fp32-vs-bf16 counterfactual
    trace) and must win over the DL4J_DTYPE_POLICY env override —
    otherwise the env A/B would silently trace BOTH sides of the
    comparison under the same policy and the evidence degenerates to
    1.0 ratios. `policy=None` (the CLI default) still honors the env,
    so headline reports remain A/B-able."""
    from deeplearning4j_tpu.nd.dtype import as_policy, env_policy
    if policy is not None:
        return as_policy(policy)
    return env_policy() or as_policy(default)


def _policy_net(conf, policy, seed=123):
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    net = MultiLayerNetwork(conf, dtype_policy=policy)
    # pin the resolved policy past the container's own env-aware
    # resolution (env semantics were already applied above)
    net.dtype = policy
    return net.init(seed)


def build_mlp(batch=None, steps=None, policy=None):
    """Tiny dense net — the golden-test config (not a bench headline)."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    batch, steps = batch or 16, steps or 2
    pol = _resolve_builder_policy(policy, default="float32")
    conf = (NeuralNetConfiguration.builder().seed(0).list()
            .layer(DenseLayer(n_in=4, n_out=8))
            .layer(OutputLayer(n_in=8, n_out=3))
            .build())
    net = _policy_net(conf, pol, seed=conf.seed)
    x = jax.ShapeDtypeStruct((batch, 4), jnp.float32)
    y = jax.ShapeDtypeStruct((batch, 3), jnp.float32)
    return dict(model="mlp", net=net, x=x, y=y, steps=steps,
                examples_per_step=batch, unit="examples/sec",
                config={"batch": batch, "steps": steps,
                        "dtype_policy": pol.name})


def build_lenet(batch=None, steps=None, policy=None):
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.zoo.lenet import LeNet
    batch, steps = batch or 128, steps or 100
    pol = _resolve_builder_policy(policy)
    net = _policy_net(LeNet(num_classes=10).conf(), pol)
    x = jax.ShapeDtypeStruct((batch, 28, 28, 1), jnp.float32)
    y = jax.ShapeDtypeStruct((batch, 10), jnp.float32)
    return dict(model="lenet", net=net, x=x, y=y, steps=steps,
                examples_per_step=batch, unit="images/sec",
                config={"batch": batch, "steps": steps,
                        "dtype_policy": pol.name})


def build_resnet50(batch=None, steps=None, policy=None):
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.common.updaters import Nesterovs
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.zoo.resnet50 import ResNet50
    batch, steps = batch or 128, steps or 20
    pol = _resolve_builder_policy(policy)
    model = ResNet50(num_classes=1000, height=224, width=224, channels=3)
    conf = model.conf()
    # same bench-only lr override bench_resnet50 applies — identical
    # FLOPs, and keeps this lowering byte-for-byte the headline program
    for node in conf.nodes.values():
        if node.layer is not None and getattr(node.layer, "updater",
                                              None) is not None:
            node.layer.updater = Nesterovs(0.005, 0.9)
    net = ComputationGraph(conf, dtype_policy=pol)
    net.dtype = pol          # see _policy_net: explicit policy is final
    net.init(model.seed)
    x = jax.ShapeDtypeStruct((batch, 224, 224, 3),
                             pol.compute_dtype)
    y = jax.ShapeDtypeStruct((batch, 1000), jnp.float32)
    return dict(model="resnet50", net=net, x=x, y=y, steps=steps,
                examples_per_step=batch, unit="images/sec",
                config={"batch": batch, "image_size": 224, "steps": steps,
                        "dtype_policy": pol.name})


def build_transformer(batch=None, steps=None, policy=None):
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.zoo.transformer import TransformerLM
    B, T, V = batch or 16, 256, 512
    steps = steps or 30
    pol = _resolve_builder_policy(policy)
    lm = TransformerLM(vocab_size=V, d_model=256, n_layers=4, n_heads=8,
                       max_len=T)
    net = _policy_net(lm.conf(), pol)
    x = jax.ShapeDtypeStruct((B, T), jnp.float32)
    y = jax.ShapeDtypeStruct((B, T, V), jnp.float32)
    return dict(model="transformer", net=net, x=x, y=y, steps=steps,
                examples_per_step=B * T, unit="tokens/sec",
                config={"batch": B, "seq_len": T, "d_model": 256,
                        "n_layers": 4, "n_heads": 8, "vocab": V,
                        "dtype_policy": pol.name,
                        "attention": ("xla fallback — flash attention "
                                      "rides only the TPU backend; same "
                                      "matmul FLOPs")})


def build_lstm(batch=None, steps=None, policy=None):
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.zoo.textgenlstm import TextGenerationLSTM
    B, T, V = batch or 64, 100, 77
    steps = steps or 50
    pol = _resolve_builder_policy(policy)
    net = _policy_net(TextGenerationLSTM(vocab_size=V).conf(), pol)
    x = jax.ShapeDtypeStruct((B, T, V), jnp.float32)
    y = jax.ShapeDtypeStruct((B, T, V), jnp.float32)
    return dict(model="lstm", net=net, x=x, y=y, steps=steps,
                examples_per_step=B * T, unit="chars/sec",
                config={"batch": B, "seq_len": T, "vocab": V,
                        "dtype_policy": pol.name})


MODELS = {
    "mlp": build_mlp,
    "lenet": build_lenet,
    "resnet50": build_resnet50,
    "transformer": build_transformer,
    "lstm": build_lstm,
}
HEADLINE_MODELS = ("lenet", "resnet50", "transformer", "lstm")


# ----------------------------------------------------------- peak resolution
def resolve_peaks(peak_tflops: Optional[float] = None,
                  hbm_gbps: Optional[float] = None) -> dict:
    """Compute/memory ceilings for the roofline: explicit flags, else
    the published peaks of `TARGET_DEVICE_KIND`."""
    from deeplearning4j_tpu import bench
    table = bench.device_peaks(TARGET_DEVICE_KIND)
    source = f"bench.DEVICE_PEAKS[{TARGET_DEVICE_KIND!r}]: {table['source']}"
    if peak_tflops is None:
        peak_tflops = table["bf16_tflops"]
    else:
        source = "explicit --peak-tflops flag"
    if hbm_gbps is None:
        hbm_gbps = table["hbm_gbps"]
    return {"peak_tflops": float(peak_tflops), "hbm_gbps": float(hbm_gbps),
            "device_kind": TARGET_DEVICE_KIND, "peak_source": source}


# ------------------------------------------------------------------ analyze
def analyze(model: str, *, batch: Optional[int] = None,
            steps: Optional[int] = None, top: int = 10,
            peak_tflops: Optional[float] = None,
            hbm_gbps: Optional[float] = None,
            ici_gbps: Optional[float] = None,
            compile_exe: bool = False, program: bool = True,
            deep_compare: Optional[bool] = None) -> dict:
    """Full AOT cost analysis of one headline config: lower the exact
    train-step, run XLA cost analysis, build the per-op table and the
    roofline. `program=True` additionally XLA-compiles the lowering
    and records the program section (jaxpr equation count, compile
    seconds via `JitCompileCollector`, peak-temp/activation bytes).
    `deep_compare` (default: transformer only) embeds the committed
    scan-vs-unrolled + remat-policy evidence blocks. Returns the report
    dict (what ``cost_<model>.json`` contains)."""
    from deeplearning4j_tpu.monitor.xprof import roofline
    if model not in MODELS:
        raise ValueError(f"unknown model {model!r}: {sorted(MODELS)}")
    spec = MODELS[model](batch=batch, steps=steps)
    net, x, y, k = spec["net"], spec["x"], spec["y"], spec["steps"]

    t0 = time.perf_counter()
    lowered = net.lower_train_step(x, y, steps=k)
    lower_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    try:
        xla = dict(lowered.cost_analysis() or {})
    except Exception as e:  # noqa: BLE001 — per-backend API surface
        xla = {"error": f"{type(e).__name__}: {e}"[:200]}
    xla_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    jaxpr = net.train_step_jaxpr(x, y, steps=k)
    table = per_op_table(jaxpr, fused_steps=k, top=top)
    table_s = time.perf_counter() - t0

    peaks = resolve_peaks(peak_tflops, hbm_gbps)
    peak_fs = peaks["peak_tflops"] * 1e12
    peak_bs = peaks["hbm_gbps"] * 1e9
    roof = roofline(table["total_flops_per_step"],
                    table["total_bytes_per_step"], peak_fs, peak_bs)

    model_flops = table["conv_dot_flops_per_step"]
    t_pred = roof["predicted_step_seconds"]
    predicted = {
        "step_seconds": t_pred,
        "throughput": spec["examples_per_step"] / t_pred,
        "unit": spec["unit"],
        "examples_per_step": spec["examples_per_step"],
        # standard MFU definition: model (conv+dot) FLOPs over wall time
        # x peak — lower bound (memory ceiling uses unfused bytes)...
        "mfu": model_flops / (t_pred * peak_fs),
        # ...and the matching upper bound at the compute ceiling
        "mfu_if_compute_bound": (
            model_flops / max(table["total_flops_per_step"], 1.0)),
        "mfu_note": ("mfu = conv+dot FLOPs (2/MAC — the published MFU "
                     "accounting) / (predicted step time x the target "
                     "device's published bf16 peak); a prediction, not "
                     "a measurement"),
    }

    report = {
        "model": model,
        "config": spec["config"],
        "generated_by": "benchtools/hlo_cost.py (AOT, device-free)",
        "lowering": {
            "backend": _backend_name(),
            "fused_steps": k,
            "lower_seconds": round(lower_s, 3),
            "xla_cost_analysis_seconds": round(xla_s, 3),
            "jaxpr_walk_seconds": round(table_s, 3),
        },
        "xla_cost_analysis": _trim_xla(xla),
        "per_op": table,
        "roofline": {**roof, **peaks},
        "predicted": predicted,
    }
    if program:
        from deeplearning4j_tpu.nn import scan_stack
        prog = {"jaxpr_eqn_count": count_jaxpr_eqns(jaxpr),
                "scan_layers": scan_stack.scan_enabled(net.conf),
                # dense-vs-threshold gradient-exchange payload for this
                # model's param tree (gradient_sharing wire format) —
                # the committed comm-bytes evidence, device-free
                "comm_bytes": comm_bytes_block(net)}
        try:
            # exposed-vs-overlapped comm bytes of the (default)
            # bucketed exchange: per-bucket payloads against the
            # backward FLOPs available to hide them — backward ~2x
            # forward ~2/3 of the step's total
            prog["comm_overlap"] = comm_overlap_block(
                net,
                backward_flops_per_step=(
                    table["total_flops_per_step"] * 2.0 / 3.0),
                peak_tflops=peaks["peak_tflops"],
                ici_gbps=ici_gbps,
                device_kind=peaks["device_kind"])
        except Exception as e:  # noqa: BLE001 — per-model plan surface
            prog["comm_overlap"] = {
                "error": f"{type(e).__name__}: {e}"[:200]}
        prog.update(compile_program(lowered))
        report["program"] = prog
        try:
            # fp32-vs-bf16 dtype-policy evidence (jaxpr walk only — no
            # second XLA compile; ~2x jaxpr_walk_seconds)
            report["precision"] = precision_block(model, spec, table,
                                                  batch=batch, steps=steps)
        except Exception as e:  # noqa: BLE001 — per-model surface
            report["precision"] = {"error": f"{type(e).__name__}: {e}"[:200]}
    if deep_compare is None:
        # the evidence battery XLA-compiles five deep-stack programs —
        # honoring --no-program's "no XLA compile" promise means it
        # must not run unless explicitly requested
        deep_compare = program and model == "transformer"
    if deep_compare:
        report["scan_vs_unrolled"] = scan_vs_unrolled()
        report["remat_compare"] = remat_compare()
    if compile_exe:
        if program:
            # the program section already compiled this exact lowering
            # — don't pay the (minutes-long for ResNet on CPU) XLA
            # compile a second time for the same numbers
            keep = ("compile_seconds", "argument_size_in_bytes",
                    "output_size_in_bytes", "temp_size_in_bytes",
                    "generated_code_size_in_bytes", "error")
            report["compiled"] = {k: report["program"][k]
                                  for k in keep if k in report["program"]}
        else:
            report["compiled"] = _compiled_block(lowered)
    return report


def _backend_name() -> str:
    try:
        import jax
        return jax.default_backend()
    except Exception:  # noqa: BLE001
        return "?"


def _trim_xla(xla: dict) -> dict:
    """Headline keys of XLA's analysis (the full dict carries one
    'bytes accessedN{}' entry per parameter — hundreds for ResNet)."""
    keep = {k: v for k, v in xla.items()
            if k in ("flops", "bytes accessed", "transcendentals",
                     "optimal_seconds", "error")}
    keep["note"] = ("unoptimized-HLO analysis; scan/while bodies counted "
                    "ONCE by XLA (inner time loops under-counted — the "
                    "per_op table multiplies trip counts instead)")
    return keep


def _compiled_block(lowered) -> dict:
    t0 = time.perf_counter()
    try:
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        out = {"compile_seconds": round(time.perf_counter() - t0, 3)}
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "generated_code_size_in_bytes"):
            try:
                out[attr] = int(getattr(mem, attr))
            except (AttributeError, TypeError):
                pass
        return out
    except Exception as e:  # noqa: BLE001 — opt-in extra, never fatal
        return {"error": f"{type(e).__name__}: {e}"[:200]}


# ---------------------------------------------------------------------- CLI
def run(models, *, out_dir: str = "PROFILE_aot", batch=None, steps=None,
        top: int = 10, peak_tflops=None, hbm_gbps=None, ici_gbps=None,
        compile_exe: bool = False, program: bool = True,
        deep_compare: Optional[bool] = None,
        publish: bool = True) -> List[dict]:
    from deeplearning4j_tpu.monitor import xprof
    os.makedirs(out_dir, exist_ok=True)
    reports = []
    for m in models:
        rep = analyze(m, batch=batch, steps=steps, top=top,
                      peak_tflops=peak_tflops, hbm_gbps=hbm_gbps,
                      ici_gbps=ici_gbps,
                      compile_exe=compile_exe, program=program,
                      deep_compare=deep_compare)
        path = os.path.join(out_dir, f"cost_{m}.json")
        with open(path, "w") as f:
            json.dump(rep, f, indent=1, default=str)
            f.write("\n")
        if publish:
            xprof.publish_cost_report(rep)
        p, pr = rep["per_op"], rep["predicted"]
        line = {
            "model": m,
            "flops_per_step": round(p["total_flops_per_step"]),
            "conv_dot_flops_per_step": round(p["conv_dot_flops_per_step"]),
            "bytes_per_step": round(p["total_bytes_per_step"]),
            "arithmetic_intensity": round(
                rep["roofline"]["arithmetic_intensity_flop_per_byte"], 2),
            "bound": rep["roofline"]["bound"],
            "predicted_step_ms": round(pr["step_seconds"] * 1e3, 3),
            "predicted_mfu": round(pr["mfu"], 4),
            "mfu_if_compute_bound": round(pr["mfu_if_compute_bound"], 4),
            "top_op": (p["top10"][0]["op"] if p["top10"] else None),
            "artifact": path,
        }
        prog = rep.get("program")
        if prog:
            line["jaxpr_eqn_count"] = prog.get("jaxpr_eqn_count")
            line["compile_seconds"] = prog.get("compile_seconds")
            line["peak_temp_bytes"] = prog.get("peak_temp_bytes")
            cb = prog.get("comm_bytes") or {}
            line["comm_bytes_dense"] = cb.get("dense_bytes_per_step")
            line["comm_bytes_threshold"] = cb.get("threshold_bytes_per_step")
            line["comm_reduction"] = cb.get("reduction")
            co = prog.get("comm_overlap") or {}
            line["comm_exposed_bytes"] = co.get("exposed_bytes")
            line["comm_overlapped_bytes"] = co.get("overlapped_bytes")
        prec = rep.get("precision") or {}
        if prec.get("bytes_reduction"):
            line["precision_bytes_reduction"] = prec["bytes_reduction"]
            line["precision_wire_reduction"] = prec.get("wire_reduction")
        svu = rep.get("scan_vs_unrolled")
        if svu:
            line["scan_eqn_reduction"] = svu.get("eqn_reduction")
            line["scan_compile_speedup"] = svu.get("compile_speedup")
        print(json.dumps(line), flush=True)
        reports.append(rep)
    return reports


def main(argv=None) -> int:
    # device-free by construction: counts come from the CPU backend's
    # lowering, so the tool never takes a chip another process may hold
    import jax
    jax.config.update("jax_platforms", "cpu")
    ap = argparse.ArgumentParser(
        prog="benchtools.hlo_cost",
        description="Device-free AOT HLO cost analysis of the headline "
                    "bench configs")
    ap.add_argument("--model", choices=sorted(MODELS), action="append",
                    help="config(s) to analyze (repeatable)")
    ap.add_argument("--all", action="store_true",
                    help=f"all headline configs: {', '.join(HEADLINE_MODELS)}")
    ap.add_argument("--out", default="PROFILE_aot",
                    help="artifact directory (cost_<model>.json)")
    ap.add_argument("--batch", type=int, default=None,
                    help="override the headline batch size")
    ap.add_argument("--steps", type=int, default=None,
                    help="override fused steps-per-execution")
    ap.add_argument("--top", type=int, default=10, help="top-N op table size")
    ap.add_argument("--peak-tflops", type=float, default=None,
                    help="compute ceiling override (default: the "
                         "target device's published bf16 peak)")
    ap.add_argument("--hbm-gbps", type=float, default=None,
                    help="memory-bandwidth ceiling override")
    ap.add_argument("--ici-gbps", type=float, default=None,
                    help="ICI-bandwidth ceiling for the exposed-vs-"
                         "overlapped comm accounting (default: "
                         "DL4J_ICI_GBPS env, else the target device's "
                         "published figure)")
    ap.add_argument("--compile", action="store_true", dest="compile_exe",
                    help="also record the legacy `compiled` block "
                         "(superseded by the default `program` section)")
    ap.add_argument("--no-program", action="store_false", dest="program",
                    help="skip the program section (no XLA compile: "
                         "faster, but drops compile_seconds/peak-memory)")
    ap.add_argument("--deep-compare", action="store_true", default=None,
                    dest="deep_compare",
                    help="embed scan-vs-unrolled + remat-policy evidence "
                         "blocks (default: transformer only)")
    args = ap.parse_args(argv)
    models = list(args.model or [])
    if args.all or not models:
        models = list(HEADLINE_MODELS)
    run(models, out_dir=args.out, batch=args.batch, steps=args.steps,
        top=args.top, peak_tflops=args.peak_tflops, hbm_gbps=args.hbm_gbps,
        ici_gbps=args.ici_gbps,
        compile_exe=args.compile_exe, program=args.program,
        deep_compare=args.deep_compare)
    return 0


if __name__ == "__main__":
    sys.exit(main())
