#!/usr/bin/env python3
"""Print a hash of the lowered StableHLO of the fused train step, so a PR
that moves the step's code can show the program did not move with it; or,
with `--moves`, what the COMPILED step moves without computing anything.

    JAX_PLATFORMS=cpu python3 scripts/train_step_hlo.py [--repo DIR] [--dump DIR]
    JAX_PLATFORMS=cpu python3 scripts/train_step_hlo.py --moves [--repo DIR] [--dump DIR]

Two programs, both lowered from shapes only (nothing is allocated or run):
the `gpt2m_train_t1024` cell's `net._make_train_step(tbptt=False)` with the
arguments `benchmark/tools/compile_only.py` builds, answered for the TPU
branch, and the per-step and 4-fused steps of the packed-chain
`ComputationGraph` of `tests/test_scan_layers.py::TestGraphChains` under
Adam (a packed run whose masters and Adam state stay per-layer leaves).
Location metadata is not printed: `as_text()` leaves it out of the
StableHLO, and each Pallas kernel's serialized Mosaic module (bytecode that
carries the call stack's file names and line numbers) is replaced by the
hash of its text without locations. `--repo` hashes another checkout.

`--moves` compiles the cell's step for a v5e that is described and not
attached (~20 s, as `benchmark/tools/compile_only.py` does) and prints, for
the ENTRY computation and for each scan body, the count and the bytes
written of the operations that only move data (`reshape`, `copy`, `pad`,
`slice`, `dynamic-update-slice`, alone or as the fusion the compiler named
after them), by element type, and `memory_analysis()`. Nothing runs: counts
and bytes, no time. ISSUE 37 found the pack / flatten / unflatten / unpack of
the float32 masters and Adam state in the ENTRY computation this way.
"""

from __future__ import annotations

import argparse
import base64
import hashlib
import os
import re
import sys

MOVES = ("dynamic-update-slice", "pad", "reshape", "copy", "slice")
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
          "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
          "f64": 8}
_SHAPE = re.compile(r"\b([a-z]+\d+|pred)\[([\d,]*)\]")
_INSTR = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = (\(.*?\)|\S+) ([\w\-]+)\((.*)$")


def _shape_bytes(shape):
    """(bytes, element type of the largest array) of an HLO shape."""
    total, biggest = 0, (0, "")
    for dtype, dims in _SHAPE.findall(shape):
        n = _BYTES.get(dtype, 0)
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n
        biggest = max(biggest, (n, dtype))
    return total, biggest[1]


def _computations(text):
    """{name: [(instruction, shape, opcode, rest of the line)]} of an
    optimized HLO module's text; the entry computation under "ENTRY"."""
    comps, cur = {}, None
    for line in text.splitlines():
        head = re.match(r"^(ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            cur = comps.setdefault("ENTRY" if head.group(1)
                                   else head.group(2), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            m = _INSTR.match(line)
            if m:
                cur.append(m.groups())
    return comps


def _written(comps, comp, instr):
    """(bytes, element type) an instruction writes: its output, except
    that an in-place `dynamic-update-slice` (alone or inside the fusion
    named after it) writes its update operand and aliases the rest."""
    _, shape, opcode, rest = instr
    called = (re.search(r"calls=%?([\w.\-]+)", rest)
              if opcode == "fusion" else None)
    scope = comps.get(called.group(1), []) if called else comps[comp]
    for _, _, op, args in (scope if called else [instr]):
        if op == "dynamic-update-slice":
            update = re.findall(r"%?([\w.\-]+)", args)[1]
            shapes = {name: sh for name, sh, _, _ in scope}
            return _shape_bytes(shapes.get(update, shape))
    return _shape_bytes(shape)


def moves_table(text):
    """[(computation, class, element type, count, bytes written)] for the
    entry computation and every `while` body of an optimized module: the
    instructions whose opcode, or whose fusion's name, is one of `MOVES`.
    `*-start` halves of asynchronous copies are skipped and the `*-done`
    halves listed under their own names (the compiler's prefetches into
    its fast memory, not moves the program asked for)."""
    comps = _computations(text)
    bodies = sorted(set(re.findall(r"body=%?([\w.\-]+)", text)))
    rows = []
    for comp in ["ENTRY"] + bodies:
        tally = {}
        for instr in comps.get(comp, []):
            name, _, opcode, _ = instr
            label = (re.sub(r"(\.\d+|\.remat\d*|\.clone)+$", "", name)
                     if opcode == "fusion" else opcode)
            if label.endswith("-start"):
                continue
            kind = (label if label in ("slice-done", "copy-done") else next(
                (k for k in MOVES if k in label), None))
            if kind is None:
                continue
            nbytes, dtype = _written(comps, comp, instr)
            n, b = tally.get((kind, dtype), (0, 0))
            tally[(kind, dtype)] = (n + 1, b + nbytes)
        rows += [(comp, kind, dtype, n, b)
                 for (kind, dtype), (n, b) in sorted(
                     tally.items(), key=lambda kv: -kv[1][1])]
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--repo", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    ap.add_argument("--dump", default=None)
    ap.add_argument("--moves", action="store_true")
    a = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [os.path.join(a.repo, "benchmark"), a.repo]

    import jax
    import jax.numpy as jnp

    import harness
    from deeplearning4j_tpu.common.updaters import Adam
    from deeplearning4j_tpu.nn.graph import (
        ComputationGraph, ComputationGraphConfiguration)
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer

    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir
    ctx = mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True

    def kernel(m):
        with ctx:
            asm = ir.Module.parse(base64.b64decode(m.group(1)), ctx) \
                .operation.get_asm(enable_debug_info=False)
        return '\\22body\\22: \\22mosaic:%s\\22' % hashlib.sha256(
            asm.encode()).hexdigest()

    def show(name, lowered):
        text = re.sub(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', kernel,
                      lowered.as_text())
        print(name, hashlib.sha256(text.encode()).hexdigest(),
              f"{len(text):,} bytes", flush=True)
        if a.dump:
            os.makedirs(a.dump, exist_ok=True)
            with open(os.path.join(a.dump, name + ".mlir"), "w") as f:
                f.write(text)

    def cell_step():
        """The cell's step lowered from shapes for the described v5e, the
        chip's branch taken (as benchmark/tools/compile_only.py)."""
        jax.default_backend = lambda: "tpu"
        _, cell, cfg = harness.load_cell("gpt2m_train_t1024")
        net = harness.load_module("models", cell["config"]).build(cfg)
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        one = SingleDeviceSharding(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0])

        def sds(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one)
        params, state, upd = jax.tree_util.tree_map(
            lambda s: sds(s.shape, s.dtype),
            jax.eval_shape(net._init_trees, 0))
        B, T, V = cell["batch"], cell["seq_len"], cfg["vocab_size"]
        return net._make_train_step(tbptt=False).lower(
            params, upd, state, 0, sds((B, T), jnp.int32),
            sds((B, T, V), jnp.float32), sds((2,), jnp.uint32),
            None, None, None)

    if a.moves:
        jax.config.update("jax_enable_compilation_cache", False)
        compiled = cell_step().compile()
        text = compiled.as_text()
        if a.dump:
            os.makedirs(a.dump, exist_ok=True)
            with open(os.path.join(a.dump, "gpt2m_train_t1024_step.hlo"),
                      "w") as f:
                f.write(text)
        for comp, kind, dtype, n, nbytes in moves_table(text):
            print(f"{comp[:48]:48s} {kind:22s} {dtype:5s} {n:5d} "
                  f"{nbytes / 1e9:9.3f} GB")
        m = compiled.memory_analysis()
        print(f"memory_analysis: arguments {m.argument_size_in_bytes:,} "
              f"outputs {m.output_size_in_bytes:,} aliased "
              f"{m.alias_size_in_bytes:,} temporaries "
              f"{m.temp_size_in_bytes:,}")
        return

    g = ComputationGraphConfiguration.graph_builder().add_inputs("in")
    prev = "in"
    for i in range(4):
        g.add_layer(f"d{i}", DenseLayer(n_in=8 if i == 0 else 16, n_out=16,
                                        activation="relu",
                                        updater=Adam(1e-2)), prev)
        prev = f"d{i}"
    g.add_layer("out", OutputLayer(n_in=16, n_out=3, updater=Adam(1e-2)),
                prev).set_outputs("out")
    graph = ComputationGraph(g.build()).init(2)
    x = jax.ShapeDtypeStruct((8, 8), jnp.float32)
    y = jax.ShapeDtypeStruct((8, 3), jnp.float32)
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32)
    show("graph_chain_step", graph._make_train_step(tbptt=False).lower(
        graph.params, graph.updater_state, graph.net_state, 0,
        (x,), (y,), rng, (None,), (None,), None))
    show("graph_chain_fused4", graph.lower_train_step(x, y, steps=4))
    show("gpt2m_train_t1024_step", cell_step())


if __name__ == "__main__":
    main()
