#!/usr/bin/env python3
"""Print a hash of the lowered StableHLO of the fused train step, so a PR
that moves the step's code can show the program did not move with it.

    JAX_PLATFORMS=cpu python3 scripts/train_step_hlo.py [--repo DIR] [--dump DIR]

Two programs, both lowered from shapes only (nothing is allocated or run):
the `gpt2m_train_t1024` cell's `net._make_train_step(tbptt=False)` with the
arguments `benchmark/tools/compile_only.py` builds, answered for the TPU
branch, and the per-step and 4-fused steps of the packed-chain
`ComputationGraph` of `tests/test_scan_layers.py::TestGraphChains` under
Adam (the fused-Adam packed path). Location metadata is not printed:
`as_text()` leaves it out of the StableHLO, and each Pallas kernel's
serialized Mosaic module (bytecode that carries the call stack's file names
and line numbers) is replaced by the hash of its text without locations.
`--repo` hashes another checkout.
"""

from __future__ import annotations

import argparse
import base64
import hashlib
import os
import re
import sys


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--repo", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    ap.add_argument("--dump", default=None)
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

    jax.default_backend = lambda: "tpu"   # the chip's branch, as compile_only
    _, cell, cfg = harness.load_cell("gpt2m_train_t1024")
    net = harness.load_module("models", cell["config"]).build(cfg)
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    one = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)
    params, state, upd = jax.tree_util.tree_map(
        lambda s: sds(s.shape, s.dtype), jax.eval_shape(net._init_trees, 0))
    B, T, V = cell["batch"], cell["seq_len"], cfg["vocab_size"]
    show("gpt2m_train_t1024_step", net._make_train_step(tbptt=False).lower(
        params, upd, state, 0, sds((B, T), jnp.int32),
        sds((B, T, V), jnp.float32), sds((2,), jnp.uint32), None, None, None))


if __name__ == "__main__":
    main()
