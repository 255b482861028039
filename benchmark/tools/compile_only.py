#!/usr/bin/env python3
"""Compile a training cell's step at its real size for a v5e that is
described and not attached (libtpu's compile-only client), and print
`memory_analysis()`.  Nothing runs: this gives no time and no result.

    JAX_PLATFORMS=cpu python3 benchmark/tools/compile_only.py <cell> [batch ...]
"""

from __future__ import annotations

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))

import harness  # noqa: E402


def main(argv):
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import deeplearning4j_tpu  # noqa: F401
    # the program asks jax.default_backend() whether to donate and to use
    # its Pallas kernels: answer as the chip would (as the repo's own
    # tests/test_tpu_aot_compile.py does), or this compiles the CPU branch
    jax.default_backend = lambda: "tpu"
    jax.config.update("jax_enable_compilation_cache", False)
    _, cell, cfg = harness.load_cell(argv[0])
    batches = [int(b) for b in argv[1:]] or [cell["batch"]]
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    model = harness.load_module("models", cell["config"])
    net = model.build(cfg)
    shapes = jax.eval_shape(net._init_trees, 0)
    place = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one), t)
    params, state, upd = (place(t) for t in shapes)
    step = net._make_train_step(tbptt=False)
    for B in batches:
        T, V = cell["seq_len"], cfg["vocab_size"]
        x = jax.ShapeDtypeStruct((B, T), jnp.int32, sharding=one)
        y = jax.ShapeDtypeStruct((B, T, V), jnp.float32, sharding=one)
        rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one)
        args = (params, upd, state, 0, x, y, rng, None, None, None)
        try:
            c = step.lower(*args).compile()
            m = c.memory_analysis()
            tot = (m.argument_size_in_bytes + m.output_size_in_bytes
                   + m.temp_size_in_bytes - m.alias_size_in_bytes)
            print(f"batch {B}: args {m.argument_size_in_bytes:,} out "
                  f"{m.output_size_in_bytes:,} temp {m.temp_size_in_bytes:,} "
                  f"alias {m.alias_size_in_bytes:,} total {tot:,}", flush=True)
        except Exception as e:  # the compiler's refusal is the finding
            print(f"batch {B}: REFUSED: {str(e)[:600]}", flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
