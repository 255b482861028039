#!/usr/bin/env python3
"""Print a hash of the lowered StableHLO of a serving cell's decode step
(`gpt2m_serve_chat`'s by default), so a PR that extends the decode kernel
or the engine can show that the program this cell runs did not move with
it.

    JAX_PLATFORMS=cpu python3 scripts/decode_step_hlo.py [--cell CELL] [--repo DIR] [--dump DIR]

The greedy and the sampling variant of `PagedDecodeEngine._decode_body`
under the cell's own server arguments, at the configuration's published
widths with TWO layers of each kind it has (the `hash_layers` group of the
configuration's own file: a layer is a layer, and the sandbox builds no
full-size net), lowered from shapes for a
described v5e and answered for the TPU branch.  A configuration whose
policy is mixed is initialised (its serving copy is a cast that shapes
cannot run: two layers of `gpt2-medium` are small); the others never hold
a weight.
As `scripts/train_step_hlo.py`: location metadata is not printed, and each
Pallas kernel's serialized Mosaic module is replaced by the hash of its
text without locations.  `--repo` hashes another checkout.
"""

from __future__ import annotations

import argparse
import base64
import hashlib
import os
import re
import sys


S, F = "sliding_attention", "full_attention"
# The keys that leave two layers of each kind are the configuration's own:
# the `hash_layers` group of its file. These three files were accepted
# without one and only a `benchmark` PR may edit them: theirs wait here
# until one moves them. A new configuration brings its group and needs no
# edit to this script.
ACCEPTED_WITHOUT_GROUP = {
    "gpt2-medium": dict(n_layer=2),
    "sarvam-105b": dict(num_hidden_layers=4, first_k_dense_replace=2),
    "command-a-plus-05-2026": dict(num_hidden_layers=4,
                                   layer_types=[S, S, F, F]),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", default="gpt2m_serve_chat")
    ap.add_argument("--repo", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    ap.add_argument("--dump", default=None)
    a = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [os.path.join(a.repo, "benchmark"), a.repo]

    import jax

    import deeplearning4j_tpu  # noqa: F401 - before the first device lookup
    import harness
    from deeplearning4j_tpu.serving import PagedDecodeEngine

    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    ctx = mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True

    def kernel(m):
        with ctx:
            asm = ir.Module.parse(base64.b64decode(m.group(1)), ctx) \
                .operation.get_asm(enable_debug_info=False)
        return '\\22body\\22: \\22mosaic:%s\\22' % hashlib.sha256(
            asm.encode()).hexdigest()

    one = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    jax.default_backend = lambda: "tpu"   # the chip's branch

    def shapes(tree):
        return jax.tree_util.tree_map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one), tree)

    _, cell, cfg = harness.load_cell(a.cell)
    cfg = dict(cfg, **(cfg.get("hash_layers")
                       or ACCEPTED_WITHOUT_GROUP[cell["config"]]))
    model = harness.load_module("models", cell["config"])
    net = model.build(cfg)
    if net.dtype.is_mixed:
        net.init(0)
    else:
        ref = harness.load_module("reference", cell["config"])
        net.params = jax.eval_shape(lambda k: model.to_program(
            ref.init_params(cfg, k), cfg), jax.random.PRNGKey(0))
        net.net_state, net.updater_state, net._initialized = {}, {}, True
    eng = PagedDecodeEngine(net, **cell["server"])
    args = shapes((eng._params, net.net_state, eng.pool.kv)
                  + tuple(eng._decode_args()))
    for name, greedy in (("decode_greedy", True), ("decode_sampling", False)):
        text = re.sub(
            r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', kernel,
            jax.jit(eng._decode_body(greedy_only=greedy),
                    donate_argnums=2).lower(*args).as_text())
        print(name, hashlib.sha256(text.encode()).hexdigest(),
              f"{len(text):,} bytes", "kernels",
              sorted(set(re.findall(r'kernel_name = "([^"]+)"', text))),
              flush=True)
        if a.dump:
            os.makedirs(a.dump, exist_ok=True)
            with open(os.path.join(a.dump, name + ".mlir"), "w") as f:
                f.write(text)


if __name__ == "__main__":
    main()
