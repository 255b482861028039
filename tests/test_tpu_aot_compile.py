"""The programs the chip runs, compiled for a v5e WITHOUT a chip.

libtpu ships a compile-only client: `jax.experimental.topologies`
describes a v5e and `.lower(...).compile()` runs the real XLA:TPU +
Mosaic compilers on this CPU host. Nothing executes, so no time, rate
or utilisation comes out of it — but every refusal the compilers can
make (a primitive Mosaic cannot lower, a tiling or VMEM limit, a crash)
shows up here, in tier-1, before it costs a chip call. Both blockers
PR 21 met on the chip reproduce under it exactly.

Each case is a subprocess: trace-time platform decisions are made as on
the chip (`jax.default_backend` answers "tpu"), and one case expects the
compiler to kill its process.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PRELUDE = '''
import json, re, sys
import jax, jax.numpy as jnp
{import_package}
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
try:
    topo = topologies.get_topology_desc("v5e:2x2", "tpu")
except Exception as e:
    print(json.dumps({{"skip": f"{{type(e).__name__}}: {{e}}"[:300]}}))
    sys.exit(0)
S = SingleDeviceSharding(topo.devices[0])
jax.default_backend = lambda: "tpu"      # trace as on the chip
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.zoo.transformer import TransformerLM


def sds(a):
    return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=S)


def tree(t):
    return jax.tree_util.tree_map(sds, t)


def lm(**kw):
    conf = TransformerLM(seed=11, **kw).conf()
    conf.dtype_policy = "mixed_bf16"
    return MultiLayerNetwork(conf).init(11)
'''

_TRAIN = '''
# T = 1024 reaches the Pallas flash BACKWARD (_PALLAS_BWD_MIN_T)
net = lm(vocab_size=128, d_model=128, n_layers=2, n_heads=2, max_len=1024)
k, B, T, V = 2, 1, 1024, 128
key = jax.random.PRNGKey(0)
net._jit_multi_step = net._make_multi_step()
low = net._jit_multi_step.lower(
    tree(net.params), tree(net.updater_state), tree(net.net_state), 0,
    jax.ShapeDtypeStruct((k, B, T), jnp.float32, sharding=S),
    jax.ShapeDtypeStruct((k, B, T, V), jnp.float32, sharding=S),
    jax.ShapeDtypeStruct((k,) + key.shape, key.dtype, sharding=S))
names = sorted(set(re.findall(r'kernel_name = "([^"]+)"', low.as_text())))
low.compile()
print(json.dumps({"kernels": names, "compiled": True}))
'''

_PREFILL = '''
# the serving prefill of an 8-wide admission wave at the 32-token
# bucket: 256 rows through FFN out-projection -> vocabulary projection
# -> softmax, which XLA:TPU nests three fusions deep
from deeplearning4j_tpu.nn.layers.recurrent import BaseRecurrentLayer
from deeplearning4j_tpu.zoo.transformer import get_prefill_bucketed
net = lm(vocab_size=512, d_model=512, n_layers=1, n_heads=8, max_len=64)
k2, Pb = 8, 32
carries = {
    str(i): jax.tree_util.tree_map(
        sds, jax.eval_shape(lambda l=l: l.init_carry(
            k2, net.dtype.compute_dtype)))
    for i, l in enumerate(net.layers) if isinstance(l, BaseRecurrentLayer)}
get_prefill_bucketed(net).lower(
    tree(net.params), tree(net.net_state),
    jax.ShapeDtypeStruct((k2, Pb), jnp.int32, sharding=S), carries,
    jax.ShapeDtypeStruct((k2,), jnp.int32, sharding=S)).compile()
print(json.dumps({"compiled": True}))
'''


_DECODE = '''
# the serving decode step at a pool the in-place kernel can tile: bf16
# pages of 16 positions, H*Dh = 128 lanes
from deeplearning4j_tpu.serving import PagedDecodeEngine
net = lm(vocab_size=128, d_model=128, n_layers=2, n_heads=2, max_len=64)
eng = PagedDecodeEngine(net, n_slots=4, n_blocks=16, block_len=16)
args = (tree(eng._params), tree(net.net_state), tree(eng.pool.kv)) + tuple(
    sds(a) for a in eng._decode_args())
low = jax.jit(eng._decode_body(greedy_only=True),
              donate_argnums=2).lower(*args)
names = sorted(set(re.findall(r'kernel_name = "([^"]+)"', low.as_text())))
hlo = low.compile().as_text()
pool = "bf16[16,16,128]"
print(json.dumps({"kernels": names, "in_place": list(eng._in_place),
                  "pool_copies": len(re.findall(
                      r"= " + re.escape(pool) + r"\\S* copy\\(", hlo)),
                  "pool_seen": pool in hlo}))
'''


_DECODE_WEIGHTS = '''
# the same decode step, given the float32 masters and given the tree
# the engine serves from (a mixed net's one bfloat16 copy)
from deeplearning4j_tpu.nd import quant
from deeplearning4j_tpu.serving import PagedDecodeEngine
net = lm(vocab_size=128, d_model=128, n_layers=2, n_heads=2, max_len=64)
eng = PagedDecodeEngine(net, n_slots=4, n_blocks=16, block_len=16)
rest = (tree(net.net_state), tree(eng.pool.kv)) + tuple(
    sds(a) for a in eng._decode_args())
dims = {",".join(map(str, a.shape))
        for a in jax.tree_util.tree_leaves(net.params)}
out = {}
for name, params in (("masters", net.params), ("served", eng._params)):
    c = jax.jit(eng._decode_body(greedy_only=True),
                donate_argnums=2).lower(tree(params), *rest).compile()
    hlo = c.as_text()
    out[name] = {
        "f32_weight_args": sum(
            d in dims for d in re.findall(
                r"= f32\\[([0-9,]+)\\]\\S* parameter\\(", hlo)),
        "weight_converts": sum(
            d in dims for d in re.findall(
                r"= bf16\\[([0-9,]+)\\]\\S* convert\\(", hlo)),
        "argument_bytes": c.memory_analysis().argument_size_in_bytes}
out["master_bytes"] = quant.weight_bytes(net.params)
print(json.dumps(out))
'''


_DECODE_SORT = '''
# the two decode variants of a 32-slot engine: what each one sorts
from deeplearning4j_tpu.serving import PagedDecodeEngine
from deeplearning4j_tpu.serving.engine import _SAMPLE_CHUNK_ROWS
net = lm(vocab_size=512, d_model=128, n_layers=2, n_heads=2, max_len=64)
eng = PagedDecodeEngine(net, n_slots=32, n_blocks=64, block_len=16)
args = (tree(eng._params), tree(net.net_state), tree(eng.pool.kv)) + tuple(
    sds(a) for a in eng._decode_args())
out = {"chunk_rows": _SAMPLE_CHUNK_ROWS}
for name, greedy in (("greedy", True), ("full", False)):
    hlo = jax.jit(eng._decode_body(greedy_only=greedy),
                  donate_argnums=2).lower(*args).compile().as_text()
    out[name] = sorted(set(
        dims for line in hlo.splitlines() if " sort(" in line
        for dims in re.findall(r"\\[([0-9,]+)\\]",
                               line.split(" sort(")[0])))
print(json.dumps(out))
'''


_MLA_DECODE = '''
# sarvam-105b's decode attention at its published widths: 32 slots, 64
# heads, a cache row of 576 padded to 640 lanes, bf16 pages of 64
# positions, 8,704 positions a slot; then one latent block's whole
# paged step round it (narrow feed-forward: the kernel is what is tried)
from deeplearning4j_tpu.kernels.mla_paged_attention import (
    KERNEL_NAME, mla_paged_decode_attention)
from deeplearning4j_tpu.nn.layers.latent import LatentAttentionBlock
bf = jnp.bfloat16
shape = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=S)
low = jax.jit(lambda q, pool, bt, ln: mla_paged_decode_attention(
    q, pool, bt, ln, latent=512, scale=0.135)).lower(
    shape((32, 64, 576), bf), shape((4352, 64, 640), bf),
    shape((32, 136), jnp.int32), shape((32,), jnp.int32))
names = sorted(set(re.findall(r'kernel_name = "([^"]+)"', low.as_text())))
low.compile()
blk = LatentAttentionBlock(
    n_in=4096, n_heads=64, kv_lora_rank=512, qk_nope_head_dim=128,
    qk_rope_head_dim=64, v_head_dim=128, ffn="dense", ffn_hidden=256,
    rope_scaling={"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
                  "mscale_all_dim": 1,
                  "original_max_position_embeddings": 4096})
params = jax.tree_util.tree_map(
    lambda a: shape(a.shape, a.dtype),
    jax.eval_shape(lambda: blk.init_params(jax.random.PRNGKey(0), bf)))
pool = shape((4352, 64, 640), bf)
step = jax.jit(lambda p, x, pool, bt, pos, live: blk.paged_step(
    p, x, (pool,), bt, pos, live), donate_argnums=2)
hlo = step.lower(params, shape((32, 1, 4096), bf), pool,
                 shape((32, 136), jnp.int32), shape((32,), jnp.int32),
                 shape((32,), jnp.bool_)).compile().as_text()
print(json.dumps({"kernels": names, "name": KERNEL_NAME,
                  "in_place": blk.paged_in_place((pool,)),
                  "step_has_kernel": KERNEL_NAME in hlo,
                  "pool_copies": len(re.findall(
                      r"= bf16\\[4352,64,640\\]\\S* copy\\(", hlo))}))
'''


_GQA_DECODE = '''
# command-a-plus-05-2026's decode attention at its published widths: 32
# slots, 128 query heads over 8 key heads of 128, bf16 pages of 64
# positions; a full layer over 9,216 positions a slot and a window layer
# over its ring of 65 blocks from a first position; then one window
# block's whole paged step round it (narrow experts: the kernel is what
# is tried)
from deeplearning4j_tpu.kernels.paged_attention import (
    KERNEL_NAME, paged_decode_attention)
from deeplearning4j_tpu.nn.layers.parallel import ParallelAttentionMoEBlock
bf, i32 = jnp.bfloat16, jnp.int32
shape = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=S)
names = set()
for blocks, cols, windowed in ((4609, 144, False), (2081, 65, True)):
    args = [shape((32, 1, 16384), bf), shape((blocks, 64, 1024), bf),
            shape((blocks, 64, 1024), bf), shape((32, cols), i32),
            shape((32,), i32)] + [shape((32,), i32)] * windowed
    low = jax.jit(lambda q, k, v, bt, ln, st=None: paged_decode_attention(
        q, k, v, bt, ln, n_heads=128, n_kv_heads=8, starts=st)).lower(*args)
    names |= set(re.findall(r'kernel_name = "([^"]+)"', low.as_text()))
    low.compile()
blk = ParallelAttentionMoEBlock(
    n_in=4096, n_heads=128, n_kv_heads=8, head_dim=128, window=4096,
    rotary=True, rope_theta=50000.0, ffn_hidden=256, n_routed=128,
    experts_per_token=8, held_count=16, n_shared=4)
params = jax.tree_util.tree_map(
    lambda a: shape(a.shape, a.dtype),
    jax.eval_shape(lambda: blk.init_params(jax.random.PRNGKey(0), bf)))
pools = (shape((2081, 64, 1024), bf),) * 2
step = jax.jit(lambda p, x, pools, bt, pos, live: blk.paged_step(
    p, x, pools, bt, pos, live), donate_argnums=2)
hlo = step.lower(params, shape((32, 1, 4096), bf), pools,
                 shape((32, 65), i32), shape((32,), i32),
                 shape((32,), jnp.bool_)).compile().as_text()
print(json.dumps({"kernels": sorted(names), "name": KERNEL_NAME,
                  "in_place": blk.paged_in_place(pools),
                  "step_has_kernel": KERNEL_NAME in hlo,
                  "pool_copies": len(re.findall(
                      r"= bf16\\[2081,64,1024\\]\\S* copy\\(", hlo))}))
'''


_SSM_SCAN = '''
# AI21-Jamba2-3B's Mamba layer at its published widths (5,120 channels,
# 16 state columns): the prefill recurrence of a 4 x 2,048 wave through
# the layer's own forward_prefill, and the decode step over 64 slots'
# states (narrow MLP: the mixer is what is tried)
from deeplearning4j_tpu.kernels.selective_scan import KERNEL_NAME
from deeplearning4j_tpu.nn.layers.statespace import HybridStateSpaceBlock
bf, i32 = jnp.bfloat16, jnp.int32
shape = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=S)
blk = HybridStateSpaceBlock(n_in=2560, ffn_hidden=256, dt_rank=160)
params = jax.tree_util.tree_map(
    lambda a: shape(a.shape, a.dtype),
    jax.eval_shape(lambda: blk.init_params(jax.random.PRNGKey(0), bf)))
pre = jax.jit(lambda p, x, n: blk.forward_prefill(p, x, n)).lower(
    params, shape((4, 2048, 2560), bf), shape((4,), i32))
names = sorted(set(re.findall(r'kernel_name = "([^"]+)"', pre.as_text())))
pre.compile()
state = tuple(shape(a.shape, a.dtype) for a in jax.eval_shape(
    lambda: blk.slot_state_arrays(64, bf)))
step = jax.jit(lambda p, x, st, live: blk.state_step(p, x, st, live),
               donate_argnums=2)
hlo = step.lower(params, shape((64, 1, 2560), bf), state,
                 shape((64,), jnp.bool_)).compile().as_text()
print(json.dumps({"kernels": names, "name": KERNEL_NAME,
                  "state": [list(a.shape) for a in state],
                  "step_has_kernel": KERNEL_NAME in hlo,
                  "state_copies": len(re.findall(
                      r"= f32\\[64,16,5120\\]\\S* copy\\(", hlo))}))
'''


def _child(body, *, import_package=True):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("LIBTPU_INIT_ARGS", None)
    code = _PRELUDE.format(
        import_package="import deeplearning4j_tpu" if import_package
        else "") + body
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=900)
    out = None
    for line in reversed(proc.stdout.strip().splitlines()):
        if line.startswith("{"):
            out = json.loads(line)
            break
    if out and "skip" in out:
        pytest.skip(f"no compile-only TPU client here: {out['skip']}")
    return proc, out


def test_train_step_kernels_are_mosaic_compiled_for_v5e():
    """Every Pallas kernel of the fused train step lowers to a
    `tpu_custom_call` under its stable name and the whole step passes
    the TPU compilers. (PR 21: a kernel Mosaic refused — the Adam
    kernel of the time, since deleted: PR 37 — being ON by default on
    a TPU took every `fit()` of a packed Adam run down with it.)"""
    from deeplearning4j_tpu.kernels import layernorm
    from deeplearning4j_tpu.kernels.flash_attention import KERNEL_NAMES
    proc, out = _child(_TRAIN)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert out["compiled"] is True
    assert out["kernels"] == sorted(KERNEL_NAMES + layernorm.KERNEL_NAMES)


def test_decode_step_attends_over_the_pool_in_place_on_v5e():
    """The single-token decode program lowers `dl4tpu_paged_decode` to
    a `tpu_custom_call`, passes the TPU compilers, and moves no pool:
    the pools go from argument to scatter to kernel to result in their
    own layout, with no `copy` of a pool's shape in the compiled
    module (the 4-D pool of PR 27 and before had the block index
    minor-most on the device, and every layer's scatter was bracketed
    by two copies of the whole pool)."""
    from deeplearning4j_tpu.kernels.paged_attention import KERNEL_NAME
    proc, out = _child(_DECODE)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert out["in_place"] == [True, True]
    assert KERNEL_NAME in out["kernels"]
    assert out["pool_seen"] and out["pool_copies"] == 0


def test_decode_step_reads_the_served_copy_and_casts_no_weight_on_v5e():
    """Given the tree a mixed net is served from, the compiled decode
    program has no float32 argument of a weight's shape and no
    float32 -> bfloat16 `convert` of one (given the masters it has one
    of each a leaf), and its arguments are smaller by half the
    masters' bytes (`memory_analysis()`)."""
    proc, out = _child(_DECODE_WEIGHTS)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert out["masters"]["f32_weight_args"] > 0
    assert out["masters"]["weight_converts"] > 0
    assert out["served"]["f32_weight_args"] == 0
    assert out["served"]["weight_converts"] == 0
    # to the tile padding of the small leaves
    assert (out["masters"]["argument_bytes"]
            - out["served"]["argument_bytes"]) == pytest.approx(
                out["master_bytes"] / 2, rel=0.02)


def test_decode_step_sorts_a_chunk_of_sampled_rows_and_no_more_on_v5e():
    """The full decode variant compiled for a described v5e sorts
    `[_SAMPLE_CHUNK_ROWS, V]`, inside its loop over the sampled rows,
    and nothing with a row for every slot; the greedy variant sorts
    nothing."""
    proc, out = _child(_DECODE_SORT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert out["chunk_rows"] < 32
    assert out["greedy"] == []
    assert out["full"] == [f"{out['chunk_rows']},512"]


def test_latent_decode_kernel_compiles_at_published_widths_on_v5e():
    """`dl4tpu_mla_paged_decode` at sarvam-105b's widths (576 is not a
    multiple of 128: the row is padded to 640 lanes) lowers through
    Mosaic for a described v5e, alone and inside a latent block's paged
    step, which takes the kernel and copies no pool."""
    proc, out = _child(_MLA_DECODE)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert out["kernels"] == [out["name"]] == ["dl4tpu_mla_paged_decode"]
    assert out["in_place"] is True and out["step_has_kernel"] is True
    assert out["pool_copies"] == 0


def test_grouped_windowed_decode_compiles_at_published_widths_on_v5e():
    """`dl4tpu_paged_decode` with 8 key heads under 128 query heads, from
    position 0 over a full layer's table and from a first position over
    a window layer's ring, lowers through Mosaic for a described v5e,
    alone and inside a parallel block's paged step, which takes the
    kernel and copies no pool."""
    proc, out = _child(_GQA_DECODE)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert out["kernels"] == [out["name"]] == ["dl4tpu_paged_decode"]
    assert out["in_place"] is True and out["step_has_kernel"] is True
    assert out["pool_copies"] == 0


def test_selective_scan_compiles_at_published_widths_on_v5e():
    """`dl4tpu_selective_scan` at 5,120 channels and 16 state columns
    lowers through Mosaic for a described v5e inside the Mamba layer's
    prefill of a 4 x 2,048 wave; the decode step over 64 slots' states is
    XLA's own (no kernel) and copies no state array."""
    proc, out = _child(_SSM_SCAN)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert out["kernels"] == [out["name"]] == ["dl4tpu_selective_scan"]
    assert out["state"] == [[64, 16, 5120], [3, 64, 5120]]
    assert out["step_has_kernel"] is False and out["state_copies"] == 0


def test_256_row_prefill_compiles_with_the_packages_libtpu_stacks():
    """Importing the package widens libtpu's compiler fiber stacks
    before the TPU client starts (`deeplearning4j_tpu/__init__.py`), so
    the program that killed the first chip smoke compiles."""
    proc, out = _child(_PREFILL)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert out == {"compiled": True}


def test_the_libtpu_crash_the_workaround_exists_for_is_still_there():
    """Control: the same program under libtpu's DEFAULT fiber stack —
    the TPU client is started BEFORE the package is imported, so the
    package's flag comes too late to apply — dies in the compiler with
    SIGSEGV, uncatchable from Python, which is why the fix is a
    start-up flag and not an `except`. When a newer libtpu makes this
    test fail, delete `_widen_tpu_compiler_stacks` and this control
    with it."""
    proc, out = _child(_PREFILL, import_package=False)
    assert out is None
    assert proc.returncode < 0, (proc.returncode, proc.stderr[-2000:])
    assert "STACK OVERFLOW" in proc.stderr
