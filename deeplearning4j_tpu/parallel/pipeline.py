"""Pipeline parallelism (GPipe-style) over the "pipe" mesh axis.

No reference equivalent (SURVEY §2.13: pipeline parallelism ❌). TPU
design: the model is a stack of S IDENTICAL blocks (the transformer /
repeated-MLP case — the standard JAX pipelining pattern); stage s holds
block s's params (leading stage axis sharded over "pipe"), microbatches
flow through the ring via `ppermute`, and the schedule is a
`lax.scan` over M + S - 1 ticks (fill + drain). Autodiff works through
the whole schedule (ppermute transposes to the reverse permute), so
one `jax.grad` gives pipeline-parallel backprop — no hand-written 1F1B
bookkeeping.

API: `pipeline_apply(block_fn, stage_params, x_microbatches, axis_name)`
runs inside shard_map; `pipeline_forward` wraps the shard_map for full
arrays.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.lax import axis_size
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def pipeline_apply(block_fn: Callable, stage_params, x_mb, axis_name: str):
    """Per-shard: stage_params = THIS stage's block params (pytree),
    x_mb [M, B, ...] microbatches (replicated on every stage). Returns
    [M, B, ...] outputs (valid on the LAST stage; zeros elsewhere).

    Must run inside shard_map with `axis_name` bound.
    """
    S = axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    M = x_mb.shape[0]
    ticks = M + S - 1
    zero = jnp.zeros_like(x_mb[0])
    shift_down = [(j, (j + 1) % S) for j in range(S)]  # stage s → s+1

    def tick(carry, t):
        incoming, out_acc = carry
        # stage 0 injects microbatch t (if still filling); others use the
        # activation handed over from stage s-1 on the previous tick
        x_t = lax.dynamic_index_in_dim(x_mb, jnp.minimum(t, M - 1), 0,
                                       keepdims=False)
        inp = jnp.where(idx == 0, jnp.where(t < M, x_t, zero), incoming)
        y = block_fn(stage_params, inp)
        # last stage: microbatch m = t - (S-1) completes at tick t
        m = t - (S - 1)
        is_ready = jnp.logical_and(idx == S - 1, m >= 0)
        out_acc = lax.cond(
            jnp.logical_and(is_ready, m < M),
            lambda acc: lax.dynamic_update_index_in_dim(
                acc, y, jnp.clip(m, 0, M - 1), 0),
            lambda acc: acc, out_acc)
        handed = lax.ppermute(y, axis_name, shift_down)
        return (handed, out_acc), None

    out0 = jnp.zeros_like(x_mb)
    (final_in, outputs), _ = lax.scan(tick, (zero, out0), jnp.arange(ticks))
    return outputs


def pipeline_forward(block_fn, stacked_params, x, mesh: Mesh, *,
                     pipe_axis: str = "pipe", microbatches: int = 4,
                     data_axis: str = None):
    """Full-array wrapper: `stacked_params` has a leading stage axis
    (size = mesh["pipe"]), x is [B_total, ...]; B_total must divide by
    `microbatches`. Returns [B_total, ...] of the final stage.

    `data_axis` composes DP with the pipeline: the microbatch BATCH
    dim shards over it (each data-shard runs its own GPipe stream over
    the same pipe ring; params replicate across "data"), so a
    ("data", "pipe") mesh trains with both axes live."""
    B = x.shape[0]
    assert B % microbatches == 0, "batch must divide microbatches"
    x_mb = x.reshape((microbatches, B // microbatches) + x.shape[1:])
    p_spec = jax.tree_util.tree_map(lambda _: P(pipe_axis), stacked_params)
    mb_spec = P(None, data_axis) if data_axis else P()

    @partial(shard_map, mesh=mesh,
             in_specs=(p_spec, mb_spec), out_specs=mb_spec,
             check_vma=False)
    def run(params_stage, mb):
        local = jax.tree_util.tree_map(lambda a: a[0], params_stage)
        out = pipeline_apply(block_fn, local, mb, pipe_axis)
        # outputs are valid only on the last stage; broadcast them
        return _broadcast_from(out, pipe_axis, axis_size(pipe_axis) - 1)

    out_mb = run(stacked_params, x_mb)
    return out_mb.reshape((B,) + out_mb.shape[2:])


def _broadcast_from(x, axis_name, src):
    """All stages receive stage `src`'s value (psum of masked values)."""
    idx = lax.axis_index(axis_name)
    masked = jnp.where(idx == src, x, jnp.zeros_like(x))
    return lax.psum(masked, axis_name)
