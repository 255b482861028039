"""All-to-all sequence parallelism (DeepSpeed-Ulysses style).

No 2017-reference equivalent (like ring attention, this is first-class
new-design territory per SURVEY §5 long-context): an alternative to the
ring schedule for long sequences. Instead of rotating K/V blocks around
the ICI ring, ONE all-to-all re-partitions the activations from
sequence-sharded to head-sharded, each device computes EXACT full-
sequence attention for its head subset, and a second all-to-all returns
to sequence sharding.

Trade-off vs ring (why both exist):
- ulysses: 2 collectives total, full-sequence attention kernels (best
  MXU utilization), but requires num_heads % seq_devices == 0 and
  all-to-all bandwidth;
- ring: P-1 ppermutes with compute overlap, no head-count constraint,
  preferred when heads are few or the ring is the fast path (1D ICI
  torus).

Implemented with `shard_map` + `lax.all_to_all` so XLA lowers the
re-partitions to native ICI all-to-alls.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.lax import axis_size
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.parallel.ring import reference_attention


def _full_attention(q, k, v, causal: bool):
    """Exact attention on full sequences: [B, T, H, Dh] blocks."""
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], q.dtype))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        T = q.shape[1]
        ok = jnp.tril(jnp.ones((T, T), bool))
        scores = jnp.where(ok[None, None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def ulysses_attention(q, k, v, axis_name: str, causal: bool = False,
                      use_flash: bool = False):
    """Per-shard: q/k/v [B, T_local, H, Dh] (sequence-sharded). Returns
    o [B, T_local, H, Dh]. Run inside shard_map with `axis_name` bound;
    requires H % axis_size == 0.

    `use_flash=True` runs the post-all-to-all full-sequence attention
    through the Pallas flash kernels (`kernels/flash_attention.py`,
    differentiable) — since each device sees the FULL sequence for its
    head subset, this is where the O(block)-VMEM streaming matters most
    in the Ulysses schedule."""
    Pn = axis_size(axis_name)
    B, Tl, H, Dh = q.shape
    if H % Pn != 0:
        raise ValueError(f"num_heads={H} must divide by seq devices={Pn}")

    # seq-sharded [B, Tl, H, Dh] → head-sharded [B, Tl*P, H/P, Dh]:
    # all_to_all splits the head axis across devices and concatenates
    # the gathered sequence chunks along time
    def to_heads(x):
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def to_seq(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    qh, kh, vh = to_heads(q), to_heads(k), to_heads(v)   # [B, T, H/P, Dh]
    if use_flash:
        from deeplearning4j_tpu.kernels.flash_attention import (
            flash_attention)
        oh = flash_attention(qh, kh, vh, causal)
    else:
        oh = _full_attention(qh, kh, vh, causal)
    return to_seq(oh)                                    # [B, Tl, H, Dh]


def ulysses_parallel_attention(q, k, v, mesh: Mesh, *,
                               axis_name: str = "seq",
                               causal: bool = False,
                               use_flash: bool = False):
    """Full arrays [B, T, H, Dh]; shards T over `axis_name`, runs the
    all-to-all schedule, returns full [B, T, H, Dh]."""
    spec = P(None, axis_name, None, None)

    @partial(shard_map, mesh=mesh, in_specs=(spec, spec, spec),
             out_specs=spec, check_vma=False)
    def run(ql, kl, vl):
        return ulysses_attention(ql, kl, vl, axis_name, causal=causal,
                                 use_flash=use_flash)

    sh = NamedSharding(mesh, spec)
    return run(jax.device_put(q, sh), jax.device_put(k, sh),
               jax.device_put(v, sh))
