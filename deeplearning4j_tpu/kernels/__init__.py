"""Pallas TPU kernels — custom fast paths for ops XLA doesn't fuse
optimally (the deeplearning4j-cuda role: hand-tuned kernels behind the
same layer API, SURVEY §2.2). Five of them: flash attention (forward
and backward, `flash_attention.py`), fused LayerNorm (`layernorm.py`),
the serving decode step's length-bounded paged attention
(`paged_attention.py`:
`dl4tpu_paged_decode`, which reads the K/V pages a slot holds in place
instead of gathering every slot's whole block table;
`mla_paged_attention.py`: `dl4tpu_mla_paged_decode`, the same over a
latent pool in the absorbed form) and a state-space layer's prefill
recurrence (`selective_scan.py`: `dl4tpu_selective_scan`, the state in
VMEM across time).

Kernel gating (`kernels_enabled`): compiled kernels ride the TPU
backend by default; on other backends the (slow, python-level)
interpret mode only runs when ``DL4J_PALLAS_KERNELS=1`` forces it —
which is how the CPU parity suite exercises the kernels without taxing
every ordinary CPU test. ``DL4J_PALLAS_KERNELS=0`` opts out everywhere
(the cuDNN-helper on/off switch). The flash-attention layer keeps its
own finer-grained ``use_flash`` knob on top.
"""

import os

from deeplearning4j_tpu.kernels.flash_attention import flash_attention

_ENV_VAR = "DL4J_PALLAS_KERNELS"
_OFF = ("0", "off", "false", "no")
_ON = ("1", "on", "true", "yes")


def kernels_enabled() -> bool:
    """Should the Pallas fused-kernel fast paths (LayerNorm, paged
    decode attention) dispatch? Env override wins;
    default = TPU backend only."""
    env = os.environ.get(_ENV_VAR)
    if env is not None and env.strip():
        v = env.strip().lower()
        if v in _OFF:
            return False
        if v in _ON:
            return True
        raise ValueError(
            f"{_ENV_VAR}={env!r}: expected one of {_OFF + _ON}")
    import jax
    return jax.default_backend() == "tpu"
