"""Dtype policy for the framework — real mixed-precision training.

Reference behavior: ND4J has a global data-type setting
(`Nd4j.setDataType`, consumed throughout deeplearning4j-nn). On TPU the
useful policy is finer-grained: parameters and updater state in float32
(the fp32 "master" copy), matmul/conv compute optionally in bfloat16
(MXU-native), reductions/losses in float32. `DataTypePolicy` captures
that split, and the containers thread it end to end:

- the whole (packed) param tree is cast to ``compute_dtype`` ONCE at
  the train-step boundary, OUTSIDE ``value_and_grad`` — so activations,
  backward, and the gradients themselves are ``compute_dtype`` (the
  wire payload of a data-parallel all-reduce halves under bf16);
- losses, softmax statistics, and normalization statistics stay fp32
  (the containers upcast at the output layer; the norm layers compute
  their row statistics in fp32 regardless of activation dtype);
- the updater consumes gradients UPCAST back to ``param_dtype``, so
  Adam/momentum state and the parameters themselves remain an fp32
  master copy — checkpoints are byte-identical in layout to pure-fp32
  training, and the fault runtime's bit-parity contract is unaffected;
- the gradient-sharing paths upcast to fp32 before the error-feedback
  encode, so the EF identity enc·τ + res' = upd + res holds exactly in
  fp32 (docs/PRECISION.md).

Policy resolution mirrors ``DL4J_SCAN_LAYERS``: the
``DL4J_DTYPE_POLICY`` environment override wins (fleet A/B without
code changes), then an explicit container argument, then the
configuration's ``dtype_policy`` field, then the process-global
default (`set_default_dtype` / factory float32).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional

import jax
import jax.numpy as jnp

_ENV_VAR = "DL4J_DTYPE_POLICY"


@dataclasses.dataclass(frozen=True)
class DataTypePolicy:
    """Param / compute / output dtype split.

    param_dtype:   dtype parameters are stored in (and updater state —
                   the fp32 master copy under a mixed policy).
    compute_dtype: dtype activations are computed in. bfloat16 feeds the
                   MXU at full rate on TPU; float32 is the safe default.
    output_dtype:  dtype of network outputs / losses (always float32 by
                   default so eval numerics are stable).
    """

    param_dtype: jnp.dtype = jnp.float32
    compute_dtype: jnp.dtype = jnp.float32
    output_dtype: jnp.dtype = jnp.float32

    # ------------------------------------------------------------- queries
    @property
    def is_mixed(self) -> bool:
        """True when compute runs in a different (lower) precision than
        the parameter master copy — the policies that change programs."""
        return jnp.dtype(self.compute_dtype) != jnp.dtype(self.param_dtype)

    @property
    def name(self) -> str:
        if not self.is_mixed and jnp.dtype(self.param_dtype) == jnp.float32 \
                and jnp.dtype(self.output_dtype) == jnp.float32:
            return "float32"
        if (jnp.dtype(self.param_dtype) == jnp.float32
                and jnp.dtype(self.compute_dtype) == jnp.bfloat16
                and jnp.dtype(self.output_dtype) == jnp.float32):
            return "mixed_bf16"
        if (jnp.dtype(self.param_dtype) == jnp.bfloat16
                and jnp.dtype(self.compute_dtype) == jnp.bfloat16
                and jnp.dtype(self.output_dtype) == jnp.float32):
            return "bf16_params"
        return "custom"

    # --------------------------------------------------------------- casts
    def cast_compute(self, x):
        """Cast one array to the compute dtype. Non-floating inputs
        (int token ids for embeddings, bool masks) pass through
        UNCHANGED — a bf16 cast would corrupt ids above 256."""
        return x.astype(self.compute_dtype) if self.casts(x) else x

    def casts(self, x) -> bool:
        """Would `cast_compute` change `x`: a floating array in another
        dtype than the compute dtype."""
        return (hasattr(x, "dtype")
                and jnp.issubdtype(x.dtype, jnp.floating)
                and x.dtype != self.compute_dtype)

    def cast_output(self, x):
        if (hasattr(x, "dtype")
                and jnp.issubdtype(x.dtype, jnp.floating)
                and x.dtype != self.output_dtype):
            return x.astype(self.output_dtype)
        return x

    def cast_params(self, tree):
        """Whole param tree → compute dtype (floating leaves only).
        Identity — the SAME tree object, no convert ops traced — for a
        non-mixed policy, so pure-fp32 programs are untouched."""
        if not self.is_mixed:
            return tree
        return jax.tree_util.tree_map(self.cast_compute, tree)

    def cast_output_params(self, lparams):
        """Output-layer params → output dtype (losses/softmax stay
        fp32 under a mixed policy). Identity when not mixed."""
        if not self.is_mixed:
            return lparams
        return jax.tree_util.tree_map(self.cast_output, lparams)

    # --------------------------------------------------------------- serde
    def to_dict(self) -> dict:
        return {
            "param_dtype": jnp.dtype(self.param_dtype).name,
            "compute_dtype": jnp.dtype(self.compute_dtype).name,
            "output_dtype": jnp.dtype(self.output_dtype).name,
        }

    @staticmethod
    def from_dict(d: dict) -> "DataTypePolicy":
        return DataTypePolicy(
            param_dtype=jnp.dtype(d.get("param_dtype", "float32")),
            compute_dtype=jnp.dtype(d.get("compute_dtype", "float32")),
            output_dtype=jnp.dtype(d.get("output_dtype", "float32")),
        )


_FACTORY = DataTypePolicy()
_DEFAULT = _FACTORY


def default_policy() -> DataTypePolicy:
    return _DEFAULT


def get_default_policy() -> DataTypePolicy:
    """The ACTIVE process-global policy (callers used to only see
    `get_default_dtype()`'s param_dtype and could not tell whether a
    mixed policy was in force)."""
    return _DEFAULT


def get_default_dtype():
    """Param (master) dtype of the active policy — the narrow legacy
    view; prefer `get_default_policy()`."""
    return _DEFAULT.param_dtype


def set_default_dtype(param_dtype=None, compute_dtype=None,
                      output_dtype=None, *, reset: bool = False):
    """Global policy override, mirroring `Nd4j.setDataType`.

    Unset fields keep their current values; ``reset=True`` restores the
    factory float32 policy FIRST (an explicit reset used to be
    impossible — `None` meant "keep", so a bf16 compute override could
    never be undone)."""
    global _DEFAULT
    base = _FACTORY if reset else _DEFAULT
    _DEFAULT = DataTypePolicy(
        param_dtype=param_dtype or base.param_dtype,
        compute_dtype=compute_dtype or base.compute_dtype,
        output_dtype=output_dtype or base.output_dtype,
    )
    return _DEFAULT


def set_default_policy(policy: Optional[DataTypePolicy] = None):
    """Install a policy object as the process default (None restores
    the factory float32 policy)."""
    global _DEFAULT
    _DEFAULT = policy if policy is not None else _FACTORY
    return _DEFAULT


def mixed_bf16() -> DataTypePolicy:
    """fp32 master params / bf16 compute / fp32 losses — the standard
    TPU mixed-precision training recipe (the named preset
    ``NeuralNetConfiguration.dtype_policy("mixed_bf16")`` selects)."""
    return DataTypePolicy(compute_dtype=jnp.bfloat16)


def bf16_policy() -> DataTypePolicy:
    """float32 MASTER params, bfloat16 compute — an alias of
    `mixed_bf16()`, kept for the bench/hlo_cost call sites that predate
    the preset registry. Despite its name it does NOT hold parameters
    in bfloat16: that is `bf16_params()`."""
    return mixed_bf16()


def bf16_params() -> DataTypePolicy:
    """Parameters HELD in bfloat16, bfloat16 compute, float32 outputs
    and losses: the serving policy of a model whose weights only fit a
    chip at two bytes each. No float32 master exists, `cast_params` is
    the identity (the policy is not mixed) and a serving program reads
    the leaves as they are. Training under it updates bfloat16 weights
    in place, with the rounding that brings; the mixed recipe is
    `mixed_bf16()`."""
    return DataTypePolicy(param_dtype=jnp.bfloat16,
                          compute_dtype=jnp.bfloat16)


# "bf16" has meant `mixed_bf16` (float32 masters) since before a
# bfloat16-parameter policy existed, and configurations on disk say it;
# the policy that holds the parameters in bfloat16 is "bf16_params"
_NAMED = {
    "float32": DataTypePolicy,
    "fp32": DataTypePolicy,
    "mixed_bf16": mixed_bf16,
    "bf16": mixed_bf16,
    "bf16_params": bf16_params,
}


def policy_from_name(name: str) -> DataTypePolicy:
    key = str(name).strip().lower()
    if key not in _NAMED:
        raise ValueError(
            f"unknown dtype policy {name!r}; known: "
            f"{sorted(set(_NAMED))}")
    return _NAMED[key]()


def as_policy(p) -> Optional[DataTypePolicy]:
    """Coerce a user-facing policy spec (policy object, preset name,
    serde dict, or None) to a DataTypePolicy (or None)."""
    if p is None or isinstance(p, DataTypePolicy):
        return p
    if isinstance(p, str):
        return policy_from_name(p)
    if isinstance(p, dict):
        return DataTypePolicy.from_dict(p)
    raise TypeError(f"cannot interpret {p!r} as a dtype policy")


def env_policy() -> Optional[DataTypePolicy]:
    """The ``DL4J_DTYPE_POLICY`` override if set (validated), else
    None. ``0/off/false/no`` force plain float32 (the A/B opt-out
    spelling `DL4J_SCAN_LAYERS` uses); preset names select presets."""
    env = os.environ.get(_ENV_VAR)
    if env is None or not env.strip():
        return None
    v = env.strip().lower()
    if v in ("0", "off", "false", "no"):
        return DataTypePolicy()
    if v in ("1", "on", "true", "yes"):
        return mixed_bf16()
    return policy_from_name(v)


def resolve_policy(explicit=None, conf=None) -> DataTypePolicy:
    """Container-side policy resolution: DL4J_DTYPE_POLICY env override
    wins, then the explicit constructor argument, then the
    configuration's ``dtype_policy`` field, then the process-global
    default."""
    forced = env_policy()
    if forced is not None:
        return forced
    explicit = as_policy(explicit)
    if explicit is not None:
        return explicit
    conf_p = as_policy(getattr(conf, "dtype_policy", None))
    if conf_p is not None:
        return conf_p
    return _DEFAULT
