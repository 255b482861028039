"""deeplearning4j_tpu — a TPU-native deep learning framework.

Capability-equivalent rebuild of the deeplearning4j stack (reference:
arthuremanuel/deeplearning4j @ 0.9.2-SNAPSHOT) designed TPU-first on
JAX/XLA: params are pytrees, gradients come from ``jax.value_and_grad``,
device parallelism is a sharding annotation over a ``jax.sharding.Mesh``
(not thread-per-device wrappers), and every hot op compiles onto the MXU
through XLA.

Package map (mirrors the reference's layer map, SURVEY.md §1):

- ``nd``        tensor substrate shim (dtype policy, RNG streams) —
                stands in for ND4J/libnd4j.
- ``common``    activations / losses / updaters / schedules / weight init —
                ND4J's IActivation / ILossFunction / IUpdater surface.
- ``nn``        layer configs (config-as-data DSL), functional layer
                implementations, MultiLayerNetwork & ComputationGraph
                containers (reference: deeplearning4j-nn).
- ``optimize``  listeners + training utilities (reference: optimize/).
- ``eval``      Evaluation / RegressionEvaluation / ROC (reference: eval/).
- ``datasets``  DataSet, iterators, fetchers (reference: datasets/).
- ``parallel``  SPMD mesh training — the single engine replacing
                ParallelWrapper, ParameterAveraging and SharedTraining
                (reference: deeplearning4j-scaleout).
- ``zoo``       model zoo (reference: deeplearning4j-zoo).
- ``nlp``       sequence-vector embedding stack (reference: deeplearning4j-nlp).
- ``keras``     Keras model import (reference: deeplearning4j-modelimport).
- ``util``      model serialization & helpers.
"""

__version__ = "0.1.0"

import os as _os


def _widen_tpu_compiler_stacks():
    """libtpu 0.0.34 runs XLA:TPU's emitters on fibers whose default
    stack its own fusion cost model overflows — a SIGSEGV that kills the
    process, uncatchable from Python — when it nests three matmul
    fusions (FFN out-projection -> vocabulary projection -> softmax)
    over exactly 256 rows: the serving prefill of an 8-wide admission
    wave at the 32-token bucket, on the chip and in the compile-only
    client alike (PR 21; 512 KiB already suffices). The flag is read
    when the TPU backend first starts, so it is set here, at import,
    the way `jax/_src/cloud_tpu_init.py` sets its own; a process that
    started its backend before importing this package is not covered.
    An operator's own setting of the flag wins. Re-test and delete on
    the next libtpu."""
    flag = "--fibers_default_thread_stack_size"
    args = _os.environ.get("LIBTPU_INIT_ARGS", "")
    if flag not in args:
        _os.environ["LIBTPU_INIT_ARGS"] = f"{args} {flag}={8 << 20}".strip()


_widen_tpu_compiler_stacks()

from deeplearning4j_tpu.nd import dtype as _dtype  # noqa: F401
