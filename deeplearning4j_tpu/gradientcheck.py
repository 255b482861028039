"""Finite-difference gradient checker — the framework's correctness
oracle.

Reference: `gradientcheck/GradientCheckUtil.java:112,207-222`: perturb
each parameter ±ε in float64, compare (f(θ+ε)−f(θ−ε))/2ε against the
analytic gradient with a max-relative-error threshold. The reference
runs this over every layer/loss/vertex combination
(`deeplearning4j-core/src/test/java/org/deeplearning4j/gradientcheck/`).

Here the analytic gradient is jax autodiff; the checker still earns its
keep by validating every layer's forward math end-to-end (a wrong
forward gives a consistent-but-wrong gradient; a non-differentiable /
numerically unstable forward shows up as mismatch). Runs in float64 on
CPU via the `jax.enable_x64` context.
"""

from __future__ import annotations

from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np


def check_gradients_fn(
    loss_fn: Callable[[Dict], jnp.ndarray],
    params: Dict,
    epsilon: float = 1e-6,
    max_rel_error: float = 1e-5,
    min_abs_error: float = 1e-8,
    max_params_per_array: int = 64,
    seed: int = 0,
    verbose: bool = False,
):
    """Check autodiff gradients of `loss_fn(params)` against central
    finite differences.

    Samples up to `max_params_per_array` coordinates per param tensor
    (the reference checks all; sampling keeps test time sane for big
    tensors while covering every tensor).

    Returns (ok, max_rel_err, failures).
    """
    with jax.enable_x64(True):
        params64 = jax.tree_util.tree_map(
            lambda a: jnp.asarray(np.asarray(a), jnp.float64), params)
        loss64 = jax.jit(lambda p: jnp.asarray(loss_fn(p), jnp.float64))
        grads = jax.jit(jax.grad(lambda p: loss64(p)))(params64)
        flat_params, treedef = jax.tree_util.tree_flatten(params64)
        flat_grads = jax.tree_util.tree_leaves(grads)
        rng = np.random.default_rng(seed)
        failures = []
        worst = 0.0
        for ti, (arr, g) in enumerate(zip(flat_params, flat_grads)):
            size = int(np.prod(arr.shape)) if arr.shape else 1
            n_check = min(size, max_params_per_array)
            idxs = rng.choice(size, size=n_check, replace=False)
            host = np.asarray(arr, dtype=np.float64)
            for flat_idx in idxs:
                idx = np.unravel_index(int(flat_idx), arr.shape) if arr.shape else ()
                orig = host[idx] if arr.shape else float(host)

                def eval_at(v):
                    pert = host.copy()
                    pert[idx] = v
                    new_flat = list(flat_params)
                    new_flat[ti] = jnp.asarray(pert)
                    return float(loss64(jax.tree_util.tree_unflatten(treedef, new_flat)))

                plus = eval_at(orig + epsilon)
                minus = eval_at(orig - epsilon)
                numeric = (plus - minus) / (2 * epsilon)
                analytic = float(np.asarray(g)[idx] if arr.shape else float(g))
                denom = max(abs(numeric), abs(analytic))
                abs_err = abs(numeric - analytic)
                rel = abs_err / denom if denom > 0 else 0.0
                if abs_err > min_abs_error and rel > max_rel_error:
                    failures.append((ti, idx, analytic, numeric, rel))
                worst = max(worst, rel if abs_err > min_abs_error else 0.0)
                if verbose:
                    print(f"tensor {ti} idx {idx}: analytic {analytic:.3e} "
                          f"numeric {numeric:.3e} rel {rel:.3e}")
        return len(failures) == 0, worst, failures


def check_model_gradients(
    model,
    features,
    labels,
    epsilon: float = 1e-6,
    max_rel_error: float = 1e-4,
    max_params_per_array: int = 32,
    features_mask=None,
    labels_mask=None,
    seed: int = 0,
):
    """Gradient-check a container on one minibatch (reference
    `GradientCheckUtil.checkGradients(mln, ...)` and its graph
    overload): features / labels / masks are what its `_loss_fn` takes
    — one array each, or one sequence entry per graph input / output.

    Dropout must be disabled in the config (the reference asserts this
    too — stochastic forward breaks finite differences)."""
    for _, layer in model._keyed_layers():
        d = layer.dropout
        if d is not None and (not isinstance(d, (int, float)) or d < 1.0):
            raise ValueError("Gradient checks require dropout disabled "
                             "(reference GradientCheckUtil precondition)")
    if not model._initialized:
        model.init()
    tmap = jax.tree_util.tree_map
    x, y = tmap(lambda a: np.asarray(a, dtype=np.float64),
                (model._as_io(features), model._as_io(labels)))
    fm, lm = tmap(lambda m: jnp.asarray(np.asarray(m)),
                  (features_mask, labels_mask))

    from deeplearning4j_tpu.nd.dtype import DataTypePolicy

    saved_policy = model.dtype
    model.dtype = DataTypePolicy(param_dtype=jnp.float64, compute_dtype=jnp.float64,
                                 output_dtype=jnp.float64)
    saved_state = model.net_state
    model.net_state = tmap(
        lambda a: np.asarray(a, dtype=np.float64), model.net_state)

    def loss_fn(p):
        loss, _ = model._loss_fn(p, model.net_state, *tmap(jnp.asarray, (x, y)),
                                 None, fm, lm, train=False)
        return loss

    try:
        return check_gradients_fn(loss_fn, model.params, epsilon=epsilon,
                                  max_rel_error=max_rel_error,
                                  max_params_per_array=max_params_per_array, seed=seed)
    finally:
        model.dtype = saved_policy
        model.net_state = saved_state


def check_graph_gradients(
    model,
    inputs,
    labels,
    epsilon: float = 1e-6,
    max_rel_error: float = 1e-4,
    max_params_per_array: int = 32,
    seed: int = 0,
):
    """`check_model_gradients` under the name and argument order of the
    reference's `checkGradients(graph, ...)` overload."""
    return check_model_gradients(model, inputs, labels, epsilon,
                                 max_rel_error, max_params_per_array,
                                 seed=seed)
