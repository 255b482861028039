"""Line-search solver family: ConjugateGradient, LBFGS,
LineGradientDescent, BackTrackLineSearch + step functions.

Reference: `optimize/solvers/BaseOptimizer.java:54` (`optimize()`
:197-250 — gradientAndScore → search direction → line search → step),
`ConjugateGradient.java` (Polak-Ribière beta, restart on negative),
`LBFGS.java` (two-loop recursion over (s, y) memory),
`LineGradientDescent.java` (steepest descent + line search),
`BackTrackLineSearch.java` (Armijo backtracking with step
contraction), `nn/conf/stepfunctions/*` (4 step functions), and the
`nn/api/OptimizationAlgorithm.java` enum selected on the builder.

TPU-first redesign: the reference mutates a flat param vector in place;
here the loss is a pure jitted function of the param pytree, flattened
once with `ravel_pytree`. Loss/gradient evaluations run on device
(jitted, MXU-bound); the line-search control flow — inherently
data-dependent and sequential — stays on the host, the same split
jaxopt uses. Each solver is deterministic full-batch math, so the whole
`optimize()` loop is reproducible.
"""

from __future__ import annotations

from enum import Enum
from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree


class OptimizationAlgorithm(str, Enum):
    """Reference `nn/api/OptimizationAlgorithm.java`."""

    STOCHASTIC_GRADIENT_DESCENT = "sgd"
    LINE_GRADIENT_DESCENT = "line_gradient_descent"
    CONJUGATE_GRADIENT = "conjugate_gradient"
    LBFGS = "lbfgs"


# ------------------------------------------------------------ step functions
class StepFunction:
    """Reference `nn/conf/stepfunctions/StepFunction.java`: how a search
    direction is applied to the params."""

    name = "step"
    sign = 1.0

    def step(self, x: jnp.ndarray, direction: jnp.ndarray,
             alpha: float) -> jnp.ndarray:
        return x + self.sign * alpha * direction

    def to_dict(self):
        return {"step_function": self.name}


class DefaultStepFunction(StepFunction):
    """x ← x + alpha * d (direction already carries descent sign)."""

    name = "default"
    sign = 1.0


class NegativeDefaultStepFunction(StepFunction):
    """x ← x - alpha * d; the container default (pairs with raw-gradient
    directions)."""

    name = "negative_default"
    sign = -1.0


class GradientStepFunction(StepFunction):
    name = "gradient"
    sign = 1.0


class NegativeGradientStepFunction(StepFunction):
    name = "negative_gradient"
    sign = -1.0


_STEP_FUNCTIONS = {c.name: c for c in
                   (DefaultStepFunction, NegativeDefaultStepFunction,
                    GradientStepFunction, NegativeGradientStepFunction)}


def step_function_from_dict(d) -> StepFunction:
    if isinstance(d, StepFunction):
        return d
    name = d["step_function"] if isinstance(d, dict) else str(d)
    return _STEP_FUNCTIONS[name]()


# -------------------------------------------------------------- line search
class BackTrackLineSearch:
    """Armijo backtracking (reference `BackTrackLineSearch.java`:
    contract the step by `step_decrease` until
    f(x + a·d) ≤ f(x) + c1·a·gᵀd, give up after `max_iterations`)."""

    def __init__(self, *, max_iterations: int = 20, c1: float = 1e-4,
                 step_decrease: float = 0.5, min_step: float = 1e-12,
                 step_function: Optional[StepFunction] = None):
        self.max_iterations = max_iterations
        self.c1 = c1
        self.step_decrease = step_decrease
        self.min_step = min_step
        self.step_function = step_function or DefaultStepFunction()

    def optimize(self, f: Callable[[jnp.ndarray], float], x: jnp.ndarray,
                 f0: float, g: jnp.ndarray, direction: jnp.ndarray,
                 initial_step: float = 1.0) -> Tuple[float, float]:
        """Returns (alpha, f_new). alpha == 0.0 means no acceptable step."""
        slope = float(jnp.vdot(g, direction)) * self.step_function.sign
        if slope >= 0:
            # not a descent direction under this step function
            return 0.0, f0
        alpha = initial_step
        for _ in range(self.max_iterations):
            fa = float(f(self.step_function.step(x, direction, alpha)))
            if np.isfinite(fa) and fa <= f0 + self.c1 * alpha * slope:
                return alpha, fa
            alpha *= self.step_decrease
            if alpha < self.min_step:
                break
        return 0.0, f0


# ------------------------------------------------------------------ solvers
class BaseLineSearchOptimizer:
    """Shared optimize() loop (reference `BaseOptimizer.optimize()`
    :197-250): score+gradient → direction → line search → step, until
    `max_iterations` or convergence."""

    def __init__(self, *, max_iterations: int = 100, tolerance: float = 1e-6,
                 line_search: Optional[BackTrackLineSearch] = None):
        self.max_iterations = max_iterations
        self.tolerance = tolerance
        self.line_search = line_search or BackTrackLineSearch()
        self.scores: List[float] = []

    def _reset(self, n: int):
        pass

    def _direction(self, it: int, x, g, prev_g, prev_d):
        raise NotImplementedError

    def _post_step(self, s, y):
        pass

    def optimize(self, loss_fn: Callable, x0: jnp.ndarray,
                 *args) -> jnp.ndarray:
        """Minimize `loss_fn(flat, *args)` over `flat`, from `x0`.

        Extra `*args` (e.g. the minibatch) are passed through to the
        jitted loss so the jit cache persists across calls — one trace
        per (solver, loss_fn) pair, not one per minibatch."""
        if getattr(self, "_jit_src", None) is not loss_fn:
            self._jit_vg = jax.jit(jax.value_and_grad(loss_fn))
            self._jit_f = jax.jit(loss_fn)
            self._jit_src = loss_fn
        vg = lambda xx: self._jit_vg(xx, *args)
        f = lambda xx: self._jit_f(xx, *args)
        x = jnp.asarray(x0)
        self._reset(x.size)
        self.scores = []
        prev_g = prev_d = None
        f0, g = vg(x)
        f0 = float(f0)
        self.scores.append(f0)
        for it in range(self.max_iterations):
            d = self._direction(it, x, g, prev_g, prev_d)
            alpha, f_new = self.line_search.optimize(f, x, f0, g, d,
                                                     initial_step=1.0)
            if alpha == 0.0:
                if prev_d is None:
                    break
                # restart from steepest descent once before giving up
                # (also drop curvature memory so LBFGS really restarts)
                prev_g = prev_d = None
                self._reset(x.size)
                d = self._direction(0, x, g, None, None)
                alpha, f_new = self.line_search.optimize(f, x, f0, g, d,
                                                         initial_step=1.0)
                if alpha == 0.0:
                    break
            x_new = self.line_search.step_function.step(x, d, alpha)
            f1, g_new = vg(x_new)
            f1 = float(f1)
            self._post_step(x_new - x, g_new - g)
            converged = abs(f0 - f1) < self.tolerance * max(1.0, abs(f0))
            x, f0, prev_g, prev_d, g = x_new, f1, g, d, g_new
            self.scores.append(f0)
            if converged:
                break
        return x


class LineGradientDescent(BaseLineSearchOptimizer):
    """Steepest descent + line search (reference
    `LineGradientDescent.java`)."""

    def _direction(self, it, x, g, prev_g, prev_d):
        return -g


class ConjugateGradient(BaseLineSearchOptimizer):
    """Nonlinear CG, Polak-Ribière beta with automatic restart
    (reference `ConjugateGradient.java`: beta = gᵀ(g-g_prev)/g_prevᵀg_prev,
    clamped at 0 → steepest-descent restart)."""

    def _direction(self, it, x, g, prev_g, prev_d):
        if prev_g is None or prev_d is None:
            return -g
        denom = float(jnp.vdot(prev_g, prev_g))
        if denom <= 0:
            return -g
        beta = max(0.0, float(jnp.vdot(g, g - prev_g)) / denom)
        return -g + beta * prev_d


class LBFGS(BaseLineSearchOptimizer):
    """Limited-memory BFGS via the standard two-loop recursion
    (reference `LBFGS.java`, memory m=10)."""

    def __init__(self, *, memory: int = 10, **kw):
        super().__init__(**kw)
        self.memory = memory
        self._s: List[jnp.ndarray] = []
        self._y: List[jnp.ndarray] = []

    def _reset(self, n):
        self._s, self._y = [], []

    def _post_step(self, s, y):
        ys = float(jnp.vdot(y, s))
        if ys > 1e-10:  # curvature condition; skip bad pairs
            self._s.append(s)
            self._y.append(y)
            if len(self._s) > self.memory:
                self._s.pop(0)
                self._y.pop(0)

    def _direction(self, it, x, g, prev_g, prev_d):
        if not self._s:
            return -g
        q = g
        alphas = []
        for s, y in zip(reversed(self._s), reversed(self._y)):
            rho = 1.0 / float(jnp.vdot(y, s))
            a = rho * float(jnp.vdot(s, q))
            alphas.append((a, rho))
            q = q - a * y
        s, y = self._s[-1], self._y[-1]
        gamma = float(jnp.vdot(s, y)) / float(jnp.vdot(y, y))
        r = gamma * q
        for (a, rho), s, y in zip(reversed(alphas), self._s, self._y):
            b = rho * float(jnp.vdot(y, r))
            r = r + (a - b) * s
        return -r


_SOLVERS = {
    OptimizationAlgorithm.LINE_GRADIENT_DESCENT: LineGradientDescent,
    OptimizationAlgorithm.CONJUGATE_GRADIENT: ConjugateGradient,
    OptimizationAlgorithm.LBFGS: LBFGS,
}


class Solver:
    """Reference `Solver.Builder` → `ConvexOptimizer`: run a line-search
    solver over a model container's full-batch loss.

    `model` is a MultiLayerNetwork or ComputationGraph; params are
    flattened with `ravel_pytree`, optimized, and written back.
    """

    def __init__(self, model, algorithm: OptimizationAlgorithm
                 = OptimizationAlgorithm.CONJUGATE_GRADIENT, *,
                 max_iterations: int = 100, tolerance: float = 1e-6,
                 line_search: Optional[BackTrackLineSearch] = None):
        algorithm = OptimizationAlgorithm(algorithm)
        if algorithm == OptimizationAlgorithm.STOCHASTIC_GRADIENT_DESCENT:
            raise ValueError("SGD runs through the containers' jitted train "
                             "step (fit); Solver handles the line-search family")
        self.model = model
        self.algorithm = algorithm
        self.optimizer = _SOLVERS[algorithm](
            max_iterations=max_iterations, tolerance=tolerance,
            **({"line_search": line_search} if line_search else {}))
        self._loss_fn = None
        self._unravel = None

    def optimize(self, x, y, fmask=None, lmask=None) -> float:
        """Full-batch optimization of the model's loss on (x, y).
        Updates model.params (and stateful-layer state, e.g. BatchNorm
        running stats) in place; returns the final score.

        The loss runs in train mode with rng=None — deterministic (no
        dropout/weight noise, which would break the line search) but
        including train-only terms (BN batch stats, MoE aux loss).
        `model.net_state` is a jit *argument*, never a baked-in
        constant, so interleaving with SGD fit() stays consistent.

        x/y/fmask/lmask are whatever the container's `_loss_fn` takes
        (one array each, or one sequence entry per graph input/output;
        an omitted mask is None). The loss closure is built once and
        jitted with the batch as an argument, so repeated calls (one per
        fit() minibatch) reuse the compiled step."""
        model = self.model
        if self._loss_fn is None:
            _, unravel = ravel_pytree(model.params)
            self._unravel = unravel

            def loss_full(flat, state, x, y, fm, lm):
                loss, aux = model._loss_fn(unravel(flat), state, x, y,
                                           None, fm, lm, train=True)
                return loss, aux[0]  # (new_state, carries) → state
            self._loss_full = jax.jit(loss_full)
            self._loss_fn = lambda flat, *a: loss_full(flat, *a)[0]

        args = (model.net_state,) + jax.tree_util.tree_map(
            jnp.asarray, (x, y, fmask, lmask))
        flat0, _ = ravel_pytree(model.params)
        flat = self.optimizer.optimize(self._loss_fn, flat0, *args)
        model.params = jax.tree_util.tree_map(
            lambda a, b: b.astype(a.dtype),
            model.params, self._unravel(flat))
        # one more evaluation at the solution to refresh layer state
        loss, new_state = self._loss_full(flat, *args)
        model.net_state = {**model.net_state, **new_state}
        model.score_value = float(loss)
        return model.score_value

    @property
    def scores(self):
        return self.optimizer.scores
