"""Mixture-of-Experts layer.

No reference equivalent (SURVEY §2.13: expert parallelism ❌ in the
2017 codebase); first-class here because the mesh design reserves an
"expert" axis. Dense dispatch formulation: router softmax over E
experts, top-k gating renormalised, expert FFNs applied via a single
einsum over stacked expert params — no capacity/overflow logic, so the
whole layer is static-shape XLA. Expert parallelism = sharding the
leading expert axis of "We1"/"We2" over the "expert" mesh axis (see
`parallel.tensor.moe_param_specs`); GSPMD turns the einsum into
all-to-all style collectives without changing the math.

Param names: "Wg" router [F, E]; experts "We1" [E, F, H], "be1" [E, H],
"We2" [E, H, F], "be2" [E, F].
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.common.weights import init_weights
from deeplearning4j_tpu.kernels import grouped_swiglu, kernels_enabled
from deeplearning4j_tpu.nn.conf.inputs import InputType, InputTypeRecurrent
from deeplearning4j_tpu.nn.layers.base import Layer, register_layer


@register_layer
@dataclasses.dataclass(eq=False)
class MixtureOfExperts(Layer):
    layer_name = "mixture_of_experts"

    # forward emits a fresh "aux_loss" state key the containers' loss
    # consumes — a stacked-params scan carry cannot thread that, so MoE
    # stacks stay on the unrolled path (same exclusion the pipeline
    # container enforces)
    stackable_params = False

    n_in: int = 0
    n_out: int = 0          # defaults to n_in
    n_experts: int = 4
    hidden_size: int = 0    # expert FFN hidden dim (defaults to 4*n_in)
    top_k: int = 2
    load_balance_coef: float = 0.01

    def __post_init__(self):
        if self.activation is None:
            self.activation = "relu"  # expert hidden activation
        super().__post_init__()

    def set_n_in(self, input_type, override=True):
        size = input_type.size if isinstance(input_type, InputTypeRecurrent) \
            else input_type.arity()
        if override or not self.n_in:
            self.n_in = size
        if not self.n_out:
            self.n_out = self.n_in
        if not self.hidden_size:
            self.hidden_size = 4 * self.n_in

    def get_output_type(self, input_type):
        if isinstance(input_type, InputTypeRecurrent):
            return InputType.recurrent(self.n_out, input_type.timesteps)
        return InputType.feed_forward(self.n_out)

    def init_params(self, rng, dtype=jnp.float32):
        E, F, H, O = self.n_experts, self.n_in, self.hidden_size, self.n_out
        ks = jax.random.split(rng, 3)
        we1 = jnp.stack([init_weights(jax.random.fold_in(ks[1], e), (F, H),
                                      self.weight_init, fan_in=F, fan_out=H,
                                      distribution=self.dist, dtype=dtype)
                         for e in range(E)])
        we2 = jnp.stack([init_weights(jax.random.fold_in(ks[2], e), (H, O),
                                      self.weight_init, fan_in=H, fan_out=O,
                                      distribution=self.dist, dtype=dtype)
                         for e in range(E)])
        return {
            "Wg": init_weights(ks[0], (F, E), self.weight_init, fan_in=F,
                               fan_out=E, distribution=self.dist, dtype=dtype),
            "We1": we1, "be1": jnp.zeros((E, H), dtype),
            "We2": we2, "be2": jnp.zeros((E, O), dtype),
        }

    def _gate(self, params, x):
        """Top-k renormalised gates [..., E] + load-balance aux loss."""
        logits = x @ params["Wg"]
        probs = jax.nn.softmax(logits, axis=-1)
        if self.top_k < self.n_experts:
            kth = jnp.sort(probs, axis=-1)[..., -self.top_k][..., None]
            gates = jnp.where(probs >= kth, probs, 0.0)
            gates = gates / jnp.clip(jnp.sum(gates, axis=-1, keepdims=True),
                                     1e-9, None)
        else:
            gates = probs
        # Switch-style load balance: E * sum_e fraction_e * prob_e
        flat = probs.reshape(-1, self.n_experts)
        frac = jnp.mean((gates.reshape(-1, self.n_experts) > 0).astype(x.dtype),
                        axis=0)
        aux = self.n_experts * jnp.sum(frac * jnp.mean(flat, axis=0))
        return gates, aux

    def forward(self, params, state, x, *, train=False, rng=None, mask=None):
        x = self.apply_input_dropout(x, train, rng)
        gates, aux = self._gate(params, x)                 # [..., E]
        # all experts on all tokens (dense dispatch), combine by gate
        h = self.activation(jnp.einsum("...f,efh->...eh", x, params["We1"])
                            + params["be1"])
        y = jnp.einsum("...eh,eho->...eo", h, params["We2"]) + params["be2"]
        out = jnp.einsum("...eo,...e->...o", y, gates)
        if train and self.load_balance_coef:
            # thread the aux loss functionally through the returned state;
            # the container's loss fn pops "aux_loss" entries and adds
            # them to the objective (no Python-object mutation under jit)
            state = {**state, "aux_loss": self.load_balance_coef * aux}
        return out, state


# --------------------------------------------------------------------------
# Held experts: the share of a routed expert layer that one chip computes.
#
# Expert parallelism divides a layer's experts over chips; every chip
# routes each of its tokens over ALL the experts (the router keeps its
# published width), then computes the tokens routed to the experts it
# holds.  The functions below are that chip's part: no capacity, no
# dropped token, rows sorted by expert and multiplied in groups
# (`kernels/grouped_swiglu.py`), never every expert on every token.  What
# the absent experts would add is left out: on one chip the layer runs
# without its exchange, and nothing here stands in for it.
# --------------------------------------------------------------------------
def sigmoid_topk_route(h, router, bias, top_k: int, scaling: float,
                       eps: float = 0.0):
    """h [N, D] -> (chosen [N, k] expert ids, gates [N, k] float32).
    Scores are `sigmoid(h router)` in float32; the `top_k` largest of
    `score + bias` are chosen (the bias chooses and does not weigh:
    DeepSeek-V3's auxiliary-loss-free balancing); the gates are the
    chosen scores over their sum (plus `eps` where a family's code adds
    one to the sum) and scaled."""
    logits = jnp.matmul(h, router, preferred_element_type=jnp.float32)
    score = jax.nn.sigmoid(logits)
    _, chosen = jax.lax.top_k(score + bias.astype(jnp.float32), top_k)
    picked = jnp.take_along_axis(score, chosen, axis=-1)
    if eps:
        gates = scaling * picked / (jnp.sum(picked, axis=-1, keepdims=True)
                                    + eps)
    else:
        gates = scaling * picked / jnp.sum(picked, axis=-1, keepdims=True)
    return chosen.astype(jnp.int32), gates


def held_expert_groups(chosen, valid, first: int, count: int):
    """Sort the (token, chosen expert) pairs by held expert.  -> (order
    [N*k]: pair indices, the held experts' pairs first, grouped by
    expert; sizes [count]: pairs of each held expert; held [N, k]: which
    pairs a held expert serves).  A pair whose expert is absent, or whose
    token is not `valid` (padding, an idle slot), belongs to no group and
    sorts last."""
    local = chosen - first
    held = (local >= 0) & (local < count)
    if valid is not None:
        held = held & valid[:, None]
    key = jnp.where(held, local, count).reshape(-1)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.sum(key[:, None] == jnp.arange(count)[None, :], axis=0,
                    dtype=jnp.int32)
    return order, sizes, held


def held_experts_swiglu(h, chosen, gates, w_gate, w_up, w_down, *,
                        first: int, valid=None, stats=None):
    """The held experts' part of `sum_e g_e SwiGLU_e(h)` for h [N, D]:
    w_gate, w_up [E, D, F], w_down [E, F, D] are the experts
    `first .. first+E-1` of the layer.  -> y [N, D] in h.dtype; `stats`
    (a dict, or None) gets the layer's load added (`record_load`).

    The grouped products run in `dl4tpu_grouped_swiglu`, which reads each
    touched expert's weights once and an untouched one's not at all,
    where the `N x k` rows fit it (every decode dispatch) and kernels
    run; XLA's `ragged_dot` otherwise (a long prefill wave)."""
    N, k = chosen.shape
    E = w_gate.shape[0]
    order, sizes, held = held_expert_groups(chosen, valid, first, E)
    rows = h[order // k]                                     # [N*k, D]
    kernel = N * k <= grouped_swiglu.MAX_ROWS and kernels_enabled()
    y = (grouped_swiglu.grouped_swiglu if kernel
         else grouped_swiglu.grouped_swiglu_xla)(rows, sizes, w_gate, w_up,
                                                 w_down)     # [N*k, D]
    record_load(stats, sizes, kernel)
    # back to (token, chosen) order, then each token's held experts are
    # weighed and summed in float32. Pairs of no group are selected out,
    # not multiplied by nought: their rows are whatever the grouped
    # product left there
    y = y[jnp.argsort(order)].reshape(N, k, -1)
    weight = jnp.where(held, gates, 0.0)[..., None]
    out = jnp.sum(jnp.where(weight != 0, y.astype(jnp.float32) * weight,
                            0.0), axis=1)
    return out.astype(h.dtype)


def expert_load_stats(sizes):
    """(rows, fullest over mean, touched share) of one dispatch's held
    experts, as float32 scalars: the ratio reads 0 where no row was
    routed here; the share is 100 x the held experts given at least one
    row, which is the share of the held expert weights the dispatch
    reads."""
    rows = jnp.sum(sizes).astype(jnp.float32)
    mean = rows / sizes.shape[0]
    touched = 100.0 * jnp.mean((sizes > 0).astype(jnp.float32))
    return rows, jnp.where(rows > 0, jnp.max(sizes) / jnp.maximum(mean, 1e-9),
                           0.0), touched


def record_load(stats, sizes, kernel: bool):
    """Add one routed expert layer's `expert_load_stats` to `stats` (a
    dict a traced forward hands down, or None), and its rows again under
    `moe_kernel_rows` where `dl4tpu_grouped_swiglu` multiplied them: the
    serving engine reads the means over the layers back with the
    tokens."""
    if stats is None:
        return
    rows, ratio, touched = expert_load_stats(sizes)
    stats["moe_rows"] = stats.get("moe_rows", 0.0) + rows
    stats["moe_load_max_over_mean"] = stats.get(
        "moe_load_max_over_mean", 0.0) + ratio
    stats["moe_touched_pct"] = stats.get("moe_touched_pct", 0.0) + touched
    stats["moe_kernel_rows"] = stats.get("moe_kernel_rows", 0.0) + (
        rows if kernel else 0.0)
    stats["moe_layers"] = stats.get("moe_layers", 0) + 1


def shared_experts_mean(h, w_gate, w_up, w_down, n_shared: int):
    """`(1/n) sum_j SwiGLU_j(h)`: the average of `n_shared` shared
    experts held side by side, w_gate and w_up [D, n*F] (expert j's F
    columns at `j*F`), w_down [n*F, D]: one product `n*F` wide, the
    `1/n` on its output."""
    a = jnp.matmul(h, w_gate)
    y = jnp.matmul(jax.nn.silu(a) * jnp.matmul(h, w_up), w_down)
    return y if n_shared == 1 else y * (1.0 / n_shared)
