"""Float64 finite-difference gradient checks for the transformer stack
(the repo's correctness oracle, reference GradientCheckUtil pattern)."""

import numpy as np
import jax
import jax.numpy as jnp

from deeplearning4j_tpu.gradientcheck import check_gradients_fn
from deeplearning4j_tpu.nn.layers import (
    LayerNormalization,
    TransformerEncoderBlock,
)


class TestTransformerGradients:
    def test_layernorm_gradients(self):
        with jax.enable_x64(True):
            ln = LayerNormalization(n_out=6)
            p = jax.tree_util.tree_map(
                lambda a: jnp.asarray(a, jnp.float64),
                ln.init_params(jax.random.PRNGKey(0)))
            rng = np.random.default_rng(0)
            x = jnp.asarray(rng.standard_normal((3, 6)), jnp.float64)
            t = jnp.asarray(rng.standard_normal((3, 6)), jnp.float64)

            def loss(pp):
                y, _ = ln.forward(pp, {}, x)
                return jnp.sum((y - t) ** 2)

            assert check_gradients_fn(loss, p, max_rel_error=1e-5)

    def test_encoder_block_gradients(self):
        with jax.enable_x64(True):
            blk = TransformerEncoderBlock(n_in=8, n_heads=2, use_flash=False)
            p = jax.tree_util.tree_map(
                lambda a: jnp.asarray(a, jnp.float64),
                blk.init_params(jax.random.PRNGKey(1)))
            rng = np.random.default_rng(1)
            x = jnp.asarray(rng.standard_normal((2, 5, 8)), jnp.float64)
            t = jnp.asarray(rng.standard_normal((2, 5, 8)), jnp.float64)

            def loss(pp):
                y, _ = blk.forward(pp, {}, x)
                return jnp.sum((y - t) ** 2)

            assert check_gradients_fn(loss, p, max_rel_error=1e-4,
                                      max_params_per_array=24)
