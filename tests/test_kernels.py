"""Pallas kernel parity tests (the reference's accelerated-path
validation pattern: `CuDNNGradientChecks`, `ValidateCudnnLSTM` — helper
vs built-in on identical inputs). Interpret mode on CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.kernels import flash_attention
from deeplearning4j_tpu.kernels.flash_attention import _xla_attention


@pytest.fixture(scope="module")
def qkv():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    return tuple(jax.random.normal(k, (2, 64, 2, 16)) for k in ks)


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("blocks", [(32, 32), (16, 64), (64, 16)])
    def test_forward_parity(self, qkv, causal, blocks):
        q, k, v = qkv
        bq, bk = blocks
        got = flash_attention(q, k, v, causal, bq, bk, True)
        want = _xla_attention(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-5)

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("T", [40, 100, 129])
    def test_ragged_tail_blocks(self, T, causal):
        # T not divisible by 32 → padded tail block must not corrupt
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        q, k, v = (jax.random.normal(kk, (1, T, 2, 8)) for kk in ks)
        got = flash_attention(q, k, v, causal, 32, 32, True)
        want = _xla_attention(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-5)

    def test_ragged_backward_parity(self):
        ks = jax.random.split(jax.random.PRNGKey(2), 3)
        q, k, v = (jax.random.normal(kk, (1, 40, 2, 8)) for kk in ks)

        def loss_flash(q_, k_, v_):
            return jnp.sum(flash_attention(q_, k_, v_, True, 32, 32, True) ** 2)

        def loss_ref(q_, k_, v_):
            return jnp.sum(_xla_attention(q_, k_, v_, True) ** 2)

        g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)

    def test_layer_flash_path_ragged_seq_grad(self):
        # MultiHeadAttention routed through the flash path at T=40:
        # forward parity AND gradient check vs the XLA path.
        from deeplearning4j_tpu.nn.layers import MultiHeadAttention
        layer_flash = MultiHeadAttention(n_in=8, n_out=8, n_heads=2,
                                         causal=True, use_flash=True)
        layer_xla = MultiHeadAttention(n_in=8, n_out=8, n_heads=2,
                                       causal=True, use_flash=False)
        params = layer_flash.init_params(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 40, 8))
        y1, _ = layer_flash.forward(params, {}, x)
        y2, _ = layer_xla.forward(params, {}, x)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                                   rtol=1e-4, atol=1e-5)

        def loss(layer):
            def f(p):
                y, _ = layer.forward(p, {}, x)
                return jnp.sum(y ** 2)
            return f

        g1 = jax.grad(loss(layer_flash))(params)
        g2 = jax.grad(loss(layer_xla))(params)
        for name in g1:
            # atol=5e-5, not 1e-5: the Pallas flash backward accumulates
            # blockwise (different order than the XLA vjp), so grads that
            # are analytic zeros by softmax shift-invariance (bk here,
            # magnitude ~1e-6 against W-grads of ~1e2) sit at the fp32
            # cancellation noise floor rather than matching bitwise
            np.testing.assert_allclose(np.asarray(g1[name]),
                                       np.asarray(g2[name]),
                                       rtol=1e-4, atol=5e-5)

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("blocks", [(32, 32), (16, 64), (64, 16)])
    def test_backward_parity(self, qkv, causal, blocks):
        # exercises BOTH Pallas backward kernels (dq and dk/dv) against
        # the XLA vjp across unequal block sizes — q-time and k-time are
        # padded independently per kernel (T=64 with bq=16/bk=64 pads
        # each axis to its own block multiple)
        q, k, v = qkv
        bq, bk = blocks

        def loss_flash(q_, k_, v_):
            return jnp.sum(
                flash_attention(q_, k_, v_, causal, bq, bk, True) ** 2)

        def loss_ref(q_, k_, v_):
            return jnp.sum(_xla_attention(q_, k_, v_, causal) ** 2)

        g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)

    def test_no_padding_blowup_between_default_blocks(self):
        # q-time and k-time pad INDEPENDENTLY (to a bq / bk multiple),
        # so T strictly between the default block sizes can never
        # balloon the buffers (an earlier joint-lcm padding scheme
        # blew T=600 up to 38400)
        from deeplearning4j_tpu.kernels.flash_attention import _ceil_to
        for T in (600, 513, 1000, 1500):
            bq = min(512, T)
            bk = min(1024, T)
            assert _ceil_to(T, bq) < 2 * T
            assert _ceil_to(T, bk) < 2 * T

    def test_default_blocks_between_window_parity(self):
        # T=600 runs through the coerced-block path end to end
        ks = jax.random.split(jax.random.PRNGKey(7), 3)
        q, k, v = (jax.random.normal(kk, (1, 600, 1, 8)) for kk in ks)
        got = flash_attention(q, k, v, True)
        want = _xla_attention(q, k, v, True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-5)

    @pytest.mark.parametrize("T", [100, 129])
    def test_backward_ragged_tails(self, T):
        # ragged T through the backward's lcm padding: padded queries and
        # keys must contribute exactly zero to every gradient
        ks = jax.random.split(jax.random.PRNGKey(5), 3)
        q, k, v = (jax.random.normal(kk, (1, T, 2, 8)) for kk in ks)

        def loss_flash(q_, k_, v_):
            return jnp.sum(flash_attention(q_, k_, v_, True, 32, 32, True) ** 2)

        def loss_ref(q_, k_, v_):
            return jnp.sum(_xla_attention(q_, k_, v_, True) ** 2)

        g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=2e-5)

    def test_layer_flash_path_matches_xla_path(self):
        from deeplearning4j_tpu.nn.layers import MultiHeadAttention
        layer_flash = MultiHeadAttention(n_in=16, n_out=16, n_heads=2,
                                         causal=True, use_flash=True)
        layer_xla = MultiHeadAttention(n_in=16, n_out=16, n_heads=2,
                                       causal=True, use_flash=False)
        params = layer_flash.init_params(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 16))
        y1, _ = layer_flash.forward(params, {}, x)
        y2, _ = layer_xla.forward(params, {}, x)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                                   rtol=1e-4, atol=1e-5)


class TestFlashNoFallback:
    """On a TPU the Pallas kernel IS the attention path: a kernel that
    fails to build raises out of the forward in auto mode exactly as
    it does when forced — nothing catches it and substitutes the XLA
    path (a fallback there hid the device for three rounds)."""

    def _layer(self, use_flash):
        import jax
        from deeplearning4j_tpu.nn.conf.inputs import InputType
        from deeplearning4j_tpu.nn.layers.attention import MultiHeadAttention

        layer = MultiHeadAttention(n_in=8, n_out=8, n_heads=2,
                                   use_flash=use_flash)
        layer.set_n_in(InputType.recurrent(8))
        params = layer.init_params(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 6, 8))
        return layer, params, x

    def test_auto_mode_kernel_failure_propagates(self, monkeypatch):
        import jax
        import pytest
        import deeplearning4j_tpu.kernels as kmod

        def boom(*a, **k):
            raise RuntimeError("kernel exploded")

        monkeypatch.setattr(kmod, "flash_attention", boom)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        layer, params, x = self._layer(None)       # auto
        with pytest.raises(RuntimeError, match="kernel exploded"):
            layer.forward(params, {}, x)

    def test_auto_mode_off_tpu_takes_xla_path(self, monkeypatch):
        import deeplearning4j_tpu.kernels as kmod

        def boom(*a, **k):
            raise RuntimeError("kernel must not run off-TPU in auto mode")

        monkeypatch.setattr(kmod, "flash_attention", boom)
        layer, params, x = self._layer(None)
        layer.forward(params, {}, x)               # cpu: XLA path

    def test_forced_flash_failure_surfaces(self, monkeypatch):
        import pytest
        import deeplearning4j_tpu.kernels as kmod

        def boom(*a, **k):
            raise RuntimeError("kernel exploded")

        monkeypatch.setattr(kmod, "flash_attention", boom)
        layer, params, x = self._layer(True)
        with pytest.raises(RuntimeError, match="kernel exploded"):
            layer.forward(params, {}, x)


class TestKernelGate:
    """`kernels_enabled()` — the DL4J_PALLAS_KERNELS switch: off/on
    spellings, TPU-only default, typo'd values loud."""

    def test_env_spellings(self, monkeypatch):
        from deeplearning4j_tpu.kernels import kernels_enabled
        for v in ("0", "off", "false", "no"):
            monkeypatch.setenv("DL4J_PALLAS_KERNELS", v)
            assert kernels_enabled() is False
        for v in ("1", "on", "true", "yes"):
            monkeypatch.setenv("DL4J_PALLAS_KERNELS", v)
            assert kernels_enabled() is True
        monkeypatch.setenv("DL4J_PALLAS_KERNELS", "maybe")
        with pytest.raises(ValueError):
            kernels_enabled()

    def test_default_is_backend_gated(self, monkeypatch):
        from deeplearning4j_tpu.kernels import kernels_enabled
        monkeypatch.delenv("DL4J_PALLAS_KERNELS", raising=False)
        assert kernels_enabled() is (jax.default_backend() == "tpu")


class TestLayerNormKernel:
    """Fused LayerNorm(+residual) vs the jnp reference
    (`layer_norm_reference`) — interpret mode on CPU."""

    def _data(self, D=24, dtype=jnp.float32):
        k = jax.random.PRNGKey(0)
        x = jax.random.normal(k, (2, 40, D), dtype)
        g = (jax.random.normal(jax.random.fold_in(k, 1), (D,), dtype)
             + jnp.asarray(1.0, dtype))
        b = jax.random.normal(jax.random.fold_in(k, 2), (D,), dtype)
        return x, g, b

    def test_forward_parity(self):
        from deeplearning4j_tpu.kernels.layernorm import layer_norm
        from deeplearning4j_tpu.nn.layers.normalization import (
            layer_norm_reference)
        x, g, b = self._data()
        got = layer_norm(x, g, b, 1e-5, 256, True)
        want = layer_norm_reference(x, g, b, 1e-5)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)

    @pytest.mark.parametrize("R", [3, 8, 130])  # ragged row padding
    def test_ragged_rows(self, R):
        from deeplearning4j_tpu.kernels.layernorm import layer_norm
        from deeplearning4j_tpu.nn.layers.normalization import (
            layer_norm_reference)
        k = jax.random.PRNGKey(3)
        x = jax.random.normal(k, (R, 16))
        g = jnp.ones((16,))
        b = jnp.zeros((16,))
        got = layer_norm(x, g, b, 1e-5, 64, True)
        want = layer_norm_reference(x, g, b, 1e-5)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)

    def test_backward_parity(self):
        from deeplearning4j_tpu.kernels.layernorm import layer_norm
        from deeplearning4j_tpu.nn.layers.normalization import (
            layer_norm_reference)
        x, g, b = self._data()

        def lk(x_, g_, b_):
            return jnp.sum(layer_norm(x_, g_, b_, 1e-5, 256, True) ** 2)

        def lr(x_, g_, b_):
            return jnp.sum(layer_norm_reference(x_, g_, b_, 1e-5) ** 2)

        ga = jax.grad(lk, argnums=(0, 1, 2))(x, g, b)
        gb = jax.grad(lr, argnums=(0, 1, 2))(x, g, b)
        for a, c in zip(ga, gb):
            np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                       rtol=2e-4, atol=2e-5)

    def test_residual_fusion_parity(self):
        from deeplearning4j_tpu.kernels.layernorm import (
            residual_layer_norm)
        from deeplearning4j_tpu.nn.layers.normalization import (
            layer_norm_reference)
        x, g, b = self._data()
        h = jax.random.normal(jax.random.PRNGKey(9), x.shape)
        s, y = residual_layer_norm(x, h, g, b, 1e-5, 256, True)
        np.testing.assert_allclose(np.asarray(s), np.asarray(x + h),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(layer_norm_reference(x + h, g, b,
                                                           1e-5)),
            rtol=1e-6, atol=1e-6)

        def lk(x_, h_):
            s_, y_ = residual_layer_norm(x_, h_, g, b, 1e-5, 256, True)
            return jnp.sum(y_ ** 2) + jnp.sum(s_ ** 3)

        def lr(x_, h_):
            s_ = x_ + h_
            return (jnp.sum(layer_norm_reference(s_, g, b, 1e-5) ** 2)
                    + jnp.sum(s_ ** 3))

        ga = jax.grad(lk, argnums=(0, 1))(x, h)
        gb = jax.grad(lr, argnums=(0, 1))(x, h)
        for a, c in zip(ga, gb):
            np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                       rtol=2e-4, atol=2e-5)

    def test_bf16_activations(self):
        # mixed_bf16 policy: bf16 in/out, fp32 row statistics inside
        from deeplearning4j_tpu.kernels.layernorm import layer_norm
        from deeplearning4j_tpu.nn.layers.normalization import (
            layer_norm_reference)
        x, g, b = self._data(dtype=jnp.bfloat16)
        got = layer_norm(x, g, b, 1e-5, 256, True)
        assert got.dtype == jnp.bfloat16
        want = layer_norm_reference(x, g, b, 1e-5)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=2e-2, atol=2e-2)

    def test_layer_dispatch_identical_on_off(self, monkeypatch):
        # the DL4J_PALLAS_KERNELS=0 fallback and the kernel path must
        # agree through the LayerNormalization layer API
        from deeplearning4j_tpu.nn.layers.normalization import (
            LayerNormalization)
        layer = LayerNormalization(n_out=16)
        params = layer.init_params(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 12, 16))
        monkeypatch.setenv("DL4J_PALLAS_KERNELS", "0")
        off, _ = layer.forward(params, {}, x)
        monkeypatch.setenv("DL4J_PALLAS_KERNELS", "1")
        on, _ = layer.forward(params, {}, x)
        np.testing.assert_allclose(np.asarray(on), np.asarray(off),
                                   rtol=1e-6, atol=1e-6)

    def test_transformer_block_fused_residual_on_off(self, monkeypatch):
        from deeplearning4j_tpu.nn.conf.inputs import InputType
        from deeplearning4j_tpu.nn.layers.transformer import (
            TransformerEncoderBlock)
        blk = TransformerEncoderBlock(n_in=16, n_heads=2, use_flash=False)
        blk.set_n_in(InputType.recurrent(16))
        params = blk.init_params(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 16))
        monkeypatch.setenv("DL4J_PALLAS_KERNELS", "0")
        off, _ = blk.forward(params, {}, x)
        monkeypatch.setenv("DL4J_PALLAS_KERNELS", "1")
        on, _ = blk.forward(params, {}, x)
        np.testing.assert_allclose(np.asarray(on), np.asarray(off),
                                   rtol=1e-5, atol=1e-5)


class TestFlashBf16:
    def test_flash_attention_bf16_inputs(self):
        # mixed_bf16 policy feeds the attention kernel bf16 q/k/v —
        # fp32 accumulation inside, parity vs the XLA path in bf16 band
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q, k, v = (jax.random.normal(kk, (2, 64, 2, 16), jnp.bfloat16)
                   for kk in ks)
        got = flash_attention(q, k, v, True, 32, 32, True)
        assert got.dtype == jnp.bfloat16
        want = _xla_attention(q, k, v, True)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=2e-2, atol=2e-2)

    @pytest.mark.parametrize("T, D, blocks, causal", [
        (64, 16, (16, 16), True),      # skipped, interior and diagonal tiles
        (64, 16, (16, 32), True),      # rectangular blocks: run square
        (64, 8, (16, 16), True),       # 1/sqrt(8): scale on the fp32 scores
        (100, 16, (32, 32), True),     # ragged: padded keys and queries
        (300, 16, (None, None), True),  # the chooser's one tile of T
        (64, 16, (32, 32), True),      # the diagonal tile whole
        (128, 16, (64, 64), True),     # the diagonal tile in sub-blocks
        (100, 16, (32, 32), False),    # padding mask on the edge tile only
    ])
    def test_bf16_products_against_fp32_reference(self, T, D, blocks,
                                                   causal):
        # bfloat16 operands into every product (P and dS rounded to it),
        # fp32 statistics and accumulators: forward values and all three
        # gradients against the float32 XLA attention, in bfloat16's band
        ks = jax.random.split(jax.random.PRNGKey(T + D), 4)
        q, k, v = (jax.random.normal(kk, (1, T, 2, D), jnp.bfloat16)
                   for kk in ks[:3])
        w = jax.random.normal(ks[3], (1, T, 2, D))
        bq, bk = blocks

        def loss_flash(q_, k_, v_):
            o = flash_attention(q_, k_, v_, causal, bq, bk, True)
            return jnp.sum(o.astype(jnp.float32) * w), o

        def loss_ref(q_, k_, v_):
            o = _xla_attention(q_, k_, v_, causal)
            return jnp.sum(o * w), o

        f32 = tuple(a.astype(jnp.float32) for a in (q, k, v))
        (_, o1), g1 = jax.value_and_grad(loss_flash, argnums=(0, 1, 2),
                                         has_aux=True)(q, k, v)
        (_, o2), g2 = jax.value_and_grad(loss_ref, argnums=(0, 1, 2),
                                         has_aux=True)(*f32)
        assert o1.dtype == jnp.bfloat16
        for a, b in zip((o1,) + g1, (o2,) + g2):
            a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
            scale = np.abs(b).max()
            np.testing.assert_allclose(a, b, rtol=0, atol=2e-2 * scale)
            assert np.abs(a - b).mean() < 3e-3 * scale

    @pytest.mark.parametrize("T, asked, causal, tiles", [
        (4096, (None, None), True, (1024, 1024)),  # square: static diagonal
        (4096, (None, None), False, (512, 1024)),  # nothing to skip
        (1024, (None, None), True, (1024, 1024)),  # the cell: one tile
        (300, (None, None), True, (300, 300)),     # one tile of T
        (300, (None, None), False, (300, 300)),
        (4096, (512, 1024), True, (512, 512)),     # causal: the smaller
        (4096, (512, 1024), False, (512, 1024)),   # as asked
    ])
    def test_tile_choice_follows_causality(self, T, asked, causal, tiles):
        from deeplearning4j_tpu.kernels.flash_attention import _tiles
        assert _tiles(T, T, *asked, causal) == tiles

    def test_causal_needs_as_many_queries_as_keys(self):
        # square causal tiles leave padded keys unmasked off the
        # diagonal, which is exact only where every padded key lies
        # after every real query
        from deeplearning4j_tpu.kernels.flash_attention import _tiles
        with pytest.raises(ValueError, match="as many queries as keys"):
            _tiles(96, 80, 32, 32, True)
        assert _tiles(96, 80, 32, 32, False) == (32, 32)
        ks = jax.random.split(jax.random.PRNGKey(4), 3)
        q = jax.random.normal(ks[0], (1, 48, 2, 8))
        k, v = (jax.random.normal(kk, (1, 40, 2, 8)) for kk in ks[1:])
        with pytest.raises(ValueError, match="as many queries as keys"):
            flash_attention(q, k, v, True, 16, 16, True)

    @pytest.mark.parametrize("b, split, sub", [
        (1024, 4, 256), (512, 4, 128), (64, 4, 16), (32, 4, 32),
        (600, 4, 600), (48, 4, 48), (1024, 2, 512), (32, 2, 16),
        (1024, 1, 1024),
    ])
    def test_diagonal_tile_sub_blocks(self, b, split, sub):
        # the diagonal tile is cut `split` ways a side where each piece
        # is whole bfloat16 sublane tiles; otherwise it runs whole, masked
        from deeplearning4j_tpu.kernels.flash_attention import _sub_block
        assert _sub_block(b, split) == sub

    @pytest.mark.parametrize("i, j, k_tile, q_tile", [
        (0, 0, 0, 0), (0, 3, 0, 3), (1, 2, 1, 2), (3, 3, 3, 3),
        (2, 0, 0, 2), (3, 1, 1, 3),
    ])
    def test_skipped_steps_repeat_the_resident_block(self, i, j, k_tile,
                                                     q_tile):
        # causal, 4 x 4 square tiles, grid step (i, j): a step the
        # kernel skips maps its operand to a block a neighbouring real
        # step holds, so it copies nothing (forward / dQ, query tile i:
        # key steps past tile i repeat it; dK/dV, key tile i: query
        # steps before tile i repeat it)
        from deeplearning4j_tpu.kernels.flash_attention import (
            _k_index_map, _q_index_map)
        assert int(_k_index_map(True)(0, 0, i, j)[2]) == k_tile
        assert int(_q_index_map(True)(0, 0, i, j)[2]) == q_tile
        assert _k_index_map(False)(0, 0, i, j)[2] == j


class TestPagedDecodeGroupedWindowed:
    """`dl4tpu_paged_decode` with fewer key heads than query heads and a
    first position (`nn/layers/parallel.py`'s decode step), interpret
    mode, against a plain numpy softmax a slot and a head at a time.
    Every pool position no slot may read (before its first position,
    past its length, other blocks) holds 1e30."""

    @staticmethod
    def _case(rng, n_heads, n_kv, dh, bl, window, budget=96):
        S = 6
        max_blocks = budget // bl
        ring = (min(max_blocks, -(-window // bl) + 1) if window
                else max_blocks)
        pos = np.array([0, 7, 8, 45, 95, 30], np.int32)
        live = np.array([1, 1, 1, 1, 1, 0], bool)
        lengths = np.where(live, pos + 1, 0).astype(np.int32)
        starts = (np.maximum(lengths - window, 0) if window
                  else np.zeros(S)).astype(np.int32)
        nb = 1 + S * ring
        table = np.zeros((S, ring), np.int32)
        ids = rng.permutation(np.arange(1, nb))
        c = 0
        for s in np.flatnonzero(live):
            n = min(-(-int(lengths[s]) // bl), ring)
            table[s, :n] = ids[c:c + n]
            c += n
        kp = np.full((nb, bl, n_kv * dh), 1e30, np.float32)
        vp = kp.copy()
        for s in range(S):
            for p in range(int(starts[s]), int(lengths[s])):
                b = table[s, (p // bl) % ring]
                kp[b, p % bl] = rng.standard_normal(n_kv * dh)
                vp[b, p % bl] = rng.standard_normal(n_kv * dh)
        q = rng.standard_normal((S, 1, n_heads * dh)).astype(np.float32)
        return q, kp, vp, table, lengths, starts, ring

    @staticmethod
    def _plain(q, kp, vp, table, lengths, starts, ring, n_heads, n_kv):
        S, bl = q.shape[0], kp.shape[1]
        dh = kp.shape[2] // n_kv
        out = np.zeros((S, n_heads, dh), np.float32)
        for s in range(S):
            ps = np.arange(int(starts[s]), int(lengths[s]))
            if not len(ps):
                continue
            blk = table[s, (ps // bl) % ring]
            k = kp[blk, ps % bl].reshape(len(ps), n_kv, dh)
            v = vp[blk, ps % bl].reshape(len(ps), n_kv, dh)
            for h in range(n_heads):
                g = h // (n_heads // n_kv)
                sc = k[:, g] @ q[s, 0].reshape(n_heads, dh)[h] / np.sqrt(dh)
                p = np.exp(sc - sc.max())
                out[s, h] = (p / p.sum()) @ v[:, g]
        return out.reshape(S, 1, n_heads * dh)

    @pytest.mark.parametrize("n_heads,n_kv,dh,window,dtype,tol", [
        (4, 1, 128, None, jnp.float32, 2e-5),    # one key head, from 0
        (4, 2, 128, None, jnp.float32, 2e-5),    # two key heads, from 0
        (4, 2, 128, 20, jnp.float32, 2e-5),      # first position past a
        (4, 1, 128, 20, jnp.float32, 2e-5),      #   block's edge, a ring
        (2, 2, 64, 20, jnp.float32, 2e-5),       # as many key heads: the
        (2, 2, 64, None, jnp.float32, 2e-5),     #   block-diagonal variant
        (32, 2, 128, 20, jnp.bfloat16, 3e-2),
        (16, 8, 16, None, jnp.float32, 2e-5),    # key heads narrower than
        (16, 8, 16, 20, jnp.float32, 2e-5),      #   the lanes: blocks of
        (32, 8, 64, None, jnp.bfloat16, 3e-2),   #   G rows (LFM2's shape)
    ])
    def test_matches_a_plain_softmax(self, n_heads, n_kv, dh, window,
                                     dtype, tol):
        from deeplearning4j_tpu.kernels.paged_attention import (
            paged_decode_attention)
        rng = np.random.default_rng(0)
        q, kp, vp, table, lengths, starts, ring = self._case(
            rng, n_heads, n_kv, dh, 8 if dtype == jnp.float32 else 16,
            window)
        got = paged_decode_attention(
            jnp.asarray(q, dtype), jnp.asarray(kp, dtype),
            jnp.asarray(vp, dtype), jnp.asarray(table), jnp.asarray(lengths),
            n_heads=n_heads, n_kv_heads=n_kv,
            starts=None if window is None else jnp.asarray(starts),
            interpret=True)
        want = self._plain(
            np.asarray(jnp.asarray(q, dtype), np.float32),
            np.asarray(jnp.asarray(kp, dtype), np.float32),
            np.asarray(jnp.asarray(vp, dtype), np.float32),
            table, lengths, starts, ring, n_heads, n_kv)
        got = np.asarray(got, np.float32)
        assert np.isfinite(got).all()                # no 1e30 was read
        np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
        assert not got[5].any()                      # length 0: zeros

    def test_group_size_is_no_part_of_the_answer(self):
        from deeplearning4j_tpu.kernels.paged_attention import (
            paged_decode_attention)
        q, kp, vp, table, lengths, starts, _ = self._case(
            np.random.default_rng(1), 4, 2, 128, 8, 20)
        args = [jnp.asarray(a) for a in (q, kp, vp, table, lengths)]
        a, b = (paged_decode_attention(
            *args, n_heads=4, n_kv_heads=2, starts=jnp.asarray(starts),
            group_positions=g, interpret=True) for g in (8, 32))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-6)

    def test_shapes_it_cannot_tile_say_why(self):
        from deeplearning4j_tpu.kernels.paged_attention import (
            unsupported_reason)
        assert unsupported_reason((2081, 64, 1024), jnp.bfloat16, 128, 8) \
            is None
        assert "grouped-query" in unsupported_reason(
            (40, 8, 128), jnp.float32, 4, 2)         # head_dim 64
        assert "multiple of n_kv_heads" in unsupported_reason(
            (40, 8, 384), jnp.float32, 4, 3)
        # 8 key heads of 64: the block-diagonal form takes them
        assert unsupported_reason((5633, 64, 512), jnp.bfloat16, 32, 8) \
            is None


class TestPagedDecodeKernel:
    """`dl4tpu_paged_decode` (interpret mode) against the plain
    reference it replaces on the chip: gather by block table +
    `MultiHeadAttention._attend_cached`, through the layer's own
    single-token entry point, so the new token's write, the `live`
    mask and the `Wo` projection are in the comparison."""

    D, HEADS, BUDGET = 128, 2, 64          # Dh = 64

    def _layer(self):
        from deeplearning4j_tpu.nn.layers.attention import (
            MultiHeadAttention)
        mha = MultiHeadAttention(n_in=self.D, n_out=self.D,
                                 n_heads=self.HEADS, causal=True)
        return mha, mha.init_params(jax.random.PRNGKey(4))

    def _case(self, dtype, bl, *, poison=False, d=None):
        """Five decoding slots at ragged depths — 0, both sides of a
        block boundary, mid-stream, the last position of the budget —
        and two idle ones whose table is all GARBAGE_BLOCK under a
        stale `pos`. `poison` fills every pool position a slot does
        not hold (past its `pos`, unowned blocks, the garbage block)
        with 1e30."""
        from deeplearning4j_tpu.serving import GARBAGE_BLOCK
        d = d or self.D
        rng = np.random.default_rng(7)
        max_blocks = self.BUDGET // bl
        pos = np.array([0, 15, 16, 37, self.BUDGET - 1, 23,
                        self.BUDGET - 1], np.int32)
        live = np.array([1, 1, 1, 1, 1, 0, 0], bool)
        S = len(pos)
        n_blocks = 1 + 5 * max_blocks + 3
        ids = rng.permutation(np.arange(1, n_blocks))
        table = np.full((S, max_blocks), GARBAGE_BLOCK, np.int32)
        fill = 1e30 if poison else 0.0
        pools = [np.full((n_blocks, bl, d), fill, np.float32)
                 for _ in range(2)]
        c = 0
        held = np.random.default_rng(11)       # same draws, poison or not
        for s in np.flatnonzero(live):
            n = -(-(int(pos[s]) + 1) // bl)
            table[s, :n] = ids[c:c + n]
            c += n
            for p in range(int(pos[s])):       # what the slot holds
                for pool in pools:
                    pool[table[s, p // bl], p % bl] = (
                        held.standard_normal(d))
        x = jnp.asarray(rng.standard_normal((S, 1, d)), dtype)
        return (x, jnp.asarray(pools[0], dtype),
                jnp.asarray(pools[1], dtype), jnp.asarray(table),
                jnp.asarray(pos), jnp.asarray(live))

    def _run(self, monkeypatch, kernels, mha, params, case):
        monkeypatch.setenv("DL4J_PALLAS_KERNELS", kernels)
        x, k_pool, v_pool, table, pos, live = case
        params = jax.tree_util.tree_map(
            lambda a: a.astype(x.dtype), params)
        y, k_new, v_new = mha.forward_with_paged_cache(
            params, x, k_pool, v_pool, table, pos, live)
        return (np.asarray(y.astype(jnp.float32)), np.asarray(live),
                k_new, v_new)

    @pytest.mark.parametrize("dtype,bl,tol,poison", [
        (jnp.float32, 8, 2e-5, False),
        (jnp.float32, 16, 2e-5, False),
        (jnp.bfloat16, 16, 3e-2, False),
        (jnp.float32, 8, 2e-5, True),
        (jnp.bfloat16, 16, 3e-2, True),
    ])
    def test_matches_gather_and_reads_nothing_past_the_length(
            self, monkeypatch, dtype, bl, tol, poison):
        mha, params = self._layer()
        clean = self._case(dtype, bl)
        assert mha.paged_decode_in_place(clean[1]) is False  # CPU default
        want, live, k_ref, v_ref = self._run(monkeypatch, "0", mha,
                                             params, clean)
        got, _, k_new, v_new = self._run(monkeypatch, "1", mha, params,
                                         clean)
        assert mha.paged_decode_in_place(clean[1]) is True
        np.testing.assert_allclose(got[live], want[live], rtol=tol,
                                   atol=tol)
        # both cores write the new token's K and V the same way
        np.testing.assert_array_equal(np.asarray(k_new), np.asarray(k_ref))
        np.testing.assert_array_equal(np.asarray(v_new), np.asarray(v_ref))
        # a slot that is not decoding reads nothing: zeros through Wo
        idle = np.asarray(params["bo"], np.float32)
        np.testing.assert_allclose(got[~live],
                                   np.broadcast_to(idle, got[~live].shape),
                                   atol=tol)
        if poison:
            # 1e30 in every position no slot holds: any read past a
            # slot's length, of K or of V, would move the output
            dirty, _, _, _ = self._run(monkeypatch, "1", mha, params,
                                       self._case(dtype, bl, poison=True))
            assert np.isfinite(dirty).all()
            np.testing.assert_array_equal(dirty, got)

    @pytest.mark.parametrize("dtype,bl,d,why", [
        (jnp.bfloat16, 8, 128, "sublane tile"),
        (jnp.float32, 4, 128, "sublane tile"),
        (jnp.float32, 8, 96, "128 lanes"),
    ])
    def test_shapes_the_kernel_cannot_tile_take_the_gather_path(
            self, monkeypatch, caplog, dtype, bl, d, why):
        from deeplearning4j_tpu.kernels import paged_attention
        from deeplearning4j_tpu.nn.layers import attention
        from deeplearning4j_tpu.nn.layers.attention import (
            MultiHeadAttention)
        mha = MultiHeadAttention(n_in=d, n_out=d, n_heads=self.HEADS,
                                 causal=True)
        params = mha.init_params(jax.random.PRNGKey(4))
        case = self._case(dtype, bl, d=d)
        assert why in paged_attention.unsupported_reason(
            case[1].shape, case[1].dtype, self.HEADS)
        with pytest.raises(ValueError, match=why):
            paged_attention.paged_decode_attention(
                case[0], case[1], case[2], case[3], case[4] + 1,
                n_heads=self.HEADS)
        want, _, _, _ = self._run(monkeypatch, "0", mha, params, case)
        monkeypatch.setattr(attention, "_PAGED_FALLBACK_WARNED", set())
        with caplog.at_level("WARNING", logger=attention.__name__):
            got, _, _, _ = self._run(monkeypatch, "1", mha, params, case)
            self._run(monkeypatch, "1", mha, params, case)
        np.testing.assert_array_equal(got, want)     # the same core
        said = [r for r in caplog.records
                if "fell back to the gather path" in r.getMessage()]
        assert len(said) == 1 and why in said[0].getMessage()


class TestGroupedSwiGLU:
    """`dl4tpu_grouped_swiglu` (interpret mode) inside
    `moe.held_experts_swiglu` against XLA's `ragged_dot` path at small
    widths with each expert configuration's F / D: which path a row count
    takes, the layer's result, the blocks the grid reads, the gradient."""

    # (D, F) at 128 columns with the configuration's F / D: LFM2 1,792 /
    # 2,048, sarvam 2,048 / 4,096, command-a 4,096 / 4,096
    WIDTHS = {"lfm2": (128, 112), "sarvam": (128, 64),
              "command-a": (128, 128)}
    E = 8

    def _case(self, widths, n, k, route, dtype=jnp.float32):
        D, F = self.WIDTHS[widths]
        E = self.E
        ks = jax.random.split(jax.random.PRNGKey(n * 7 + len(route)), 6)
        h = jax.random.normal(ks[0], (n, D), jnp.float32).astype(dtype)
        w = [(0.1 * jax.random.normal(kk, s, jnp.float32)).astype(dtype)
             for kk, s in zip(ks[1:4], ((E, D, F), (E, D, F), (E, F, D)))]
        gates = jax.random.uniform(ks[4], (n, k), jnp.float32, 0.1, 1.0)
        valid = None
        if route == "empty first and last":
            chosen = jax.random.randint(ks[5], (n, k), 1, E - 1)
        elif route == "one group":
            chosen = jnp.full((n, k), 3)
        elif route == "no routed rows":
            chosen = jax.random.randint(ks[5], (n, k), 0, E)
            valid = jnp.zeros((n,), bool)
        elif route == "rows of no group":
            # experts E .. E+3 are another chip's, the last tokens idle
            chosen = jax.random.randint(ks[5], (n, k), 0, E + 4)
            valid = jnp.arange(n) < n - 3
        else:
            chosen = jax.random.randint(ks[5], (n, k), 0, E)
        return h, chosen.astype(jnp.int32), gates, w, valid

    @staticmethod
    def _layer(monkeypatch, kernels_on, case):
        from deeplearning4j_tpu.nn.layers import moe
        h, chosen, gates, w, valid = case
        monkeypatch.setenv("DL4J_PALLAS_KERNELS", "1" if kernels_on else "0")

        def fn(h, gates, wg, wu, wd, stats=None):
            return moe.held_experts_swiglu(h, chosen, gates, wg, wu, wd,
                                           first=0, valid=valid, stats=stats)
        args = (h, gates, *w)
        return fn, args, str(jax.make_jaxpr(fn)(*args))

    @pytest.mark.parametrize("widths,n,k,route,dtype", [
        ("lfm2", 12, 4, "empty first and last", jnp.float32),
        ("sarvam", 12, 4, "one group", jnp.float32),
        ("command-a", 12, 4, "no routed rows", jnp.float32),
        ("lfm2", 12, 4, "rows of no group", jnp.float32),
        ("sarvam", 9, 4, "spread", jnp.bfloat16),
        ("command-a", 256, 4, "spread", jnp.float32),   # at the bound
        ("lfm2", 260, 4, "spread", jnp.float32),        # a row tile past
    ])
    def test_kernel_path_matches_ragged_dot(self, monkeypatch, widths, n, k,
                                            route, dtype):
        from deeplearning4j_tpu.kernels import grouped_swiglu as gs
        assert 256 * 4 == gs.MAX_ROWS
        case = self._case(widths, n, k, route, dtype)
        fn, args, xla_jaxpr = self._layer(monkeypatch, False, case)
        want = np.asarray(jax.jit(fn)(*args), np.float32)
        fn, args, jaxpr = self._layer(monkeypatch, True, case)
        got = np.asarray(jax.jit(fn)(*args), np.float32)
        assert "ragged_dot" in xla_jaxpr and "pallas_call" not in xla_jaxpr
        if n * k <= gs.MAX_ROWS:
            assert "pallas_call" in jaxpr and "ragged_dot" not in jaxpr
        else:
            assert jaxpr == xla_jaxpr
        assert np.isfinite(got).all()
        stats = {}
        fn(*args, stats=stats)
        kernel = n * k <= gs.MAX_ROWS
        assert float(stats["moe_kernel_rows"]) == (
            float(stats["moe_rows"]) if kernel else 0.0)
        if route == "no routed rows":
            assert not got.any() and not want.any()
        scale = max(float(np.abs(want).max()), 1e-6)
        tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
        np.testing.assert_allclose(got, want, atol=tol * scale, rtol=0)

    @pytest.mark.parametrize("sizes,nf", [
        ([0, 3, 0, 0, 2, 0], 2), ([5, 0, 0, 0], 3), ([0, 0, 0, 0], 2),
        ([1, 1, 1, 1], 1), ([0, 0, 0, 4], 4),
    ])
    def test_an_expert_with_no_rows_reads_no_weights(self, sizes, nf):
        """Walking the grid in order, the weight block changes (a DMA)
        only at the steps of an expert with rows, which read their own
        blocks; a call with no rows at all reads one block."""
        from deeplearning4j_tpu.kernels import grouped_swiglu as gs
        starts, blk = gs.group_blocks(jnp.asarray(sizes, jnp.int32), nf)
        seen, fetches = None, 0
        for e in range(len(sizes)):
            for j in range(nf):
                b = tuple(int(x) for x in gs.weight_block(
                    e, j, starts, blk, nf=nf))
                if sizes[e]:
                    assert b == (e, j)
                fetches += b != seen
                seen = b
        assert fetches == max(1, nf * sum(s > 0 for s in sizes))

    def test_gradient_through_the_layer_is_the_ragged_dot_paths(
            self, monkeypatch):
        case = self._case("lfm2", 12, 4, "rows of no group")
        cot = jax.random.normal(jax.random.PRNGKey(9), case[0].shape)

        def grads(on):
            fn, args, _ = self._layer(monkeypatch, on, case)
            g = jax.grad(lambda *a: jnp.sum(fn(*a) * cot),
                         argnums=tuple(range(5)))
            text = str(jax.make_jaxpr(g)(*args))
            return jax.jit(g)(*args), text
        want, xla_text = grads(False)
        got, text = grads(True)
        assert "pallas_call" in text and "pallas_call" not in xla_text
        for a, b in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)
