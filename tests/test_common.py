"""Tests for activations, losses, updaters, schedules, weight init,
distributions."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.common.activations import ACTIVATIONS, get_activation
from deeplearning4j_tpu.common.losses import (
    LossBinaryXENT,
    LossMCXENT,
    LossMSE,
    get_loss,
    loss_from_dict,
)
from deeplearning4j_tpu.common.schedules import (
    ExponentialSchedule,
    FixedSchedule,
    MapSchedule,
    StepSchedule,
    WarmupCosineSchedule,
    schedule_from_dict,
)
from deeplearning4j_tpu.common.updaters import (
    Adam,
    AdaDelta,
    AdaGrad,
    AdaMax,
    Nadam,
    Nesterovs,
    NoOp,
    RmsProp,
    Sgd,
    updater_from_dict,
)
from deeplearning4j_tpu.common.weights import WeightInit, init_weights
from deeplearning4j_tpu.common.distributions import (
    NormalDistribution,
    OrthogonalDistribution,
    TruncatedNormalDistribution,
    UniformDistribution,
    distribution_from_dict,
)


class TestActivations:
    @pytest.mark.parametrize("name", sorted(ACTIVATIONS))
    def test_finite_and_shape(self, name):
        act = get_activation(name)
        x = jnp.linspace(-3, 3, 32).reshape(4, 8)
        y = act(x)
        assert y.shape == x.shape
        assert bool(jnp.all(jnp.isfinite(y)))

    def test_known_values(self):
        x = jnp.array([-1.0, 0.0, 2.0])
        np.testing.assert_allclose(get_activation("relu")(x), [0, 0, 2])
        np.testing.assert_allclose(get_activation("identity")(x), x)
        np.testing.assert_allclose(get_activation("hardtanh")(x), [-1, 0, 1])
        np.testing.assert_allclose(get_activation("cube")(x), [-1, 0, 8])
        sm = get_activation("softmax")(jnp.array([[1.0, 1.0]]))
        np.testing.assert_allclose(sm, [[0.5, 0.5]], atol=1e-6)

    def test_unknown_raises(self):
        with pytest.raises(ValueError):
            get_activation("nope")


class TestLosses:
    def test_mse_exact(self):
        loss = LossMSE()
        labels = jnp.array([[1.0, 0.0]])
        preout = jnp.array([[0.5, 0.5]])
        v = loss(labels, preout, get_activation("identity"))
        np.testing.assert_allclose(v, (0.25 + 0.25) / 2, atol=1e-6)

    def test_mcxent_softmax_fused_matches_manual(self):
        loss = LossMCXENT()
        labels = jnp.array([[0.0, 1.0, 0.0]])
        preout = jnp.array([[0.1, 2.0, -1.0]])
        fused = loss(labels, preout, get_activation("softmax"))
        probs = jax.nn.softmax(preout)
        manual = -jnp.log(probs[0, 1])
        np.testing.assert_allclose(fused, manual, rtol=1e-3)

    def test_xent_sigmoid_fused_matches_manual(self):
        loss = LossBinaryXENT()
        labels = jnp.array([[1.0, 0.0]])
        preout = jnp.array([[0.3, -0.2]])
        fused = loss(labels, preout, get_activation("sigmoid"))
        p = jax.nn.sigmoid(preout)
        manual = jnp.sum(-(labels * jnp.log(p) + (1 - labels) * jnp.log(1 - p)))
        np.testing.assert_allclose(fused, manual, rtol=1e-5)

    def test_masked_loss(self):
        loss = LossMSE()
        labels = jnp.ones((2, 3))
        preout = jnp.zeros((2, 3))
        mask = jnp.array([1.0, 0.0])
        v = loss(labels, preout, get_activation("identity"), mask=mask)
        np.testing.assert_allclose(v, 1.0, atol=1e-6)  # only first example counts

    def test_serde_roundtrip(self):
        for name in ["mse", "mcxent", "xent", "hinge", "poisson", "kl_divergence"]:
            l = get_loss(name)
            l2 = loss_from_dict(l.to_dict())
            assert type(l2) is type(l)


class TestUpdaters:
    @pytest.mark.parametrize("updater", [
        Sgd(0.1), Adam(0.01), AdaMax(0.01), Nadam(0.01), Nesterovs(0.1, 0.9),
        AdaGrad(0.1), AdaDelta(), RmsProp(0.01), NoOp(),
    ])
    def test_descends_quadratic(self, updater):
        """Each updater should reduce f(x)=||x||² over iterations."""
        x = jnp.array([1.0, -2.0, 3.0])
        state = updater.init_state(x)
        f0 = float(jnp.sum(x * x))
        for it in range(50):
            grad = 2 * x
            delta, state = updater.apply(grad, state, it)
            x = x - delta
        f1 = float(jnp.sum(x * x))
        if isinstance(updater, NoOp):
            assert f1 == f0
        else:
            assert f1 < f0

    def test_sgd_exact(self):
        u = Sgd(0.5)
        delta, _ = u.apply(jnp.array([2.0]), {}, 0)
        np.testing.assert_allclose(delta, [1.0])

    def test_adam_bias_correction_first_step(self):
        u = Adam(learning_rate=1e-3, beta1=0.9, beta2=0.999, epsilon=0.0)
        g = jnp.array([0.5])
        delta, _ = u.apply(g, u.init_state(g), 0)
        # first step with bias correction: update ≈ lr * sign(g)
        np.testing.assert_allclose(delta, [1e-3], rtol=1e-5)

    def test_schedule_lr(self):
        u = Sgd(StepSchedule(1.0, 0.1, 10))
        d0, _ = u.apply(jnp.array([1.0]), {}, 0)
        d1, _ = u.apply(jnp.array([1.0]), {}, 15)
        np.testing.assert_allclose(d0, [1.0], rtol=1e-6)
        np.testing.assert_allclose(d1, [0.1], rtol=1e-5)

    def test_serde_roundtrip(self):
        for u in [Sgd(0.1), Adam(0.01, 0.8, 0.95, 1e-9), Nesterovs(0.2, 0.8),
                  RmsProp(0.3), AdaDelta(0.9, 1e-5), NoOp()]:
            u2 = updater_from_dict(u.to_dict())
            assert u2 == u

    def test_schedule_serde_in_updater(self):
        u = Adam(learning_rate=ExponentialSchedule(0.1, 0.99))
        u2 = updater_from_dict(u.to_dict())
        assert isinstance(u2.learning_rate, ExponentialSchedule)
        np.testing.assert_allclose(float(u2.learning_rate.value_at(10)),
                                   float(u.learning_rate.value_at(10)))


class TestSchedules:
    def test_values(self):
        assert float(FixedSchedule(0.5).value_at(100)) == 0.5
        np.testing.assert_allclose(float(ExponentialSchedule(1.0, 0.5).value_at(2)), 0.25)
        np.testing.assert_allclose(float(StepSchedule(1.0, 0.5, 10).value_at(25)), 0.25)
        m = MapSchedule({0: 1.0, 10: 0.1, 20: 0.01})
        np.testing.assert_allclose(float(m.value_at(5)), 1.0)
        np.testing.assert_allclose(float(m.value_at(15)), 0.1)
        np.testing.assert_allclose(float(m.value_at(99)), 0.01)

    def test_warmup_cosine(self):
        s = WarmupCosineSchedule(1.0, 10, 100)
        assert float(s.value_at(0)) == 0.0
        np.testing.assert_allclose(float(s.value_at(10)), 1.0, atol=1e-6)
        np.testing.assert_allclose(float(s.value_at(100)), 0.0, atol=1e-6)

    def test_serde(self):
        for s in [FixedSchedule(0.1), ExponentialSchedule(1, 0.9),
                  StepSchedule(1, 0.5, 7), MapSchedule({0: 1.0, 5: 0.5}),
                  WarmupCosineSchedule(0.1, 5, 50)]:
            s2 = schedule_from_dict(s.to_dict())
            np.testing.assert_allclose(float(s2.value_at(7)), float(s.value_at(7)))


class TestWeightInit:
    def test_variances(self):
        rng = jax.random.PRNGKey(0)
        n_in, n_out = 400, 300
        w = init_weights(rng, (n_in, n_out), WeightInit.XAVIER, n_in, n_out)
        np.testing.assert_allclose(float(jnp.var(w)), 2.0 / (n_in + n_out), rtol=0.1)
        w = init_weights(rng, (n_in, n_out), WeightInit.RELU, n_in, n_out)
        np.testing.assert_allclose(float(jnp.var(w)), 2.0 / n_in, rtol=0.1)
        w = init_weights(rng, (n_in, n_out), WeightInit.LECUN_NORMAL, n_in, n_out)
        np.testing.assert_allclose(float(jnp.var(w)), 1.0 / n_in, rtol=0.1)

    def test_special(self):
        rng = jax.random.PRNGKey(0)
        assert float(jnp.sum(init_weights(rng, (3, 4), WeightInit.ZERO, 3, 4))) == 0
        assert float(jnp.sum(init_weights(rng, (3, 4), WeightInit.ONES, 3, 4))) == 12
        np.testing.assert_allclose(init_weights(rng, (3, 3), WeightInit.IDENTITY, 3, 3),
                                   jnp.eye(3))

    def test_uniform_bounds(self):
        rng = jax.random.PRNGKey(1)
        w = init_weights(rng, (100, 100), WeightInit.XAVIER_UNIFORM, 100, 100)
        bound = np.sqrt(6.0 / 200)
        assert float(jnp.max(jnp.abs(w))) <= bound + 1e-6


class TestDistributions:
    def test_normal(self):
        d = NormalDistribution(2.0, 0.5)
        s = d.sample(jax.random.PRNGKey(0), (10000,))
        np.testing.assert_allclose(float(jnp.mean(s)), 2.0, atol=0.05)
        np.testing.assert_allclose(float(jnp.std(s)), 0.5, atol=0.05)

    def test_uniform(self):
        d = UniformDistribution(-2, 3)
        s = d.sample(jax.random.PRNGKey(0), (1000,))
        assert float(jnp.min(s)) >= -2 and float(jnp.max(s)) <= 3

    def test_truncated(self):
        d = TruncatedNormalDistribution(0.0, 1.0)
        s = d.sample(jax.random.PRNGKey(0), (1000,))
        assert float(jnp.max(jnp.abs(s))) <= 2.0 + 1e-5

    def test_orthogonal(self):
        d = OrthogonalDistribution()
        s = d.sample(jax.random.PRNGKey(0), (16, 16))
        np.testing.assert_allclose(np.asarray(s @ s.T), np.eye(16), atol=1e-2)

    def test_serde(self):
        d = NormalDistribution(1.0, 2.0)
        d2 = distribution_from_dict(d.to_dict())
        assert d2 == d
