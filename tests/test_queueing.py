"""Queueing model tests against textbook closed forms."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from isotope_tpu.sim import queueing


def test_erlang_b_known_values():
    # B(1, a) = a / (1 + a); B(2, a) = a*B1 / (2 + a*B1)
    a = jnp.asarray([0.5, 2.0])
    rows = queueing.erlang_b(a, 2)
    np.testing.assert_allclose(rows[0], [0.5 / 1.5, 2.0 / 3.0], rtol=1e-6)
    b1 = np.asarray([0.5 / 1.5, 2.0 / 3.0])
    np.testing.assert_allclose(
        rows[1], a * b1 / (2 + a * b1), rtol=1e-6
    )


def test_erlang_c_reduces_to_rho_for_single_server():
    # M/M/1: P(wait) = rho
    p = queueing.mmk_params(
        arrival_rate=jnp.asarray([300.0]),
        service_rate=jnp.asarray([1000.0]),
        replicas=jnp.asarray([1]),
        k_max=4,
    )
    np.testing.assert_allclose(p.p_wait, [0.3], rtol=1e-5)
    np.testing.assert_allclose(p.utilization, [0.3], rtol=1e-6)
    assert not bool(p.unstable[0])


def test_erlang_c_mm2_textbook():
    # M/M/2 with lambda=3, mu=2 => rho=0.75, C = 0.6428571...
    p = queueing.mmk_params(3.0, 2.0, jnp.asarray([2]), k_max=2)
    np.testing.assert_allclose(p.p_wait, 9.0 / 14.0, rtol=1e-5)
    np.testing.assert_allclose(p.wait_rate, 1.0, rtol=1e-5)


def test_unstable_station_flagged_and_clamped():
    p = queueing.mmk_params(2000.0, 1000.0, jnp.asarray([1]), k_max=1)
    assert bool(p.unstable[0])
    assert float(p.utilization[0]) == pytest.approx(2.0)
    assert float(p.wait_rate[0]) > 0  # clamped, still finite sampling


def test_sampled_mean_wait_matches_closed_form():
    lam, mu, k = 800.0, 1000.0, jnp.asarray([1])
    p = queueing.mmk_params(lam, mu, k, k_max=1)
    key = jax.random.PRNGKey(0)
    n = 200_000
    u = jax.random.uniform(key, (n,))
    e = jax.random.exponential(jax.random.fold_in(key, 1), (n,))
    waits = queueing.sample_wait(p, u, e)
    expected = float(queueing.mmk_mean_wait(lam, mu, k, k_max=1)[0])
    assert float(waits.mean()) == pytest.approx(expected, rel=0.02)


def test_mm1_sojourn_quantile():
    # mu - lambda = 200 => p50 = ln(2)/200
    q = queueing.mm1_sojourn_quantile(0.5, 800.0, 1000.0)
    assert float(q) == pytest.approx(np.log(2) / 200.0, rel=1e-5)

def test_conditional_wait_matches_two_tensor_sampler():
    # Same marginal as sample_wait: P(W=0) = 1 - p_wait, and conditional
    # on waiting the wait is Exp(wait_rate).
    lam, mu, k = 800.0, 1000.0, jnp.asarray([1])
    p = queueing.mmk_params(lam, mu, k, k_max=1)
    key = jax.random.PRNGKey(7)
    n = 200_000
    u = jax.random.uniform(key, (n,))
    waits = queueing.sample_wait_conditional(p.p_wait, p.wait_rate, u)
    frac_wait = float((waits > 0).mean())
    assert frac_wait == pytest.approx(float(p.p_wait[0]), abs=0.01)
    expected_mean = float(queueing.mmk_mean_wait(lam, mu, k, k_max=1)[0])
    assert float(waits.mean()) == pytest.approx(expected_mean, rel=0.02)
    # conditional mean given waiting = 1 / wait_rate
    cond = waits[waits > 0]
    assert float(cond.mean()) == pytest.approx(
        1.0 / float(p.wait_rate[0]), rel=0.02
    )


def test_conditional_wait_zero_p_wait_is_zero():
    w = queueing.sample_wait_conditional(
        jnp.asarray([0.0]), jnp.asarray([100.0]), jnp.asarray([0.5])
    )
    assert float(w[0]) == 0.0


@pytest.mark.parametrize("sampler", ["two_tensor", "conditional"])
@pytest.mark.parametrize("p_wait, fires", [
    (0.0, False), (1e-10, False), (2.0**-23, True), (0.3, True)])
def test_delay_coin_at_the_zero_lattice_point(sampler, p_wait, fires):
    """``jax.random.uniform`` is exactly 0 once in 2**23 draws.  Under a
    ``p_wait`` of less than one lattice step that draw is no delay (it
    was one, of 46 mean waits, for ANY positive ``p_wait``: a quiet run
    waited); from one step up the coin is the lattice's, as before."""
    u = jnp.asarray([0.0])
    p = jnp.asarray([p_wait], jnp.float32)
    rate = jnp.asarray([100.0])
    if sampler == "conditional":
        w = queueing.sample_wait_conditional(p, rate, u)
    else:
        w = queueing.sample_wait(
            queueing.QueueParams(p, rate, p, p < 0), u, jnp.asarray([1.0]))
    assert bool(w[0] > 0) is fires
    assert bool(jnp.isfinite(w[0]))


def test_delay_coin_leaves_every_positive_draw_as_it_was():
    u = jnp.asarray([2.0**-23, 1e-6, 0.25, 0.5])
    for p_wait in (0.0, 1e-10, 2.0**-23, 3e-7, 0.3, 1.0):
        p = jnp.full(u.shape, p_wait, jnp.float32)
        np.testing.assert_array_equal(
            np.asarray(queueing.delay_coin(u, p)), np.asarray(u < p))


def test_convolution_matches_mva_on_k1_networks():
    # the cross-check mva_load_dependent's docstring promises: on k=1
    # networks (where exact MVA is numerically sound) the stable Buzen
    # convolution must agree to float precision
    import numpy as np

    from isotope_tpu.sim import closed

    v = np.array([1.0, 0.6, 1.0])
    k = np.ones(3)
    lam_c, pi_c, pid_c = closed.convolution_marginals(
        v, k, 13000.0, 1.5e-3, 48
    )
    lam_m, pi_m, pid_m = closed.mva_load_dependent(
        v, v, k, 13000.0, 1.5e-3, 48
    )
    assert lam_c == pytest.approx(lam_m, rel=1e-9)
    np.testing.assert_allclose(
        pi_c, pi_m[:, : pi_c.shape[1]], atol=1e-9
    )
