"""M/M/k queueing model for service stations.

The reference's latency-beyond-sleeps comes from real contention: each
service is a Go HTTP server whose throughput saturates around 12-14k QPS
per vCPU (isotope/service/README.md:28-34), scaled out via ``NumReplicas``
k8s replicas (svc/service.go:33, kubernetes.go:200).  The simulator models
each service as an M/M/k station: k = NumReplicas servers, per-server rate
mu = 1 / cpu_time, offered load lambda = root RPS x expected visits.

The waiting-time distribution of M/M/k is exactly

    P(W > t) = C(k, a) * exp(-(k*mu - lambda) * t)

with ``C`` the Erlang-C delay probability and a = lambda/mu, so sampling a
wait is a coin flip + one exponential draw — fully vectorized over
(request, hop).  Closed forms below double as the oracle for golden tests
(SURVEY.md §4: validate simulated p50/p99 against M/M/1).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


def erlang_b(a: jax.Array, k_max: int) -> jax.Array:
    """Erlang-B blocking probability B(j, a) for j = 1..k_max.

    Uses the stable recursion B(j) = a*B(j-1) / (j + a*B(j-1)), B(0) = 1.
    Returns shape (k_max, *a.shape); row j-1 holds B(j, a).
    """
    a = jnp.asarray(a, jnp.float32)

    def body(b, j):
        b = a * b / (j + a * b)
        return b, b

    _, rows = jax.lax.scan(
        body, jnp.ones_like(a), jnp.arange(1, k_max + 1, dtype=jnp.float32)
    )
    return rows


class QueueParams(NamedTuple):
    """Per-station sampling parameters (all shaped like ``replicas``)."""

    p_wait: jax.Array       # Erlang-C delay probability C(k, a)
    wait_rate: jax.Array    # k*mu - lambda: rate of the conditional wait
    utilization: jax.Array  # rho = lambda / (k*mu)
    unstable: jax.Array     # bool: offered load >= capacity


# Stations at/over capacity have no stationary distribution; we pin them
# just under saturation so the sim stays finite and flag them instead
# (the reference analogue: runs with >10% errors are discarded by
# perf/benchmark/runner/fortio.py:175-177, and overload shows up as errors).
_MAX_RHO = 0.9999


def mmk_params(
    arrival_rate: jax.Array,
    service_rate: jax.Array,
    replicas: jax.Array,
    k_max: int,
) -> QueueParams:
    """Compute Erlang-C sampling parameters for each station.

    ``arrival_rate``: lambda per station; ``service_rate``: mu per server;
    ``replicas``: integer k per station; ``k_max``: static max k (sets the
    recursion length).
    """
    lam = jnp.asarray(arrival_rate, jnp.float32)
    mu = jnp.asarray(service_rate, jnp.float32)
    k = jnp.asarray(replicas, jnp.int32)
    kf = k.astype(jnp.float32)

    rho_raw = lam / (kf * mu)
    unstable = rho_raw >= 1.0
    rho = jnp.minimum(rho_raw, _MAX_RHO)
    a = rho * kf  # effective (possibly clamped) offered load in erlangs

    b_rows = erlang_b(a, k_max)                 # (k_max, S)
    b_k = jnp.take_along_axis(b_rows, (k - 1)[None, ...], axis=0)[0]
    p_wait = b_k / (1.0 - rho * (1.0 - b_k))
    wait_rate = kf * mu * (1.0 - rho)
    return QueueParams(
        p_wait=p_wait,
        wait_rate=wait_rate,
        utilization=rho_raw,
        unstable=unstable,
    )


#: ``jax.random.uniform`` draws float32 from the lattice k * 2**-23,
#: k = 0 .. 2**23 - 1: it is exactly 0 once in 2**23 draws
_LATTICE_STEP = 2.0**-23


def delay_coin(uniform: jax.Array, p_wait: jax.Array) -> jax.Array:
    """The Erlang-C delay coin ``uniform < p_wait``, with the lattice
    point ``uniform == 0`` no delay where ``p_wait`` is under one
    lattice step.

    A plain-uniform draw is exactly 0 with probability 2**-23, and 0 is
    under ANY positive ``p_wait``: a station that should delay one
    request in 1e10 delayed one in 8e6, each by the 46 mean waits the
    conditional draw's clamp gives ``uniform == 0``.  Below one step the
    lattice cannot resolve ``p_wait`` at all, so that point stands for
    no delay there (a quiet run has no wait); from one step up the coin
    fires ceil(p_wait * 2**23) times in 2**23 as before, and every draw
    with ``uniform > 0`` is untouched at any ``p_wait``.
    """
    return (uniform < p_wait) & (
        (uniform > 0.0) | (p_wait >= _LATTICE_STEP)
    )


def sample_wait(
    params: QueueParams,
    uniform: jax.Array,
    exponential: jax.Array,
) -> jax.Array:
    """Draw waiting times: coin ``uniform`` vs p_wait, scaled ``exponential``.

    ``uniform`` ~ U[0,1) and ``exponential`` ~ Exp(1) must broadcast with
    the station parameters (typically (N, H) vs per-hop-gathered params).
    """
    wait = exponential / params.wait_rate
    return jnp.where(delay_coin(uniform, params.p_wait), wait, 0.0)


def sample_wait_conditional(
    p_wait: jax.Array,
    wait_rate: jax.Array,
    uniform: jax.Array,
) -> jax.Array:
    """Single-tensor wait draw via the conditional-uniform trick.

    Given U ~ U[0,1), conditional on U < p the ratio U/p is again U[0,1),
    so one uniform yields both the Erlang-C delay coin and the conditional
    Exp(wait_rate) wait — halving the RNG tensors the engine materializes.
    Distributionally identical to :func:`sample_wait`.
    """
    ratio = uniform / jnp.maximum(p_wait, 1e-30)
    # floor must stay in f32 normal range: subnormals (e.g. 1e-38) are
    # flushed to zero on TPU/CPU XLA, which would let u == 0 produce inf
    return jnp.where(
        delay_coin(uniform, p_wait),
        -jnp.log(jnp.maximum(ratio, 1e-20)) / wait_rate,
        0.0,
    )


# -- closed forms (test oracles) ------------------------------------------


def mm1_sojourn_quantile(q, arrival_rate, service_rate):
    """M/M/1 sojourn time quantile: T ~ Exp(mu - lambda)."""
    return -jnp.log1p(-jnp.asarray(q)) / (service_rate - arrival_rate)


def mmk_mean_wait(arrival_rate, service_rate, replicas, k_max):
    """Mean M/M/k waiting time: C(k, a) / (k*mu - lambda)."""
    p = mmk_params(arrival_rate, service_rate, replicas, k_max)
    return p.p_wait / p.wait_rate
