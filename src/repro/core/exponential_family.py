"""Exponential-family input distributions for Einsum Networks.

The paper (§3.4) computes the whole input layer as one ``D x K x R`` tensor of
exponential-family (EF) log-densities

    log L = log h(x) + T(x)^T theta - A(theta),

with parameters kept in *expectation form* ``phi`` (Sato, 1999) so that the EM
M-step is a simple moment average:  phi <- (sum_x p_L(x) T(x)) / (sum_x p_L(x)).

Each EF below provides:
  * ``num_stats``                      -- |T|, dimensionality of T(x)
  * ``sufficient_statistics(x)``       -- (...,) -> (..., |T|)
  * ``log_h(x)``                       -- base measure, (...,) -> (...,)
  * ``expectation_to_natural(phi)``    -- theta(phi), (..., |T|) -> (..., |T|)
  * ``log_normalizer(theta)``          -- A(theta), (..., |T|) -> (...,)
  * ``sample(key, phi, shape)``        -- ancestral sampling at the leaves
  * ``init_phi(key, shape)``           -- random valid initialization
  * ``project_phi(phi)``               -- clamp to the valid domain (e.g. the
                                          paper projects Gaussian variances to
                                          [1e-6, 1e-2] after each EM update)

Parameter tensors have shape ``(D, K, R, |T|)``: D variables, K densities per
leaf vector, R replica (paper notation).  ``log_density`` evaluates the
densities per entry (inner product - A(theta)); the leaf layer
(``EiNet.leaf_rows``) calls it on each leaf's (variable, replica) entries in
turn, rather than on the paper's whole D x K x R tensor "E" of §3.4.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ExponentialFamily:
    """Abstract EF over a single scalar variable (vectorized over leading dims)."""

    name: str = "abstract"

    # --- interface -----------------------------------------------------------
    @property
    def num_stats(self) -> int:
        raise NotImplementedError

    def sufficient_statistics(self, x: jax.Array) -> jax.Array:
        raise NotImplementedError

    def log_h(self, x: jax.Array) -> jax.Array:
        raise NotImplementedError

    def expectation_to_natural(self, phi: jax.Array) -> jax.Array:
        raise NotImplementedError

    def log_normalizer(self, theta: jax.Array) -> jax.Array:
        raise NotImplementedError

    def sample(self, key: jax.Array, phi: jax.Array) -> jax.Array:
        raise NotImplementedError

    def init_phi(self, key: jax.Array, shape: Tuple[int, ...]) -> jax.Array:
        raise NotImplementedError

    def project_phi(self, phi: jax.Array) -> jax.Array:
        return phi

    def mode(self, phi: jax.Array) -> jax.Array:
        """Distribution mode (deterministic decode for argmax sampling)."""
        raise NotImplementedError

    def clamp_fraction(self, phi: jax.Array) -> jax.Array:
        """Fraction of leaf parameters pinned at their projection bounds
        (scalar float32) -- the health telemetry's leak detector for EM
        updates that keep slamming into ``project_phi``'s clamps.  Families
        without hard bounds report 0."""
        return jnp.zeros((), jnp.float32)

    # --- shared machinery ----------------------------------------------------
    def log_density(self, x: jax.Array, theta: jax.Array,
                    a: jax.Array) -> jax.Array:
        """Log-densities ``log h(x) + sum_t T_t(x) theta_t - a`` of ``x``
        under natural parameters ``theta`` (..., |T|) with ``a`` = A(theta),
        elementwise: ``x``, ``theta[..., 0]`` and ``a`` broadcast together.

        Each entry is formed on its own in float32: the sum over t is
        unrolled over the static |T| (a depth-|T| contraction would use a
        TPU's matrix unit badly), and ``a`` comes off each entry before any
        sum over entries, since theta_t and A(theta) can be large and cancel.
        """
        t = self.sufficient_statistics(x)
        dot = t[..., 0] * theta[..., 0]
        for i in range(1, self.num_stats):
            dot = dot + t[..., i] * theta[..., i]
        return self.log_h(x) + dot - a


class Normal(ExponentialFamily):
    """Univariate Gaussian.  T(x) = [x, x^2], phi = [mu, mu^2 + sigma^2]."""

    def __init__(self, min_var: float = 1e-6, max_var: float = 10.0):
        object.__setattr__(self, "name", "normal")
        object.__setattr__(self, "min_var", float(min_var))
        object.__setattr__(self, "max_var", float(max_var))

    @property
    def num_stats(self) -> int:
        return 2

    def sufficient_statistics(self, x):
        return jnp.stack([x, x * x], axis=-1)

    def log_h(self, x):
        return jnp.full(x.shape, -0.5 * jnp.log(2.0 * jnp.pi), x.dtype)

    def _moments(self, phi):
        mu = phi[..., 0]
        var = phi[..., 1] - mu * mu
        var = jnp.clip(var, self.min_var, self.max_var)
        return mu, var

    def expectation_to_natural(self, phi):
        mu, var = self._moments(phi)
        return jnp.stack([mu / var, -0.5 / var], axis=-1)

    def log_normalizer(self, theta):
        # A(theta) = -theta1^2 / (4 theta2) - 0.5 log(-2 theta2)
        return -(theta[..., 0] ** 2) / (4.0 * theta[..., 1]) - 0.5 * jnp.log(
            -2.0 * theta[..., 1]
        )

    def sample(self, key, phi):
        mu, var = self._moments(phi)
        return mu + jnp.sqrt(var) * jax.random.normal(key, mu.shape, mu.dtype)

    def init_phi(self, key, shape):
        k1, _ = jax.random.split(key)
        mu = jax.random.normal(k1, shape) * 0.5
        var = jnp.ones(shape)
        return jnp.stack([mu, mu * mu + var], axis=-1)

    def mode(self, phi):
        return phi[..., 0]

    def project_phi(self, phi):
        mu, var = self._moments(phi)
        return jnp.stack([mu, mu * mu + var], axis=-1)

    def clamp_fraction(self, phi):
        mu = phi[..., 0]
        raw_var = phi[..., 1] - mu * mu
        pinned = (raw_var <= self.min_var) | (raw_var >= self.max_var)
        return jnp.mean(pinned.astype(jnp.float32))


class Bernoulli(ExponentialFamily):
    """x in {0,1}.  T(x) = [x], phi = [p]."""

    def __init__(self, min_p: float = 1e-6):
        object.__setattr__(self, "name", "bernoulli")
        object.__setattr__(self, "min_p", float(min_p))

    @property
    def num_stats(self) -> int:
        return 1

    def sufficient_statistics(self, x):
        return x[..., None]

    def log_h(self, x):
        return jnp.zeros(x.shape, x.dtype)

    def _p(self, phi):
        return jnp.clip(phi[..., 0], self.min_p, 1.0 - self.min_p)

    def expectation_to_natural(self, phi):
        p = self._p(phi)
        return jnp.log(p / (1.0 - p))[..., None]

    def log_normalizer(self, theta):
        return jnp.logaddexp(0.0, theta[..., 0])

    def sample(self, key, phi):
        return jax.random.bernoulli(key, self._p(phi)).astype(jnp.float32)

    def init_phi(self, key, shape):
        return jax.random.uniform(key, shape + (1,), minval=0.3, maxval=0.7)

    def mode(self, phi):
        return (self._p(phi) > 0.5).astype(jnp.float32)

    def project_phi(self, phi):
        return jnp.clip(phi, self.min_p, 1.0 - self.min_p)

    def clamp_fraction(self, phi):
        p = phi[..., 0]
        pinned = (p <= self.min_p) | (p >= 1.0 - self.min_p)
        return jnp.mean(pinned.astype(jnp.float32))


class Binomial(ExponentialFamily):
    """x in {0..N}.  Used by the paper for 8-bit image data (N=255).

    T(x) = [x], phi = [N p].  log h(x) = log C(N, x).
    """

    def __init__(self, n_trials: int, min_p: float = 1e-6):
        object.__setattr__(self, "name", "binomial")
        object.__setattr__(self, "n_trials", int(n_trials))
        object.__setattr__(self, "min_p", float(min_p))

    @property
    def num_stats(self) -> int:
        return 1

    def sufficient_statistics(self, x):
        return x[..., None]

    def log_h(self, x):
        n = self.n_trials
        return (
            jax.lax.lgamma(jnp.float32(n + 1))
            - jax.lax.lgamma(x + 1.0)
            - jax.lax.lgamma(n - x + 1.0)
        )

    def _p(self, phi):
        return jnp.clip(phi[..., 0] / self.n_trials, self.min_p, 1.0 - self.min_p)

    def expectation_to_natural(self, phi):
        p = self._p(phi)
        return jnp.log(p / (1.0 - p))[..., None]

    def log_normalizer(self, theta):
        return self.n_trials * jnp.logaddexp(0.0, theta[..., 0])

    def sample(self, key, phi):
        p = self._p(phi)
        u = jax.random.uniform(key, p.shape + (self.n_trials,))
        return jnp.sum(u < p[..., None], axis=-1).astype(jnp.float32)

    def init_phi(self, key, shape):
        p = jax.random.uniform(key, shape + (1,), minval=0.3, maxval=0.7)
        return p * self.n_trials

    def mode(self, phi):
        return jnp.round(jnp.clip(phi[..., 0], 0, self.n_trials))

    def project_phi(self, phi):
        return jnp.clip(
            phi, self.min_p * self.n_trials, (1.0 - self.min_p) * self.n_trials
        )

    def clamp_fraction(self, phi):
        p = phi[..., 0] / self.n_trials
        pinned = (p <= self.min_p) | (p >= 1.0 - self.min_p)
        return jnp.mean(pinned.astype(jnp.float32))


class Categorical(ExponentialFamily):
    """x in {0..C-1}.  T(x) = onehot(x), phi = probs (C,)."""

    def __init__(self, num_categories: int, min_p: float = 1e-6):
        object.__setattr__(self, "name", "categorical")
        object.__setattr__(self, "num_categories", int(num_categories))
        object.__setattr__(self, "min_p", float(min_p))

    @property
    def num_stats(self) -> int:
        return self.num_categories

    def sufficient_statistics(self, x):
        return jax.nn.one_hot(x.astype(jnp.int32), self.num_categories, dtype=jnp.float32)

    def log_h(self, x):
        return jnp.zeros(x.shape, jnp.float32)

    def _p(self, phi):
        p = jnp.clip(phi, self.min_p, 1.0)
        return p / jnp.sum(p, axis=-1, keepdims=True)

    def expectation_to_natural(self, phi):
        return jnp.log(self._p(phi))

    def log_normalizer(self, theta):
        # theta already normalized log-probs -> A = 0
        return jnp.zeros(theta.shape[:-1], theta.dtype)

    def sample(self, key, phi):
        logits = jnp.log(self._p(phi))
        return jax.random.categorical(key, logits, axis=-1).astype(jnp.float32)

    def init_phi(self, key, shape):
        p = jax.random.uniform(
            key, shape + (self.num_categories,), minval=0.5, maxval=1.5
        )
        return p / jnp.sum(p, axis=-1, keepdims=True)

    def mode(self, phi):
        return jnp.argmax(phi, axis=-1).astype(jnp.float32)

    def project_phi(self, phi):
        return self._p(phi)

    def clamp_fraction(self, phi):
        return jnp.mean((phi <= self.min_p).astype(jnp.float32))


EF_REGISTRY = {
    "normal": Normal,
    "bernoulli": Bernoulli,
    "binomial": Binomial,
    "categorical": Categorical,
}


def make_exponential_family(name: str, **kwargs) -> ExponentialFamily:
    return EF_REGISTRY[name](**kwargs)
