"""Random Fourier features (paper Definition 2).

For data ``X in R^{p x n}`` the RFF matrix is

    Sigma = (1/sqrt(N)) [cos(Omega X); sin(Omega X)]  in  R^{2N x n},

with ``Omega in R^{N x p}``, ``Omega_ij ~ N(0, 1/sigma^2)`` i.i.d.  ``Sigma^T Sigma``
approximates the Gaussian kernel ``K_ij = exp(-||x_i - x_j||^2 / (2 sigma^2))``
(Theorem 2 / [Rahimi-Recht 2008]).

The FedRF-TCA protocol requires every client to draw the *same* Omega from a shared
seed (Alg. 2/3: "predefined random seed S shared by all source and target clients"),
so Omega generation is a pure function of ``(seed, N, p, sigma)``.

Laplace-kernel features (Cauchy-distributed Omega) are also provided — the paper's
Appendix D (Tables XIV/XV) evaluates RF-TCA with the Laplace kernel.
"""
from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp


def draw_omega(
    seed: int,
    n_features: int,
    dim: int,
    sigma: float = 1.0,
    kernel: Literal["gauss", "laplace"] = "gauss",
) -> jax.Array:
    """Shared-seed frequency matrix Omega in R^{N x p}.

    gauss:   Omega_ij ~ N(0, 1/sigma^2)      -> Sigma^T Sigma ~= Gaussian kernel
    laplace: Omega_ij ~ Cauchy(0, 1/sigma)   -> Sigma^T Sigma ~= Laplace kernel
    """
    key = jax.random.PRNGKey(seed)
    if kernel == "gauss":
        return jax.random.normal(key, (n_features, dim)) / sigma
    elif kernel == "laplace":
        return jax.random.cauchy(key, (n_features, dim)) / sigma
    raise ValueError(f"unknown kernel {kernel!r}")


def rff_features(x: jax.Array, omega: jax.Array, *, use_kernel: bool = False) -> jax.Array:
    """Sigma = [cos(Omega X); sin(Omega X)] / sqrt(N), column-per-sample.

    Args:
      x: data matrix (p, n) — columns are samples (paper convention).
      omega: (N, p) frequency matrix from :func:`draw_omega`.
      use_kernel: route the matmul+cos/sin through the Pallas TPU kernel
        (interpret-mode on CPU); otherwise plain XLA.

    Returns: (2N, n) RFF matrix.
    """
    n_features = omega.shape[0]
    if use_kernel:
        from repro.kernels import ops as kops

        return kops.rff(x, omega)
    z = jnp.matmul(omega, x, precision=jax.lax.Precision.HIGHEST)  # (N, n)
    return jnp.concatenate([jnp.cos(z), jnp.sin(z)], axis=0) / jnp.sqrt(n_features)


def rff_features_rows(x_rows: jax.Array, omega: jax.Array) -> jax.Array:
    """Row-major convenience: x_rows (n, p) -> (n, 2N). Used by model heads."""
    return rff_features(x_rows.T, omega).T


@functools.partial(jax.jit, static_argnames=())
def rff_message(x: jax.Array, omega: jax.Array, sign: float = 1.0) -> jax.Array:
    """The paper's compressed client message  Sigma @ ell  in R^{2N}.

    For a source client ell = 1/n_S (sign=+1); for the target ell = -1/n_T
    (sign=-1), per eq. (2).  The message size is independent of n — the heart
    of the O(KN) communication claim (Table I).
    """
    sigma = rff_features(x, omega)
    n = x.shape[1]
    return sign * jnp.sum(sigma, axis=1) / n
