"""Pallas TPU kernel: centered Gram matrix  G = (S - mu 1^T)(S - mu 1^T)^T.

This is the  Sigma H Sigma^T  operator at the heart of RF-TCA (Algorithm 1,
eq. 7): H = I - 11^T/n is idempotent so SH(SH)^T = S H S^T, and centering is
algebraically a rank-one correction we fuse into the block loads — the
centered (2N, n) matrix is never materialised in HBM.

Grid: (2N/bi, 2N/bj, n/bk), contraction over samples innermost, fp32 scratch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gram_kernel(a_ref, b_ref, mu_i_ref, mu_j_ref, out_ref, acc_ref, *, k_steps: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ai = a_ref[...].astype(jnp.float32) - mu_i_ref[...].astype(jnp.float32)
    bj = b_ref[...].astype(jnp.float32) - mu_j_ref[...].astype(jnp.float32)
    acc_ref[...] += jax.lax.dot_general(
        ai, bj, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(k == k_steps - 1)
    def _write():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def centered_gram_pallas(
    sigma: jax.Array,  # (2N, n)
    *,
    block: int = 128,
    block_k: int = 128,
    interpret: bool,
) -> jax.Array:
    """Returns (2N, 2N) centered Gram of the RFF matrix."""
    two_n, n = sigma.shape
    bi = min(block, two_n)
    bk = min(block_k, n)
    if two_n % bi or n % bk:
        raise ValueError(f"({two_n},{n}) must tile by ({bi},{bk})")
    k_steps = n // bk
    grid = (two_n // bi, two_n // bi, k_steps)
    mu = jnp.mean(sigma, axis=1, keepdims=True).astype(sigma.dtype)  # (2N, 1)

    kernel = functools.partial(_gram_kernel, k_steps=k_steps)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bi, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bi, bk), lambda i, j, k: (j, k)),
            pl.BlockSpec((bi, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((bi, 1), lambda i, j, k: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bi, bi), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((two_n, two_n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bi, bi), jnp.float32)],
        interpret=interpret,
    )(sigma, sigma, mu, mu)
