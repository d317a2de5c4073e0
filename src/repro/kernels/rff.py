"""Pallas TPU kernel: fused random-Fourier-feature map (paper Definition 2).

Computes  cos(Omega @ X)/sqrt(N)  and  sin(Omega @ X)/sqrt(N)  in one pass:
the (N, n) matmul is tiled into MXU-aligned VMEM blocks, accumulated in fp32
over the contraction (p) grid axis, and the cos/sin + 1/sqrt(N) epilogue is
fused into the final accumulation step — the (N, n) phase matrix never makes
a round trip to HBM (a GPU-style implementation materialises it twice).

Grid: (N/bn, n/bm, p/bp), contraction innermost. Scratch: fp32 (bn, bm).

The seed-fused variant (:func:`rff_fused_pallas`) has no ``omega`` operand:
each program instance draws its ``(bn, bp)`` weight block from the
counter-based threefry stream of :mod:`repro.kernels.prng` at its absolute
``(row, col)`` offset, so the ``(N, p)`` matrix never exists in HBM — the
8-byte seed is the weight.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.prng import fused_omega_block


def _rff_kernel(omega_ref, x_ref, cos_ref, sin_ref, acc_ref, *, n_features: int, k_steps: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        omega_ref[...], x_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(k == k_steps - 1)
    def _epilogue():
        z = acc_ref[...]
        inv = 1.0 / jnp.sqrt(jnp.float32(n_features))
        cos_ref[...] = (jnp.cos(z) * inv).astype(cos_ref.dtype)
        sin_ref[...] = (jnp.sin(z) * inv).astype(sin_ref.dtype)


def rff_pallas(
    x: jax.Array,  # (p, n)
    omega: jax.Array,  # (N, p)
    *,
    block_n: int = 128,
    block_m: int = 128,
    block_p: int = 128,
    scale_n: int | None = None,  # true N when omega rows are padded
    interpret: bool,
) -> jax.Array:
    """Returns Sigma = [cos(Omega X); sin(Omega X)]/sqrt(N) of shape (2N, n)."""
    n_features, p = omega.shape
    _, n = x.shape
    bn = min(block_n, n_features)
    bm = min(block_m, n)
    bp = min(block_p, p)
    if n_features % bn or n % bm or p % bp:
        raise ValueError(f"shapes ({n_features},{p})x({p},{n}) must tile by ({bn},{bm},{bp})")
    k_steps = p // bp
    grid = (n_features // bn, n // bm, k_steps)

    kernel = functools.partial(_rff_kernel, n_features=scale_n or n_features, k_steps=k_steps)
    cos_out, sin_out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bp), lambda i, j, k: (i, k)),
            pl.BlockSpec((bp, bm), lambda i, j, k: (k, j)),
        ],
        out_specs=[
            pl.BlockSpec((bn, bm), lambda i, j, k: (i, j)),
            pl.BlockSpec((bn, bm), lambda i, j, k: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_features, n), x.dtype),
            jax.ShapeDtypeStruct((n_features, n), x.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bn, bm), jnp.float32)],
        interpret=interpret,
    )(omega, x)
    return jnp.concatenate([cos_out, sin_out], axis=0)


def _rff_fused_kernel(
    x_ref, cos_ref, sin_ref, acc_ref,
    *, n_features: int, k_steps: int, block_n: int, block_p: int,
    seed: int, ensemble_index: int, sigma: float, rf_kernel: str,
):
    i = pl.program_id(0)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    om = fused_omega_block(
        seed, block_n, block_p, row0=i * block_n, col0=k * block_p,
        ensemble_index=ensemble_index, sigma=sigma, rf_kernel=rf_kernel,
    )
    acc_ref[...] += jnp.dot(om, x_ref[...], preferred_element_type=jnp.float32)

    @pl.when(k == k_steps - 1)
    def _epilogue():
        z = acc_ref[...]
        inv = 1.0 / jnp.sqrt(jnp.float32(n_features))
        cos_ref[...] = (jnp.cos(z) * inv).astype(cos_ref.dtype)
        sin_ref[...] = (jnp.sin(z) * inv).astype(sin_ref.dtype)


def rff_fused_pallas(
    x: jax.Array,  # (p_pad, n), zero-padded feature rows
    *,
    nf_pad: int,  # padded draw height (rows [scale_n, nf_pad) are garbage)
    scale_n: int,  # true N for the 1/sqrt(N) normalization
    seed: int,
    ensemble_index: int = 0,
    sigma: float = 1.0,
    rf_kernel: str = "gauss",
    block_n: int = 128,
    block_m: int = 128,
    block_p: int = 128,
    interpret: bool,
) -> jax.Array:
    """Seed-fused featurize: Sigma = [cos; sin]/sqrt(N) of shape (2*nf_pad, n)
    with the weight blocks drawn inside the kernel.  Weight columns past the
    true data dim multiply zero-padded x rows, so their (drawn, finite)
    values contribute exact zeros to the phase accumulation."""
    p, n = x.shape
    bn = min(block_n, nf_pad)
    bm = min(block_m, n)
    bp = min(block_p, p)
    if nf_pad % bn or n % bm or p % bp:
        raise ValueError(f"shapes ({nf_pad},{p})x({p},{n}) must tile by ({bn},{bm},{bp})")
    k_steps = p // bp
    grid = (nf_pad // bn, n // bm, k_steps)

    kernel = functools.partial(
        _rff_fused_kernel, n_features=scale_n, k_steps=k_steps,
        block_n=bn, block_p=bp, seed=seed, ensemble_index=ensemble_index,
        sigma=sigma, rf_kernel=rf_kernel,
    )
    cos_out, sin_out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bp, bm), lambda i, j, k: (k, j))],
        out_specs=[
            pl.BlockSpec((bn, bm), lambda i, j, k: (i, j)),
            pl.BlockSpec((bn, bm), lambda i, j, k: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nf_pad, n), x.dtype),
            jax.ShapeDtypeStruct((nf_pad, n), x.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bn, bm), jnp.float32)],
        interpret=interpret,
    )(x)
    return jnp.concatenate([cos_out, sin_out], axis=0)
