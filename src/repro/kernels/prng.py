"""Counter-based in-kernel PRNG: element-addressed threefry-2x32 draws.

The seed-fused kernels (``rff.py``, ``rff_gram_stream.py``) generate their
W_RF/Omega rows *inside* the kernel instead of reading a materialized
``(N, p)`` weight tensor from HBM.  That only works if the draw for any
element is a pure function of its *absolute* coordinates — independent of
which tile computes it, in what order, at what padding.  This module is that
function, shared verbatim by the Pallas kernels (interpret mode on CPU,
Mosaic-lowered uint32 ops on TPU) and their XLA generator twins, so
fused-vs-twin agreement is bit-for-bit by construction:

    key     = (seed, ensemble_index)            per random-feature draw
    counter = (row, col)                        per Omega element
    bits    = threefry2x32(key, counter)        2 x uint32
    omega   = box_muller(bits) / sigma          N(0, 1/sigma^2)   (gauss)
            = cauchy(bits) / sigma              Cauchy(0, 1/sigma) (laplace)

Properties the tests pin down:

- **tile-index independence** — a ``(rows, cols)`` block at offset
  ``(r0, c0)`` equals the same slice of the full matrix, whatever other
  blocks are drawn (each element only ever sees its own counter);
- **cross-layout equality** — tiled, untiled, and twin draws agree
  bit-for-bit at overlapping N;
- **ensemble independence** — draw ``e`` is keyed, not offset, so
  ``ensemble=1`` is the single-draw stream (``e=0``) exactly.

This is the classic Random123 threefry-2x32-20 (the same core jax's
``threefry2x32`` implements), written in plain ``jnp`` uint32 ops so the
identical trace runs inside a Pallas kernel body and in an XLA program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# threefry-2x32 rotation schedule (Random123): even / odd round quads
_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))
_PARITY = np.uint32(0x1BD11BDA)
_TWO_PI = 6.283185307179586


def _rotl(x: jax.Array, r: int) -> jax.Array:
    return (x << np.uint32(r)) | (x >> np.uint32(32 - r))


def threefry2x32(k0, k1, c0, c1) -> tuple[jax.Array, jax.Array]:
    """20-round threefry-2x32 of counter ``(c0, c1)`` under key ``(k0, k1)``.

    All inputs uint32 (scalars or broadcastable arrays); returns two uint32
    arrays of the broadcast shape.  Pure jnp — traceable inside Pallas.
    """
    k0 = jnp.asarray(k0, jnp.uint32)
    k1 = jnp.asarray(k1, jnp.uint32)
    ks = (k0, k1, _PARITY ^ k0 ^ k1)
    x0 = jnp.asarray(c0, jnp.uint32) + ks[0]
    x1 = jnp.asarray(c1, jnp.uint32) + ks[1]
    for d in range(5):
        for r in _ROTATIONS[d % 2]:
            x0 = x0 + x1
            x1 = _rotl(x1, r) ^ x0
        x0 = x0 + ks[(d + 1) % 3]
        x1 = x1 + ks[(d + 2) % 3] + np.uint32(d + 1)
    return x0, x1


def _uniform(bits: jax.Array) -> jax.Array:
    """uint32 -> fp32 uniform on [0, 1) with 24-bit resolution.

    The 24-bit value goes through int32 on its way to fp32: Mosaic has no
    uint32 -> fp32 cast, and below 2^24 both conversions are exact.
    """
    return (bits >> np.uint32(8)).astype(jnp.int32).astype(jnp.float32) * jnp.float32(2.0**-24)


def _normal(b0: jax.Array, b1: jax.Array) -> jax.Array:
    """One N(0, 1) draw per element via Box-Muller on a bit pair.

    ``u1`` enters as ``1 - u`` in (0, 1] so the log is always finite; the
    radius is bounded by sqrt(-2 ln 2^-24) ~ 5.77.
    """
    u1 = _uniform(b0)
    u2 = _uniform(b1)
    r = jnp.sqrt(-2.0 * jnp.log1p(-u1))
    return r * jnp.cos(jnp.float32(_TWO_PI) * u2)


def _cauchy(b0: jax.Array, b1: jax.Array) -> jax.Array:
    """One Cauchy(0, 1) draw per element (inverse CDF on the first word)."""
    u = _uniform(b0)
    return jnp.tan(jnp.float32(np.pi) * (u - 0.5))


_DISTS = {"gauss": _normal, "laplace": _cauchy}


def fused_omega_block(
    seed: int,
    rows: int,
    cols: int,
    *,
    row0=0,
    col0=0,
    ensemble_index: int = 0,
    sigma: float = 1.0,
    rf_kernel: str = "gauss",
) -> jax.Array:
    """A ``(rows, cols)`` block of the seed-defined Omega at offset
    ``(row0, col0)`` — the single generator both the fused Pallas kernels and
    their XLA twins call.

    ``row0`` may be a traced scalar (tiled kernels pass the tile offset);
    everything else is static.  gauss: N(0, 1/sigma^2); laplace:
    Cauchy(0, 1/sigma) — matching :func:`repro.core.rff.draw_omega`'s kernel
    semantics under a different (counter-based) stream.
    """
    if rf_kernel not in _DISTS:
        raise ValueError(f"unknown rf kernel {rf_kernel!r}")
    r = jnp.asarray(row0, jnp.uint32) + jax.lax.broadcasted_iota(
        jnp.uint32, (rows, cols), 0
    )
    c = jnp.asarray(col0, jnp.uint32) + jax.lax.broadcasted_iota(
        jnp.uint32, (rows, cols), 1
    )
    b0, b1 = threefry2x32(
        np.uint32(np.uint64(seed) & 0xFFFFFFFF), np.uint32(ensemble_index), r, c
    )
    draw = _DISTS[rf_kernel](b0, b1)
    if sigma != 1.0:
        draw = draw * jnp.float32(1.0 / sigma)
    return draw


def fused_omega(
    seed: int,
    n_features: int,
    dim: int,
    *,
    ensemble_index: int = 0,
    sigma: float = 1.0,
    rf_kernel: str = "gauss",
) -> jax.Array:
    """The full ``(N, p)`` Omega of the fused stream — the *generator twin*.

    The fused kernels never materialize this; tests and small out-of-sample
    transforms do.  Bit-identical to assembling :func:`fused_omega_block`
    tiles at any tiling.
    """
    return fused_omega_block(
        seed, n_features, dim,
        ensemble_index=ensemble_index, sigma=sigma, rf_kernel=rf_kernel,
    )
