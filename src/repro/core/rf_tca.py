"""RF-TCA (paper Algorithm 1, Section III).

Finds ``W_RF in R^{2N x m}`` as the top-m eigenvectors of

    (Sigma l l^T Sigma^T + gamma I_2N)^{-1} Sigma H Sigma^T,                (7)

a 2N x 2N problem instead of vanilla TCA's n x n one.  We solve the *symmetric
definite generalized* eigenproblem

    G_H w = lambda (gamma I + u u^T) w,     G_H = Sigma H Sigma^T,  u = Sigma l.

Two layers make the fit scale independently of the sample count n:

**Statistics pass** (``mode``): the default ``"stream"`` path consumes X in
sample blocks and accumulates G_H and u directly — via the fused Pallas kernel
``kernels.ops.rff_gram_stream`` on TPU (``use_pallas=True``) or an XLA
``lax.scan`` with the identical O(N^2 + N b) memory profile elsewhere.  The
(2N, n) RFF matrix Sigma never exists.  ``mode="dense"`` is the original
materializing path, kept as the benchmark baseline and small-n reference.

**Solve** (``solver``): B = gamma I + u u^T is an identity-plus-rank-one, so
its inverse square root has the closed Sherman–Morrison-style form

    B^{-1/2} = gamma^{-1/2} (I + c uhat uhat^T),  c = sqrt(gamma/(gamma+|u|^2)) - 1,

which replaces the Cholesky factorization + two triangular solves with two
rank-one updates (O(N^2) instead of O(N^3)).  The whitened operator
C = B^{-1/2} G_H B^{-1/2} is then diagonalized by:

- ``solver="eigh"``   — the top-m eigenpairs of C, the path chosen by shape.
  When the matrix is large next to m (2N >= 16m), blocked subspace
  iteration with k = 2m columns runs on the device where C already lives:
  products C·X at ``HIGHEST`` with a QR after each, and every few products
  a Rayleigh–Ritz step whose only host work is a float64 ``eigh`` of the
  k x k matrix X^T C X.  It stops when the top-m residuals fall under a
  relative tolerance; 2N-sized data never leaves the device.  Smaller
  matrices, and a solve that reaches the iteration cap unconverged, go to
  host SciPy ``syevr`` on the top-m subset (a fallback costs time, never
  accuracy).  Deterministic for its inputs on either path.
- ``solver="lobpcg"`` — matrix-free top-m LOBPCG
  (``jax.experimental.sparse.linalg.lobpcg_standard``) inside one compiled
  program, with no host work at all.  Its search block is m itself, so it
  converges slowly where the spectrum is flat past the m-th pair.  Falls
  back to ``eigh`` (as an in-program host callback) when 5m >= 2N.
- ``solver="cholesky"`` — the original Cholesky-whitening + full ``eigh``
  reference path (seed implementation), kept for benchmarking.

Unlike vanilla TCA (transductive), RF-TCA yields an *out-of-sample* map:
``transform(X_new) = W_RF^T Sigma(X_new)`` — this is what FedRF-TCA exploits.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.kernels_math import (
    assemble_streamed_gram,
    assemble_streamed_gram_ensemble,
    ell_vector,
)
from repro.core.rff import draw_omega, rff_features
from repro.obs.registry import get_registry as metrics
from repro.obs.tracing import span


class RFTCAState(NamedTuple):
    omega: jnp.ndarray | None  # (N, p) frequency matrix; None on the fused path
    w_rf: jnp.ndarray  # (2N, m) aligner
    eigvals: jnp.ndarray  # (m,)
    # seed-fused spec (seed, ensemble, sigma, kernel) when omega is None: the
    # frequency matrix is a pure function of these and is re-drawn on demand
    fused: tuple | None = None


def _mm(a, b):
    """a @ b at full fp32 precision: on TPU the default is one bf16 pass,
    too coarse for phases Omega X that reach |z| ~ sqrt(p) / sigma."""
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


# --------------------------------------------------------------------------
# statistics pass: (G_H, u) from data, streaming or dense
# --------------------------------------------------------------------------


def _gram_stream_body(x: jnp.ndarray, ell: jnp.ndarray, omega: jnp.ndarray, *, block: int):
    """lax.scan streaming accumulation of (G_H, u) — Sigma never materialized.

    Mirrors the Pallas rff_gram_stream kernel's structure and memory profile
    on backends where interpret-mode Pallas would be slow (CPU/GPU): per step
    only an (N, block) cos and sin slab exists, plus (N, N) fp32 accumulators.
    Accumulating the three blocks G_cc / G_cs / G_ss separately instead of the
    concatenated (2N, block) slab saves the G_sc = G_cs^T quarter of the
    contraction FLOPs and a per-step copy.
    """
    p, n = x.shape
    nf = omega.shape[0]
    pad = (-n) % block
    xp = jnp.pad(x, ((0, 0), (0, pad)))
    ep = jnp.pad(ell.astype(jnp.float32), (0, pad))
    nb = (n + pad) // block
    xb = xp.T.reshape(nb, block, p)
    eb = ep.reshape(nb, block)
    if pad:  # static: mask slabs only exist when sample columns are padded
        mb = jnp.pad(jnp.ones((n,), jnp.float32), (0, pad)).reshape(nb, block)
    else:
        mb = jnp.ones((nb, 1), jnp.float32)

    def body(carry, inp):
        cc, cs, ss, u_c, u_s, s_c, s_s = carry
        xblk, elb, mkb = inp
        z = _mm(omega, xblk.T).astype(jnp.float32)
        # unscaled features; the 1/sqrt(N) normalization is folded into the
        # final statistics (quadratic for G, linear for u and the column sum)
        c = jnp.cos(z)
        s = jnp.sin(z)
        if pad:
            c = c * mkb[None, :]  # zero out padded sample columns
            s = s * mkb[None, :]
        return (
            cc + _mm(c, c.T),
            cs + _mm(c, s.T),
            ss + _mm(s, s.T),
            u_c + _mm(c, elb),
            u_s + _mm(s, elb),
            s_c + jnp.sum(c, axis=1),
            s_s + jnp.sum(s, axis=1),
        ), None

    init = (
        jnp.zeros((nf, nf), jnp.float32),
        jnp.zeros((nf, nf), jnp.float32),
        jnp.zeros((nf, nf), jnp.float32),
        jnp.zeros((nf,), jnp.float32),
        jnp.zeros((nf,), jnp.float32),
        jnp.zeros((nf,), jnp.float32),
        jnp.zeros((nf,), jnp.float32),
    )
    (cc, cs, ss, u_c, u_s, s_c, s_s), _ = jax.lax.scan(body, init, (xb, eb, mb))
    return assemble_streamed_gram(cc, cs, ss, u_c, u_s, s_c, s_s, n=n, fold_n=nf)


_gram_stream_xla = jax.jit(_gram_stream_body, static_argnames=("block",))


def _tile_featurize(om_i, xblk, mkb):
    """Unscaled masked cos/sin slabs of one feature tile on one sample block."""
    z = _mm(om_i, xblk.T).astype(jnp.float32)
    return jnp.cos(z) * mkb[None, :], jnp.sin(z) * mkb[None, :]


def _tile_pair_stats(om_i, om_j, xb, mb):
    """One (i, j) tile pair of the tiled streaming Gram: scan over sample
    blocks, (tile, tile) accumulators only — module-level so the VMEM-proxy
    test can bound its jaxpr intermediates by the tile size."""
    tile = om_i.shape[0]

    def body(carry, inp):
        cc, cs, ss = carry
        xblk, mkb = inp
        c_i, s_i = _tile_featurize(om_i, xblk, mkb)
        c_j, s_j = _tile_featurize(om_j, xblk, mkb)
        return (cc + _mm(c_i, c_j.T), cs + _mm(c_i, s_j.T), ss + _mm(s_i, s_j.T)), None

    init = tuple(jnp.zeros((tile, tile), jnp.float32) for _ in range(3))
    (cc, cs, ss), _ = jax.lax.scan(body, init, (xb, mb))
    return jnp.stack([cc, cs, ss])


def _tile_row_moments(om_i, xb, eb, mb):
    """Row-tile moment accumulators (u and column sums) of the tiled layout."""
    tile = om_i.shape[0]

    def body(carry, inp):
        u_c, u_s, s_c, s_s = carry
        xblk, elb, mkb = inp
        c_i, s_i = _tile_featurize(om_i, xblk, mkb)
        return (
            u_c + _mm(c_i, elb),
            u_s + _mm(s_i, elb),
            s_c + jnp.sum(c_i, axis=1),
            s_s + jnp.sum(s_i, axis=1),
        ), None

    init = tuple(jnp.zeros((tile,), jnp.float32) for _ in range(4))
    out, _ = jax.lax.scan(body, init, (xb, eb, mb))
    return jnp.stack(out)


def _gram_stream_tiled_body(
    x: jnp.ndarray, ell: jnp.ndarray, omega: jnp.ndarray, *, block: int, tile: int
):
    """Tiled-layout XLA twin of ``kernels.rff_gram_stream_tiled_pallas``.

    ``lax.map`` over (i, j) feature-tile pairs with the sample-block
    ``lax.scan`` innermost — exactly the tiled kernel's loop nest, so the live
    intermediates of one pair are two (tile, block) cos/sin slabs and three
    (tile, tile) accumulators, never an (N, block) slab (the untiled twin's
    per-step footprint) let alone the (2N, n) Sigma.  Feature-tile rows
    recompute their slabs once per (j, k) step, the same flop-for-memory trade
    the tiled kernel makes.
    """
    p, n = x.shape
    nf = omega.shape[0]
    pad_n = (-n) % block
    xp = jnp.pad(x, ((0, 0), (0, pad_n)))
    ep = jnp.pad(ell.astype(jnp.float32), (0, pad_n))
    nb = (n + pad_n) // block
    xb = xp.T.reshape(nb, block, p)
    eb = ep.reshape(nb, block)
    mb = jnp.pad(jnp.ones((n,), jnp.float32), (0, pad_n)).reshape(nb, block)
    pad_f = (-nf) % tile
    ni = (nf + pad_f) // tile
    om_t = jnp.pad(omega, ((0, pad_f), (0, 0))).reshape(ni, tile, p)

    def pair_stats(ij):
        return _tile_pair_stats(om_t[ij // ni], om_t[ij % ni], xb, mb)

    def row_moments(i):
        return _tile_row_moments(om_t[i], xb, eb, mb)

    blocks = jax.lax.map(pair_stats, jnp.arange(ni * ni))  # (ni^2, 3, t, t)
    blocks = blocks.reshape(ni, ni, 3, tile, tile).transpose(2, 0, 3, 1, 4)
    blocks = blocks.reshape(3, ni * tile, ni * tile)[:, :nf, :nf]
    mom = jax.lax.map(row_moments, jnp.arange(ni))  # (ni, 4, t)
    mom = mom.transpose(1, 0, 2).reshape(4, ni * tile)[:, :nf]
    return assemble_streamed_gram(
        blocks[0], blocks[1], blocks[2], mom[0], mom[1], mom[2], mom[3], n=n, fold_n=nf
    )


_gram_stream_tiled_xla = jax.jit(_gram_stream_tiled_body, static_argnames=("block", "tile"))


# --------------------------------------------------------------------------
# seed-fused statistics: XLA generator twins of the fused Pallas kernels
# --------------------------------------------------------------------------


def _fused_blocks(x, ell, *, block: int, nf_mult: int, n_features: int):
    """Mirror ``kernels.ops.rff_gram_stream_fused``'s padding exactly:
    (sample-blocked x (nb, p_pad, bk), lm blocks (nb, 2, bk), nf_pad).
    Identical padded shapes are a precondition for bit-for-bit agreement —
    the fused draw covers padded rows/cols too, and only identical block
    geometry makes the twin trace the same float ops as the kernel."""
    from repro.kernels.rff_gram_stream import DRAW_COLS

    p, n = x.shape
    pad_n = (-n) % block
    lm = jnp.stack([ell.astype(x.dtype), jnp.ones((n,), x.dtype)])  # (2, n)
    xp = jnp.pad(x, ((0, (-p) % DRAW_COLS), (0, pad_n)))
    lmp = jnp.pad(lm, ((0, 0), (0, pad_n)))
    nb = (n + pad_n) // block
    xb = xp.reshape(xp.shape[0], nb, block).transpose(1, 0, 2)  # (nb, p_pad, bk)
    lmb = lmp.reshape(2, nb, block).transpose(1, 0, 2)  # (nb, 2, bk)
    nf_pad = n_features + (-n_features) % nf_mult
    return xb, lmb, nf_pad


def _block_loader(xblk):
    """The twins' ``load_x``: DRAW_COLS rows of a (p_pad, bk) sample block."""
    from repro.kernels.rff_gram_stream import DRAW_COLS

    return lambda c0: jax.lax.dynamic_slice_in_dim(xblk, c0, DRAW_COLS, 0)


def _gram_stream_fused_body(
    x, ell, *, n_features: int, seed: int, ensemble: int, sigma: float,
    rf_kernel: str, block: int,
):
    """Bit-exact XLA twin of the untiled seed-fused Pallas path.

    Same padded geometry as the ``ops`` wrapper, same per-step math
    (:func:`repro.kernels.rff_gram_stream.fused_step_stats`, shared verbatim),
    same sequential accumulation order over sample blocks — so the twin and
    the interpret-mode kernel execute the identical float op sequence and
    agree to 0 ULP.  No (N, p) weight tensor exists here either: the draw is
    re-generated per sample block from the counter stream.
    """
    from repro.kernels.rff_gram_stream import fused_step_stats

    n = x.shape[1]
    xb, lmb, nf_pad = _fused_blocks(
        x, ell, block=block, nf_mult=block, n_features=n_features
    )
    mw = 2 * ensemble

    def body(carry, inp):
        xblk, lmk = inp
        d = fused_step_stats(
            _block_loader(xblk), xblk.shape[0], lmk, nf=nf_pad, n_features=n_features, seed=seed,
            ensemble=ensemble, sigma=sigma, rf_kernel=rf_kernel,
        )
        return tuple(a + t for a, t in zip(carry, d)), None

    init = (
        jnp.zeros((nf_pad, nf_pad), jnp.float32),
        jnp.zeros((nf_pad, nf_pad), jnp.float32),
        jnp.zeros((nf_pad, nf_pad), jnp.float32),
        jnp.zeros((nf_pad, mw), jnp.float32),
        jnp.zeros((nf_pad, mw), jnp.float32),
    )
    (cc, cs, ss, mc, ms), _ = jax.lax.scan(body, init, (xb, lmb))
    nf = n_features
    return assemble_streamed_gram_ensemble(
        cc[:nf, :nf], cs[:nf, :nf], ss[:nf, :nf], mc[:nf], ms[:nf],
        n=n, ensemble=ensemble,
    )


_gram_stream_fused_xla = jax.jit(
    _gram_stream_fused_body,
    static_argnames=("n_features", "seed", "ensemble", "sigma", "rf_kernel", "block"),
)


def _gram_stream_fused_tiled_body(
    x, ell, *, n_features: int, seed: int, ensemble: int, sigma: float,
    rf_kernel: str, block: int, tile: int,
):
    """Tiled-layout XLA twin of the seed-fused Pallas kernel: ``lax.map`` over
    (i, j) feature-tile pairs with the sample scan innermost, each pair
    re-drawing its two row tiles per step from the counter stream, DRAW_COLS
    columns at a time — the tiled kernel's loop nest, nothing N-sized
    live beyond the output statistics."""
    from repro.kernels.rff_gram_stream import (
        fused_tile_moment_step,
        fused_tile_pair_step,
    )

    n = x.shape[1]
    xb, lmb, nf_pad = _fused_blocks(
        x, ell, block=block, nf_mult=tile, n_features=n_features
    )
    ni = nf_pad // tile
    mw = 2 * ensemble
    kw = dict(
        tile=tile, n_features=n_features, seed=seed, ensemble=ensemble,
        sigma=sigma, rf_kernel=rf_kernel,
    )

    def pair_stats(ij):
        row_i = (ij // ni) * tile
        row_j = (ij % ni) * tile

        def body(carry, inp):
            xblk, lmk = inp
            d = fused_tile_pair_step(
                _block_loader(xblk), xblk.shape[0], lmk, row_i, row_j, **kw
            )
            return tuple(a + t for a, t in zip(carry, d)), None

        init = tuple(jnp.zeros((tile, tile), jnp.float32) for _ in range(3))
        out, _ = jax.lax.scan(body, init, (xb, lmb))
        return jnp.stack(out)

    def row_moments(i):
        def body(carry, inp):
            xblk, lmk = inp
            d = fused_tile_moment_step(
                _block_loader(xblk), xblk.shape[0], lmk, i * tile, **kw
            )
            return tuple(a + t for a, t in zip(carry, d)), None

        init = tuple(jnp.zeros((tile, mw), jnp.float32) for _ in range(2))
        out, _ = jax.lax.scan(body, init, (xb, lmb))
        return jnp.stack(out)

    blocks = jax.lax.map(pair_stats, jnp.arange(ni * ni))  # (ni^2, 3, t, t)
    blocks = blocks.reshape(ni, ni, 3, tile, tile).transpose(2, 0, 3, 1, 4)
    blocks = blocks.reshape(3, ni * tile, ni * tile)
    mom = jax.lax.map(row_moments, jnp.arange(ni))  # (ni, 2, t, 2S)
    mom = mom.transpose(1, 0, 2, 3).reshape(2, ni * tile, mw)
    nf = n_features
    return assemble_streamed_gram_ensemble(
        blocks[0, :nf, :nf], blocks[1, :nf, :nf], blocks[2, :nf, :nf],
        mom[0, :nf], mom[1, :nf], n=n, ensemble=ensemble,
    )


_gram_stream_fused_tiled_xla = jax.jit(
    _gram_stream_fused_tiled_body,
    static_argnames=(
        "n_features", "seed", "ensemble", "sigma", "rf_kernel", "block", "tile"
    ),
)


def fused_streaming_gram(
    x: jnp.ndarray,
    ell: jnp.ndarray,
    *,
    n_features: int,
    seed: int,
    ensemble: int = 1,
    sigma: float = 1.0,
    rf_kernel: str = "gauss",
    use_pallas: bool = False,
    block: int = 128,
    tile: int | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Seed-fused (G_H (2N, 2N), u (2N,)) — no omega operand anywhere.

    Dispatches to the fused Pallas kernel (``use_pallas=True``) or its XLA
    generator twin; both draw W_RF inside the pass from
    ``threefry(seed, row, col)`` and agree bit-for-bit.  The layout (untiled
    vs (t, t)-tiled) follows ``kernels.ops.gram_tile_plan`` on both paths so
    Pallas and twin always pick the same geometry.
    """
    from repro.kernels import ops as kops

    if use_pallas:
        return kops.rff_gram_stream_fused(
            x, ell, n_features=n_features, seed=seed, ensemble=ensemble,
            sigma_rf=sigma, rf_kernel=rf_kernel, block=block, tile=tile,
        )
    plan_tile = kops.gram_tile_plan(
        n_features, x.shape[0], tile=tile, fused=True, block=block
    )["tile"]
    if plan_tile is None:
        return _gram_stream_fused_xla(
            x, ell, n_features=n_features, seed=seed, ensemble=ensemble,
            sigma=sigma, rf_kernel=rf_kernel, block=block,
        )
    return _gram_stream_fused_tiled_xla(
        x, ell, n_features=n_features, seed=seed, ensemble=ensemble,
        sigma=sigma, rf_kernel=rf_kernel, block=block, tile=plan_tile,
    )


def streaming_gram(
    x: jnp.ndarray,
    ell: jnp.ndarray,
    omega: jnp.ndarray,
    *,
    block: int = 1024,
    use_pallas: bool = False,
    tile: int | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(G_H (2N, 2N), u (2N,)) fp32 from X (p, n) in one blocked pass.

    ``tile`` selects the feature-axis accumulator layout: None auto-selects on
    the Pallas path (``kernels.ops.gram_tile_plan``) and keeps the untiled
    scan on the XLA path; an int forces the (tile, tile)-blocked layout on
    either path (0 forces untiled).
    """
    if use_pallas:
        from repro.kernels import ops as kops

        return kops.rff_gram_stream(
            x, omega, ell, block=min(128, max(8, block)), tile=tile
        )
    if tile:
        return _gram_stream_tiled_xla(
            x, ell, omega, block=min(block, x.shape[1]), tile=tile
        )
    return _gram_stream_xla(x, ell, omega, block=min(block, x.shape[1]))


def _dense_gram(
    sigma: jnp.ndarray, ell: jnp.ndarray, *, use_kernel: bool = False
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Materializing reference: (G_H, u) from an explicit Sigma (2N, n)."""
    if use_kernel:
        from repro.kernels import ops as kops

        g_h = kops.centered_gram(sigma)
    else:
        mu = jnp.mean(sigma, axis=1, keepdims=True)
        s_c = sigma - mu
        g_h = s_c @ s_c.T  # Sigma H Sigma^T  (H idempotent: SH(SH)^T = S H S^T)
    return 0.5 * (g_h + g_h.T), sigma @ ell


# --------------------------------------------------------------------------
# solve: top-m of  G_H w = lambda (gamma I + u u^T) w
# --------------------------------------------------------------------------


def _whiten_half(u: jnp.ndarray, gamma: float) -> Callable[[jnp.ndarray], jnp.ndarray]:
    """Closed-form B^{-1/2} for B = gamma I + u u^T (identity plus rank one).

    B has eigenvalue gamma + |u|^2 along uhat and gamma elsewhere, so
    B^{-1/2} = gamma^{-1/2} (I + c uhat uhat^T) with
    c = sqrt(gamma / (gamma + |u|^2)) - 1.  Applying it is two rank-one
    updates, O(N k) for a (2N, k) block — no Cholesky, no triangular solves.
    """
    uu = _mm(u, u)
    c = jnp.sqrt(gamma / (gamma + uu)) - 1.0
    uhat = u * jax.lax.rsqrt(uu + 1e-30)
    inv_sqrt_gamma = jax.lax.rsqrt(jnp.asarray(gamma, u.dtype))

    def apply(v: jnp.ndarray) -> jnp.ndarray:
        return (v + c * jnp.outer(uhat, _mm(uhat, v))) * inv_sqrt_gamma

    return apply


@jax.jit
def _whitened_cmat(g_h: jnp.ndarray, u: jnp.ndarray, gamma) -> jnp.ndarray:
    """C = B^{-1/2} G_H B^{-1/2} via two rank-one whitening passes (jitted)."""
    bihalf = _whiten_half(u, gamma)
    cmat = bihalf(bihalf(g_h).T)
    return 0.5 * (cmat + cmat.T)


def _solve_whitened_top_m(g_h, u, gamma, key, *, m: int, iters: int, tol):
    """Traceable top-m of the whitened operator: matrix-free LOBPCG when the
    [X, R, P] search block fits (5m < 2N — jax's lobpcg_standard rejects
    5k >= n), symmetric eigh otherwise.  The single home of that guard."""
    bihalf = _whiten_half(u, gamma)
    if 5 * m < g_h.shape[0]:
        from jax.experimental.sparse.linalg import lobpcg_standard

        def matvec(v):
            return bihalf(_mm(g_h, bihalf(v)))

        x0 = jax.random.normal(key, (g_h.shape[0], m), g_h.dtype)
        vals, vecs, _ = lobpcg_standard(matvec, x0, m=iters, tol=tol)
    else:
        vals, vecs = _top_eigh(_whitened_cmat(g_h, u, gamma), m)
    return bihalf(vecs), vals


_lobpcg_solve = functools.partial(
    jax.jit, static_argnames=("m", "iters", "tol")
)(_solve_whitened_top_m)


def _host_top_eigh(cmat, *, m: int):
    """Host-side LAPACK subset eigendecomposition (syevr): top-m pairs only."""
    from scipy.linalg import eigh

    two_n = cmat.shape[0]
    vals, vecs = eigh(
        np.asarray(cmat, np.float32), subset_by_index=[two_n - m, two_n - 1]
    )
    return (
        np.ascontiguousarray(vals[::-1]).astype(np.float32),
        np.ascontiguousarray(vecs[:, ::-1]).astype(np.float32),
    )


# The device eigensolve: blocked subspace iteration on C where it lives.
EIGH_BLOCK = 2  # search block k = EIGH_BLOCK * m columns (2m: fastest of 2m-4m on a v5e)
EIGH_CHECK_EVERY = 10  # products C·X between two Rayleigh–Ritz checks
EIGH_MAX_PRODUCTS = 300  # past this unconverged, the host solve takes over
EIGH_RTOL = 2e-6  # largest ||C v - theta v|| / theta of the top m Ritz pairs


def _device_eigh_fits(two_n: int, m: int) -> bool:
    """The shape rule of ``_top_eigh``: subspace iteration wants a block of
    2m to 4m columns well inside the matrix, at most a quarter of it
    (2N >= 16m); below that the copy and LAPACK cost little."""
    return two_n >= 16 * m


@functools.partial(jax.jit, static_argnames=("two_n", "k"))
def _start_block(two_n: int, k: int):
    """The iteration's Gaussian start, from a fixed key (deterministic fits)."""
    return jax.random.normal(jax.random.PRNGKey(0), (two_n, k), jnp.float32)


@functools.partial(jax.jit, static_argnames=("steps",))
def _subspace_steps(cmat, y, *, steps: int):
    """``steps`` products x <- qr(y), y <- C x, then the Rayleigh–Ritz data
    of the last (x, y): H = x^T C x and the Gram R^T R of
    R = y - x H = (I - x x^T) C x, stacked as (2, k, k).

    For a Ritz pair (theta, s) of H, C x s - theta x s = R s, so the host
    reads every residual norm as sqrt(s^T R^T R s) from the same 2 k^2
    numbers, with no second trip to the device."""

    def body(_, xy):
        x = jnp.linalg.qr(xy[1])[0]
        return x, _mm(cmat, x)

    x, y = jax.lax.fori_loop(0, steps, body, (y, y))
    h = _mm(x.T, y)
    h = 0.5 * (h + h.T)
    r = y - _mm(x, h)
    return y, x, jnp.stack([h, _mm(r.T, r)])


@jax.jit
def _ritz_vectors(x, s):
    return _mm(x, s)


def _device_top_eigh(cmat, m: int):
    """Top-m (vals desc, vecs) of the symmetric PSD ``cmat``, on its device.

    Blocked subspace iteration with k = EIGH_BLOCK * m columns: the m-th
    pair converges at lambda_{k+1} / lambda_m per product, so a block well
    past m gets through a flat stretch of the spectrum.  Every
    EIGH_CHECK_EVERY products the k x k H = X^T C X and the Gram of its
    residual come to the host (2 k^2 float32 words); NumPy solves H in
    float64, the only step that needs more than float32, and the solve
    stops when every top-m pair has ||C v - theta v|| <= EIGH_RTOL theta.
    The k x m Ritz coefficients then go back and the device forms X S.

    Returns (vals, vecs, products); vals and vecs are None when
    EIGH_MAX_PRODUCTS products did not converge.
    """
    two_n = int(cmat.shape[0])
    y = _start_block(two_n, EIGH_BLOCK * m)
    products = 0
    while products < EIGH_MAX_PRODUCTS:
        steps = min(EIGH_CHECK_EVERY, EIGH_MAX_PRODUCTS - products)
        y, x, ritz = _subspace_steps(cmat, y, steps=steps)
        products += steps
        h, rr = np.asarray(ritz, np.float64)
        theta, s = np.linalg.eigh(h)
        theta, s = theta[::-1][:m], s[:, ::-1][:, :m]
        resid = np.sqrt(np.maximum(np.einsum("ij,ik,kj->j", s, rr, s), 0.0))
        if np.all(resid <= EIGH_RTOL * theta):
            vecs = _ritz_vectors(x, jnp.asarray(s, jnp.float32))
            return jnp.asarray(theta, jnp.float32), vecs, products
    return None, None, products


def _top_eigh(cmat, m: int):
    """Top-m (vals desc, vecs) of a symmetric matrix.

    On concrete arrays the path follows the shape alone.  When 2N >= 16m
    (``_device_eigh_fits``) ``_device_top_eigh`` solves on the device that
    holds ``cmat``; only k x k matrices cross to the host, for its float64
    Rayleigh–Ritz step.  Smaller matrices, and a device solve that reaches
    EIGH_MAX_PRODUCTS unconverged, take the host path: ``cmat`` is copied
    to the host once the program that made it has finished and the LAPACK
    subset driver (syevr) back-transforms only the m requested vectors.
    SciPy is called outside the program because an in-program callback
    stalls it badly (XLA's spin-waiting worker threads starve the
    single-threaded LAPACK call).  Under tracing the host solve becomes a
    ``pure_callback``.

    On concrete arrays each step is a program span (``repro.obs.span``):
    ``rf_tca.stats_wait`` (the wait for the program that made ``cmat``) and
    ``rf_tca.eigh`` (args ``two_n``, ``m``, ``path``; on the device also
    ``block`` and ``iters``, the products it took), with the host path's
    copy ``rf_tca.cmat_to_host`` before its eigh and upload
    ``rf_tca.vecs_to_device`` after.  A fallback shows the device attempt
    and the host solve as two ``rf_tca.eigh`` spans.  The registry counter
    ``rf_tca.eigh_solves`` counts solves by ``path`` ("device", "host",
    "host_fallback").
    """
    if not isinstance(cmat, jax.core.Tracer):
        with span("rf_tca.stats_wait"):
            jax.block_until_ready(cmat)
        two_n = int(cmat.shape[0])
        path = "host"
        if _device_eigh_fits(two_n, m):
            with span("rf_tca.eigh", two_n=two_n, m=int(m), block=EIGH_BLOCK * m) as sp:
                vals, vecs, iters = _device_top_eigh(cmat, m)
                path = "host_fallback" if vals is None else "device"
                sp.set_metadata(iters=iters, path=path)
        metrics().counter("rf_tca.eigh_solves").inc(path=path)
        if path == "device":
            return vals, vecs
        with span("rf_tca.cmat_to_host", bytes=int(cmat.nbytes)):
            host = np.asarray(cmat)
        with span("rf_tca.eigh", two_n=two_n, m=int(m), path=path):
            vals, vecs = _host_top_eigh(host, m=m)
        with span("rf_tca.vecs_to_device", bytes=int(vals.nbytes + vecs.nbytes)):
            return jnp.asarray(vals), jnp.asarray(vecs)
    out_shapes = (
        jax.ShapeDtypeStruct((m,), jnp.float32),
        jax.ShapeDtypeStruct((cmat.shape[0], m), jnp.float32),
    )
    return jax.pure_callback(
        functools.partial(_host_top_eigh, m=m), out_shapes, cmat.astype(jnp.float32)
    )


@jax.jit
def _apply_whiten(u, gamma, vecs):
    """w = B^{-1/2} vecs as one dispatch (the final back-transform)."""
    return _whiten_half(u, gamma)(vecs)


def solve_w_rf_gram(
    g_h: jnp.ndarray,
    u: jnp.ndarray,
    gamma: float,
    m: int,
    *,
    solver: str = "eigh",
    lobpcg_iters: int = 100,
    lobpcg_tol: float | None = None,
    seed: int = 0,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Top-m solution of (7) from the streamed statistics (G_H, u).

    Returns (w_rf (2N, m), eigvals (m,)).  See the module docstring for the
    eigh-vs-lobpcg trade-off.
    """
    if solver == "lobpcg":
        return _lobpcg_solve(
            g_h, u, gamma, jax.random.PRNGKey(seed),
            m=m, iters=lobpcg_iters, tol=lobpcg_tol,
        )
    if solver != "eigh":
        raise ValueError(f"unknown solver {solver!r}")
    cmat = _whitened_cmat(g_h, u, gamma)
    vals, vecs = _top_eigh(cmat, m)
    return _apply_whiten(u, gamma, vecs), vals


def solve_w_rf_cholesky(
    sigma: jnp.ndarray, ell: jnp.ndarray, gamma: float, m: int, *, use_kernel: bool = False
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Original Cholesky-whitening + full-eigh reference (the seed dense path).

    Kept verbatim as the benchmark baseline and a numerical cross-check for
    the Sherman–Morrison solvers.
    """
    two_n = sigma.shape[0]
    g_h, u = _dense_gram(sigma, ell, use_kernel=use_kernel)
    b = gamma * jnp.eye(two_n) + jnp.outer(u, u)
    chol = jnp.linalg.cholesky(b)
    li_g = jax.scipy.linalg.solve_triangular(chol, g_h, lower=True)
    c = jax.scipy.linalg.solve_triangular(chol, li_g.T, lower=True).T
    c = 0.5 * (c + c.T)
    vals, vecs = jnp.linalg.eigh(c)
    vals = vals[::-1][:m]
    vecs = vecs[:, ::-1][:, :m]
    w_rf = jax.scipy.linalg.solve_triangular(chol.T, vecs, lower=False)
    return w_rf, vals


def solve_w_rf(
    sigma: jnp.ndarray,
    ell: jnp.ndarray,
    gamma: float,
    m: int,
    *,
    use_kernel: bool = False,
    solver: str = "eigh",
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Top-m solution of (7) given an explicit RFF matrix Sigma (2N, n).

    Returns (w_rf (2N, m), eigvals (m,)).  ``solver="cholesky"`` reproduces
    the original implementation; "eigh"/"lobpcg" use Sherman–Morrison
    whitening (same eigenpairs, W B-orthonormal in both cases).
    """
    if solver == "cholesky":
        return solve_w_rf_cholesky(sigma, ell, gamma, m, use_kernel=use_kernel)
    g_h, u = _dense_gram(sigma, ell, use_kernel=use_kernel)
    return solve_w_rf_gram(g_h, u, gamma, m, solver=solver)


# --------------------------------------------------------------------------
# public fit / transform
# --------------------------------------------------------------------------


def _draw_omega_traced(key, p: int, sigma, *, n_features: int, kernel: str):
    if kernel == "gauss":
        return jax.random.normal(key, (n_features, p)) / sigma
    if kernel == "laplace":
        return jax.random.cauchy(key, (n_features, p)) / sigma
    raise ValueError(f"unknown kernel {kernel!r}")


@functools.partial(jax.jit, static_argnames=("n_features", "block", "kernel"))
def _fit_stream_stats(
    x_s, x_t, key, gamma, sigma, *, n_features: int, block: int, kernel: str
):
    """Streamed statistics as ONE compiled program: omega draw, blocked Gram
    scan and Sherman–Morrison whitening fuse into (omega, C, u).  The top-m
    eigensolve follows in ``_top_eigh``, on C where it lies."""
    omega = _draw_omega_traced(key, x_s.shape[0], sigma, n_features=n_features, kernel=kernel)
    x = jnp.concatenate([x_s, x_t], axis=1)
    ell = ell_vector(x_s.shape[1], x_t.shape[1])
    g_h, u = _gram_stream_body(x, ell, omega, block=block)
    return omega, _whitened_cmat(g_h, u, gamma), u


@functools.partial(
    jax.jit, static_argnames=("n_features", "m", "block", "kernel", "lobpcg_iters", "lobpcg_tol")
)
def _fit_stream_lobpcg(
    x_s, x_t, key, gamma, sigma,
    *, n_features: int, m: int, block: int, kernel: str, lobpcg_iters: int, lobpcg_tol,
):
    """Fully-fused streamed fit with the matrix-free LOBPCG solve (no host
    work at all — the right shape for accelerators and large 2N)."""
    omega = _draw_omega_traced(key, x_s.shape[0], sigma, n_features=n_features, kernel=kernel)
    x = jnp.concatenate([x_s, x_t], axis=1)
    ell = ell_vector(x_s.shape[1], x_t.shape[1])
    g_h, u = _gram_stream_body(x, ell, omega, block=block)
    w_rf, vals = _solve_whitened_top_m(
        g_h, u, gamma, jax.random.fold_in(key, 1), m=m, iters=lobpcg_iters, tol=lobpcg_tol
    )
    return omega, w_rf, vals


def _parse_fused_spec(w_rf) -> int | None:
    """``w_rf="fused:<seed>"`` -> seed; None passes through; else error."""
    if w_rf is None:
        return None
    if isinstance(w_rf, str) and w_rf.startswith("fused:"):
        return int(w_rf.split(":", 1)[1])
    raise ValueError(
        f'w_rf must be None or "fused:<seed>", got {w_rf!r}'
    )


def _fit_fused(
    x_s, x_t, *, n_features: int, m: int, gamma: float, sigma: float,
    seed: int, kernel: str, use_pallas: bool, solver: str,
    fused_seed: int, ensemble: int,
) -> tuple[RFTCAState, dict]:
    """Seed-fused statistics pass, returning the fitted state *and* the
    (G_H, u) statistics it solved from — the moment-space refresh input."""
    x = jnp.concatenate([x_s, x_t], axis=1)
    ell = ell_vector(x_s.shape[1], x_t.shape[1])
    g_h, u = fused_streaming_gram(
        x, ell, n_features=n_features, seed=fused_seed, ensemble=ensemble,
        sigma=sigma, rf_kernel=kernel, use_pallas=use_pallas,
    )
    w, vals = solve_w_rf_gram(g_h, u, gamma, m, solver=solver, seed=seed)
    state = RFTCAState(
        omega=None, w_rf=w, eigvals=vals,
        fused=(fused_seed, ensemble, sigma, kernel),
    )
    stats = {
        "gram": g_h, "u": u, "gamma": float(gamma), "m": int(m),
        "solver": str(solver), "seed": int(seed),
    }
    return state, stats


def rf_tca_fit_with_stats(
    x_s: jnp.ndarray,
    x_t: jnp.ndarray,
    *,
    n_features: int,
    m: int,
    gamma: float = 1.0,
    sigma: float = 1.0,
    seed: int = 0,
    kernel: str = "gauss",
    use_pallas: bool = False,
    solver: str = "eigh",
    w_rf: str | None = None,
    ensemble: int = 1,
) -> tuple[RFTCAState, dict]:
    """Seed-fused :func:`rf_tca_fit` that also returns the fit statistics.

    The returned dict carries the merged Gram ``gram`` (G_H), the mean
    discrepancy ``u`` and the solve hyperparameters — everything
    :func:`rf_tca_resolve` needs to re-solve W_RF later from *updated*
    moments (e.g. after target drift) without touching raw data again.
    The state is bitwise identical to ``rf_tca_fit`` with the same
    arguments (the fit delegates to the same fused pass).
    """
    fused_seed = _parse_fused_spec(w_rf)
    if fused_seed is None:
        raise ValueError(
            'rf_tca_fit_with_stats requires the seed-fused path: '
            'pass w_rf="fused:<seed>"'
        )
    if solver not in ("eigh", "lobpcg"):
        raise ValueError(f"unknown solver {solver!r}")
    return _fit_fused(
        x_s, x_t, n_features=n_features, m=m, gamma=gamma, sigma=sigma,
        seed=seed, kernel=kernel, use_pallas=use_pallas, solver=solver,
        fused_seed=fused_seed, ensemble=ensemble,
    )


def rf_tca_resolve(
    gram: jnp.ndarray,
    u: jnp.ndarray,
    *,
    gamma: float,
    m: int,
    solver: str = "eigh",
    seed: int = 0,
    fused_spec: tuple,
) -> RFTCAState:
    """Re-solve W_RF from statistics alone (no data pass).

    ``gram``/``u`` are the (possibly updated) (G_H, u) pair and
    ``fused_spec`` the ``(seed, ensemble, sigma, kernel)`` tuple of the
    original fit — transforms of the returned state draw the same feature
    map.  This is the aligner auto-refresh primitive: a drifted target mean
    changes ``u = mu_S - mu_T`` but not the merged Gram, so a refresh is one
    O(N^2 m) eigensolve instead of a refit over raw data.
    """
    if solver not in ("eigh", "lobpcg"):
        raise ValueError(f"unknown solver {solver!r}")
    w, vals = solve_w_rf_gram(gram, u, gamma, m, solver=solver, seed=seed)
    return RFTCAState(omega=None, w_rf=w, eigvals=vals, fused=tuple(fused_spec))


def rf_tca_fit(
    x_s: jnp.ndarray,
    x_t: jnp.ndarray,
    *,
    n_features: int,
    m: int,
    gamma: float = 1.0,
    sigma: float = 1.0,
    seed: int = 0,
    kernel: str = "gauss",
    use_pallas: bool = False,
    mode: str = "stream",
    solver: str = "eigh",
    block: int = 1024,
    w_rf: str | None = None,
    ensemble: int = 1,
) -> RFTCAState:
    """Algorithm 1: fit W_RF on source (p, n_S) and target (p, n_T) data.

    mode="stream" (default) never materializes the (2N, n) RFF matrix;
    mode="dense" is the original materializing path (solver "cholesky"
    reproduces the seed implementation exactly).

    ``w_rf="fused:<seed>"`` switches the statistics pass to the seed-fused
    generators: the frequency matrix is drawn *inside* the kernel (or its XLA
    twin) from a counter-based stream and never exists as a tensor — the
    returned state has ``omega=None`` and carries the spec instead.
    ``ensemble=S`` then averages the (G_H, u) statistics over S
    independently-keyed draws in the same pass (S=1 is bitwise the
    single-draw path); out-of-sample transforms use draw 0's feature map.

    The call is the program span ``rf_tca.fit`` (``repro.obs.span``).
    """
    n = int(x_s.shape[1] + x_t.shape[1])
    with span("rf_tca.fit", n=n, p=int(x_s.shape[0]), n_features=int(n_features),
              m=int(m)):
        if mode not in ("stream", "dense"):
            raise ValueError(f"unknown mode {mode!r}")
        if solver not in ("eigh", "lobpcg", "cholesky"):
            raise ValueError(f"unknown solver {solver!r}")
        if mode == "stream" and solver == "cholesky":
            raise ValueError(
                'solver="cholesky" factorizes the explicit-Sigma path and requires '
                'mode="dense"; the streaming solvers are "eigh" and "lobpcg"'
            )
        fused_seed = _parse_fused_spec(w_rf)
        if ensemble != 1 and fused_seed is None:
            raise ValueError('ensemble > 1 requires w_rf="fused:<seed>"')
        if fused_seed is not None:
            if mode != "stream":
                raise ValueError('w_rf="fused:<seed>" requires mode="stream"')
            state, _ = _fit_fused(
                x_s, x_t, n_features=n_features, m=m, gamma=gamma, sigma=sigma,
                seed=seed, kernel=kernel, use_pallas=use_pallas, solver=solver,
                fused_seed=fused_seed, ensemble=ensemble,
            )
            return state
        if mode == "stream" and not use_pallas:
            key = jax.random.PRNGKey(seed)
            blk = min(block, n)
            if solver == "lobpcg":
                omega, w_rf, vals = _fit_stream_lobpcg(
                    x_s, x_t, key, gamma, sigma,
                    n_features=n_features, m=m, block=blk, kernel=kernel,
                    lobpcg_iters=100, lobpcg_tol=None,
                )
            else:
                omega, cmat, u = _fit_stream_stats(
                    x_s, x_t, key, gamma, sigma,
                    n_features=n_features, block=blk, kernel=kernel,
                )
                vals, vecs = _top_eigh(cmat, m)
                w_rf = _apply_whiten(u, gamma, vecs)
            return RFTCAState(omega=omega, w_rf=w_rf, eigvals=vals)
        p = x_s.shape[0]
        omega = draw_omega(seed, n_features, p, sigma=sigma, kernel=kernel)
        x = jnp.concatenate([x_s, x_t], axis=1)
        ell = ell_vector(x_s.shape[1], x_t.shape[1])
        if mode == "stream":
            g_h, u = streaming_gram(x, ell, omega, block=block, use_pallas=use_pallas)
            w_rf, vals = solve_w_rf_gram(g_h, u, gamma, m, solver=solver, seed=seed)
        else:
            sig = rff_features(x, omega, use_kernel=use_pallas)
            w_rf, vals = solve_w_rf(sig, ell, gamma, m, use_kernel=use_pallas, solver=solver)
        return RFTCAState(omega=omega, w_rf=w_rf, eigvals=vals)


# Fused-path transform omega memo: the draw is a pure function of the spec
# (seed, N, p, sigma, kernel), so repeated serving transforms must not redraw
# it per call.  FIFO-capped cache with a ``regenerations`` counter, mirroring
# ``comm.codecs.SeedReplayCodec.decode`` (the wire-side twin of this memo).
_FUSED_OMEGA_CACHE: dict[tuple, jnp.ndarray] = {}
_FUSED_OMEGA_CACHE_MAX = 16
fused_omega_regenerations: int = 0


def fused_transform_omega(state: RFTCAState, dim: int) -> jnp.ndarray:
    """Draw-0 frequency matrix of a seed-fused state, memoized per spec.

    ``dim`` is the data dimension p of the batch about to be featurized.  The
    first call per ``(seed, N, p, sigma, kernel)`` materializes the (N, p)
    matrix from the counter stream and counts one regeneration; subsequent
    transforms (the serving hot path) hit the cache.
    """
    global fused_omega_regenerations
    f_seed, _, f_sigma, f_kernel = state.fused
    n_features = state.w_rf.shape[0] // 2
    key = (int(f_seed), int(n_features), int(dim), float(f_sigma), str(f_kernel))
    hit = _FUSED_OMEGA_CACHE.get(key)
    if hit is not None:
        return hit
    from repro.kernels.prng import fused_omega

    omega = fused_omega(f_seed, n_features, dim, sigma=f_sigma, rf_kernel=f_kernel)
    fused_omega_regenerations += 1
    if len(_FUSED_OMEGA_CACHE) >= _FUSED_OMEGA_CACHE_MAX:
        _FUSED_OMEGA_CACHE.pop(next(iter(_FUSED_OMEGA_CACHE)))
    _FUSED_OMEGA_CACHE[key] = omega
    return omega


def fused_omega_cache_info() -> dict[str, int]:
    """{"size", "max", "regenerations"} — the memo's observable state."""
    return {
        "size": len(_FUSED_OMEGA_CACHE),
        "max": _FUSED_OMEGA_CACHE_MAX,
        "regenerations": fused_omega_regenerations,
    }


def project_features(w_rf: jnp.ndarray, feats: jnp.ndarray) -> jnp.ndarray:
    """W_RF^T Sigma (m, n) as one contraction over the 2N axis.

    Written as the ``dot_general`` a jitted ``w_rf.T @ feats`` compiles to,
    so the eager transform and the serving planes run the same dot: an eager
    ``.T`` would materialize the transpose and take a dot whose summation
    order differs in the last bit.
    """
    return jax.lax.dot_general(
        w_rf, feats, (((0,), (0,)), ((), ())), precision=jax.lax.Precision.HIGHEST
    )


def rf_tca_transform(state: RFTCAState, x: jnp.ndarray) -> jnp.ndarray:
    """F = W_RF^T Sigma(X) in R^{m x n} — works on unseen data (out-of-sample).

    On the seed-fused path (``state.omega is None``) the frequency matrix is
    re-drawn from the counter stream on demand (draw 0 when the fit averaged
    an ensemble) and memoized per spec (:func:`fused_transform_omega`) — the
    fit-time statistics never materialized it, and repeated out-of-sample
    transforms materialize it exactly once.
    """
    omega = state.omega
    if omega is None:
        omega = fused_transform_omega(state, x.shape[0])
    return project_features(state.w_rf, rff_features(x, omega))


def rf_tca(
    x_s: jnp.ndarray, x_t: jnp.ndarray, **kw
) -> tuple[jnp.ndarray, jnp.ndarray, RFTCAState]:
    """Convenience: fit then return (F_S (m,n_S), F_T (m,n_T), state)."""
    state = rf_tca_fit(x_s, x_t, **kw)
    f_s = rf_tca_transform(state, x_s)
    f_t = rf_tca_transform(state, x_t)
    return f_s, f_t, state
