"""Pallas TPU kernel: streamed RFF Gram accumulation (RF-TCA Alg. 1 hot path).

Fuses the three stages of the RF-TCA statistics pass — RFF featurization
(paper Def. 2), sample masking, and Gram/moment accumulation — into one
kernel that consumes X (p, n) in sample blocks and emits only O(N^2)-sized
statistics:

    G_cc = C C^T,  G_cs = C S^T,  G_ss = S S^T      (N, N) each
    M_c  = C [ell; mask]^T,  M_s = S [ell; mask]^T  (N, 2) each

with C = cos(Omega X)/sqrt(N), S = sin(Omega X)/sqrt(N) masked to the true
sample columns.  The caller assembles Sigma H Sigma^T and u = Sigma ell from
these; the (2N, n) matrix Sigma itself NEVER exists in HBM, so peak memory is
O(N^2 + N b) for sample-block size b, independent of n — exactly the scaling
the paper claims for RF-TCA.

Two layouts share the kernel math.  Both accumulate straight into their
output blocks, which stay resident in VMEM while their block index holds:

- **untiled** (`rff_gram_stream_pallas`): grid (n / bk,) — one axis over
  sample blocks, (N_pad, N_pad) fp32 outputs held across the whole pass
  next to the whole (N_pad, p) omega block.
- **tiled** (`rff_gram_stream_tiled_pallas`): grid (N/t, N/t, n/bk) — a 2-D
  output tiling over (i, j) feature-tile pairs with the sample-block loop
  innermost, so each program instance only holds a (t, t) block of each Gram
  and two (t, p) omega blocks.  Row tile i recomputes its cos/sin slab once
  per (j, k) step — the usual flop-for-memory trade of output tiling.

``kernels.ops.gram_tile_plan`` picks the layout and tile from N *and* p,
against the scoped-VMEM limit every kernel here compiles under.

**Seed-fused variants** (`rff_gram_stream_fused_pallas`,
`rff_gram_stream_fused_tiled_pallas`): no ``omega`` operand at all — each
program instance draws its W_RF rows *inside* the kernel from the
counter-based threefry stream of :mod:`repro.kernels.prng`
(``threefry(seed, feature_row, column)`` per element), so the ``(N, p)``
weight tensor never exists in HBM on either side of the federation.  The
draw runs ``DRAW_COLS`` columns at a time inside a loop over the contraction,
so its temporaries are (rows, DRAW_COLS) whatever p is.  The per-step math
lives in :func:`fused_step_stats` / :func:`fused_tile_pair_step` /
:func:`fused_tile_moment_step`, shared verbatim by the kernels and their XLA
generator twins in ``core/rf_tca.py`` — fused-vs-twin agreement is
bit-for-bit by construction.  ``ensemble=S`` averages the Gram/moment
statistics over S independently-keyed draws in the same pass; ``S=1`` traces
the identical program as the single-draw path.

Every contraction runs at ``Precision.HIGHEST``: the phases Omega X reach
|z| ~ sqrt(p) / sigma, where a one-pass bf16 product would leave cos/sin
with errors of order 1e-1 at p = 2048.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.prng import fused_omega_block

# Scoped-VMEM limit of every Gram-stream kernel: half of the 128 MiB of VMEM
# a v5e TensorCore has (Mosaic's default scope is 16 MiB).
VMEM_LIMIT_BYTES = 64 * 2**20
# W_RF columns the fused kernels draw per step of their contraction loop.
DRAW_COLS = 128

_HIGHEST = jax.lax.Precision.HIGHEST
_CONTRACT = (((1,), (1,)), ((), ()))


def _gram(a, b):
    """a b^T, fp32 at full precision."""
    return jax.lax.dot_general(
        a, b, _CONTRACT, precision=_HIGHEST, preferred_element_type=jnp.float32
    )


def _phase(omega, x):
    """Omega X, fp32 at full precision."""
    return jnp.dot(omega, x, precision=_HIGHEST, preferred_element_type=jnp.float32)


def _feature_scales(lm, *, n_features: int, ensemble: int = 1):
    """(mask, per-feature scale, fp32 lm) for one sample block.

    Features carry 1/sqrt(N S): quadratic contractions (the Gram blocks) then
    accumulate the *mean over draws* directly, while the per-draw moment
    columns come out scaled by 1/sqrt(S) — exactly what the ensemble assembly
    (``assemble_streamed_gram_ensemble``) expects for averaging the centered
    per-draw Grams.  At ``S=1`` no extra op is traced.
    """
    inv = 1.0 / jnp.sqrt(jnp.float32(n_features))
    lmf = lm.astype(jnp.float32)  # (2, bk): row 0 = ell, row 1 = mask
    mask = lmf[1:2, :]  # (1, bk); zero on padded sample columns
    if ensemble > 1:
        inv = inv * jax.lax.rsqrt(jnp.float32(ensemble))
    return mask, inv, lmf


def _zero(*refs):
    for r in refs:
        r[...] = jnp.zeros_like(r)


def _rff_gram_kernel(
    omega_ref, x_ref, lm_ref, gcc_ref, gcs_ref, gss_ref, mc_ref, ms_ref,
    *, n_features: int,
):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        _zero(gcc_ref, gcs_ref, gss_ref, mc_ref, ms_ref)

    mask, inv, lm = _feature_scales(lm_ref[...], n_features=n_features)
    z = _phase(omega_ref[...], x_ref[...])
    c = jnp.cos(z) * inv * mask
    s = jnp.sin(z) * inv * mask
    gcc_ref[...] += _gram(c, c)
    gcs_ref[...] += _gram(c, s)
    gss_ref[...] += _gram(s, s)
    mc_ref[...] += _gram(c, lm)
    ms_ref[...] += _gram(s, lm)


def _rff_gram_tiled_kernel(
    omega_i_ref, omega_j_ref, x_ref, lm_ref, gcc_ref, gcs_ref, gss_ref, mc_ref, ms_ref,
    *, n_features: int,
):
    j = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        _zero(gcc_ref, gcs_ref, gss_ref)

    # the (t, 2) moment blocks only depend on the row tile i: they stay
    # resident across the whole (j, k) sweep and accumulate on j == 0
    @pl.when((k == 0) & (j == 0))
    def _init_moments():
        _zero(mc_ref, ms_ref)

    mask, inv, lm = _feature_scales(lm_ref[...], n_features=n_features)
    x = x_ref[...]
    z_i = _phase(omega_i_ref[...], x)
    z_j = _phase(omega_j_ref[...], x)
    c_i = jnp.cos(z_i) * inv * mask
    s_i = jnp.sin(z_i) * inv * mask
    c_j = jnp.cos(z_j) * inv * mask
    s_j = jnp.sin(z_j) * inv * mask
    gcc_ref[...] += _gram(c_i, c_j)
    gcs_ref[...] += _gram(c_i, s_j)
    gss_ref[...] += _gram(s_i, s_j)

    @pl.when(j == 0)
    def _moments():
        mc_ref[...] += _gram(c_i, lm)
        ms_ref[...] += _gram(s_i, lm)


def _check_blocks(n: int, block_k: int, lm) -> tuple[int, int]:
    bk = min(block_k, n)
    if n % bk or lm.shape[1] != n:
        raise ValueError(f"n={n} must tile by {bk} and match lm {lm.shape}")
    return bk, n // bk


def _stat_shapes(rows: int, moment_cols: int) -> list[jax.ShapeDtypeStruct]:
    gram = jax.ShapeDtypeStruct((rows, rows), jnp.float32)
    moment = jax.ShapeDtypeStruct((rows, moment_cols), jnp.float32)
    return [gram, gram, gram, moment, moment]


def _untiled_out_specs(rows: int, moment_cols: int) -> list[pl.BlockSpec]:
    gram = pl.BlockSpec((rows, rows), lambda k: (0, 0))
    moment = pl.BlockSpec((rows, moment_cols), lambda k: (0, 0))
    return [gram, gram, gram, moment, moment]


def _tiled_out_specs(tile: int, moment_cols: int) -> list[pl.BlockSpec]:
    gram = pl.BlockSpec((tile, tile), lambda i, j, k: (i, j))
    moment = pl.BlockSpec((tile, moment_cols), lambda i, j, k: (i, 0))
    return [gram, gram, gram, moment, moment]


def rff_gram_stream_tiled_pallas(
    x: jax.Array,  # (p, n)
    omega: jax.Array,  # (N, p), N a multiple of ``tile``
    lm: jax.Array,  # (2, n): stacked [ell; column-mask]
    *,
    tile: int,
    block_k: int = 128,
    scale_n: int | None = None,  # true N when omega rows are padded
    interpret: bool,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Tiled layout of :func:`rff_gram_stream_pallas` (same five outputs).

    Grid (N/t, N/t, n/bk): each (i, j) program instance owns the (t, t)
    output blocks G_cc[i, j], G_cs[i, j], G_ss[i, j] and streams all sample
    blocks through them before moving on — VMEM per instance is 3 t^2 fp32
    outputs plus two (t, p) omega blocks and one (p, bk) sample block,
    *independent of N*.
    """
    n_features, p = omega.shape
    bk, k_steps = _check_blocks(x.shape[1], block_k, lm)
    if n_features % tile:
        raise ValueError(f"N={n_features} must tile by {tile}")
    n_tiles = n_features // tile
    return pl.pallas_call(
        functools.partial(_rff_gram_tiled_kernel, n_features=scale_n or n_features),
        grid=(n_tiles, n_tiles, k_steps),
        in_specs=[
            pl.BlockSpec((tile, p), lambda i, j, k: (i, 0)),
            pl.BlockSpec((tile, p), lambda i, j, k: (j, 0)),
            pl.BlockSpec((p, bk), lambda i, j, k: (0, k)),
            pl.BlockSpec((2, bk), lambda i, j, k: (0, k)),
        ],
        out_specs=_tiled_out_specs(tile, 2),
        out_shape=_stat_shapes(n_features, 2),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(omega, omega, x, lm)


def rff_gram_stream_pallas(
    x: jax.Array,  # (p, n)
    omega: jax.Array,  # (N, p)
    lm: jax.Array,  # (2, n): stacked [ell; column-mask]
    *,
    block_k: int = 128,
    scale_n: int | None = None,  # true N when omega rows are padded
    interpret: bool,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Returns (G_cc, G_cs, G_ss, M_c, M_s); see module docstring for shapes."""
    nf, p = omega.shape
    bk, k_steps = _check_blocks(x.shape[1], block_k, lm)
    return pl.pallas_call(
        functools.partial(_rff_gram_kernel, n_features=scale_n or nf),
        grid=(k_steps,),
        in_specs=[
            pl.BlockSpec((nf, p), lambda k: (0, 0)),
            pl.BlockSpec((p, bk), lambda k: (0, k)),
            pl.BlockSpec((2, bk), lambda k: (0, k)),
        ],
        out_specs=_untiled_out_specs(nf, 2),
        out_shape=_stat_shapes(nf, 2),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(omega, x, lm)


# --------------------------------------------------------------------------
# seed-fused layouts: W_RF rows drawn inside the kernel, no omega operand
# --------------------------------------------------------------------------


def _fused_phase(load_x, p: int, rows: int, row0, *, seed: int, ensemble_index: int,
                 sigma: float, rf_kernel: str):
    """Phases (rows, bk) of W_RF rows ``[row0, row0 + rows)`` on one sample
    block, drawn and contracted ``DRAW_COLS`` columns at a time.

    ``load_x(c0)`` returns rows ``[c0, c0 + DRAW_COLS)`` of the (p, bk) sample
    block: a ref slice in the kernels, a ``dynamic_slice`` in the twins.
    """
    def chunk(c0):
        om = fused_omega_block(
            seed, rows, DRAW_COLS, row0=row0, col0=c0,
            ensemble_index=ensemble_index, sigma=sigma, rf_kernel=rf_kernel,
        )
        return _phase(om, load_x(c0))

    z = chunk(0)
    if p > DRAW_COLS:
        z = jax.lax.fori_loop(1, p // DRAW_COLS, lambda c, acc: acc + chunk(c * DRAW_COLS), z)
    return z


def _ref_loader(x_ref):
    def load(c0):
        if not isinstance(c0, int):
            c0 = pl.multiple_of(c0, DRAW_COLS)
        return x_ref[pl.ds(c0, DRAW_COLS), :]

    return load


def fused_step_stats(
    load_x, p: int, lm, *, nf: int, n_features: int, seed: int, ensemble: int,
    sigma: float, rf_kernel: str,
):
    """One sample block's five stat contributions, W_RF rows drawn in-step.

    ``load_x`` / ``p`` give the (p_pad, bk) block (see :func:`_fused_phase`),
    ``lm`` (2, bk) -> (dcc (nf, nf), dcs, dss, dmc (nf, 2S), dms).  The Gram
    contributions are pooled over draws (the 1/sqrt(S) feature scale makes
    the sum the mean); the moment columns stay per draw — centering is
    quadratic in the column sums, so the assembly
    (:func:`repro.core.kernels_math.assemble_streamed_gram_ensemble`) needs
    draw ``e``'s columns at ``(2e, 2e+1)``.  Shared verbatim by the untiled
    fused kernel and its XLA twin so both trace the identical float ops.
    """
    mask, inv, lm_m = _feature_scales(lm, n_features=n_features, ensemble=ensemble)
    dcc = dcs = dss = None
    dmc_cols = []
    dms_cols = []
    for e in range(ensemble):
        z = _fused_phase(
            load_x, p, nf, 0, seed=seed, ensemble_index=e, sigma=sigma, rf_kernel=rf_kernel
        )
        c = jnp.cos(z) * inv * mask
        s = jnp.sin(z) * inv * mask
        terms = (_gram(c, c), _gram(c, s), _gram(s, s))
        if dcc is None:
            dcc, dcs, dss = terms
        else:
            dcc, dcs, dss = (a + t for a, t in zip((dcc, dcs, dss), terms))
        dmc_cols.append(_gram(c, lm_m))
        dms_cols.append(_gram(s, lm_m))
    dmc = dmc_cols[0] if ensemble == 1 else jnp.concatenate(dmc_cols, axis=1)
    dms = dms_cols[0] if ensemble == 1 else jnp.concatenate(dms_cols, axis=1)
    return dcc, dcs, dss, dmc, dms


def fused_tile_pair_step(
    load_x, p: int, lm, row_i, row_j, *, tile: int, n_features: int, seed: int,
    ensemble: int, sigma: float, rf_kernel: str,
):
    """One (i, j) feature-tile pair's Gram contributions on one sample block.

    ``row_i`` / ``row_j`` are the tiles' absolute row offsets (traced in the
    kernel: ``program_id * tile``).  Returns (dcc, dcs, dss), each (t, t).
    """
    mask, inv, _ = _feature_scales(lm, n_features=n_features, ensemble=ensemble)
    draw = dict(seed=seed, sigma=sigma, rf_kernel=rf_kernel)
    dcc = dcs = dss = None
    for e in range(ensemble):
        z_i = _fused_phase(load_x, p, tile, row_i, ensemble_index=e, **draw)
        z_j = _fused_phase(load_x, p, tile, row_j, ensemble_index=e, **draw)
        c_i = jnp.cos(z_i) * inv * mask
        s_i = jnp.sin(z_i) * inv * mask
        c_j = jnp.cos(z_j) * inv * mask
        s_j = jnp.sin(z_j) * inv * mask
        terms = (_gram(c_i, c_j), _gram(c_i, s_j), _gram(s_i, s_j))
        if dcc is None:
            dcc, dcs, dss = terms
        else:
            dcc, dcs, dss = (a + t for a, t in zip((dcc, dcs, dss), terms))
    return dcc, dcs, dss


def fused_tile_moment_step(
    load_x, p: int, lm, row_i, *, tile: int, n_features: int, seed: int, ensemble: int,
    sigma: float, rf_kernel: str,
):
    """One row tile's (t, 2S) per-draw moment contributions on one sample
    block — draw ``e``'s (ell-moment, column-sum) land in columns
    ``(2e, 2e+1)``, matching :func:`fused_step_stats`."""
    mask, inv, lm_m = _feature_scales(lm, n_features=n_features, ensemble=ensemble)
    dmc_cols = []
    dms_cols = []
    for e in range(ensemble):
        z_i = _fused_phase(
            load_x, p, tile, row_i, seed=seed, ensemble_index=e, sigma=sigma,
            rf_kernel=rf_kernel,
        )
        dmc_cols.append(_gram(jnp.cos(z_i) * inv * mask, lm_m))
        dms_cols.append(_gram(jnp.sin(z_i) * inv * mask, lm_m))
    dmc = dmc_cols[0] if ensemble == 1 else jnp.concatenate(dmc_cols, axis=1)
    dms = dms_cols[0] if ensemble == 1 else jnp.concatenate(dms_cols, axis=1)
    return dmc, dms


def _rff_gram_fused_kernel(
    x_ref, lm_ref, gcc_ref, gcs_ref, gss_ref, mc_ref, ms_ref,
    *, n_features: int, seed: int, ensemble: int, sigma: float, rf_kernel: str,
):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        _zero(gcc_ref, gcs_ref, gss_ref, mc_ref, ms_ref)

    stats = fused_step_stats(
        _ref_loader(x_ref), x_ref.shape[0], lm_ref[...], nf=gcc_ref.shape[0],
        n_features=n_features, seed=seed, ensemble=ensemble, sigma=sigma,
        rf_kernel=rf_kernel,
    )
    for ref, d in zip((gcc_ref, gcs_ref, gss_ref, mc_ref, ms_ref), stats):
        ref[...] += d


def _rff_gram_fused_tiled_kernel(
    x_ref, lm_ref, gcc_ref, gcs_ref, gss_ref, mc_ref, ms_ref,
    *, n_features: int, tile: int, seed: int, ensemble: int, sigma: float,
    rf_kernel: str,
):
    i = pl.program_id(0)
    j = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        _zero(gcc_ref, gcs_ref, gss_ref)

    @pl.when((k == 0) & (j == 0))
    def _init_moments():
        _zero(mc_ref, ms_ref)

    kw = dict(
        tile=tile, n_features=n_features, seed=seed, ensemble=ensemble,
        sigma=sigma, rf_kernel=rf_kernel,
    )
    load_x, p, lm = _ref_loader(x_ref), x_ref.shape[0], lm_ref[...]
    dcc, dcs, dss = fused_tile_pair_step(load_x, p, lm, i * tile, j * tile, **kw)
    gcc_ref[...] += dcc
    gcs_ref[...] += dcs
    gss_ref[...] += dss

    # the row slab is re-drawn for the moments — same counters, same bits
    @pl.when(j == 0)
    def _moments():
        dmc, dms = fused_tile_moment_step(load_x, p, lm, i * tile, **kw)
        mc_ref[...] += dmc
        ms_ref[...] += dms


def rff_gram_stream_fused_pallas(
    x: jax.Array,  # (p_pad, n), zero-padded feature rows, p_pad % DRAW_COLS == 0
    lm: jax.Array,  # (2, n): stacked [ell; column-mask]
    *,
    nf_pad: int,  # padded feature-row count (the kernel's draw height)
    scale_n: int,  # true N for the 1/sqrt(N) feature normalization
    seed: int,
    ensemble: int = 1,
    sigma: float = 1.0,
    rf_kernel: str = "gauss",
    block_k: int = 128,
    interpret: bool,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Seed-fused untiled layout: same five outputs, no omega operand.

    Rows ``[scale_n, nf_pad)`` of the outputs are padding garbage (drawn but
    meaningless) — the wrapper slices them off, exactly as the materialized
    kernel's zero-padded omega rows are sliced.
    """
    p, n = x.shape
    bk, k_steps = _check_blocks(n, block_k, lm)
    mw = 2 * ensemble  # per-draw moment columns: (2e, 2e+1) for draw e
    return pl.pallas_call(
        functools.partial(
            _rff_gram_fused_kernel, n_features=scale_n, seed=seed,
            ensemble=ensemble, sigma=sigma, rf_kernel=rf_kernel,
        ),
        grid=(k_steps,),
        in_specs=[
            pl.BlockSpec((p, bk), lambda k: (0, k)),
            pl.BlockSpec((2, bk), lambda k: (0, k)),
        ],
        out_specs=_untiled_out_specs(nf_pad, mw),
        out_shape=_stat_shapes(nf_pad, mw),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(x, lm)


def rff_gram_stream_fused_tiled_pallas(
    x: jax.Array,  # (p_pad, n), p_pad % DRAW_COLS == 0
    lm: jax.Array,  # (2, n)
    *,
    nf_pad: int,
    scale_n: int,
    tile: int,
    seed: int,
    ensemble: int = 1,
    sigma: float = 1.0,
    rf_kernel: str = "gauss",
    block_k: int = 128,
    interpret: bool,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Seed-fused tiled layout: grid (N/t, N/t, n/bk), W_RF rows drawn per
    tile from ``threefry(seed, tile_row_offset + r, col)`` — VMEM per
    instance is the (t, t) output blocks plus (t, DRAW_COLS) draw slabs;
    nothing N-sized exists anywhere."""
    p, n = x.shape
    bk, k_steps = _check_blocks(n, block_k, lm)
    if nf_pad % tile:
        raise ValueError(f"nf_pad={nf_pad} must tile by {tile}")
    n_tiles = nf_pad // tile
    mw = 2 * ensemble  # per-draw moment columns: (2e, 2e+1) for draw e
    return pl.pallas_call(
        functools.partial(
            _rff_gram_fused_tiled_kernel, n_features=scale_n, tile=tile, seed=seed,
            ensemble=ensemble, sigma=sigma, rf_kernel=rf_kernel,
        ),
        grid=(n_tiles, n_tiles, k_steps),
        in_specs=[
            pl.BlockSpec((p, bk), lambda i, j, k: (0, k)),
            pl.BlockSpec((2, bk), lambda i, j, k: (0, k)),
        ],
        out_specs=_tiled_out_specs(tile, mw),
        out_shape=_stat_shapes(nf_pad, mw),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(x, lm)
