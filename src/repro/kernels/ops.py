"""Jit'd public wrappers around the Pallas kernels — the only callers of the
raw ``*_pallas`` functions, which take no ``interpret`` default.

``interpret=None`` (every wrapper's default) resolves from the platform:
Mosaic-lowered kernels on TPU, interpret mode elsewhere (the CPU tests).
Wrappers handle padding to tile boundaries so callers keep arbitrary shapes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.centered_gram import centered_gram_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.quantize import fake_quant_pallas
from repro.kernels.rff import rff_fused_pallas, rff_pallas
from repro.kernels.rff_gram_stream import (
    DRAW_COLS,
    VMEM_LIMIT_BYTES,
    rff_gram_stream_fused_pallas,
    rff_gram_stream_fused_tiled_pallas,
    rff_gram_stream_pallas,
    rff_gram_stream_tiled_pallas,
)
from repro.kernels.segment_reduce import segment_reduce_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# The untiled layout holds 3 (N_pad, N_pad) fp32 outputs in VMEM; past this N
# a tiled layout takes over.
GRAM_TILE_THRESHOLD = 1024
GRAM_TILES = (512, 256, 128)  # tiled-layout edges, largest first
# Bytes of VMEM the plan lets one Gram-stream instance's blocks take: three
# quarters of the scoped limit, the rest left to Mosaic's own temporaries.
GRAM_VMEM_BUDGET = 3 * VMEM_LIMIT_BYTES // 4


def _gram_vmem_bytes(rows: int, dim: int, block: int, *, tiled: bool, fused: bool) -> int:
    """VMEM one Gram-stream program instance holds, in bytes.

    ``rows`` is the untiled N_pad or the tile edge, ``dim`` the padded p.
    Blocks whose index moves along the grid (the sample block; the tiled
    layout's omega and output blocks) are double-buffered; the untiled
    layout's whole-array omega and outputs are held once.  The fused kernels
    hold no omega block but a few (rows, DRAW_COLS) draw temporaries per row
    slab instead.  Checked against the v5e compiler in
    ``tests/test_tpu_compile.py``.
    """
    lanes = lambda c: c + (-c) % 128  # VMEM pads the minor dim to 128 lanes
    slabs = 2 if tiled else 1  # row slabs whose features one step computes
    bufs = 2 if tiled else 1
    if fused:
        omega = slabs * 8 * rows * DRAW_COLS  # threefry words, draw, phases
    else:
        omega = bufs * slabs * rows * lanes(dim)
    out = bufs * (3 * rows * lanes(rows) + 2 * rows * 128)
    x_blk = 2 * (dim + 8) * lanes(block)  # sample block + [ell; mask], double-buffered
    feats = 3 * slabs * rows * lanes(block)  # z, cos, sin per slab
    return 4 * (omega + out + x_blk + feats)


def gram_tile_plan(
    n_features: int, dim: int, *, tile: int | None = None, fused: bool = False,
    block: int = 128,
) -> dict:
    """Resolve the layout ``rff_gram_stream`` (``fused=True``:
    ``rff_gram_stream_fused``) will execute for N = ``n_features`` features
    of p = ``dim``-wide samples.

    ``tile=None`` auto-selects: the untiled fast path (``{"tile": None}``)
    while N_pad <= ``GRAM_TILE_THRESHOLD`` and its VMEM fits
    ``GRAM_VMEM_BUDGET``, else the largest (t, t) output tile in
    ``GRAM_TILES`` that keeps the N -> N_pad rounding waste small (256 up to
    N = 2048) and whose instance — (t, p) omega blocks included — fits the
    budget.  ``tile=0`` forces the untiled path, any other int forces that
    tile edge — it must be a multiple of 128 (TPU lane alignment of the
    (t, t) blocks; validated here so the mistake cannot pass CPU
    interpret-mode CI and only surface at Mosaic lowering).  Returns
    ``{"tile", "n_pad", "acc_bytes", "vmem_bytes"}``: ``acc_bytes`` is the
    per-instance fp32 Gram/moment accumulator footprint, ``vmem_bytes`` the
    whole instance's estimate the budget bounds.
    """
    if tile is not None and tile % 128:
        raise ValueError(f"tile must be a multiple of 128 (TPU lanes), got {tile}")
    p_pad = dim + (-dim) % (DRAW_COLS if fused else block)
    n_pad = n_features + (-n_features) % 128

    def vmem(t):
        rows = n_pad if t is None else t
        return _gram_vmem_bytes(rows, p_pad, block, tiled=t is not None, fused=fused)

    if tile is None:
        if n_pad <= GRAM_TILE_THRESHOLD and vmem(None) <= GRAM_VMEM_BUDGET:
            t = None
        else:
            top = 256 if n_features <= 2048 else 512
            fits = [c for c in GRAM_TILES if c <= top and vmem(c) <= GRAM_VMEM_BUDGET]
            if not fits:
                raise ValueError(f"no Gram tile fits VMEM at p={dim}")
            t = fits[0]
    else:
        t = tile or None
    if t is not None:
        n_pad = n_features + (-n_features) % t
    rows = n_pad if t is None else t
    acc = 3 * rows * rows * 4 + 2 * rows * 2 * 4
    return {"tile": t, "n_pad": n_pad, "acc_bytes": acc, "vmem_bytes": vmem(t)}


def _pad_to(x: jax.Array, axis: int, mult: int) -> tuple[jax.Array, int]:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad:
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, pad)
        x = jnp.pad(x, widths)
    return x, size


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def rff(x: jax.Array, omega: jax.Array, *, block: int = 128, interpret: bool | None = None):
    """Sigma (2N, n) from X (p, n) and Omega (N, p)."""
    interpret = (not _on_tpu()) if interpret is None else interpret
    n_orig = x.shape[1]
    x, _ = _pad_to(x, 1, block)
    x, _ = _pad_to(x, 0, block)
    omega, p_orig = _pad_to(omega, 1, block)
    omega, n_feat = _pad_to(omega, 0, block)
    out = rff_pallas(
        x, omega, block_n=block, block_m=block, block_p=block,
        scale_n=n_feat, interpret=interpret,
    )
    # rows: [cos(padded N); sin(padded N)] -> slice both halves to N
    cos = out[: omega.shape[0]][:n_feat]
    sin = out[omega.shape[0] :][:n_feat]
    return jnp.concatenate([cos, sin], axis=0)[:, :n_orig]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def centered_gram(sigma: jax.Array, *, block: int = 128, interpret: bool | None = None):
    """Sigma H Sigma^T (fp32) from Sigma (2N, n)."""
    interpret = (not _on_tpu()) if interpret is None else interpret
    two_n_orig = sigma.shape[0]
    n_orig = sigma.shape[1]
    # sample padding would corrupt the mean -> pad with the row mean (no-op
    # after centering), then correct the scale of the contraction
    pad = (-n_orig) % block
    if pad:
        mu = jnp.mean(sigma, axis=1, keepdims=True)
        sigma = jnp.concatenate([sigma, jnp.broadcast_to(mu, (sigma.shape[0], pad))], axis=1)
    sigma, _ = _pad_to(sigma, 0, block)
    out = centered_gram_pallas(sigma, block=block, block_k=block, interpret=interpret)
    return out[:two_n_orig, :two_n_orig]


@functools.partial(jax.jit, static_argnames=("block", "tile", "interpret"))
def rff_gram_stream(
    x: jax.Array,
    omega: jax.Array,
    ell: jax.Array,
    *,
    block: int = 128,
    tile: int | None = None,
    interpret: bool | None = None,
):
    """(G_H (2N, 2N) fp32, u = Sigma ell (2N,) fp32) from X (p, n), Omega (N, p).

    Streams sample blocks through the fused featurize+accumulate kernel so the
    (2N, n) RFF matrix Sigma is never materialized (peak memory O(N^2 + N b)).
    Padded sample columns are masked inside the kernel; padded feature rows
    are sliced off here before assembling the [cos; sin] block structure.

    ``tile`` picks the accumulator layout (see :func:`gram_tile_plan`): None
    auto-selects the untiled kernel for small N and a (t, t) output tiling —
    per-instance VMEM bounded by the tile, not N — past
    ``GRAM_TILE_THRESHOLD``; 0 forces untiled, an int forces that tile edge.
    """
    interpret = (not _on_tpu()) if interpret is None else interpret
    n = x.shape[1]
    plan_tile = gram_tile_plan(omega.shape[0], x.shape[0], tile=tile, block=block)["tile"]
    lm = jnp.stack([ell.astype(x.dtype), jnp.ones((n,), x.dtype)])  # (2, n)
    x, _ = _pad_to(x, 1, block)
    lm, _ = _pad_to(lm, 1, block)  # zero-pads ell AND the column mask
    x, _ = _pad_to(x, 0, block)
    omega, _ = _pad_to(omega, 1, block)
    if plan_tile is None:
        omega, n_feat = _pad_to(omega, 0, block)
        gcc, gcs, gss, mc, ms = rff_gram_stream_pallas(
            x, omega, lm, block_k=block, scale_n=n_feat, interpret=interpret
        )
    else:
        omega, n_feat = _pad_to(omega, 0, plan_tile)
        gcc, gcs, gss, mc, ms = rff_gram_stream_tiled_pallas(
            x, omega, lm, tile=plan_tile, block_k=block, scale_n=n_feat,
            interpret=interpret,
        )
    from repro.core.kernels_math import assemble_streamed_gram

    return assemble_streamed_gram(
        gcc[:n_feat, :n_feat], gcs[:n_feat, :n_feat], gss[:n_feat, :n_feat],
        mc[:n_feat, 0], ms[:n_feat, 0], mc[:n_feat, 1], ms[:n_feat, 1],
        n=n,  # fold_n=None: the kernels fold 1/sqrt(N) into cos/sin already
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_features", "seed", "ensemble_index", "sigma_rf", "rf_kernel",
        "block", "interpret",
    ),
)
def rff_fused(
    x: jax.Array,
    *,
    n_features: int,
    seed: int,
    ensemble_index: int = 0,
    sigma_rf: float = 1.0,
    rf_kernel: str = "gauss",
    block: int = 128,
    interpret: bool | None = None,
):
    """Seed-fused Sigma (2N, n) from X (p, n) — no omega operand; the weight
    blocks are drawn inside the kernel from ``threefry(seed, row, col)``."""
    interpret = (not _on_tpu()) if interpret is None else interpret
    n_orig = x.shape[1]
    x, _ = _pad_to(x, 1, block)
    x, _ = _pad_to(x, 0, block)
    nf_pad = n_features + (-n_features) % block
    out = rff_fused_pallas(
        x, nf_pad=nf_pad, scale_n=n_features, seed=seed,
        ensemble_index=ensemble_index, sigma=sigma_rf, rf_kernel=rf_kernel,
        block_n=block, block_m=block, block_p=block, interpret=interpret,
    )
    cos = out[:nf_pad][:n_features]
    sin = out[nf_pad:][:n_features]
    return jnp.concatenate([cos, sin], axis=0)[:, :n_orig]


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_features", "seed", "ensemble", "sigma_rf", "rf_kernel",
        "block", "tile", "interpret",
    ),
)
def rff_gram_stream_fused(
    x: jax.Array,
    ell: jax.Array,
    *,
    n_features: int,
    seed: int,
    ensemble: int = 1,
    sigma_rf: float = 1.0,
    rf_kernel: str = "gauss",
    block: int = 128,
    tile: int | None = None,
    interpret: bool | None = None,
):
    """Seed-fused (G_H (2N, 2N) fp32, u = Sigma ell (2N,) fp32) from X (p, n).

    Like :func:`rff_gram_stream` but with no omega operand at all: W_RF rows
    are drawn inside the kernel from the counter-based threefry stream, so
    neither the (2N, n) feature matrix nor the (N, p) weight matrix ever
    exists in HBM — peak memory is O(N^2 + N b) stats only, and the only
    W_RF "state" anywhere is the integer seed.  ``ensemble=S`` averages the
    statistics over S independently-keyed draws in the same pass (S=1 traces
    the identical single-draw program).  ``tile`` picks the layout exactly as
    in :func:`rff_gram_stream`.
    """
    interpret = (not _on_tpu()) if interpret is None else interpret
    n = x.shape[1]
    plan_tile = gram_tile_plan(
        n_features, x.shape[0], tile=tile, fused=True, block=block
    )["tile"]
    lm = jnp.stack([ell.astype(x.dtype), jnp.ones((n,), x.dtype)])  # (2, n)
    x, _ = _pad_to(x, 1, block)
    lm, _ = _pad_to(lm, 1, block)  # zero-pads ell AND the column mask
    x, _ = _pad_to(x, 0, DRAW_COLS)  # the in-kernel draw's column slabs
    if plan_tile is None:
        nf_pad = n_features + (-n_features) % block
        gcc, gcs, gss, mc, ms = rff_gram_stream_fused_pallas(
            x, lm, nf_pad=nf_pad, scale_n=n_features, seed=seed,
            ensemble=ensemble, sigma=sigma_rf, rf_kernel=rf_kernel,
            block_k=block, interpret=interpret,
        )
    else:
        nf_pad = n_features + (-n_features) % plan_tile
        gcc, gcs, gss, mc, ms = rff_gram_stream_fused_tiled_pallas(
            x, lm, nf_pad=nf_pad, scale_n=n_features, tile=plan_tile, seed=seed,
            ensemble=ensemble, sigma=sigma_rf, rf_kernel=rf_kernel,
            block_k=block, interpret=interpret,
        )
    from repro.core.kernels_math import assemble_streamed_gram_ensemble

    nf = n_features
    # the kernel folds 1/sqrt(N S) into the features; mc/ms carry draw e's
    # per-draw moment columns at (2e, 2e+1) for the rank-S centering
    return assemble_streamed_gram_ensemble(
        gcc[:nf, :nf], gcs[:nf, :nf], gss[:nf, :nf], mc[:nf], ms[:nf],
        n=n, ensemble=ensemble,
    )


# Payload columns per segment-reduce output block (the kernel tiles D).
SEGMENT_TILE_D = 2048


@functools.partial(jax.jit, static_argnames=("n_segments", "block", "interpret"))
def segment_reduce(
    values: jax.Array,
    seg_ids: jax.Array,
    weights: jax.Array,
    *,
    n_segments: int,
    block: int = 128,
    interpret: bool | None = None,
) -> jax.Array:
    """Weighted segment sums ``out[e] = sum_{k: seg[k]=e} w_k * values[k]``.

    ``values`` (K, D), ``seg_ids`` (K,) ints in [0, n_segments), ``weights``
    (K,) -> (n_segments, D) fp32 — the grouped moment merge of the two-tier
    fleet plane as one MXU matmul of the weighted membership matrix against
    the stacked payloads.  Padding: K to the client block (padded rows carry
    weight 0, so they contribute exact zeros), E to the 8-row sublane edge, D
    to the 128 lane edge and then to whole ``SEGMENT_TILE_D`` column tiles.
    """
    interpret = (not _on_tpu()) if interpret is None else interpret
    k, d = values.shape
    onehot = (seg_ids[None, :] == jnp.arange(n_segments)[:, None]).astype(jnp.float32)
    wm = onehot * weights.astype(jnp.float32)[None, :]
    vals = values.astype(jnp.float32)
    wm, _ = _pad_to(wm, 1, block)
    vals, _ = _pad_to(vals, 0, block)
    wm, _ = _pad_to(wm, 0, 8)  # sublane edge of the (E, bk) membership blocks
    vals, _ = _pad_to(vals, 1, block)
    vals, _ = _pad_to(vals, 1, min(SEGMENT_TILE_D, vals.shape[1]))
    out = segment_reduce_pallas(
        wm, vals, block_k=block, block_d=SEGMENT_TILE_D, interpret=interpret
    )
    return out[:n_segments, :d]


@functools.partial(jax.jit, static_argnames=("bits", "block", "interpret"))
def fake_quant(
    x: jax.Array,
    u: jax.Array,
    *,
    bits: int = 8,
    block: int = 8,
    interpret: bool | None = None,
):
    """Fused stochastic quantize->dequantize of any-shape ``x`` with uniforms
    ``u`` (same shape, in [0,1)) — the wire-codec round trip as one kernel.

    The per-tensor absmax scale is a cheap XLA reduction over the *unpadded*
    values; the elementwise divide/floor/clip/rescale runs in the Pallas
    kernel over a padded (rows, 128) layout (zero padding quantizes to zero
    under u=0 padding, then is sliced away).
    """
    interpret = (not _on_tpu()) if interpret is None else interpret
    qmax = (1 << (bits - 1)) - 1
    xf = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf))
    scale = jnp.where(absmax > 0, absmax / qmax, 1.0).reshape(1, 1)
    size = xf.size
    cols = 128
    rows = -(-size // cols)
    rows += (-rows) % block
    pad = rows * cols - size
    xp = jnp.pad(xf.ravel(), (0, pad)).reshape(rows, cols)
    up = jnp.pad(u.astype(jnp.float32).ravel(), (0, pad)).reshape(rows, cols)
    out = fake_quant_pallas(xp, up, scale, qmax=qmax, block_r=block, interpret=interpret)
    return out.ravel()[:size].reshape(x.shape).astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k", "interpret"))
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool | None = None,
):
    """(b,h,s,d) x (b,kv,s,d) x (b,kv,s,dv) -> (b,h,s,dv)."""
    interpret = (not _on_tpu()) if interpret is None else interpret
    return flash_attention_pallas(
        q, k, v, causal=causal, window=window,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
