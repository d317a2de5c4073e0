"""Streaming RF-TCA solver: scan/Pallas gram paths, SM whitening, eigh vs LOBPCG."""
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    ell_vector,
    rf_tca_fit,
    rf_tca_transform,
    solve_w_rf,
    solve_w_rf_cholesky,
    solve_w_rf_gram,
    streaming_gram,
)
from repro.core.rff import draw_omega, rff_features
from repro.fleet.sharding import sub_jaxprs
from repro.obs import MetricsRegistry, use_registry


@pytest.fixture(scope="module")
def data(rng):
    p, ns, nt = 8, 90, 70
    xs = jnp.asarray(rng.normal(size=(p, ns)), jnp.float32)
    xt = jnp.asarray(rng.normal(size=(p, nt)) + 1.0, jnp.float32)
    return xs, xt


def test_streaming_gram_matches_dense(data):
    """G_H and u from the blocked scan equal the materializing reference."""
    xs, xt = data
    x = jnp.concatenate([xs, xt], axis=1)
    ell = ell_vector(xs.shape[1], xt.shape[1])
    omega = draw_omega(0, 48, x.shape[0])
    g_h, u = streaming_gram(x, ell, omega, block=37)  # non-divisor block
    sig = rff_features(x, omega)
    mu = jnp.mean(sig, axis=1, keepdims=True)
    sc = sig - mu
    g_ref = sc @ sc.T
    np.testing.assert_allclose(np.asarray(g_h), np.asarray(g_ref), atol=3e-5)
    np.testing.assert_allclose(np.asarray(u), np.asarray(sig @ ell), atol=3e-5)


def test_sherman_morrison_solver_matches_cholesky(data):
    """SM-whitened eigh reproduces the Cholesky reference eigenpairs."""
    xs, xt = data
    x = jnp.concatenate([xs, xt], axis=1)
    ell = ell_vector(xs.shape[1], xt.shape[1])
    omega = draw_omega(0, 64, x.shape[0])
    sig = rff_features(x, omega)
    w_ref, v_ref = solve_w_rf_cholesky(sig, ell, 1e-2, 6)
    w_sm, v_sm = solve_w_rf(sig, ell, 1e-2, 6, solver="eigh")
    np.testing.assert_allclose(np.asarray(v_sm), np.asarray(v_ref), rtol=1e-4)
    # both W are B-orthonormal bases of the same eigenspace: compare subspaces
    qa = np.linalg.qr(np.asarray(w_ref))[0]
    qb = np.linalg.qr(np.asarray(w_sm))[0]
    cosines = np.linalg.svd(qa.T @ qb, compute_uv=False)
    assert cosines.min() > 1 - 1e-4


def test_lobpcg_matches_eigh(data):
    """Acceptance: LOBPCG top-m agrees with eigh within 1e-4 rel tolerance."""
    xs, xt = data
    x = jnp.concatenate([xs, xt], axis=1)
    ell = ell_vector(xs.shape[1], xt.shape[1])
    omega = draw_omega(0, 64, x.shape[0])  # 2N = 128
    g_h, u = streaming_gram(x, ell, omega)
    w_e, v_e = solve_w_rf_gram(g_h, u, 1e-2, 8, solver="eigh")
    w_l, v_l = solve_w_rf_gram(g_h, u, 1e-2, 8, solver="lobpcg")
    np.testing.assert_allclose(np.asarray(v_l), np.asarray(v_e), rtol=1e-4)
    qa = np.linalg.qr(np.asarray(w_e))[0]
    qb = np.linalg.qr(np.asarray(w_l))[0]
    cosines = np.linalg.svd(qa.T @ qb, compute_uv=False)
    assert cosines.min() > 1 - 1e-3


@pytest.mark.parametrize("m", [7, 8, 12])  # 5m >= 2N=32 for all of these
def test_lobpcg_small_problem_falls_back(data, m):
    """5m >= 2N degenerates LOBPCG (jax rejects it); must fall back to eigh."""
    xs, xt = data
    x = jnp.concatenate([xs, xt], axis=1)
    ell = ell_vector(xs.shape[1], xt.shape[1])
    omega = draw_omega(0, 16, x.shape[0])  # 2N = 32
    g_h, u = streaming_gram(x, ell, omega)
    w, v = solve_w_rf_gram(g_h, u, 1e-2, m, solver="lobpcg")
    w_e, v_e = solve_w_rf_gram(g_h, u, 1e-2, m, solver="eigh")
    np.testing.assert_allclose(np.asarray(v), np.asarray(v_e), rtol=1e-5)


def test_stream_cholesky_rejected_early(data):
    """cholesky needs the explicit Sigma — stream mode must refuse up front."""
    xs, xt = data
    with pytest.raises(ValueError, match="cholesky"):
        rf_tca_fit(xs, xt, n_features=32, m=4, mode="stream", solver="cholesky")


def test_fit_modes_agree(data):
    """rf_tca_fit stream (xla + pallas) and dense (cholesky) eigenvalues agree."""
    xs, xt = data
    kw = dict(n_features=64, m=8, gamma=1e-2, sigma=2.0, seed=0)
    v_dense = rf_tca_fit(xs, xt, mode="dense", solver="cholesky", **kw).eigvals
    v_stream = rf_tca_fit(xs, xt, mode="stream", **kw).eigvals
    v_pallas = rf_tca_fit(xs, xt, mode="stream", use_pallas=True, **kw).eigvals
    np.testing.assert_allclose(np.asarray(v_stream), np.asarray(v_dense), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(v_pallas), np.asarray(v_dense), rtol=1e-3)


def test_tiled_twin_matches_untiled_and_pallas(data):
    """Tiled XLA twin == untiled scan == tiled Pallas kernel, non-tile N."""
    from repro.kernels import ops as kops

    xs, xt = data
    x = jnp.concatenate([xs, xt], axis=1)
    ell = ell_vector(xs.shape[1], xt.shape[1])
    omega = draw_omega(0, 200, x.shape[0])  # N=200: pads to 256 under tile=128
    g_u, u_u = streaming_gram(x, ell, omega, block=37)
    g_t, u_t = streaming_gram(x, ell, omega, block=37, tile=128)
    g_p, u_p = kops.rff_gram_stream(x, omega, ell, block=64, tile=128)
    scale = float(jnp.abs(g_u).max())
    np.testing.assert_allclose(np.asarray(g_t) / scale, np.asarray(g_u) / scale, atol=2e-6)
    np.testing.assert_allclose(np.asarray(u_t), np.asarray(u_u), atol=2e-6)
    np.testing.assert_allclose(np.asarray(g_p) / scale, np.asarray(g_t) / scale, atol=2e-6)
    np.testing.assert_allclose(np.asarray(u_p), np.asarray(u_t), atol=2e-6)


def test_tiled_kernel_matches_twin_at_n4096():
    """Acceptance: the tiled Pallas kernel agrees with the tiled XLA twin to
    <= 1e-4 relative at N = 4096 (auto tile selection on the kernel path)."""
    from repro.kernels import ops as kops

    p, n, nf = 16, 256, 4096
    key = jax.random.PRNGKey(7)
    x = jax.random.normal(key, (p, n), jnp.float32)
    omega = jax.random.normal(jax.random.fold_in(key, 2), (nf, p), jnp.float32)
    ell = ell_vector(n // 2, n - n // 2)
    assert kops.gram_tile_plan(nf, p)["tile"] == 512  # auto-tiled past the ceiling
    g_p, u_p = kops.rff_gram_stream(x, omega, ell)  # tile=None -> auto
    g_t, u_t = streaming_gram(x, ell, omega, block=128, tile=512)
    scale = float(jnp.abs(g_t).max())
    assert float(jnp.abs(g_p - g_t).max()) / scale <= 1e-4
    assert float(jnp.abs(u_p - u_t).max()) <= 1e-4 * max(1.0, float(jnp.abs(u_t).max()))


def test_tiled_twin_per_pair_memory_bounded_by_tile():
    """Jaxpr proxy: one (i, j) tile pair of the tiled layout only ever holds
    (tile, tile) accumulators and (tile, block) slabs — an (N, block) slab or
    (N, N) accumulator (the untiled layout) would blow the bound."""
    from repro.core.rf_tca import _tile_pair_stats

    p, n, nf, tile, block = 8, 128, 2048, 128, 64
    key = jax.random.PRNGKey(0)
    om_i = jax.random.normal(key, (tile, p), jnp.float32)
    om_j = jax.random.normal(jax.random.fold_in(key, 1), (tile, p), jnp.float32)
    xb = jax.random.normal(jax.random.fold_in(key, 2), (n // block, block, p), jnp.float32)
    mb = jnp.ones((n // block, block), jnp.float32)
    jaxpr = jax.make_jaxpr(_tile_pair_stats)(om_i, om_j, xb, mb)
    limit = max(3 * tile * tile, xb.size)  # stacked accumulators, input copies

    def walk(jx):
        for eqn in jx.eqns:
            for v in eqn.outvars:
                size = int(np.prod(v.aval.shape)) if v.aval.shape else 1
                assert size <= limit, f"intermediate {v.aval.shape} exceeds tile bound"
        for sub in sub_jaxprs(jx):
            walk(sub)

    walk(jaxpr.jaxpr)
    assert nf * block > limit and nf * nf > limit  # the bound has teeth vs untiled


def test_streaming_never_materializes_sigma(data):
    """The streamed stats pass must not allocate a (2N, n) buffer.

    Checked structurally: every intermediate in the jaxpr of the scan body is
    bounded by max(block * 2N_block_rows, (2N)^2) — a (2N, n) Sigma would
    exceed it.
    """
    from repro.core.rf_tca import _gram_stream_xla

    xs, xt = data
    x = jnp.concatenate([xs, xt], axis=1)
    n = x.shape[1]
    ell = ell_vector(xs.shape[1], xt.shape[1])
    omega = draw_omega(0, 64, x.shape[0])
    two_n, block = 128, 32
    jaxpr = jax.make_jaxpr(lambda a, e, o: _gram_stream_xla(a, e, o, block=block))(
        x, ell, omega
    )
    limit = max(two_n * two_n, two_n * block, x.size)  # stats, slab, input copies

    def walk(jx):
        for eqn in jx.eqns:
            for v in eqn.outvars:
                size = int(np.prod(v.aval.shape)) if v.aval.shape else 1
                assert size <= limit, f"intermediate {v.aval.shape} exceeds streaming bound"
        for sub in sub_jaxprs(jx):
            walk(sub)

    walk(jaxpr.jaxpr)
    assert two_n * n > limit  # the bound would catch a materialized Sigma


# ---- seed-fused fit path (w_rf="fused:<seed>") -----------------------------


def test_fused_fit_state_and_transform(data):
    """w_rf="fused:<seed>": the state carries no omega tensor — only the
    (seed, ensemble, sigma, kernel) spec — and out-of-sample transform
    re-derives draw 0 from the counter stream on demand."""
    from repro.kernels.prng import fused_omega

    xs, xt = data
    st = rf_tca_fit(xs, xt, n_features=48, m=6, gamma=1e-2, w_rf="fused:7")
    assert st.omega is None
    assert st.fused == (7, 1, 1.0, "gauss")
    f = rf_tca_transform(st, xs)
    assert f.shape == (6, xs.shape[1]) and bool(jnp.isfinite(f).all())
    om = fused_omega(7, 48, xs.shape[0])
    exp = st.w_rf.T @ rff_features(xs, om)
    np.testing.assert_allclose(np.asarray(f), np.asarray(exp), rtol=1e-5, atol=1e-6)


def test_fused_fit_pallas_twin_agree(data):
    """The fused fit through the Pallas kernel and through the XLA twin see
    bit-identical (G_H, u), so the deterministic eigensolve agrees exactly."""
    xs, xt = data
    kw = dict(n_features=48, m=6, gamma=1e-2, w_rf="fused:3")
    v_p = rf_tca_fit(xs, xt, use_pallas=True, **kw).eigvals
    v_x = rf_tca_fit(xs, xt, use_pallas=False, **kw).eigvals
    np.testing.assert_array_equal(np.asarray(v_p), np.asarray(v_x))


def test_fused_ensemble_fit_and_transform(data):
    """ensemble=S fit runs end to end; the spec round-trips into the state
    and the ensemble-averaged projector still transforms unseen data."""
    xs, xt = data
    st = rf_tca_fit(xs, xt, n_features=32, m=4, gamma=1e-2, w_rf="fused:1", ensemble=4)
    assert st.fused == (1, 4, 1.0, "gauss")
    assert bool(jnp.isfinite(st.eigvals).all())
    f_t = rf_tca_transform(st, xt)
    assert f_t.shape == (4, xt.shape[1]) and bool(jnp.isfinite(f_t).all())


def test_fused_fit_validation(data):
    """The lever's misuse modes fail fast with actionable messages."""
    xs, xt = data
    kw = dict(n_features=16, m=2)
    with pytest.raises(ValueError, match="ensemble"):
        rf_tca_fit(xs, xt, ensemble=2, **kw)
    with pytest.raises(ValueError, match='mode="stream"'):
        rf_tca_fit(xs, xt, w_rf="fused:0", mode="dense", **kw)
    with pytest.raises(ValueError, match="fused"):
        rf_tca_fit(xs, xt, w_rf="not-a-spec", **kw)


# -- the device eigensolve: blocked subspace iteration on the whitened C ----

rf_tca_mod = sys.modules["repro.core.rf_tca"]  # the package re-exports a function


def _whitened_fit_cmat(n_features=256, seed=0):
    """A whitened C of the fit's own statistics pass: 2N = 512."""
    rng = np.random.default_rng(seed)
    xs = jnp.asarray(rng.normal(size=(16, 300)), jnp.float32)
    xt = jnp.asarray(rng.normal(size=(16, 260)) + 0.5, jnp.float32)
    _, cmat, _ = rf_tca_mod._fit_stream_stats(
        xs, xt, jax.random.PRNGKey(seed), 1e-2, 2.0,
        n_features=n_features, block=128, kernel="gauss")
    return cmat


def _planted_cmat(two_n=512, m=8, gap=None, seed=0):
    """Symmetric PSD with a decaying spectrum; ``gap`` plants a near-degenerate
    pair at the m-th place, lambda_{m+1} = (1 - gap) lambda_m."""
    rng = np.random.default_rng(seed)
    q = np.linalg.qr(rng.normal(size=(two_n, two_n)))[0]
    lam = 10.0 * 0.97 ** np.arange(two_n)
    if gap is not None:
        lam[m] = lam[m - 1] * (1 - gap)
    c = (q * lam) @ q.T
    return jnp.asarray(0.5 * (c + c.T), jnp.float32)


def _min_principal_cosine(a, b):
    qa = np.linalg.qr(np.asarray(a, np.float64))[0]
    qb = np.linalg.qr(np.asarray(b, np.float64))[0]
    return np.linalg.svd(qa.T @ qb, compute_uv=False).min()


@pytest.mark.parametrize("make", [
    _whitened_fit_cmat,
    _planted_cmat,
    lambda: _planted_cmat(gap=1e-3),
], ids=["fit_cmat", "decaying", "planted_gap_1e-3"])
def test_device_eigh_matches_host(make):
    """Subspace iteration on the device gives LAPACK's top-m pairs: values
    within 1e-5 relative, the same subspace (smallest principal cosine
    > 1 - 1e-5), converged before the cap, residuals under the tolerance."""
    m = 8
    cmat = make()
    assert rf_tca_mod._device_eigh_fits(cmat.shape[0], m)
    vals, vecs, products = rf_tca_mod._device_top_eigh(cmat, m)
    assert vals is not None and 0 < products < rf_tca_mod.EIGH_MAX_PRODUCTS
    h_vals, h_vecs = rf_tca_mod._host_top_eigh(np.asarray(cmat), m=m)
    np.testing.assert_allclose(np.asarray(vals), h_vals, rtol=1e-5)
    assert _min_principal_cosine(vecs, h_vecs) > 1 - 1e-5
    c64 = np.asarray(cmat, np.float64)
    v64 = np.asarray(vecs, np.float64)
    resid = np.linalg.norm(c64 @ v64 - v64 * np.asarray(vals)[None], axis=0)
    assert np.all(resid <= 2 * rf_tca_mod.EIGH_RTOL * np.asarray(vals))


def test_device_eigh_cap_falls_back_to_host(monkeypatch):
    """An iteration cap of one product cannot converge: the host path solves
    instead, with the host path's very result, and the counter says so."""
    cmat = _whitened_fit_cmat()
    monkeypatch.setattr(rf_tca_mod, "EIGH_MAX_PRODUCTS", 1)
    with use_registry(MetricsRegistry()) as reg:
        vals, vecs = rf_tca_mod._top_eigh(cmat, 8)
    assert reg.snapshot()["rf_tca.eigh_solves"] == {"path=host_fallback": 1}
    h_vals, h_vecs = rf_tca_mod._host_top_eigh(np.asarray(cmat), m=8)
    np.testing.assert_array_equal(np.asarray(vals), h_vals)
    np.testing.assert_array_equal(np.asarray(vecs), h_vecs)


@pytest.mark.parametrize("two_n,m,device", [
    (512, 8, True), (128, 8, True), (127, 8, False), (32, 4, False), (2048, 32, True),
    (8192, 32, True), (256, 32, False),
])
def test_eigh_path_follows_the_shape(two_n, m, device):
    """The device solve takes 2N >= 16m: its block of 2m-4m columns is at
    most a quarter of the matrix."""
    assert rf_tca_mod._device_eigh_fits(two_n, m) is device


def test_default_fit_on_the_device_path_matches_cholesky(data):
    """rf_tca_fit's default path at 2N = 16m solves on the device and agrees
    with the dense Cholesky reference."""
    xs, xt = data
    kw = dict(n_features=64, m=8, gamma=1e-2, sigma=2.0, seed=0)
    with use_registry(MetricsRegistry()) as reg:
        st = rf_tca_fit(xs, xt, **kw)
    assert reg.snapshot()["rf_tca.eigh_solves"] == {"path=device": 1}
    ref = rf_tca_fit(xs, xt, mode="dense", solver="cholesky", **kw)
    np.testing.assert_allclose(np.asarray(st.eigvals), np.asarray(ref.eigvals), rtol=1e-4)
    assert _min_principal_cosine(st.w_rf, ref.w_rf) > 1 - 1e-4
