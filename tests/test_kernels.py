"""Pallas kernels vs pure-jnp oracles: shape x dtype sweeps (interpret mode)."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.fleet.sharding import sub_jaxprs
from repro.kernels import ops, ref


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else dict(atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("p,n,nf", [(16, 64, 32), (33, 170, 77), (128, 128, 128), (7, 300, 130)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rff_kernel_sweep(p, n, nf, dtype):
    key = jax.random.PRNGKey(p * n)
    x = jax.random.normal(key, (p, n), dtype)
    om = jax.random.normal(jax.random.fold_in(key, 1), (nf, p), dtype)
    out = ops.rff(x, om, block=64)
    exp = ref.rff_ref(x, om)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(exp, np.float32), **_tol(dtype)
    )


@pytest.mark.parametrize("two_n,n", [(64, 128), (96, 210), (128, 64), (32, 500)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_centered_gram_sweep(two_n, n, dtype):
    key = jax.random.PRNGKey(two_n + n)
    sig = jax.random.normal(key, (two_n, n), dtype)
    out = ops.centered_gram(sig, block=32)
    exp = ref.centered_gram_ref(sig)
    scale = float(jnp.abs(exp).max())
    np.testing.assert_allclose(
        np.asarray(out) / scale, np.asarray(exp) / scale,
        atol=5e-2 if dtype == jnp.bfloat16 else 1e-5,
    )


@pytest.mark.parametrize(
    "p,n,nf", [(16, 64, 32), (7, 300, 130), (33, 170, 77), (16, 129, 64), (5, 97, 33)]
)
def test_rff_gram_stream_sweep(p, n, nf):
    """Fused streaming Gram kernel vs dense oracle, incl. non-tile shapes."""
    from repro.core.kernels_math import ell_vector

    key = jax.random.PRNGKey(p + n + nf)
    x = jax.random.normal(key, (p, n), jnp.float32)
    om = jax.random.normal(jax.random.fold_in(key, 1), (nf, p), jnp.float32)
    ell = ell_vector(n // 2, n - n // 2)
    g, u = ops.rff_gram_stream(x, om, ell, block=64)
    ge, ue = ref.rff_gram_stream_ref(x, om, ell)
    scale = float(jnp.abs(ge).max())
    np.testing.assert_allclose(np.asarray(g) / scale, np.asarray(ge) / scale, atol=2e-5)
    np.testing.assert_allclose(np.asarray(u), np.asarray(ue), atol=2e-5)


@pytest.mark.parametrize(
    "p,n,nf,tile", [(16, 64, 32, 128), (7, 300, 130, 128), (16, 129, 300, 256), (5, 97, 33, 128)]
)
def test_rff_gram_stream_tiled_sweep(p, n, nf, tile):
    """(i, j)-tiled kernel vs untiled kernel vs dense oracle, incl. N that is
    not a multiple of the tile (feature-row padding path)."""
    from repro.core.kernels_math import ell_vector

    key = jax.random.PRNGKey(p + n + nf)
    x = jax.random.normal(key, (p, n), jnp.float32)
    om = jax.random.normal(jax.random.fold_in(key, 1), (nf, p), jnp.float32)
    ell = ell_vector(n // 2, n - n // 2)
    g_t, u_t = ops.rff_gram_stream(x, om, ell, block=64, tile=tile)
    g_u, u_u = ops.rff_gram_stream(x, om, ell, block=64, tile=0)
    ge, ue = ref.rff_gram_stream_ref(x, om, ell)
    scale = float(jnp.abs(ge).max())
    np.testing.assert_allclose(np.asarray(g_t) / scale, np.asarray(g_u) / scale, atol=1e-6)
    np.testing.assert_allclose(np.asarray(u_t), np.asarray(u_u), atol=1e-6)
    np.testing.assert_allclose(np.asarray(g_t) / scale, np.asarray(ge) / scale, atol=2e-5)
    np.testing.assert_allclose(np.asarray(u_t), np.asarray(ue), atol=2e-5)


def test_gram_tile_plan_auto_selection():
    """tile=None keeps the untiled fast path up to the VMEM threshold, then
    switches to a tile whose accumulator bytes are independent of N; at the
    deployment width p=2048 the (t, p) omega blocks count as well."""
    assert ops.gram_tile_plan(256, 16)["tile"] is None
    assert ops.gram_tile_plan(ops.GRAM_TILE_THRESHOLD, 16)["tile"] is None
    t_mid = ops.gram_tile_plan(1300, 16)
    t_big = ops.gram_tile_plan(8192, 16)
    assert t_mid["tile"] == 256 and t_mid["n_pad"] % 256 == 0
    assert t_big["tile"] == 512
    # per-instance accumulator memory is set by the tile, not N
    assert t_big["acc_bytes"] == 3 * 512 * 512 * 4 + 2 * 512 * 2 * 4
    assert t_big["acc_bytes"] < 3 * 8192 * 8192 * 4
    # explicit overrides: 0 forces untiled, an int forces that tile edge
    assert ops.gram_tile_plan(4096, 16, tile=0)["tile"] is None
    assert ops.gram_tile_plan(300, 16, tile=128)["tile"] == 128
    # lane-misaligned forced tiles must fail here, not at Mosaic lowering
    with pytest.raises(ValueError, match="multiple of 128"):
        ops.gram_tile_plan(4096, 16, tile=200)
    # p: every auto layout fits the budget, and a wide enough p shrinks the
    # untiled layout into tiles and the tiles below the N rule's choice
    for nf in (512, 1024, 2048, 4096, 8192):
        for dim in (16, 2048, 8192):
            for fused in (False, True):
                plan = ops.gram_tile_plan(nf, dim, fused=fused)
                assert plan["vmem_bytes"] <= ops.GRAM_VMEM_BUDGET, (nf, dim, fused)
    assert ops.gram_tile_plan(1024, 2048)["tile"] is None
    assert ops.gram_tile_plan(1024, 8192)["tile"] == 256
    wide = ops.gram_tile_plan(8192, 12288)
    assert wide["tile"] < 512 and wide["vmem_bytes"] <= ops.GRAM_VMEM_BUDGET
    with pytest.raises(ValueError, match="no Gram tile fits"):
        ops.gram_tile_plan(4096, 2**20)


def test_tiled_kernel_vmem_accumulators_bounded_by_tile():
    """The pallas_call's accumulating output blocks (the VMEM proxy) must be
    (t, t) blocks, not (N_pad, N_pad) — checked on the traced kernel jaxpr."""
    from repro.core.kernels_math import ell_vector

    p, n, nf, tile = 8, 128, 1536, 256
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (p, n), jnp.float32)
    om = jax.random.normal(jax.random.fold_in(key, 1), (nf, p), jnp.float32)
    ell = ell_vector(n // 2, n - n // 2)
    jaxpr = jax.make_jaxpr(
        lambda a, o, e: ops.rff_gram_stream(a, o, e, tile=tile)
    )(x, om, ell)

    def find_pallas(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
        for sub in sub_jaxprs(jx):
            yield from find_pallas(sub)

    eqns = list(find_pallas(jaxpr.jaxpr))
    assert eqns, "tiled path must lower through pallas_call"
    kernel_jaxpr = eqns[0].params["jaxpr"]
    limit = tile * tile  # largest per-instance buffer the tiled layout allows
    for v in list(kernel_jaxpr.invars) + [
        o for eqn in kernel_jaxpr.eqns for o in eqn.outvars
    ]:
        shape = getattr(getattr(v, "aval", None), "shape", None)
        if shape is None:
            continue
        size = int(np.prod(shape)) if shape else 1
        assert size <= limit, f"kernel buffer {shape} exceeds tile bound"
    assert nf * nf > limit and nf * n > limit  # bound would catch untiled accs


@pytest.mark.parametrize("p,n,nf", [(16, 130, 40), (3, 257, 16)])
def test_rff_padding_non_multiple_of_block(p, n, nf):
    """Default-block (128) wrapper padding paths must match the XLA reference."""
    key = jax.random.PRNGKey(n)
    x = jax.random.normal(key, (p, n), jnp.float32)
    om = jax.random.normal(jax.random.fold_in(key, 1), (nf, p), jnp.float32)
    out = ops.rff(x, om)  # block=128 > all dims: every axis takes the pad path
    exp = ref.rff_ref(x, om)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("two_n,n", [(40, 130), (130, 257)])
def test_centered_gram_padding_non_multiple_of_block(two_n, n):
    """Mean-padding of sample columns (the centering-safe pad) at block=128."""
    key = jax.random.PRNGKey(two_n * n)
    sig = jax.random.normal(key, (two_n, n), jnp.float32)
    out = ops.centered_gram(sig)
    exp = ref.centered_gram_ref(sig)
    scale = float(jnp.abs(exp).max())
    np.testing.assert_allclose(np.asarray(out) / scale, np.asarray(exp) / scale, atol=1e-5)


@pytest.mark.parametrize(
    "b,h,kv,s,d,dv",
    [(1, 2, 1, 128, 32, 32), (2, 4, 2, 128, 16, 16), (1, 4, 4, 256, 32, 16), (2, 8, 2, 64, 64, 64)],
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("window", [0, 48])
def test_flash_attention_sweep(b, h, kv, s, d, dv, dtype, window):
    key = jax.random.PRNGKey(b * h * s)
    q = jax.random.normal(key, (b, h, s, d), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, kv, s, d), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, kv, s, dv), dtype)
    out = ops.flash_attention(q, k, v, window=window, block_q=64, block_k=64)
    exp = ref.attention_ref(q, k, v, window=window)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(exp, np.float32),
        atol=3e-2 if dtype == jnp.bfloat16 else 2e-5,
    )


def test_flash_non_causal():
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (1, 2, 64, 16))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 2, 64, 16))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 2, 64, 16))
    out = ops.flash_attention(q, k, v, causal=False, block_q=32, block_k=32)
    exp = ref.attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=2e-5)


def test_rff_kernel_feeds_rf_tca():
    """End-to-end: RF-TCA solved through the Pallas path matches XLA path."""
    from repro.core.rf_tca import rf_tca

    rng = np.random.default_rng(0)
    xs = jnp.asarray(rng.normal(size=(16, 100)), jnp.float32)
    xt = jnp.asarray(rng.normal(size=(16, 60)) + 1, jnp.float32)
    _, _, s1 = rf_tca(xs, xt, n_features=64, m=8, gamma=1e-2, use_pallas=True)
    _, _, s2 = rf_tca(xs, xt, n_features=64, m=8, gamma=1e-2, use_pallas=False)
    np.testing.assert_allclose(np.asarray(s1.eigvals), np.asarray(s2.eigvals), rtol=1e-2)


# ---- seed-fused RFF kernels (W_RF drawn inside the kernel) -----------------


def _rf_tca_module():
    # repro.core re-exports the rf_tca *function*, which shadows the submodule
    # on attribute access — import the module explicitly.
    return importlib.import_module("repro.core.rf_tca")


def _fused_case(p=7, n=150, key_seed=0):
    from repro.core.kernels_math import ell_vector

    key = jax.random.PRNGKey(key_seed)
    x = jax.random.normal(key, (p, n), jnp.float32)
    ell = ell_vector(n // 2, n - n // 2)
    return x, ell


@pytest.mark.parametrize("ensemble", [1, 3])
@pytest.mark.parametrize("tile", [0, 128])
def test_fused_gram_pallas_matches_twin_bitwise(ensemble, tile):
    """Acceptance: the seed-fused Pallas kernel equals its XLA generator twin
    at 0 ULP in both layouts — same counter draws, same padded geometry, same
    sequential accumulation order, hence the identical float op sequence."""
    rf = _rf_tca_module()
    x, ell = _fused_case(key_seed=tile + ensemble)
    kw = dict(n_features=96, seed=11, ensemble=ensemble, tile=tile)
    g_p, u_p = rf.fused_streaming_gram(x, ell, use_pallas=True, **kw)
    g_x, u_x = rf.fused_streaming_gram(x, ell, use_pallas=False, **kw)
    assert bool(jnp.array_equal(g_p, g_x)), float(jnp.abs(g_p - g_x).max())
    assert bool(jnp.array_equal(u_p, u_x)), float(jnp.abs(u_p - u_x).max())


def test_fused_ensemble1_degenerate_to_materialized():
    """ensemble=1 is bitwise the single-draw program: the fused kernel with
    S=1 equals the materialized kernel fed the generator twin's omega."""
    from repro.core.kernels_math import ell_vector
    from repro.kernels.prng import fused_omega

    p, n, nf, seed = 9, 130, 64, 4
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (p, n), jnp.float32)
    ell = ell_vector(n // 2, n - n // 2)
    g_f, u_f = ops.rff_gram_stream_fused(x, ell, n_features=nf, seed=seed)
    g_m, u_m = ops.rff_gram_stream(x, fused_omega(seed, nf, p), ell)
    assert bool(jnp.array_equal(g_f, g_m)), float(jnp.abs(g_f - g_m).max())
    assert bool(jnp.array_equal(u_f, u_m)), float(jnp.abs(u_f - u_m).max())


def test_fused_ensemble_matches_dense_oracle():
    """ensemble=S averages the per-draw *centered* statistics: the fused pass
    must match the mean over S materialized single-draw oracles."""
    rf = _rf_tca_module()
    x, ell = _fused_case(p=6, n=110, key_seed=5)
    kw = dict(n_features=64, seed=3, ensemble=3)
    g, u = rf.fused_streaming_gram(x, ell, **kw)
    ge, ue = ref.rff_gram_stream_fused_ref(x, ell, **kw)
    scale = float(jnp.abs(ge).max())
    np.testing.assert_allclose(np.asarray(g) / scale, np.asarray(ge) / scale, atol=3e-5)
    np.testing.assert_allclose(np.asarray(u), np.asarray(ue), atol=3e-5)


@pytest.mark.parametrize("p,n,nf", [(16, 64, 32), (7, 130, 96)])
def test_rff_fused_featurize_matches_materialized(p, n, nf):
    """Seed-fused featurize kernel vs rff_ref on the materialized twin omega
    (per-block accumulation vs one matmul: allclose, not bitwise)."""
    from repro.kernels.prng import fused_omega

    key = jax.random.PRNGKey(p * n)
    x = jax.random.normal(key, (p, n), jnp.float32)
    sig = ops.rff_fused(x, n_features=nf, seed=2)
    exp = ref.rff_ref(x, fused_omega(2, nf, p))
    np.testing.assert_allclose(np.asarray(sig), np.asarray(exp), atol=2e-5, rtol=2e-5)


def test_fused_path_weightless_jaxpr():
    """Acceptance: W_RF is absent from the fused path's jaxpr — the pass
    consumes only (x, ell), bakes in no weight-sized constants, and never
    materializes the (2N, n) feature matrix; the only weight state anywhere
    is the static integer seed.  (Per-sample-block transient draws inside the
    scan body are the point of the design and stay within the size bound.)"""
    rf = _rf_tca_module()
    from repro.core.kernels_math import ell_vector

    p, n, nf, block = 8, 1000, 256, 128
    key = jax.random.PRNGKey(1)
    x = jax.random.normal(key, (p, n), jnp.float32)
    ell = ell_vector(n // 2, n - n // 2)
    closed = jax.make_jaxpr(
        lambda a, e: rf.fused_streaming_gram(
            a, e, n_features=nf, seed=5, use_pallas=False, block=block
        )
    )(x, ell)
    # no weight operand: x and ell are the entire input
    assert len(closed.jaxpr.invars) == 2
    # no weight-sized constants baked into the program
    for c in closed.consts:
        assert np.size(c) < nf * p, f"const of shape {np.shape(c)} smells like omega"
    nf_pad, n_pad, p_pad = 256, 1024, 128
    # stats + assembly (2N, 2N) blocks and the blocked input are the biggest
    # legitimate buffers; a materialized Sigma (2N_pad, n_pad) would exceed it
    limit = max(4 * nf_pad * nf_pad, p_pad * n_pad)

    def walk(jx):
        for eqn in jx.eqns:
            for v in eqn.outvars:
                size = int(np.prod(v.aval.shape)) if v.aval.shape else 1
                assert size <= limit, f"intermediate {v.aval.shape} exceeds fused bound"
        for sub in sub_jaxprs(jx):
            walk(sub)

    walk(closed.jaxpr)
    assert 2 * nf_pad * n_pad > limit  # the bound would catch a materialized Sigma

    # the Pallas lowering is equally weightless: same 2-operand surface
    closed_p = jax.make_jaxpr(
        lambda a, e: rf.fused_streaming_gram(
            a, e, n_features=nf, seed=5, use_pallas=True, block=block
        )
    )(x, ell)
    assert len(closed_p.jaxpr.invars) == 2
    for c in closed_p.consts:
        assert np.size(c) < nf * p


@pytest.mark.parametrize("shape", [(512,), (512, 32), (7, 13), (1,), (1024, 5)])
@pytest.mark.parametrize("bits", [8, 4])
def test_fake_quant_kernel_matches_xla_twin(shape, bits):
    """Fused Pallas quantize/dequantize == jitted XLA twin, bitwise (the two
    receive identical uniforms, so stochastic rounding agrees exactly)."""
    key = jax.random.PRNGKey(sum(shape) + bits)
    x = jax.random.normal(key, shape)
    u = jax.random.uniform(jax.random.fold_in(key, 1), shape)
    got = ops.fake_quant(x, u, bits=bits)
    exp = jax.jit(lambda a, b: ref.fake_quant_ref(a, b, bits=bits))(x, u)
    assert jnp.array_equal(got, exp), float(jnp.abs(got - exp).max())


@pytest.mark.parametrize("bits", [8, 4])
def test_fake_quant_roundtrip_error_bound(bits):
    """Stochastic rounding moves each value by < one quantization step."""
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (256, 16)) * 5.0
    u = jax.random.uniform(jax.random.fold_in(key, 1), x.shape)
    out = ops.fake_quant(x, u, bits=bits)
    qmax = (1 << (bits - 1)) - 1
    step = float(jnp.abs(x).max()) / qmax
    assert float(jnp.abs(out - x).max()) <= step * (1 + 1e-6)


def test_fake_quant_zero_and_halfu_deterministic():
    """All-zero inputs survive exactly; u=0.5 gives round-to-nearest."""
    z = jnp.zeros((64,))
    assert jnp.array_equal(ops.fake_quant(z, jnp.full(z.shape, 0.5), bits=8), z)
    x = jnp.asarray([1.0, -1.0, 0.49, -0.49]) * 0.127
    u = jnp.full(x.shape, 0.5)
    out = ops.fake_quant(x, u, bits=8)  # scale = 0.001: nearest code per entry
    np.testing.assert_allclose(np.asarray(out), [0.127, -0.127, 0.062, -0.062], atol=1e-6)
