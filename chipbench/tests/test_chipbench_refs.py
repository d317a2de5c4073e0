"""Each plain reference agrees with the program at a tiny size on the CPU,
and the three-pass bfloat16 control departs from it."""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import tinybench  # noqa: E402,F401

from chipbench.lib import refs  # noqa: E402
from chipbench.lib.data import make_domains  # noqa: E402

CONFIG = {"feature_dim": 32, "n_classes": 3, "domains": {"a": 300, "b": 200},
          "generator": {"class_sep": 3.0, "noise": 1.0, "scale_jitter": 0.3, "shift": 1.0}}


@pytest.fixture(scope="module")
def pair():
    doms = make_domains(CONFIG, ["a", "b"], 2**31 + 5)
    return doms["a"][0], doms["b"][0]


def test_domains_are_unit_norm_and_seeded():
    d1 = make_domains(CONFIG, ["a", "b"], 3)
    d2 = make_domains(CONFIG, ["b"], 3)
    assert np.allclose(jnp.linalg.norm(d1["a"][0], axis=0), 1.0, atol=1e-5)
    assert np.array_equal(d1["b"][0], d2["b"][0])  # a domain's draw is its own
    assert d1["a"][0].shape == (32, 300) and int(d1["a"][1].max()) < 3


def test_fit_reference_matches_rf_tca_fit(pair):
    from repro.core.rf_tca import rf_tca_fit

    x_s, x_t = pair
    st = rf_tca_fit(x_s, x_t, n_features=16, m=4, seed=11)
    omega = refs.gauss_omega(11, 16, 32)
    assert np.array_equal(np.asarray(st.omega), np.asarray(omega))
    ref = refs.fit_reference(x_s, x_t, omega, m=4, block=128)
    nums = refs.fit_numbers(ref, st.w_rf, st.eigvals, omega, x_t)
    assert nums["eig_rel"] < 1e-4 and nums["w_resid"] < 1e-4 and nums["aligned_rel"] < 1e-3


def test_fit_control_departs_from_the_reference(pair):
    x_s, x_t = pair
    omega = refs.gauss_omega(11, 16, 32)
    ref = refs.fit_reference(x_s, x_t, omega, m=4, block=128)
    low = refs.fit_reference(x_s, x_t, omega, m=4, block=128, precision="high")
    nums = refs.fit_numbers(ref, low["w_rf"], low["eigvals"][:4], omega, x_t)
    assert nums["eig_rel"] > 1e-6


def test_fused_omega_and_transform_match_the_program(pair):
    from repro.core.rf_tca import RFTCAState, rf_tca_transform
    from repro.kernels.prng import fused_omega

    x_t = pair[1]
    omega = refs.fused_omega(2**31 + 77, 16, 32)
    assert np.array_equal(np.asarray(omega), np.asarray(fused_omega(2**31 + 77, 16, 32)))
    w = jax.random.normal(jax.random.PRNGKey(0), (32, 4))
    st = RFTCAState(omega=None, w_rf=w, eigvals=jnp.ones(4), fused=(2**31 + 77, 1, 1.0, "gauss"))
    got = np.asarray(rf_tca_transform(st, x_t[:, :37]))
    want = refs.transform_columns(w, omega, x_t[:, :37])
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()


def test_dot_high_is_three_bf16_passes():
    a = jax.random.normal(jax.random.PRNGKey(1), (64, 64))
    exact = np.asarray(a, np.float64) @ np.asarray(a, np.float64)
    err_high = np.abs(np.asarray(refs.dot_high(a, a)) - exact).max()
    err_f32 = np.abs(np.asarray(refs.dot_highest(a, a)) - exact).max()
    err_bf16 = np.abs(np.asarray(jnp.matmul(a.astype(jnp.bfloat16), a.astype(jnp.bfloat16),
                                            preferred_element_type=jnp.float32)) - exact).max()
    assert err_f32 < err_high < err_bf16


def test_leaf_gaps_one_leaf_off_moves_the_worst_and_not_the_median():
    ref = [np.ones(4), np.ones(9), np.ones(16)]  # norms 2, 3, 4
    keep = np.ones(3, bool)
    one_off = [ref[0] * 1.01, ref[1], ref[2]]
    all_off = [r * 1.01 for r in ref]
    assert refs.leaf_gap(one_off, ref, keep) == pytest.approx(0.02 / 3, rel=1e-5)
    assert np.median(refs.leaf_gaps(one_off, ref, keep)) == 0
    assert np.median(refs.leaf_gaps(all_off, ref, keep)) == pytest.approx(0.01, rel=1e-5)
    assert refs.leaf_gaps(all_off, ref, np.asarray([True, False, True])).size == 2
