"""Driver of ``rf_tca_fit``: aligner fits back to back on one domain pair.

Traffic (the cell's ``traffic``): ``source`` and ``target`` domain names of
the configuration, ``n_features`` (N) and ``m``.  Every fit takes a new seed
derived from the run's seed, so its random features, statistics and W_RF
differ from fit to fit.  The fit runs on the program's default path: only
data, shapes and the seed are passed.

Window: fits back to back, none cut; it ends at the first fit boundary after
``seconds``.  ``fit_s`` is the window's wall time over the fits completed,
each ended by ``block_until_ready`` on its W_RF and eigenvalues.

Check: one fit of the window, drawn from the seed, against the plain
reference fit (``chipbench.lib.refs``) from the same data and seed.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from chipbench.lib import refs
from chipbench.lib.data import derived_seed, make_domains

PROBE_COLUMNS = 2048  # target columns whose aligned features are compared


def setup(ctx):
    from repro.core.rf_tca import rf_tca_fit

    tp = ctx.params
    doms = make_domains(ctx.config, [tp["source"], tp["target"]], ctx.seed)
    x_s, x_t = doms[tp["source"]][0], doms[tp["target"]][0]
    jax.block_until_ready((x_s, x_t))

    def fit(seed):
        st = rf_tca_fit(x_s, x_t, n_features=int(tp["n_features"]), m=int(tp["m"]), seed=seed)
        return jax.block_until_ready((st.w_rf, st.eigvals))

    fit(derived_seed(ctx.seed, 0))  # warm-up: compiles every program a fit runs
    return {"fit": fit, "x_s": x_s, "x_t": x_t}


def window(state, seconds, ctx):
    fits = []
    t0 = time.perf_counter()
    while True:
        seed = derived_seed(ctx.seed, len(fits) + 1)
        with ctx.annotate("chipbench.fit"):
            w_rf, vals = state["fit"](seed)
        fits.append((seed, w_rf, vals))
        if time.perf_counter() - t0 >= seconds:
            break
    wall = time.perf_counter() - t0
    state["fits"] = fits
    x_s, x_t = state["x_s"], state["x_t"]
    n = int(x_s.shape[1] + x_t.shape[1])
    return {
        "metrics": {"fit_s": (wall / len(fits), "s")},
        "attempted": len(fits), "failed": 0,
        "record": {"fits": len(fits), "n": n, "p": int(x_s.shape[0]),
                   "n_features": int(ctx.params["n_features"]), "wall_s": wall},
        "info": {"fits": len(fits), "window_s": wall},
    }


def check(state, record, ctx):
    fits = state.pop("fits")
    pick = derived_seed(ctx.seed, 999_999) % len(fits)
    seed, w_rf, vals = fits[pick]
    del fits, state["fit"]
    tp = ctx.params
    x_s, x_t = state["x_s"], state["x_t"]
    p, n_features, m = x_s.shape[0], int(tp["n_features"]), int(tp["m"])
    omega = refs.gauss_omega(seed, n_features, p)
    ref = refs.fit_reference(x_s, x_t, omega, m=m)
    n_t = x_t.shape[1]
    k = min(PROBE_COLUMNS, n_t)
    cols = jax.random.choice(jax.random.PRNGKey(derived_seed(ctx.seed, 77)), n_t, (k,),
                             replace=False)
    nums = refs.fit_numbers(ref, w_rf, vals, omega, jnp.take(x_t, cols, axis=1))
    record["info"].update(checked_fit=pick, **nums)
    return {name: (nums[name], float(lim)) for name, lim in ctx.cell["limits"].items()}

