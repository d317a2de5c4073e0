"""Paper Fig. 3 + Tables X-XIII analogue: accuracy/runtime of RF-TCA vs DA
baselines (TCA, R-TCA, JDA, CORAL, DaNN, source-only) on the synthetic suite,
plus the PR-over-PR perf contract for the streaming solver and the batched
round engine.

Claims checked:
 - RF-TCA runs >=5x faster than vanilla TCA at comparable accuracy;
 - accuracy grows with the number of random features N (Fig. 3 blue circles);
 - the streaming fit (scan gram + Sherman-Morrison eigh) is >=3x faster than
   the seed dense path (materialized Sigma + Cholesky + full eigh) at
   (n=4096, N=256, m=32), with O(N^2) instead of O(N n) peak memory;
 - the batched (vmap/scan) round engine beats the serial per-client dispatch.

Emits ``BENCH_rf_tca.json`` (fit wall-times, speedup, peak-memory proxy,
solver agreement, per-round engine wall-times, accuracies) so the perf
trajectory is machine-trackable across PRs.
"""
from __future__ import annotations

import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import da_suite, emit, timed
from repro.baselines import (
    coral_baseline,
    dann_mmd_baseline,
    jda_baseline,
    rf_tca_baseline,
    source_only,
    tca_baseline,
)

JSON_PATH = Path(__file__).resolve().parent.parent / "BENCH_rf_tca.json"


def fit_perf(n: int = 4096, n_features: int = 256, m: int = 32) -> dict:
    """Streaming vs seed-dense rf_tca_fit at the acceptance shapes.

    Timing is best-of-reps (min, as in ``timeit``): the container shares
    cores, and the minimum is the least-noise estimator of a path's actual
    cost.  All paths are measured interleaved and identically.
    """
    from repro.core.rf_tca import rf_tca_fit

    rng = np.random.default_rng(0)
    p = 16
    xs = jnp.asarray(rng.normal(size=(p, n // 2)), jnp.float32)
    xt = jnp.asarray(rng.normal(size=(p, n - n // 2)) + 1.0, jnp.float32)
    kw = dict(n_features=n_features, m=m, gamma=1e-2)

    dense = lambda: rf_tca_fit(xs, xt, mode="dense", solver="cholesky", **kw).w_rf
    stream = lambda: rf_tca_fit(xs, xt, mode="stream", solver="eigh", **kw).w_rf
    lobpcg = lambda: rf_tca_fit(xs, xt, mode="stream", solver="lobpcg", **kw).w_rf
    stream()  # warm the jitted scan (compile excluded, as for any serving path)
    lobpcg()
    # timeit-style: consecutive reps per path, min of the block — each path is
    # measured at its own steady state on the shared cores
    ts: dict = {dense: [], stream: [], lobpcg: []}
    for fn, reps in ((dense, 11), (stream, 11), (lobpcg, 5)):
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            ts[fn].append(time.perf_counter() - t0)
    t_dense, t_stream, t_lobpcg = (min(ts[f]) for f in (dense, stream, lobpcg))

    v_dense = np.asarray(rf_tca_fit(xs, xt, mode="dense", solver="cholesky", **kw).eigvals)
    v_stream = np.asarray(rf_tca_fit(xs, xt, mode="stream", solver="eigh", **kw).eigvals)
    v_lob = np.asarray(rf_tca_fit(xs, xt, mode="stream", solver="lobpcg", **kw).eigvals)
    rel_stream = float(np.max(np.abs((v_stream - v_dense) / v_dense)))
    rel_lobpcg = float(np.max(np.abs((v_lob - v_stream) / v_stream)))

    two_n = 2 * n_features
    block = 1024
    out = {
        "shape": {"n": n, "N": n_features, "m": m, "p": p},
        "dense_s": t_dense,
        "stream_s": t_stream,
        "lobpcg_s": t_lobpcg,
        "speedup_stream_vs_dense": t_dense / t_stream,
        "eigvals_rel_err_stream_vs_dense": rel_stream,
        "eigvals_rel_err_lobpcg_vs_eigh": rel_lobpcg,
        # peak-memory proxy: largest fp32 intermediate each path materializes
        # (dense: the (2N, n) Sigma; stream: the (2N, 2N) stats + one slab)
        "memory_proxy_bytes": {
            "dense": 4 * two_n * n,
            "stream": 4 * (two_n * two_n + two_n * block),
        },
    }
    emit("fig3/fit_dense", t_dense * 1e6, f"n={n},N={n_features},m={m}")
    emit(
        "fig3/fit_stream", t_stream * 1e6,
        f"speedup_vs_dense={out['speedup_stream_vs_dense']:.1f}x,rel_err={rel_stream:.1e}",
    )
    emit("fig3/fit_lobpcg", t_lobpcg * 1e6, f"rel_err_vs_eigh={rel_lobpcg:.1e}")
    return out


def large_n_perf(n_features: int = 2048, n: int = 512) -> dict:
    """Tiled streaming-Gram kernel past the untiled VMEM ceiling.

    Times the auto-tiled Pallas path (interpret mode on CPU) against the
    tiled XLA twin at the same shape, records their relative agreement and
    the per-instance accumulator footprint the tiling buys (bounded by the
    tile, not N — the quantity the VMEM-proxy test asserts on).
    """
    from repro.core.kernels_math import ell_vector
    from repro.core.rf_tca import streaming_gram
    from repro.kernels import ops as kops

    p = 16
    plan = kops.gram_tile_plan(n_features, p)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(p, n)), jnp.float32)
    ell = ell_vector(n // 2, n - n // 2)
    omega = jnp.asarray(rng.normal(size=(n_features, p)), jnp.float32)

    pallas = lambda: kops.rff_gram_stream(x, omega, ell)  # auto-tiled
    twin = lambda: streaming_gram(x, ell, omega, block=128, tile=plan["tile"])
    g_p, u_p = jax.block_until_ready(pallas())  # warm both compiles
    g_t, u_t = jax.block_until_ready(twin())
    ts: dict = {"pallas": [], "twin": []}
    for name, fn in (("pallas", pallas), ("twin", twin)):
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            ts[name].append(time.perf_counter() - t0)
    scale = float(jnp.abs(g_t).max())
    rel = float(jnp.abs(g_p - g_t).max()) / scale
    out = {
        "shape": {"n": n, "N": n_features, "p": p},
        "tile": plan["tile"],
        "tiled_pallas_s": min(ts["pallas"]),
        "tiled_twin_s": min(ts["twin"]),
        "rel_err_pallas_vs_twin": rel,
        "u_abs_err": float(jnp.abs(u_p - u_t).max()),
        # what the tiling buys: per-instance accumulator bytes vs untiled
        "acc_bytes_tiled": plan["acc_bytes"],
        "acc_bytes_untiled": kops.gram_tile_plan(n_features, p, tile=0)["acc_bytes"],
    }
    emit(
        "fig3/gram_large_N", out["tiled_pallas_s"] * 1e6,
        f"N={n_features},tile={plan['tile']},rel_err={rel:.1e},"
        f"acc_mem={plan['acc_bytes']/2**20:.1f}MiB",
    )
    return out


def _ulp_diff(a, b) -> int:
    """Max ULP distance between two fp32 arrays (0 == bit-for-bit)."""
    order = lambda i: np.where(i >= 0, i, np.int64(-(2**31)) - i)
    ai = order(np.asarray(a, np.float32).reshape(-1).view(np.int32).astype(np.int64))
    bi = order(np.asarray(b, np.float32).reshape(-1).view(np.int32).astype(np.int64))
    return int(np.max(np.abs(ai - bi), initial=0))


def _fused_memory_proxy(n_features: int, p: int = 16, ensemble: int = 1) -> dict:
    """Analytic peak-HBM proxy of one statistics pass at feature count N.

    Both paths hold the O(N^2) output statistics; the materialized path
    additionally keeps the (N_pad, p_pad) frequency matrix resident for the
    whole pass — the allocation the seed-fused kernels delete (the 8-byte
    seed is the weight).  Analytic so the ladder can include N far past what
    interpret-mode CI can run."""
    from repro.kernels import ops as kops

    plan = kops.gram_tile_plan(n_features, p, fused=True)
    npad = plan["n_pad"]
    p_pad = p + (-p) % 128
    stats = 4 * (3 * npad * npad + 2 * npad * 2 * ensemble)
    omega_bytes = 4 * npad * p_pad
    return {
        "materialized": stats + omega_bytes,
        "fused": stats,
        "omega_bytes": omega_bytes,
        "tile": plan["tile"],
    }


def fused_perf(
    n_features: int = 192, n: int = 256, ensemble: int = 3,
    proxy_ns: tuple = (512, 1024, 2048, 4096, 8192),
) -> dict:
    """Seed-fused statistics pass: the tentpole evidence rows.

    - fused Pallas vs XLA generator twin at 0 ULP, untiled AND tiled layouts;
    - ensemble=1 bitwise-degenerate to the single-draw (materialized) path;
    - ensemble=S agreement with the mean-of-centered-draws dense oracle;
    - analytic peak-memory proxy ladder (fused strictly below materialized,
      the margin = the deleted omega allocation) up to N far past the sweep;
    - fused vs materialized kernel wall-time at the test shape.
    """
    import importlib

    from repro.core.kernels_math import ell_vector
    from repro.kernels import ops as kops
    from repro.kernels.prng import fused_omega
    from repro.kernels.ref import rff_gram_stream_fused_ref

    rf = importlib.import_module("repro.core.rf_tca")
    rng = np.random.default_rng(0)
    p = 16
    x = jnp.asarray(rng.normal(size=(p, n)), jnp.float32)
    ell = ell_vector(n // 2, n - n // 2)
    seed = 33
    kw = dict(n_features=n_features, seed=seed)

    # fused Pallas vs its XLA generator twin — both layouts, single draw
    g_pu, u_pu = rf.fused_streaming_gram(x, ell, use_pallas=True, **kw)
    g_xu, u_xu = rf.fused_streaming_gram(x, ell, use_pallas=False, **kw)
    ulp_untiled = max(_ulp_diff(g_pu, g_xu), _ulp_diff(u_pu, u_xu))
    g_pt, u_pt = rf.fused_streaming_gram(x, ell, use_pallas=True, tile=128, **kw)
    g_xt, u_xt = rf.fused_streaming_gram(x, ell, use_pallas=False, tile=128, **kw)
    ulp_tiled = max(_ulp_diff(g_pt, g_xt), _ulp_diff(u_pt, u_xt))

    # ensemble=1 degeneracy: the fused kernel must be bitwise the materialized
    # kernel fed the generator-twin omega (garbage-padded draws contribute
    # exact zeros, so the two programs accumulate identical floats)
    omega = fused_omega(seed, n_features, p)
    g_m, u_m = kops.rff_gram_stream(x, omega, ell)
    ens1_diff = max(
        float(jnp.abs(g_pu - g_m).max()), float(jnp.abs(u_pu - u_m).max())
    )

    # ensemble=S vs the dense mean-of-centered-draws oracle
    g_s, u_s = rf.fused_streaming_gram(x, ell, use_pallas=True, ensemble=ensemble, **kw)
    g_o, u_o = rff_gram_stream_fused_ref(x, ell, ensemble=ensemble, **kw)
    scale = float(jnp.abs(g_o).max())
    ens_rel = max(
        float(jnp.abs(g_s - g_o).max()) / scale, float(jnp.abs(u_s - u_o).max())
    )

    fused = lambda: rf.fused_streaming_gram(x, ell, use_pallas=True, **kw)
    mat = lambda: kops.rff_gram_stream(x, omega, ell)
    ts: dict = {"fused": [], "materialized": []}
    for name, fn in (("fused", fused), ("materialized", mat)):
        jax.block_until_ready(fn())
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            ts[name].append(time.perf_counter() - t0)

    out = {
        "shape": {"n": n, "N": n_features, "p": p, "ensemble": ensemble},
        "ulp_untiled": ulp_untiled,
        "ulp_tiled": ulp_tiled,
        "ensemble1_max_abs_diff": ens1_diff,
        "ensemble_rel_err_vs_oracle": ens_rel,
        "fused_s": min(ts["fused"]),
        "materialized_s": min(ts["materialized"]),
        "memory_proxy_bytes": {
            str(nn): _fused_memory_proxy(nn, p=p) for nn in proxy_ns
        },
    }
    emit("fig3/fused_ulp", 0.0,
         f"untiled={ulp_untiled},tiled={ulp_tiled},ens1_diff={ens1_diff:.1e}")
    emit("fig3/fused_gram", out["fused_s"] * 1e6,
         f"N={n_features},vs_materialized={out['materialized_s']/out['fused_s']:.2f}x")
    top = out["memory_proxy_bytes"][str(proxy_ns[-1])]
    emit("fig3/fused_memory", 0.0,
         f"N={proxy_ns[-1]},fused={top['fused']/2**20:.1f}MiB,"
         f"materialized={top['materialized']/2**20:.1f}MiB")
    return out


def accuracy_resweep(
    sources, target, *, n_sweep: tuple, ensemble_n: int, ensembles: tuple = (1, 4),
    seed: int = 0,
) -> dict:
    """Fig. 3 accuracy-vs-N re-sweep on the seed-fused path, now that large N
    is reachable without materializing (N, p)/(2N, n) tensors.

    Emits the tracked resolution row for the BENCH anomaly where N=500 beat
    N=1000 on the materialized sweep: with more features (and optionally
    ensemble averaging) the curve should recover, or the row records that the
    anomaly persists (solver/feature-budget limited)."""
    accs: dict = {}
    for nn in n_sweep:
        acc, t = timed(
            rf_tca_baseline, sources, target, n_features=nn, gamma=1e-3, m=16,
            w_rf=f"fused:{seed}",
        )
        accs[nn] = acc
        emit(f"fig3/rf_tca_fused_N{nn}", t, f"acc={acc:.3f}")
    ens_accs: dict = {}
    for s in ensembles:
        acc, t = timed(
            rf_tca_baseline, sources, target, n_features=ensemble_n, gamma=1e-3,
            m=16, w_rf=f"fused:{seed}", ensemble=s,
        )
        ens_accs[s] = acc
        emit(f"fig3/rf_tca_fused_N{ensemble_n}_S{s}", t, f"acc={acc:.3f}")

    ns = sorted(accs)
    small = ns[len(ns) // 2 - 1] if len(ns) > 1 else ns[0]
    acc_small = accs[small]
    best_large = max(accs[nn] for nn in ns if nn > small) if ns[-1] > small else acc_small
    status = "resolved" if best_large >= acc_small - 0.005 else "persists"
    anomaly = {
        "small_n": small,
        "acc_small_n": acc_small,
        "best_acc_larger_n": best_large,
        "status": status,
    }
    emit("fig3/claim_N_anomaly", 0.0,
         f"status={status},acc_N{small}={acc_small:.3f},best_larger={best_large:.3f}")
    return {
        "fused": {str(nn): a for nn, a in accs.items()},
        "ensemble_at_N": ensemble_n,
        "ensemble": {str(s): a for s, a in ens_accs.items()},
        "anomaly_small_vs_large_n": anomaly,
    }


def round_engine_perf(rounds: int = 10, n_per_domain: int = 400) -> dict:
    """Per-round wall-time of the serial vs batched protocol data plane."""
    from repro.data import make_domains
    from repro.federated import ClientConfig, FedRFTCATrainer, ProtocolConfig

    doms = make_domains(5, n_per_domain, shift=0.8, seed=0)
    cfg = ClientConfig(input_dim=16, n_classes=5, n_rff=128, m=16)
    res = {}
    for engine in ("serial", "batched"):
        proto = ProtocolConfig(
            n_rounds=rounds, t_c=5, warmup_rounds=0, seed=0, engine=engine
        )
        tr = FedRFTCATrainer(doms[:4], doms[4], cfg, proto)
        tr.round(0)  # compile
        t0 = time.perf_counter()
        tr.train()
        res[engine] = (time.perf_counter() - t0) / rounds
        emit(f"fig3/round_{engine}", res[engine] * 1e6, f"K=4,rounds={rounds}")
    res["speedup_batched_vs_serial"] = res["serial"] / res["batched"]
    emit("fig3/round_speedup", 0.0, f"batched_vs_serial={res['speedup_batched_vs_serial']:.1f}x")
    return res


def ragged_round_perf(rounds: int = 6) -> dict:
    """Ragged-K rounds: unequal per-client datasets through both planes.

    The batched plane pads each client to the max width and masks — this row
    tracks its per-round cost on heterogeneous clients plus the max parameter
    divergence from the serial reference under full participation (should sit
    at fp32 noise; the seed engine's min-truncation made the planes diverge).
    """
    from repro.data import make_domains
    from repro.data.domains import Domain
    from repro.federated import ClientConfig, FedRFTCATrainer, ProtocolConfig
    from repro.federated import network as fed_network
    from repro.federated.network import RoundPlan

    doms = make_domains(5, 400, shift=0.8, seed=0)
    sizes = (400, 250, 120, 40)
    sources = [
        Domain(f"rag{i}", d.x[:, :s], d.y[:s]) for i, (d, s) in enumerate(zip(doms, sizes))
    ]
    cfg = ClientConfig(input_dim=16, n_classes=5, n_rff=128, m=16)
    orig_plan = fed_network.plan_round
    fed_network.plan_round = lambda rng, n, s: RoundPlan(
        list(range(n)), list(range(n)), list(range(n))
    )
    try:
        res: dict = {"client_sizes": list(sizes)}
        trainers = {}
        for engine in ("serial", "batched"):
            proto = ProtocolConfig(
                n_rounds=rounds, t_c=5, warmup_rounds=1, batch_size=64,
                message_batch_size=256, seed=0, engine=engine,
            )
            tr = FedRFTCATrainer(sources, doms[4], cfg, proto)
            tr.round(0)  # compile
            t0 = time.perf_counter()
            tr.train()
            res[f"{engine}_s"] = (time.perf_counter() - t0) / rounds
            trainers[engine] = tr
            emit(f"fig3/ragged_round_{engine}", res[f"{engine}_s"] * 1e6,
                 f"K=4,n_k={sizes}")
        err = max(
            float(np.abs(np.asarray(a) - np.asarray(b)).max())
            for a, b in zip(
                jax.tree_util.tree_leaves(trainers["serial"].tgt_params),
                jax.tree_util.tree_leaves(trainers["batched"].tgt_params),
            )
        )
        res["speedup_batched_vs_serial"] = res["serial_s"] / res["batched_s"]
        res["max_param_divergence"] = err
        emit("fig3/ragged_round_equiv", 0.0,
             f"max_param_div={err:.1e},speedup={res['speedup_batched_vs_serial']:.1f}x")
        return res
    finally:
        fed_network.plan_round = orig_plan


def run(smoke: bool = False) -> None:
    """Full bench by default; ``smoke=True`` runs every row at tiny sizes so
    CI can validate the emitted BENCH_rf_tca.json schema in seconds."""
    record: dict = {"bench": "rf_tca", "smoke": smoke}
    if smoke:
        record["fit"] = fit_perf(n=256, n_features=64, m=8)
        record["large_n"] = large_n_perf(n_features=1280, n=128)
        record["fused"] = fused_perf(n_features=96, n=128, ensemble=2)
        record["round_engine"] = round_engine_perf(rounds=2, n_per_domain=120)
        record["ragged_rounds"] = ragged_round_perf(rounds=2)
    else:
        record["fit"] = fit_perf()
        record["large_n"] = large_n_perf()
        record["fused"] = fused_perf()
        record["round_engine"] = round_engine_perf()
        record["ragged_rounds"] = ragged_round_perf()

    sources, target = da_suite(n=60 if smoke else 400)
    acc_src, t_src = timed(source_only, sources, target, seed=0)
    emit("fig3/source_only", t_src, f"acc={acc_src:.3f}")

    acc_tca, t_tca = timed(tca_baseline, sources, target, gamma=1e-3, m=16)
    emit("fig3/tca", t_tca, f"acc={acc_tca:.3f}")

    acc_rtca, t_rtca = timed(tca_baseline, sources, target, gamma=1e-3, m=16, variant="r")
    emit("fig3/r_tca", t_rtca, f"acc={acc_rtca:.3f}")

    n_sweep = (50, 100) if smoke else (100, 500, 1000)
    accs = {}
    for n in n_sweep:
        acc, t = timed(rf_tca_baseline, sources, target, n_features=n, gamma=1e-3, m=16)
        accs[n] = acc
        emit(f"fig3/rf_tca_N{n}", t, f"acc={acc:.3f},speedup_vs_tca={t_tca/t:.1f}x")

    acc_coral, t = timed(coral_baseline, sources, target)
    emit("fig3/coral", t, f"acc={acc_coral:.3f}")
    acc_jda, t = timed(jda_baseline, sources, target, gamma=1e-3, iters=2)
    emit("fig3/jda", t, f"acc={acc_jda:.3f}")
    acc_dann, t = timed(dann_mmd_baseline, sources, target, steps=30 if smoke else 300)
    emit("fig3/dann", t, f"acc={acc_dann:.3f}")

    # paper claim: more random features never hurts much (monotone-ish)
    emit(
        "fig3/claim_N_trend", 0.0,
        f"acc_N{n_sweep[0]}={accs[n_sweep[0]]:.3f}<=~acc_N{n_sweep[-1]}={accs[n_sweep[-1]]:.3f}",
    )

    record["accuracy"] = {
        "source_only": acc_src,
        "tca": acc_tca,
        "r_tca": acc_rtca,
        **{f"rf_tca_N{n}": a for n, a in accs.items()},
        "coral": acc_coral,
        "jda": acc_jda,
        "dann": acc_dann,
    }
    # seed-fused re-sweep: large N now reachable (no (N, p)/(2N, n) tensors)
    if smoke:
        record["accuracy_resweep"] = accuracy_resweep(
            sources, target, n_sweep=(50, 100), ensemble_n=50, ensembles=(1, 2)
        )
    else:
        record["accuracy_resweep"] = accuracy_resweep(
            sources, target, n_sweep=(100, 500, 1000, 2000, 4000), ensemble_n=500
        )
    JSON_PATH.write_text(json.dumps(record, indent=2) + "\n")
    emit("fig3/json", 0.0, f"wrote={JSON_PATH.name}")


if __name__ == "__main__":
    run()
