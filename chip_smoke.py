"""Bring-up smoke run of FedRF-TCA on a TPU, at deployment width.

    python chip_smoke.py             # fit, federated rounds and serving: one chip
    python chip_smoke.py --chips 4   # the sharded paths on four chips, nothing else

One process runs every phase through the entry points a user calls, on data
drawn from fixed seeds.  Each phase prints one line first: its shapes, its
agreement with its reference, and its seconds including compilation (a smoke
timing, not a metric).  A disagreement raises, so the script exits non-zero
and prints no result.  The last line is the JSON summary
``{"ok": true, "device": {"platform", "kind", "count"}}``.  With no TPU
(for example ``JAX_PLATFORMS=cpu``) it exits non-zero before any phase.

Phases on one chip, at p = 2048 (the width of pooled ResNet-50 features):

- fit: ``rf_tca_fit`` with n = 16384 samples per domain, N in {1024, 4096}
  and m = 32, with materialized omega and with ``w_rf="fused:<seed>"``, both
  ``use_pallas=True``.  Each Pallas statistics pass lowers to
  ``tpu_custom_call`` and agrees with its XLA twin at rel <= 1e-4; the
  eigensolve, on the device when called eagerly, also runs under jit (on the
  host, as a ``pure_callback``) and the two agree.
- rounds: ``FedRFTCATrainer`` (batched engine) on an Office-31-shaped
  federation (31 classes, 4 sources and 1 target) with N = 1024, m = 32, a
  two-edge topology and the qint8 codec, so ``segment_reduce`` and
  ``fake_quant`` run as Pallas kernels.  Accuracy and loss are finite, and
  the byte ledger equals the count the round plans imply.
- serve: ``AlignerServer`` fits one seed-fused pair at N = 1024, warms up and
  answers 64 requests of 4-32 columns, each within 1e-5 of
  ``rf_tca_transform``.

``--chips 4``: ``build_sharded_round`` on a 4-device ``clients`` mesh against
the host math of the same synchronous round, and ``sharded_client_map`` at
K = 1024 against one-device ``chunked_vmap``.
"""
from __future__ import annotations

import argparse
import functools
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

P = 2048  # pooled ResNet-50 feature width
N_PER_DOMAIN = 16384
FIT_FEATURES = (1024, 4096)
N_FEATURES = 1024  # rounds, serve and the sharded paths
OFFICE31_SAMPLES = 512  # per client
MAP_CLIENTS = 1024
M = 32
SEED = 0
FUSED_SEED = 1234
STATS_TOL = 1e-4  # Pallas statistics pass vs its XLA twin (the CPU tests' gate)
SERVE_TOL = 1e-5  # served answer vs rf_tca_transform
SHARD_TOL = 1e-5  # sharded paths vs their one-device references


def _rel(a, b) -> float:
    import numpy as np

    a, b = np.asarray(a), np.asarray(b)
    return float(np.abs(a - b).max() / max(float(np.abs(b).max()), 1e-30))


def _require(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def _domain_pair(p: int, n: int, seed: int):
    """Seeded (source, target) of unit-norm sample columns, the target's mean
    shifted — the paper normalizes its features the same way."""
    import jax
    import jax.numpy as jnp

    ks, kt, km = jax.random.split(jax.random.PRNGKey(seed), 3)
    x_s = jax.random.normal(ks, (p, n), jnp.float32)
    x_t = jax.random.normal(kt, (p, n), jnp.float32) + 2.0 * jax.random.normal(km, (p, 1))
    unit = lambda x: x / jnp.linalg.norm(x, axis=0, keepdims=True)
    return unit(x_s), unit(x_t)


def phase_fit(x_s, x_t) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.kernels_math import ell_vector
    from repro.core.rf_tca import (
        fused_streaming_gram,
        rf_tca_fit,
        solve_w_rf_gram,
        streaming_gram,
    )
    from repro.core.rff import draw_omega

    x = jnp.concatenate([x_s, x_t], axis=1)
    ell = ell_vector(x_s.shape[1], x_t.shape[1])
    out = {}
    for nf in FIT_FEATURES:
        omega = draw_omega(SEED, nf, P)
        passes = {
            "materialized": (
                lambda use, x, ell, omega: streaming_gram(x, ell, omega, use_pallas=use),
                {},
            ),
            "fused": (
                lambda use, x, ell, omega, nf=nf: fused_streaming_gram(
                    x, ell, n_features=nf, seed=FUSED_SEED, use_pallas=use
                ),
                {"w_rf": f"fused:{FUSED_SEED}"},
            ),
        }
        for name, (stats, fit_kw) in passes.items():
            args = (x, ell, omega)
            t0 = time.perf_counter()
            hlo = jax.jit(functools.partial(stats, True)).lower(*args).as_text()
            _require("tpu_custom_call" in hlo, f"{name} N={nf}: no tpu_custom_call")
            g_p, u_p = jax.block_until_ready(stats(True, *args))
            g_x, u_x = jax.block_until_ready(stats(False, *args))
            rel_g, rel_u = _rel(g_p, g_x), _rel(u_p, u_x)
            _require(
                rel_g <= STATS_TOL and rel_u <= STATS_TOL,
                f"{name} N={nf}: Pallas vs twin rel G {rel_g:.3e}, u {rel_u:.3e}",
            )
            state = rf_tca_fit(
                x_s, x_t, n_features=nf, m=M, seed=SEED, use_pallas=True, **fit_kw
            )
            vals = np.asarray(state.eigvals)
            _require(
                state.w_rf.shape == (2 * nf, M) and np.isfinite(vals).all()
                and np.isfinite(np.asarray(state.w_rf)).all(),
                f"{name} N={nf}: fit not finite / wrong shape {state.w_rf.shape}",
            )
            secs = time.perf_counter() - t0
            print(
                f"fit {name}: p={P} n={x.shape[1]} N={nf} m={M} G={tuple(g_p.shape)} "
                f"tpu_custom_call=yes rel_G={rel_g:.3e} rel_u={rel_u:.3e} "
                f"eig_top={vals[0]:.6g} seconds={secs:.1f}",
                flush=True,
            )
            out[f"{name}_{nf}"] = {"rel_G": rel_g, "rel_u": rel_u}
        if nf == FIT_FEATURES[0]:  # eager (device) vs jit (host pure_callback) eigensolve
            t0 = time.perf_counter()
            solve = functools.partial(solve_w_rf_gram, gamma=1.0, m=M)
            w_eager, v_eager = solve(g_x, u_x)
            w_jit, v_jit = jax.jit(solve)(g_x, u_x)
            rel_v = _rel(v_jit, v_eager)
            _require(rel_v <= 1e-6, f"eigensolve under jit vs eager rel {rel_v:.3e}")
            print(
                f"fit eigensolve under jit: 2N={2 * nf} m={M} rel_vals={rel_v:.3e} "
                f"seconds={time.perf_counter() - t0:.1f}",
                flush=True,
            )
    return out


def phase_rounds() -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.data import make_domains
    from repro.federated import ClientConfig, FedRFTCATrainer, ProtocolConfig
    from repro.federated.model import source_loss
    from repro.fleet import Topology

    t0 = time.perf_counter()
    n_rounds, warmup, k = 5, 2, 4
    doms = make_domains(k + 1, OFFICE31_SAMPLES, n_classes=31, dim=P, seed=3)
    cfg = ClientConfig(input_dim=P, n_classes=31, n_rff=N_FEATURES, m=M)
    proto = ProtocolConfig(
        n_rounds=n_rounds, t_c=n_rounds, warmup_rounds=warmup, engine="batched",
        codec="qint8", topology=Topology.uniform(k, 2), seed=SEED,
    )
    tr = FedRFTCATrainer(doms[:k], doms[k], cfg, proto)
    plans = []
    for t in range(1, n_rounds + 1):
        plan = tr.scenario.plan(tr.rng, tr.k, t)
        tr.run_round(t, plan)
        plans.append((t, plan))
    acc = tr.evaluate()
    xt, yt = jnp.asarray(doms[k].x), jnp.asarray(doms[k].y)
    loss, _ = source_loss(
        tr.tgt_params, tr.omega, xt, yt, jnp.zeros((2 * cfg.n_rff,)), cfg, with_mmd=False
    )
    loss = float(loss)
    secs = time.perf_counter() - t0
    _require(np.isfinite(acc) and np.isfinite(loss), f"rounds: acc {acc}, loss {loss}")
    f32 = np.dtype(np.float32)
    size = tr.transport.payload_sizes({
        "moments": {"msg": ((2 * cfg.n_rff,), f32)},
        "w_rf": {"w_rf": ((2 * cfg.n_rff, cfg.m), f32)},
        "classifier": {"w": ((cfg.m, cfg.n_classes), f32), "b": ((cfg.n_classes,), f32)},
    })
    expected = 0
    for t, plan in plans:  # one target downlink + one uplink per delivering client
        if plan.msg_clients:
            expected += (1 + len(plan.msg_clients)) * size["moments"]
        if plan.w_clients:
            expected += (1 + len(plan.w_clients)) * size["w_rf"]
        if t % proto.t_c == 0 and plan.c_clients:
            expected += len(plan.c_clients) * size["classifier"]
    got = int(tr.comm.bytes_total)
    _require(got == expected, f"rounds: bytes_total {got} != analytic {expected}")
    print(
        f"rounds: p={P} classes=31 clients={k}+1 N={cfg.n_rff} m={M} edges=2 "
        f"codec=qint8 warmup={warmup} rounds={n_rounds} acc={acc:.4f} loss={loss:.4f} "
        f"bytes_total={got} (analytic {expected}) backend={jax.default_backend()} "
        f"seconds={secs:.1f}",
        flush=True,
    )
    return {"acc": acc, "loss": loss, "bytes_total": got}


def phase_serve(x_s, x_t) -> dict:
    import jax.numpy as jnp
    import numpy as np

    from repro.core.rf_tca import rf_tca_transform
    from repro.serve import AlignerServer, synth_requests

    t0 = time.perf_counter()
    pair = ("source", "target")
    srv = AlignerServer(capacity=2, fused_seed=FUSED_SEED)
    srv.fit_domain(pair, x_s, x_t, n_features=N_FEATURES, m=M, seed=SEED, use_pallas=True)
    srv.warmup(pair)
    reqs = synth_requests([pair], dim=P, n_requests=64, seed=SEED, cols_lo=4, cols_hi=32)
    done = srv.serve(reqs)
    entry = srv.store.get(pair)
    err = 0.0
    for req, out in done:
        ref = np.asarray(rf_tca_transform(entry.state, jnp.asarray(req.x)))
        _require(out.shape == ref.shape, f"serve: shape {out.shape} != {ref.shape}")
        err = max(err, float(np.abs(out - ref).max()))
    _require(len(done) == len(reqs) and err <= SERVE_TOL, f"serve: max err {err:.3e}")
    cols = sum(int(np.shape(r.x)[1]) for r in reqs)
    print(
        f"serve: p={P} N={N_FEATURES} m={M} w_rf=fused:{FUSED_SEED} requests={len(done)} "
        f"columns={cols} dispatches={srv.dispatcher.dispatches} max_err={err:.3e} "
        f"seconds={time.perf_counter() - t0:.1f}",
        flush=True,
    )
    return {"requests": len(done), "max_err": err}


def phase_sharded() -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.mmd import mmd_projected
    from repro.federated.distributed import build_sharded_round, stack_clients, unstack_clients
    from repro.federated.model import (
        ClientConfig,
        client_message,
        init_params,
        make_omega,
        source_loss,
    )
    from repro.fleet import chunked_vmap, client_mesh, sharded_client_map
    from repro.optim import apply_updates, sgd

    k, b = 4, 64
    cfg = ClientConfig(input_dim=P, n_classes=31, n_rff=N_FEATURES, m=M)
    omega = make_omega(cfg)
    key = jax.random.PRNGKey(SEED)
    params = [init_params(cfg, jax.random.fold_in(key, i)) for i in range(k)]
    # SGD keeps the update linear in the gradient: Adam's first step,
    # g / (|g| + eps), would turn float32 summation-order noise in a
    # near-zero gradient into an O(lr) difference
    opt = sgd(1e-2)
    opts = [opt.init(p) for p in params]
    kx, ky, kt = jax.random.split(jax.random.fold_in(key, 99), 3)
    xs = jax.random.normal(kx, (k, P, b), jnp.float32)
    ys = jax.random.randint(ky, (k, b), 0, cfg.n_classes)
    x_t = jax.random.normal(kt, (P, b), jnp.float32)
    mesh = client_mesh(k)

    # both sides at full fp32 matmul precision: the comparison is of the
    # exchange, not of one-pass bf16 rounding in two differently fused programs
    with jax.default_matmul_precision("highest"):
        t0 = time.perf_counter()
        rnd = build_sharded_round(mesh, cfg, omega, opt)
        sp, so, metrics = rnd(stack_clients(params), stack_clients(opts), xs, ys, x_t)
        got = unstack_clients(jax.block_until_ready(sp), k)
        ref = []  # host math of the same synchronous round
        for i in range(k):
            msg_t = client_message(params[i], omega, x_t, -1.0)
            others = [client_message(params[j], omega, xs[j], +1.0) for j in range(k) if j != i]

            def loss_fn(p, i=i, msg_t=msg_t, others=others):
                loss, _ = source_loss(p, omega, xs[i], ys[i], msg_t, cfg, with_mmd=False)
                mean_msg = (client_message(p, omega, xs[i], +1.0) + sum(others)) / k
                return loss + cfg.lambda_mmd * mmd_projected(p["w_rf"], mean_msg, msg_t)

            upd, _ = opt.update(jax.grad(loss_fn)(params[i]), opts[i], params[i])
            ref.append(apply_updates(params[i], upd))
        ref_wrf = sum(p["w_rf"] for p in ref) / k
        for p in ref:
            p["w_rf"] = ref_wrf
        err_round = max(
            float(jnp.abs(a - r).max())
            for g, rp in zip(got, ref)
            for a, r in zip(jax.tree_util.tree_leaves(g), jax.tree_util.tree_leaves(rp))
        )
        secs_round = time.perf_counter() - t0
        _require(err_round <= SHARD_TOL, f"sharded round vs host math err {err_round:.3e}")

        t0 = time.perf_counter()
        kk, bb = MAP_CLIENTS, 16
        xk = jax.random.normal(jax.random.fold_in(key, 7), (kk, P, bb), jnp.float32)

        def message(x):
            return client_message(params[0], omega, x, +1.0)

        want = jax.jit(chunked_vmap(message, (0,), chunk=128))(xk)
        got_map = jax.block_until_ready(
            jax.jit(sharded_client_map(mesh, message, (0,), chunk=128))(xk)
        )
        err_map = float(np.abs(np.asarray(got_map) - np.asarray(want)).max())
        n_dev = len(got_map.sharding.device_set)
        secs_map = time.perf_counter() - t0
        _require(
            n_dev == k and err_map <= SHARD_TOL,
            f"sharded_client_map on {n_dev} devices err {err_map:.3e}",
        )
    print(
        f"sharded round: mesh=clients:{k} p={P} N={cfg.n_rff} m={M} batch={b} "
        f"max_err_vs_host={err_round:.3e} l_mmd={float(metrics['l_mmd']):.6g} "
        f"seconds={secs_round:.1f}",
        flush=True,
    )
    print(
        f"sharded_client_map: mesh=clients:{k} K={kk} p={P} batch={bb} chunk=128 "
        f"out={tuple(got_map.shape)} devices={n_dev} max_err_vs_chunked_vmap={err_map:.3e} "
        f"seconds={secs_map:.1f}",
        flush=True,
    )
    return {"err_round": err_round, "err_map": err_map}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="1: fit, rounds and serve on one chip; 4: only the sharded paths",
    )
    args = ap.parse_args()

    from repro.utils.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU, JAX found {devices[0].platform!r} devices")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
                 f"found {len(devices)}")
    print(f"device: {devices[0].device_kind} x{len(devices)} compile_cache={cache}",
          flush=True)
    if args.chips == 4:
        phase_sharded()
    else:
        x_s, x_t = _domain_pair(P, N_PER_DOMAIN, SEED)
        phase_fit(x_s, x_t)
        phase_rounds()
        phase_serve(x_s, x_t)
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
    }))


if __name__ == "__main__":
    main()
