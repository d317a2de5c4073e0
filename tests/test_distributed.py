"""Sharded FedRF-TCA data plane: the psum message exchange must reproduce the
host-side math. Runs in a subprocess with forced multi-device CPU (XLA device
count is locked at first jax import, so it can't be set inside this process).
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.federated.distributed import (
        build_sharded_round, stack_clients, unstack_clients,
    )
    from repro.fleet import client_mesh
    from repro.federated.model import (
        ClientConfig,
        client_message,
        init_params,
        make_omega,
        source_loss,
    )
    from repro.core.mmd import mmd_projected
    from repro.optim import adam, apply_updates

    K = 4
    cfg = ClientConfig(input_dim=6, n_classes=3, n_rff=16, m=4, extractor_widths=(8, 4))
    omega = make_omega(cfg)
    key = jax.random.PRNGKey(0)
    params = [init_params(cfg, jax.random.fold_in(key, i)) for i in range(K)]
    opt = adam(1e-2)
    opts = [opt.init(p) for p in params]
    rng = np.random.default_rng(0)
    xs = jnp.asarray(rng.normal(size=(K, 6, 8)), jnp.float32)
    ys = jnp.asarray(rng.integers(0, 3, size=(K, 8)))
    x_t = jnp.asarray(rng.normal(size=(6, 8)), jnp.float32)

    mesh = client_mesh(K)
    rnd = build_sharded_round(mesh, cfg, omega, opt)
    sp = stack_clients(params)
    so = stack_clients(opts)
    sp2, so2, metrics = rnd(sp, so, xs, ys, x_t)

    # host-side reference of the same synchronous round
    msgs = [client_message(params[i], omega, xs[i], +1.0) for i in range(K)]
    msg_mean = sum(msgs) / K
    ref_params = []
    for i in range(K):
        msg_t = client_message(params[i], omega, x_t, -1.0)
        def loss_fn(p, i=i, msg_t=msg_t):
            l, aux = source_loss(p, omega, xs[i], ys[i], msg_t, cfg, with_mmd=False)
            m_s = client_message(p, omega, xs[i], +1.0)
            all_msgs = [client_message(params[j], omega, xs[j], +1.0) for j in range(K) if j != i]
            mean_msg = (m_s + sum(all_msgs)) / K
            return l + cfg.lambda_mmd * mmd_projected(p["w_rf"], mean_msg, msg_t)
        g = jax.grad(loss_fn)(params[i])
        u, _ = opt.update(g, opts[i], params[i])
        ref_params.append(apply_updates(params[i], u))
    ref_wrf = sum(p["w_rf"] for p in ref_params) / K

    got = unstack_clients(sp2, K)
    err_wrf = float(jnp.abs(got[0]["w_rf"] - ref_wrf).max())
    err_ext = float(jnp.abs(got[1]["extractor"][0]["w"] - ref_params[1]["extractor"][0]["w"]).max())
    print(json.dumps({"err_wrf": err_wrf, "err_ext": err_ext,
                      "l_mmd": float(metrics["l_mmd"])}))
    """
)


@pytest.mark.slow
def test_sharded_round_matches_host_math(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True, env=env, timeout=480
    )
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["err_wrf"] < 1e-5, res
    assert res["err_ext"] < 1e-5, res
    assert res["l_mmd"] >= 0.0
