"""Sharded FedRF-TCA data plane: the paper's communication pattern as JAX collectives.

The host-side simulator (`protocol.py`) expresses the *asynchronous* protocol.
This module expresses the *synchronous* round (all clients in S_t) as a single
SPMD program with ``shard_map`` over a ``clients`` mesh axis:

- every client shard computes its 2N-float message  Sigma ell   locally;
- the message exchange is ONE ``psum`` over the clients axis  -> an all-reduce
  of 2N floats, byte-for-byte the O(KN) claim of Table I;
- FedAvg of W_RF is ONE ``pmean`` of the (2N, m) aligner        -> O(KNm).

Nothing here scales with the per-client sample count n — compare with a naive
federated MMD which would all-gather (n_i x d) features.

This is also the pattern the backbone integration uses on the production mesh
(clients axis == data axis); see repro.models.fda_head.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.core.mmd import mmd_projected
from repro.federated.model import ClientConfig, client_message, source_loss
from repro.optim import apply_updates


def build_sharded_round(mesh: Mesh, cfg: ClientConfig, omega: jnp.ndarray, opt):
    """Returns a jitted synchronous round over stacked per-client state.

    Stacked state: params/opt with a leading (K,) axis sharded over `clients`;
    batches (K, p, b) and labels (K, b) likewise; target batch replicated.
    """

    def one_round(stacked_params, stacked_opt, xs, ys, x_t):
        def per_client(params, opt_state, x, y, x_tgt):
            # strip the leading length-1 shard axis
            params = jax.tree_util.tree_map(lambda a: a[0], params)
            opt_state = jax.tree_util.tree_map(
                lambda a: a[0] if a.ndim > 0 else a, opt_state
            )
            x, y = x[0], y[0]

            # target message, computed with THIS client's current extractor view
            # of the target batch (synchronous round: target params == broadcast)
            msg_t = client_message(params, omega, x_tgt, -1.0)

            def loss_fn(p):
                loss, aux = source_loss(p, omega, x, y, msg_t, cfg, with_mmd=False)
                msg_s = client_message(p, omega, x, +1.0)
                # >>> THE EXCHANGE: one all-reduce of a 2N-float message <<<
                # Other clients' messages arrive over the wire and are
                # constants to this client (psum's VJP would otherwise sum
                # cotangents across shards): gradient flows through the local
                # term only, matching the host-side protocol semantics.
                msg_sum = msg_s + jax.lax.stop_gradient(
                    jax.lax.psum(msg_s, "clients") - msg_s
                )
                l_mmd = mmd_projected(p["w_rf"], msg_sum / mesh.shape["clients"], msg_t)
                return loss + cfg.lambda_mmd * l_mmd, (aux["l_c"], l_mmd)

            (loss, (l_c, l_mmd)), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
            upd, opt_state = opt.update(grads, opt_state, params)
            params = apply_updates(params, upd)
            # >>> FedAvg of the aligner: one pmean of (2N, m) <<<
            params["w_rf"] = jax.lax.pmean(params["w_rf"], "clients")
            metrics = {
                "l_c": jax.lax.pmean(l_c, "clients"),
                "l_mmd": jax.lax.pmean(l_mmd, "clients"),
            }
            params = jax.tree_util.tree_map(lambda a: a[None], params)
            # every opt leaf was stacked with a leading client axis (incl. the
            # scalar step -> (K,)), so unconditionally restore rank
            opt_state = jax.tree_util.tree_map(lambda a: jnp.asarray(a)[None], opt_state)
            return params, opt_state, metrics

        spec_k = P("clients")
        # every stacked opt leaf carries the leading (K,) client axis
        opt_spec = jax.tree_util.tree_map(lambda a: spec_k, stacked_opt)
        param_spec = jax.tree_util.tree_map(lambda _: spec_k, stacked_params)
        return jax.shard_map(
            per_client,
            mesh=mesh,
            in_specs=(param_spec, opt_spec, spec_k, spec_k, P()),
            out_specs=(param_spec, opt_spec, P()),
        )(stacked_params, stacked_opt, xs, ys, x_t)

    return jax.jit(one_round)


def stack_clients(param_list):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *param_list)


def unstack_clients(stacked, k: int):
    return [jax.tree_util.tree_map(lambda a: a[i], stacked) for i in range(k)]
