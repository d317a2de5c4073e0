"""Driver of ``FedRFTCATrainer.round``: FedRF-TCA adaptation rounds back to back.

Traffic (the cell's ``traffic``): ``sources`` (one client per source
domain) and ``target`` domain names of the configuration, ``n_rff`` (N),
``m``, ``warmup_rounds`` and ``first_round``.  Everything else is the
protocol's default: batched engine, batch 64, message batch 256, T_C = 50,
drop setting I, float32 codec.

Set-up builds one trainer and drives it through its first three rounds,
numbered from ``first_round`` (T_C - 2, so that the third is a classifier
round); those rounds compile the round program and are the ones the
reference follows.  The window hands the same trainer on: rounds back to
back until ``seconds`` have passed, then ``block_until_ready`` on the
trainer's parameters.  ``rounds_per_s`` is rounds over the window's wall time.

Check: the reference (``chipbench.lib.refs.fed_round``) follows the three
set-up rounds from its own initial model and its own batch draws, under the
plans the rounds reported.  Compared: each client's and the target's first
gradient, read from the optimizer's state after one round, and the change of
the parameters after three rounds, both by the worst leaf; and the byte
ledger of every round against the count of messages the plans imply.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.lib import refs
from chipbench.lib.data import derived_seed, make_domains

CHECKED_ROUNDS = 3
LEAF_FLOOR = 1e-3  # leaves whose reference gradient is under this share of the median leaf's


def setup(ctx):
    from repro.data.domains import Domain
    from repro.federated import ClientConfig, FedRFTCATrainer, ProtocolConfig

    tp = ctx.params
    names = list(tp["sources"]) + [tp["target"]]
    made = make_domains(ctx.config, names, ctx.seed)
    doms = {n: Domain(n, np.asarray(x), np.asarray(y)) for n, (x, y) in made.items()}
    del made
    cfg = ClientConfig(input_dim=int(ctx.config["feature_dim"]),
                       n_classes=int(ctx.config["n_classes"]),
                       n_rff=int(tp["n_rff"]), m=int(tp["m"]))
    proto = ProtocolConfig(warmup_rounds=int(tp["warmup_rounds"]),
                           seed=derived_seed(ctx.seed, 3))
    tr = FedRFTCATrainer([doms[n] for n in tp["sources"]], doms[tp["target"]], cfg, proto)
    snaps = [_snapshot(tr)]
    plans = []
    t = int(tp["first_round"])
    for _ in range(CHECKED_ROUNDS):
        plans.append((t, tr.round(t)["plan"]))
        t += 1
        snaps.append(_snapshot(tr))
    return {"trainer": tr, "t": t, "plans": plans, "snaps": snaps, "doms": doms,
            "cfg": cfg, "proto": proto}


def _snapshot(tr):
    """Host copy of the trainer's arrays (parameters and optimizer states)."""
    return jax.device_get(tr._array_state())


def window(state, seconds, ctx):
    tr, t = state["trainer"], state["t"]
    n = 0
    t0 = time.perf_counter()
    while True:
        with ctx.annotate("chipbench.round"):
            state["plans"].append((t, tr.round(t)["plan"]))
        t += 1
        n += 1
        if time.perf_counter() - t0 >= seconds:
            break
    with ctx.annotate("chipbench.wait"):
        jax.block_until_ready(tr._array_state())
    wall = time.perf_counter() - t0
    state["t"] = t
    return {
        "metrics": {"rounds_per_s": (n / wall, "rounds/s")},
        "attempted": n, "failed": 0,
        "record": {"rounds": n, "wall_s": wall},
        "info": {"rounds": n, "window_s": wall},
    }


class _Stream:
    """A client's minibatch stream: one permutation per epoch drawn from
    ``default_rng(seed)``, consecutive slices while a whole batch fits."""

    def __init__(self, n: int, b: int, seed: int):
        self.n, self.b, self.rng = n, b, np.random.default_rng(seed)
        self.perm, self.i = self.rng.permutation(n), 0

    def next(self):
        if self.i + self.b > self.n:
            self.perm, self.i = self.rng.permutation(self.n), 0
        idx = self.perm[self.i:self.i + self.b]
        self.i += self.b
        return idx


def _expected_bytes(tr, cfg, proto, plans) -> int:
    f32 = np.dtype(np.float32)
    size = tr.transport.payload_sizes({
        "moments": {"msg": ((2 * cfg.n_rff,), f32)},
        "w_rf": {"w_rf": ((2 * cfg.n_rff, cfg.m), f32)},
        "classifier": {"w": ((cfg.m, cfg.n_classes), f32), "b": ((cfg.n_classes,), f32)},
    })
    total = 0
    for t, plan in plans:  # one target downlink + one uplink per delivering client
        if plan.msg_clients:
            total += (1 + len(plan.msg_clients)) * size["moments"]
        if plan.w_clients:
            total += (1 + len(plan.w_clients)) * size["w_rf"]
        if t % proto.t_c == 0 and plan.c_clients:
            total += len(plan.c_clients) * size["classifier"]
    return total


def reference_rounds(state):
    """The reference's three rounds from its own initial model and batches:
    (first gradient, parameters after three rounds, initial parameters) per
    entity, entities being the sources in order, then the target."""
    cfg, proto = state["cfg"], state["proto"]
    srcs, tgt = state["trainer"].sources, state["trainer"].target
    k = len(srcs)
    b, mb = proto.batch_size, proto.message_batch_size
    train = [_Stream(d.x.shape[1], min(b, d.x.shape[1]), proto.seed + i)
             for i, d in enumerate(srcs)]
    msg = [_Stream(d.x.shape[1], min(mb, d.x.shape[1]), proto.seed + 500 + i)
           for i, d in enumerate(srcs)]
    t_train = _Stream(tgt.x.shape[1], min(b, tgt.x.shape[1]), proto.seed + 777)
    t_msg = _Stream(tgt.x.shape[1], min(mb, tgt.x.shape[1]), proto.seed + 999)
    omega = jax.random.normal(jax.random.PRNGKey(cfg.rff_seed),
                              (cfg.n_rff, cfg.extractor_widths[-1])) / cfg.rff_sigma
    init = refs.fed_init(jax.random.PRNGKey(proto.seed), cfg.input_dim, cfg.extractor_widths,
                         cfg.n_rff, cfg.m, cfg.n_classes)
    src = [init] * k
    src_opt = [refs.adam_init(init) for _ in range(k)]
    tgt_p, tgt_opt = init, refs.adam_init(init)
    first = [None] * (k + 1)
    for t, plan in state["plans"][:CHECKED_ROUNDS]:
        batch = {"xs": [], "ys": [], "x_msg": []}
        for i, d in enumerate(srcs):
            idx = train[i].next()
            batch["xs"].append(jnp.asarray(d.x[:, idx]))
            batch["ys"].append(jnp.asarray(d.y[idx]))
            batch["x_msg"].append(jnp.asarray(d.x[:, msg[i].next()]))
        batch["xt"] = jnp.asarray(tgt.x[:, t_train.next()])
        batch["xt_msg"] = jnp.asarray(tgt.x[:, t_msg.next()])
        src, src_opt, tgt_p, tgt_opt, g_src, g_tgt = refs.fed_round(
            src, src_opt, tgt_p, tgt_opt, batch,
            (plan.msg_clients, plan.w_clients, plan.c_clients),
            omega=omega, lr=proto.lr, lam=cfg.lambda_mmd, n_classes=cfg.n_classes,
            classifier_round=(t % proto.t_c == 0))
        for i, g in enumerate(g_src + [g_tgt]):
            if first[i] is None and g is not None:
                first[i] = g
    return first, src + [tgt_p], [init] * (k + 1)


def _program_entities(snap, k):
    """Per-entity parameter trees and Adam states of a trainer snapshot."""
    params = [jax.tree_util.tree_map(lambda a, i=i: a[i], snap["src"]["params"]) for i in range(k)]
    opts = [jax.tree_util.tree_map(lambda a, i=i: a[i], snap["src"]["opt"]) for i in range(k)]
    return params + [snap["tgt_params"]], opts + [snap["tgt_opt"]]


def numbers(state, ref, b1=0.9):
    """grad_gap and change_gap of the program against the reference ``ref``,
    by the worst kept leaf."""
    k = len(state["trainer"].sources)
    first_ref, final_ref, init_ref = ref
    init_p, _ = _program_entities(state["snaps"][0], k)
    program_final, _ = _program_entities(state["snaps"][CHECKED_ROUNDS], k)
    program_first = [None] * (k + 1)
    for snap in state["snaps"][1:]:  # an entity's first step: its Adam count reads 1
        _, opts = _program_entities(snap, k)
        for e in range(k + 1):
            if program_first[e] is None and int(np.asarray(opts[e].step)) == 1:
                program_first[e] = jax.tree_util.tree_map(lambda mu: mu / (1 - b1), opts[e].mu)
    grad_gaps, change_gaps = [], []
    for e in range(k + 1):
        g_ref = first_ref[e]
        ch_ref = [f - i for f, i in zip(jax.tree_util.tree_leaves(final_ref[e]),
                                        jax.tree_util.tree_leaves(init_ref[e]))]
        ch_prog = [f - i for f, i in zip(jax.tree_util.tree_leaves(program_final[e]),
                                         jax.tree_util.tree_leaves(init_p[e]))]
        if g_ref is None:  # never stepped in the reference: it moves by merges alone
            keep = np.asarray([float(jnp.linalg.norm(c)) > 0 for c in ch_ref])
        else:
            g_leaves = jax.tree_util.tree_leaves(g_ref)
            norms = np.asarray([float(jnp.linalg.norm(g)) for g in g_leaves])
            keep = norms >= LEAF_FLOOR * np.median(norms)
            g_prog = program_first[e]
            prog_leaves = (jax.tree_util.tree_leaves(g_prog) if g_prog is not None
                           else [jnp.zeros_like(g) for g in g_leaves])
            grad_gaps.append(refs.leaf_gap(prog_leaves, g_leaves, keep))
        change_gaps.append(refs.leaf_gap(ch_prog, ch_ref, keep))
    return {"grad_gap": max(grad_gaps), "change_gap": max(change_gaps)}


def check(state, record, ctx):
    tr = state["trainer"]
    got = int(tr.comm.bytes_total)
    expected = _expected_bytes(tr, state["cfg"], state["proto"], state["plans"])
    nums = numbers(state, reference_rounds(state))
    nums["bytes_gap"] = float(abs(got - expected))
    record["info"].update(bytes_total=got, bytes_expected=expected, **nums)
    return {name: (nums[name], float(lim)) for name, lim in ctx.cell["limits"].items()}

