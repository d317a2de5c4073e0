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

Check, after the window and untimed:

- set-up rounds: the reference (``chipbench.lib.refs.fed_round``) follows
  the three set-up rounds from its own initial model and its own batch
  draws, under the plans the rounds reported.  ``grad_gap``: each source's
  first gradient, read from the optimizer's state after one round, by the
  worst leaf; ``change_gap``: each entity's parameter change after three
  rounds, by the median leaf.  Why not the target's first gradient, nor the
  worst leaf's change: Adam's first step moves an entry by
  lr g / (|g| + eps), so where |g| is within a few eps, float32 round-off
  of g becomes a share of lr.  The target's first step takes messages from
  sources that have taken such a step, and the merges carry it on; the
  gradients and changes that follow depart by that round-off, most in
  small leaves.  The sources' first gradients come from the shared initial
  model alone; the target's gradient is compared in the window rounds.
- window rounds: the same trainer, through the same call, runs the first
  round after the window, then on to the next classifier round
  (t = 0 mod T_C) whose plan merges classifiers.  Each of the two is taken
  between snapshots of the trainer (parameters and Adam states); the
  reference replays it from the first snapshot, with the batches its own
  streams yield after the rounds already drawn, under the round's plan.
  ``round_grad_gap``: the gap of the norms of each entity's gradient (read
  from the two Adam states) by the worst leaf; ``round_grad_rel``: the norm
  of the gradient's difference by the worst leaf; ``round_change_gap``: the
  gap of the norms of the round's parameter change, merges included.
  The replays start from the program's own state, so they judge one
  round's step and not the state it starts from: a wrong initial model
  shows only in the set-up numbers, and a state altered in a window round
  that is not checked, and right in the checked ones, shows in neither.
- ``bytes_gap``: the byte ledger of every round against the count of
  messages the plans imply.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.lib import refs
from chipbench.lib.data import derived_seed, make_domains

CHECKED_ROUNDS = 3
CLF_TRIES = 8  # classifier rounds tried after the window for one whose plan merges
LEAF_FLOOR = 1e-3  # leaves whose reference gradient is under this share of the median leaf's
B1, ONE_MINUS_B1 = np.float32(0.9), np.float32(1 - 0.9)  # Adam's b1, as the program rounds it


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
    srcs, tgt = [doms[n] for n in tp["sources"]], doms[tp["target"]]
    tr = FedRFTCATrainer(srcs, tgt, cfg, proto)
    snaps = [_snapshot(tr)]
    plans = []
    t = int(tp["first_round"])
    for _ in range(CHECKED_ROUNDS):
        plans.append((t, tr.round(t)["plan"]))
        t += 1
        snaps.append(_snapshot(tr))
    return {"trainer": tr, "t": t, "first_round": int(tp["first_round"]), "plans": plans,
            "snaps": snaps, "srcs": srcs, "tgt": tgt, "cfg": cfg, "proto": proto}


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


class _Batches:
    """The reference's draws of one round's batches: per source a training
    and a message stream, and the target's two, each seeded as the protocol
    documents; every round draws once from each."""

    def __init__(self, state):
        proto, self.srcs, self.tgt = state["proto"], state["srcs"], state["tgt"]
        b, mb = proto.batch_size, proto.message_batch_size
        self.train = [_Stream(d.x.shape[1], min(b, d.x.shape[1]), proto.seed + i)
                      for i, d in enumerate(self.srcs)]
        self.msg = [_Stream(d.x.shape[1], min(mb, d.x.shape[1]), proto.seed + 500 + i)
                    for i, d in enumerate(self.srcs)]
        n_t = self.tgt.x.shape[1]
        self.t_train = _Stream(n_t, min(b, n_t), proto.seed + 777)
        self.t_msg = _Stream(n_t, min(mb, n_t), proto.seed + 999)

    def skip(self, rounds: int) -> None:
        for _ in range(rounds):
            for s in (*self.train, *self.msg, self.t_train, self.t_msg):
                s.next()

    def draw(self) -> dict:
        batch = {"xs": [], "ys": [], "x_msg": []}
        for d, tr_s, msg_s in zip(self.srcs, self.train, self.msg):
            idx = tr_s.next()
            batch["xs"].append(jnp.asarray(d.x[:, idx]))
            batch["ys"].append(jnp.asarray(d.y[idx]))
            batch["x_msg"].append(jnp.asarray(d.x[:, msg_s.next()]))
        batch["xt"] = jnp.asarray(self.tgt.x[:, self.t_train.next()])
        batch["xt_msg"] = jnp.asarray(self.tgt.x[:, self.t_msg.next()])
        return batch


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
    k = len(state["srcs"])
    batches = _Batches(state)
    init = refs.fed_init(jax.random.PRNGKey(proto.seed), cfg.input_dim, cfg.extractor_widths,
                         cfg.n_rff, cfg.m, cfg.n_classes)
    src = [init] * k
    src_opt = [refs.adam_init(init) for _ in range(k)]
    tgt_p, tgt_opt = init, refs.adam_init(init)
    first = [None] * (k + 1)
    for t, plan in state["plans"][:CHECKED_ROUNDS]:
        src, src_opt, tgt_p, tgt_opt, g_src, g_tgt = refs.fed_round_from(
            cfg, proto, src, src_opt, tgt_p, tgt_opt, batches.draw(), t, plan)
        for i, g in enumerate(g_src + [g_tgt]):
            if first[i] is None and g is not None:
                first[i] = g
    return first, src + [tgt_p], [init] * (k + 1)


def _program_entities(snap, k):
    """Per-entity parameter trees and Adam states of a trainer snapshot."""
    params = [jax.tree_util.tree_map(lambda a, i=i: a[i], snap["src"]["params"]) for i in range(k)]
    opts = [jax.tree_util.tree_map(lambda a, i=i: a[i], snap["src"]["opt"]) for i in range(k)]
    return params + [snap["tgt_params"]], opts + [snap["tgt_opt"]]


def numbers(state, ref) -> dict:
    """grad_gap (the sources, worst kept leaf) and change_gap (every entity,
    median kept leaf) of the program against the reference ``ref``; the
    worst entity of each."""
    k = len(state["srcs"])
    first_ref, final_ref, init_ref = ref
    init_p, _ = _program_entities(state["snaps"][0], k)
    program_final, _ = _program_entities(state["snaps"][CHECKED_ROUNDS], k)
    program_first = [None] * k
    for snap in state["snaps"][1:]:  # a source's first step: its Adam count reads 1
        _, opts = _program_entities(snap, k)
        for e in range(k):
            if program_first[e] is None and int(np.asarray(opts[e].step)) == 1:
                program_first[e] = [np.asarray(mu, np.float64) / np.float64(ONE_MINUS_B1)
                                    for mu in _leaves(opts[e].mu)]
    grad_gaps, change_gaps = [], []
    for e in range(k + 1):
        ch_ref = _change(final_ref[e], init_ref[e])
        ch_prog = _change(program_final[e], init_p[e])
        keep = _kept(first_ref[e], ch_ref)
        if e < k:
            g_ref = _leaves(first_ref[e])
            g_prog = program_first[e] or [np.zeros_like(g) for g in g_ref]
            grad_gaps.append(refs.leaf_gap(g_prog, g_ref, keep))
        gaps = refs.leaf_gaps(ch_prog, ch_ref, keep)
        change_gaps.append(float(np.median(gaps)) if gaps.size else 0.0)
    return {"grad_gap": max(grad_gaps), "change_gap": max(change_gaps)}


def _leaves(tree) -> list:
    return [np.asarray(a, np.float64) for a in jax.tree_util.tree_leaves(tree)]


def _change(after, before) -> list:
    return [a - b for a, b in zip(_leaves(after), _leaves(before))]


def _kept(grads, changes) -> np.ndarray:
    """The leaves compared: those whose reference gradient is at least
    LEAF_FLOOR of the median leaf's (the others move under Adam by round-off
    alone), and those with no gradient at all that the reference moves by a
    merge (with no gradient, every leaf the reference moves)."""
    moved = np.asarray([np.any(c != 0) for c in changes])
    if grads is None:
        return moved
    norms = np.asarray([np.linalg.norm(g) for g in _leaves(grads)])
    return (norms >= LEAF_FLOOR * np.median(norms)) | ((norms == 0) & moved)


def after_window(state) -> list[dict]:
    """Drive the window's trainer, untimed and through the window's own call,
    over the first round after the window and on to the next classifier round
    whose plan merges classifiers (the last one tried if none does); each of
    the two between snapshots."""
    tr, proto, t = state["trainer"], state["proto"], state["t"]

    def drive(keep: bool):
        nonlocal t
        before = _snapshot(tr) if keep else None
        plan = tr.round(t)["plan"]
        state["plans"].append((t, plan))
        t += 1
        return {"t": t - 1, "plan": plan, "before": before,
                "after": _snapshot(tr) if keep else None}

    checked = [drive(True)]
    for _ in range(CLF_TRIES):
        while t % proto.t_c:
            drive(False)
        clf = drive(True)
        if clf["plan"].c_clients:
            break
    checked.append(clf)
    state["t"] = t
    return checked


def replay(state, rec):
    """The reference's round ``rec["t"]`` from the program's snapshot before
    it: (parameters after it, gradient or None) per entity."""
    k = len(state["srcs"])
    batches = _Batches(state)
    batches.skip(rec["t"] - state["first_round"])
    params, opts = _program_entities(rec["before"], k)
    ref_opts = [{"step": int(np.asarray(o.step)), "mu": o.mu, "nu": o.nu} for o in opts]
    src, _, tgt, _, g_src, g_tgt = refs.fed_round_from(
        state["cfg"], state["proto"], params[:k], ref_opts[:k], params[k], ref_opts[k],
        batches.draw(), rec["t"], rec["plan"])
    return src + [tgt], g_src + [g_tgt]


def round_numbers(state, checked) -> dict:
    """round_grad_gap, round_grad_rel and round_change_gap of the checked
    window rounds against the reference's replays, by the worst kept leaf."""
    k = len(state["srcs"])
    grad_gaps, grad_rels, change_gaps = [0.0], [0.0], [0.0]
    for rec in checked:
        ref_params, ref_grads = replay(state, rec)
        p0, o0 = _program_entities(rec["before"], k)
        p1, o1 = _program_entities(rec["after"], k)
        for e in range(k + 1):
            stepped = int(np.asarray(o1[e].step)) == int(np.asarray(o0[e].step)) + 1
            ch_prog, ch_ref = _change(p1[e], p0[e]), _change(ref_params[e], p0[e])
            if ref_grads[e] is None:  # no step and no merge: the entity stays as it was
                moved = stepped or any(np.any(c != 0) for c in ch_prog)
                grad_gaps.append(float(moved))
                change_gaps.append(float(moved))
                continue
            keep = _kept(ref_grads[e], ch_ref)
            g_ref = _leaves(ref_grads[e])
            if stepped:  # mu' = b1 mu + (1 - b1) g, in the program's float32 constants
                g_prog = [(m1 - np.float64(B1) * m0) / np.float64(ONE_MINUS_B1)
                          for m1, m0 in zip(_leaves(o1[e].mu), _leaves(o0[e].mu))]
            else:
                g_prog = [np.zeros_like(g) for g in g_ref]
            grad_gaps.append(refs.leaf_gap(g_prog, g_ref, keep))
            grad_rels.append(refs.leaf_rel(g_prog, g_ref, keep))
            change_gaps.append(refs.leaf_gap(ch_prog, ch_ref, keep))
    return {"round_grad_gap": max(grad_gaps), "round_grad_rel": max(grad_rels),
            "round_change_gap": max(change_gaps)}


def check(state, record, ctx):
    tr = state["trainer"]
    checked = after_window(state)
    jax.block_until_ready(tr._array_state())
    got = int(tr.comm.bytes_total)
    expected = _expected_bytes(tr, state["cfg"], state["proto"], state["plans"])
    nums = numbers(state, reference_rounds(state))
    nums.update(round_numbers(state, checked))
    nums["bytes_gap"] = float(abs(got - expected))
    record["info"].update(bytes_total=got, bytes_expected=expected,
                          checked_rounds=[[r["t"], len(r["plan"].c_clients)] for r in checked],
                          **nums)
    return {name: (nums[name], float(lim)) for name, lim in ctx.cell["limits"].items()}
