"""Fleet-scale hierarchical federation: topology, two-tier-vs-flat exactness,
chunked/sharded client execution, the segment-reduce kernel, per-edge async
buffers, and server-ingress accounting."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm.netsim import LinkModel, LinkScenario, TraceScenario
from repro.data import make_domains
from repro.federated import ClientConfig, FedRFTCATrainer, ProtocolConfig
from repro.federated.aggregation import edge_weighted_sums
from repro.federated.network import RoundPlan
from repro.fedsim import AsyncConfig, AsyncScheduler
from repro.fleet import (
    Topology,
    chunked_vmap,
    client_mesh,
    edge_moment_merge,
    edge_param_merge,
    server_combine,
    sharded_client_map,
    working_set_proxy,
)
from repro.kernels import ops, ref


@pytest.fixture(scope="module")
def fleet_setup():
    """Four source clients (groupable 2x2) + one target."""
    doms = make_domains(5, 120, shift=0.5, seed=1, dim=8, n_classes=3)
    cfg = ClientConfig(input_dim=8, n_classes=3, n_rff=32, m=8, extractor_widths=(16, 8))
    return doms[:4], doms[4], cfg


def _leaf_err(a, b):
    return max(
        float(np.abs(np.asarray(x) - np.asarray(y)).max())
        for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b))
    )


def _full_trace(k, rounds):
    ids = list(range(k))
    return TraceScenario([RoundPlan(ids, ids, ids)] * rounds, cycle=True)


# ---- topology ---------------------------------------------------------------


def test_topology_constructors_and_helpers():
    t = Topology.of_groups([[0, 2], [1, 3]])
    assert t.n_clients == 4 and t.n_edges == 2
    assert t.assignment == (0, 1, 0, 1)
    assert t.members(0) == [0, 2] and t.edge_of(3) == 1
    assert t.edges_of([2]) == [0] and t.edges_of([0, 1, 3]) == [0, 1]
    m = t.edge_matrix()
    assert m.shape == (2, 4) and m.sum() == 4.0
    assert (m[0] == [1, 0, 1, 0]).all()
    u = Topology.uniform(10, 3)
    assert u.n_edges == 3
    assert sorted(len(u.members(e)) for e in range(3)) == [3, 3, 4]
    assert Topology.singleton(3).assignment == (0, 1, 2)
    assert Topology.star(3).assignment == (0, 0, 0)


def test_topology_validation():
    with pytest.raises(ValueError, match="contiguous"):
        Topology((0, 2))  # edge 1 is empty
    with pytest.raises(ValueError, match="contiguous"):
        Topology((1, 2))
    with pytest.raises(ValueError, match="at least one"):
        Topology(())
    with pytest.raises(ValueError, match="assigned to edges"):
        Topology.of_groups([[0, 1], [1]])
    with pytest.raises(ValueError, match="empty"):
        Topology.of_groups([[0, 1], []])
    with pytest.raises(ValueError, match="n_edges"):
        Topology.uniform(4, 5)


# ---- segment-reduce kernel vs twin -----------------------------------------


@pytest.mark.parametrize(
    "k,d,e", [(8, 16, 3), (128, 64, 4), (130, 70, 5), (1, 5, 1), (4, 4100, 2)]
)
def test_segment_reduce_kernel_matches_ref(k, d, e):
    rng = np.random.default_rng(k * 7 + d)
    vals = jnp.asarray(rng.normal(size=(k, d)), jnp.float32)
    seg = jnp.asarray(rng.integers(0, e, size=(k,)), jnp.int32)
    w = jnp.asarray(rng.random(size=(k,)), jnp.float32)
    out = ops.segment_reduce(vals, seg, w, n_segments=e, interpret=True)
    want = ref.segment_reduce_ref(vals, seg, w, e)
    assert out.shape == (e, d)
    assert float(jnp.abs(out - want).max()) < 1e-5
    # zero-weight rows contribute exact zeros (the padding invariant)
    out0 = ops.segment_reduce(vals, seg, jnp.zeros((k,)), n_segments=e, interpret=True)
    assert float(jnp.abs(out0).max()) == 0.0


def test_segment_reduce_matches_segment_sum_oracle():
    rng = np.random.default_rng(3)
    vals = jnp.asarray(rng.normal(size=(40, 12)), jnp.float32)
    seg = jnp.asarray(rng.integers(0, 6, size=(40,)), jnp.int32)
    w = jnp.asarray(rng.random(size=(40,)), jnp.float32)
    out = ops.segment_reduce(vals, seg, w, n_segments=6, interpret=True)
    oracle = jax.ops.segment_sum(w[:, None] * vals, seg, num_segments=6)
    assert float(jnp.abs(out - oracle).max()) < 1e-5


# ---- hierarchical merge exactness (unit level) ------------------------------


def test_edge_param_merge_matches_flat_any_topology():
    """Associativity: sum of per-edge partial sums == the flat weighted sum,
    for arbitrary groupings and non-0/1 (staleness) weights."""
    rng = np.random.default_rng(0)
    vals = jnp.asarray(rng.normal(size=(7, 6, 4)), jnp.float32)
    w = jnp.asarray(rng.random(size=(7,)), jnp.float32)
    flat = jnp.einsum("k,kij->ij", w, vals)
    for topo in (Topology.uniform(7, 3), Topology.singleton(7), Topology.star(7)):
        seg = jnp.asarray(topo.segment_ids)
        sums, mass = edge_param_merge(vals, w, seg, topo.n_edges)
        s, m = server_combine(sums, mass)
        assert float(jnp.abs(s - flat).max()) < 1e-5
        assert abs(float(m) - float(jnp.sum(w))) < 1e-5


def test_edge_moment_merge_pooling_semantics():
    """A singleton participant's pooled row is its message bit-for-bit; a
    multi-member edge's pooled row is the mass-weighted member mean (the
    Sigma-ell message of the pooled population)."""
    rng = np.random.default_rng(1)
    msgs = jnp.asarray(rng.normal(size=(4, 10)), jnp.float32)
    topo = Topology.of_groups([[0, 1], [2, 3]])
    seg = jnp.asarray(topo.segment_ids)
    # one participant per edge, unit weight: bitwise pass-through
    w = jnp.asarray([1.0, 0.0, 0.0, 1.0])
    pooled, mass = edge_moment_merge(msgs, w, seg, 2)
    assert (np.asarray(pooled[0]) == np.asarray(msgs[0])).all()
    assert (np.asarray(pooled[1]) == np.asarray(msgs[3])).all()
    assert np.allclose(np.asarray(mass), [1.0, 1.0])
    # full participation: pooled = member mean, mass = member count
    w = jnp.ones((4,))
    pooled, mass = edge_moment_merge(msgs, w, seg, 2)
    assert np.allclose(np.asarray(pooled[0]), np.asarray((msgs[0] + msgs[1]) / 2), atol=1e-6)
    assert np.allclose(np.asarray(mass), [2.0, 2.0])
    # empty edge: zero mass, finite pooled row
    w = jnp.asarray([1.0, 1.0, 0.0, 0.0])
    pooled, mass = edge_moment_merge(msgs, w, seg, 2)
    assert float(mass[1]) == 0.0 and np.isfinite(np.asarray(pooled)).all()


def test_edge_weighted_sums_jit_traceable():
    f = jax.jit(lambda v, s, w: edge_weighted_sums(v, s, w, 3))
    out = f(jnp.ones((5, 4)), jnp.asarray([0, 1, 2, 0, 1]), jnp.ones((5,)))
    assert np.allclose(np.asarray(out), [[2, 2, 2, 2], [2, 2, 2, 2], [1, 1, 1, 1]])


# ---- two-tier vs flat trainer trajectories ---------------------------------


def test_two_tier_singleton_matches_flat_engine(fleet_setup):
    """The acceptance gate: E=K identity-codec two-tier routes every merge
    through the hierarchy (segment sums, pooled moments, masses) and must
    reproduce the flat batched engine <= 1e-6."""
    sources, target, cfg = fleet_setup
    k, rounds = 4, 4
    kw = dict(
        n_rounds=rounds, t_c=2, local_steps=2, warmup_rounds=1, batch_size=32,
        seed=0, scenario=_full_trace(k, rounds),
    )
    tr_flat = FedRFTCATrainer(sources, target, cfg, ProtocolConfig(**kw))
    tr_flat.train()
    tr_two = FedRFTCATrainer(
        sources, target, cfg, ProtocolConfig(topology=Topology.singleton(k), **kw)
    )
    tr_two.train()
    assert _leaf_err(tr_flat.tgt_params, tr_two.tgt_params) <= 1e-6
    assert _leaf_err(tr_flat._src_stack, tr_two._src_stack) <= 1e-6
    # tier-1 accounting identical; the ingress leg is E=K uplinks + masses
    assert tr_flat.comm.total == tr_two.comm.total


def test_two_tier_grouped_matches_flat_one_delivery_per_edge(fleet_setup):
    """Grouped edges, one moments-participant per edge each round, full W/C
    participation: the pooled moment degenerates to the single member's
    message while the W/classifier merges exercise real grouped partial sums
    — the trajectory must still match the flat engine <= 1e-6."""
    sources, target, cfg = fleet_setup
    k, rounds = 4, 4
    ids = list(range(k))
    plans = [RoundPlan([0, 2], ids, ids), RoundPlan([1, 3], ids, ids)] * (rounds // 2)
    kw = dict(
        n_rounds=rounds, t_c=2, local_steps=2, warmup_rounds=1, batch_size=32,
        seed=0, scenario=TraceScenario(plans, cycle=True),
    )
    tr_flat = FedRFTCATrainer(sources, target, cfg, ProtocolConfig(**kw))
    tr_flat.train()
    topo = Topology.of_groups([[0, 1], [2, 3]])
    tr_two = FedRFTCATrainer(sources, target, cfg, ProtocolConfig(topology=topo, **kw))
    tr_two.train()
    assert _leaf_err(tr_flat.tgt_params, tr_two.tgt_params) <= 1e-6
    assert _leaf_err(tr_flat._src_stack, tr_two._src_stack) <= 1e-6


def test_two_tier_full_participation_trains(fleet_setup):
    """Multi-member pooled moments: a different (union-population) but valid
    estimator — training must stay finite and evaluable, and the server
    ingress must count one merged uplink per edge, not per client."""
    sources, target, cfg = fleet_setup
    k, rounds = 4, 3
    topo = Topology.of_groups([[0, 1], [2, 3]])
    kw = dict(
        n_rounds=rounds, t_c=2, warmup_rounds=1, batch_size=32, seed=0,
        scenario=_full_trace(k, rounds),
    )
    tr = FedRFTCATrainer(sources, target, cfg, ProtocolConfig(topology=topo, **kw))
    tr.train()
    for leaf in jax.tree_util.tree_leaves(tr.tgt_params):
        assert np.isfinite(np.asarray(leaf)).all()
    assert 0.0 <= tr.evaluate() <= 1.0
    # 2 active edges x 3 rounds per kind (classifier on t in {1, 2} ... t%2==0)
    assert tr.edge_transport.log.messages_by_kind["moments"] == 2 * rounds
    assert tr.edge_transport.log.messages_by_kind["w_rf"] == 2 * rounds


def test_server_ingress_two_tier_below_flat(fleet_setup):
    """At K=8 with 2 edges the ingress bytes must already shrink for the
    parameter payloads (the bench gates the K >= 64 full sweep)."""
    doms = make_domains(9, 60, shift=0.5, seed=2, dim=8, n_classes=3)
    sources, target = doms[:8], doms[8]
    cfg = ClientConfig(input_dim=8, n_classes=3, n_rff=32, m=8, extractor_widths=(16, 8))
    rounds = 2
    kw = dict(
        n_rounds=rounds, t_c=2, warmup_rounds=0, batch_size=16, seed=0,
        scenario=_full_trace(8, rounds),
    )
    tr_flat = FedRFTCATrainer(sources, target, cfg, ProtocolConfig(**kw))
    tr_flat.train()
    tr_two = FedRFTCATrainer(
        sources, target, cfg, ProtocolConfig(topology=Topology.uniform(8, 2), **kw)
    )
    tr_two.train()
    assert sum(tr_two.ingress_bytes.values()) < sum(tr_flat.ingress_bytes.values())
    assert tr_two.ingress_bytes["w_rf"] < tr_flat.ingress_bytes["w_rf"]
    assert tr_two.ingress_bytes["moments"] < tr_flat.ingress_bytes["moments"]


def test_two_tier_edge_codec_distorts(fleet_setup):
    """A lossy tier-2 codec must change the trajectory (the edge uplink is
    really distorted) while identity tier-2 stays on the exact path."""
    sources, target, cfg = fleet_setup
    k, rounds = 4, 3
    topo = Topology.of_groups([[0, 1], [2, 3]])
    kw = dict(
        n_rounds=rounds, t_c=2, warmup_rounds=1, batch_size=32, seed=0,
        scenario=_full_trace(k, rounds), transport="wire",
    )
    tr_id = FedRFTCATrainer(
        sources, target, cfg, ProtocolConfig(topology=topo, **kw)
    )
    tr_id.train()
    tr_q = FedRFTCATrainer(
        sources, target, cfg,
        ProtocolConfig(topology=topo, edge_codec="qint8", **kw),
    )
    tr_q.train()
    assert _leaf_err(tr_id.tgt_params, tr_q.tgt_params) > 0.0
    for leaf in jax.tree_util.tree_leaves(tr_q.tgt_params):
        assert np.isfinite(np.asarray(leaf)).all()
    # the tier-2 log prices the edge uplinks at the edge codec: cheaper
    assert (
        tr_q.edge_transport.log.bytes_by_kind["w_rf"]
        < tr_id.edge_transport.log.bytes_by_kind["w_rf"]
    )


def test_fleet_protocol_validation(fleet_setup):
    sources, target, cfg = fleet_setup
    with pytest.raises(ValueError, match="batched engine"):
        FedRFTCATrainer(
            sources, target, cfg,
            ProtocolConfig(engine="serial", topology=Topology.singleton(4)),
        )
    with pytest.raises(ValueError, match="topology covers"):
        FedRFTCATrainer(
            sources, target, cfg, ProtocolConfig(topology=Topology.singleton(3))
        )
    with pytest.raises(ValueError, match="seed_replay"):
        FedRFTCATrainer(
            sources, target, cfg,
            ProtocolConfig(topology=Topology.singleton(4), edge_codec="seed_replay"),
        )


# ---- chunked + sharded client execution ------------------------------------


def test_chunked_vmap_bitwise_and_padding():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(5, 6, 4)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(5, 4, 3)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(4,)), jnp.float32)

    def f(xi, wi, ci):
        z = jnp.tanh(xi @ wi)
        return z.sum(-1) + (xi @ ci).sum(), z

    want = jax.vmap(f, (0, 0, None))(x, w, c)
    for chunk in (2, 3, 5, 9, None):  # 5 % 2 and 5 % 3 != 0: padding path
        got = chunked_vmap(f, (0, 0, None), chunk=chunk)(x, w, c)
        for a, b in zip(jax.tree_util.tree_leaves(want), jax.tree_util.tree_leaves(got)):
            assert (np.asarray(a) == np.asarray(b)).all()
    with pytest.raises(ValueError, match="chunk must be"):
        chunked_vmap(f, (0, 0, None), chunk=0)
    with pytest.raises(ValueError, match="at least one mapped"):
        chunked_vmap(lambda a: a, (None,), chunk=2)(c)


def test_sharded_client_map_mocked_mesh_bitwise():
    """shard_map over a clients mesh (mocked: 1 device) + chunked scan must
    equal the plain vmap bit-for-bit."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(8, 6, 4)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(8, 4, 3)), jnp.float32)

    def f(xi, wi):
        return jnp.tanh(xi @ wi).sum(-1)

    mesh = client_mesh(1)
    want = jax.vmap(f, (0, 0))(x, w)
    got = jax.jit(sharded_client_map(mesh, f, (0, 0), chunk=4))(x, w)
    assert (np.asarray(want) == np.asarray(got)).all()


def test_client_chunk_trainer_matches_unchunked(fleet_setup):
    """The chunked local-step scan through the full trainer: <= 1e-6 of the
    unchunked trajectory (bitwise at the local-step granularity; whole-round
    XLA fusion differs by ulps once the surrounding graph changes)."""
    sources, target, cfg = fleet_setup
    k, rounds = 4, 3
    kw = dict(
        n_rounds=rounds, t_c=2, warmup_rounds=1, batch_size=32, seed=0,
        scenario=_full_trace(k, rounds),
    )
    tr_a = FedRFTCATrainer(sources, target, cfg, ProtocolConfig(**kw))
    tr_a.train()
    tr_b = FedRFTCATrainer(sources, target, cfg, ProtocolConfig(client_chunk=2, **kw))
    tr_b.train()
    assert _leaf_err(tr_a.tgt_params, tr_b.tgt_params) <= 1e-6
    assert _leaf_err(tr_a._src_stack, tr_b._src_stack) <= 1e-6


def test_working_set_proxy_bounded_by_chunk():
    rng = np.random.default_rng(2)
    k, b, p, h = 32, 16, 12, 10
    x = jnp.asarray(rng.normal(size=(k, b, p)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(k, p, h)), jnp.float32)

    def f(xi, wi):
        return jnp.tanh(xi @ wi).sum(-1)

    full = working_set_proxy(lambda *a: jax.vmap(f, (0, 0))(*a), x, w)
    prev = 0
    for chunk in (2, 4, 8):
        ws = working_set_proxy(chunked_vmap(f, (0, 0), chunk=chunk), x, w)
        assert ws == full * chunk // k  # exactly linear in the chunk
        assert ws > prev
        prev = ws


# ---- async runtime: per-edge buffers + backhaul ----------------------------


def test_async_singleton_topology_matches_flat_buffer_one(fleet_setup):
    """Per-edge buffers degenerate correctly: E=K edges with buffer_size=1
    flush exactly like the flat scheduler with buffer_size=1."""
    sources, target, cfg = fleet_setup
    k = 4
    kw = dict(n_rounds=0, t_c=3, warmup_rounds=1, batch_size=32, seed=0)
    links = LinkScenario(links=[LinkModel(latency_s=float(i + 1)) for i in range(k)])

    def run(topology):
        tr = FedRFTCATrainer(
            sources, target, cfg, ProtocolConfig(topology=topology, **kw)
        )
        sched = AsyncScheduler(
            tr, AsyncConfig(buffer_size=1, staleness="constant"),
            links=LinkScenario(links=list(links.links)),
        )
        hist = sched.run(6)
        return tr, hist

    tr_flat, h_flat = run(None)
    tr_two, h_two = run(Topology.singleton(k))
    assert [h["members"] for h in h_flat] == [h["members"] for h in h_two]
    assert [h["t"] for h in h_flat] == [h["t"] for h in h_two]
    assert _leaf_err(tr_flat.tgt_params, tr_two.tgt_params) <= 1e-6
    assert _leaf_err(tr_flat._src_stack, tr_two._src_stack) <= 1e-6


def test_async_edges_flush_their_own_buffers(fleet_setup):
    """Grouped topology: every flush consumes members of exactly one edge."""
    sources, target, cfg = fleet_setup
    topo = Topology.of_groups([[0, 1], [2, 3]])
    kw = dict(n_rounds=0, t_c=3, warmup_rounds=1, batch_size=32, seed=0)
    tr = FedRFTCATrainer(sources, target, cfg, ProtocolConfig(topology=topo, **kw))
    links = LinkScenario(
        links=[LinkModel(latency_s=0.5 + 0.3 * i, jitter_s=0.1) for i in range(4)]
    )
    sched = AsyncScheduler(tr, AsyncConfig(buffer_size=2), links=links)
    hist = sched.run(6)
    assert len(hist) == 6
    for h in hist:
        edges = {topo.edge_of(c) for c in h["members"]}
        assert len(edges) == 1  # one edge's buffer per flush
    for leaf in jax.tree_util.tree_leaves(tr.tgt_params):
        assert np.isfinite(np.asarray(leaf)).all()


def test_async_edge_links_delay_flushes(fleet_setup):
    """A slow backhaul defers the server flush past the edge-buffer fill time
    and shows up in the flush timestamps."""
    sources, target, cfg = fleet_setup
    topo = Topology.of_groups([[0, 1], [2, 3]])
    kw = dict(n_rounds=0, t_c=3, warmup_rounds=1, batch_size=32, seed=0)

    def run(edge_links):
        tr = FedRFTCATrainer(sources, target, cfg, ProtocolConfig(topology=topo, **kw))
        sched = AsyncScheduler(
            tr, AsyncConfig(buffer_size=2),
            links=LinkScenario(links=[LinkModel(latency_s=1.0) for _ in range(4)]),
            edge_links=edge_links,
        )
        return sched.run(4)

    h_fast = run(None)
    h_slow = run(LinkScenario(links=[LinkModel(latency_s=7.0) for _ in range(2)]))
    # every flush waits out at least one 7 s backhaul crossing (and later
    # flushes compound it, since members redispatch only after the flush)
    assert all(hs["t"] >= hf["t"] + 7.0 for hs, hf in zip(h_slow, h_fast))
    assert h_slow[0]["t"] == h_fast[0]["t"] + 7.0
    assert [h["members"] for h in h_slow] == [h["members"] for h in h_fast]


def test_async_fleet_validation(fleet_setup):
    sources, target, cfg = fleet_setup
    kw = dict(n_rounds=0, warmup_rounds=0, batch_size=32, seed=0)
    topo = Topology.of_groups([[0, 1, 2], [3]])
    tr = FedRFTCATrainer(sources, target, cfg, ProtocolConfig(topology=topo, **kw))
    with pytest.raises(ValueError, match="smallest edge"):
        AsyncScheduler(tr, AsyncConfig(buffer_size=2))  # edge 1 has one member
    tr_flat = FedRFTCATrainer(sources, target, cfg, ProtocolConfig(**kw))
    with pytest.raises(ValueError, match="edge_links need"):
        AsyncScheduler(
            tr_flat, AsyncConfig(buffer_size=1),
            edge_links=LinkScenario(links=[LinkModel()]),
        )
    with pytest.raises(ValueError, match="edge links for"):
        AsyncScheduler(
            tr, AsyncConfig(buffer_size=1),
            edge_links=LinkScenario(links=[LinkModel()]),
        )
    with pytest.raises(ValueError, match="eval_interval"):
        AsyncScheduler(tr_flat, AsyncConfig(buffer_size=1, eval_interval=0.0))


def test_async_eval_interval_ticks(fleet_setup):
    """Time-triggered eval events: dense accuracy-vs-virtual-time rows at the
    configured cadence, interleaved with (not replacing) the flush rows."""
    sources, target, cfg = fleet_setup
    kw = dict(n_rounds=0, t_c=3, warmup_rounds=1, batch_size=32, seed=0)
    tr = FedRFTCATrainer(sources, target, cfg, ProtocolConfig(**kw))
    links = LinkScenario(links=[LinkModel(latency_s=float(i + 1)) for i in range(4)])
    sched = AsyncScheduler(
        tr, AsyncConfig(buffer_size=2, eval_interval=1.5), links=links
    )
    hist = sched.run(5)
    evals = [h for h in hist if "eval" in h]
    flushes = [h for h in hist if "flush" in h]
    assert len(flushes) == 5
    assert len(evals) >= 2
    assert all(0.0 <= h["acc"] <= 1.0 for h in evals)
    times = [h["t"] for h in evals]
    assert times == sorted(times)
    assert all(abs(t - 1.5 * h["eval"]) < 1e-9 for t, h in zip(times, evals))
    # history rows overall are time-ordered
    all_t = [h["t"] for h in hist]
    assert all_t == sorted(all_t)
