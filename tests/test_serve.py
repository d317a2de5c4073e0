"""repro.serve: model store, batching dispatcher, live admission, load gen.

Coverage required by the subsystem's contracts:
- store: LRU eviction at capacity (hit/miss/eviction counters), version-
  tagged invalidation (bump drops older versions; pinned readers miss);
- dispatcher: bucketed padding masks leave pad columns as exact zeros and
  per-request slices match the direct transform; one jit trace per bucket
  rung (sentinel-gated);
- admission: refit-free (no cached version changes), the admitted client's
  aligner agrees with a from-scratch fit to <= 1e-3, and the moment merge
  tracks the true u statistic; the wire really carries CRC frames;
- memoized fused omega: repeated serving regenerates draw-0 exactly once;
- load generator: deterministic Poisson schedule, open-loop completion;
- telemetry off vs on: served arrays bitwise identical (PR-7 contract).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm.transport import WireTransport, resolve_codecs
from repro.core.rf_tca import (
    RFTCAState,
    fused_omega_cache_info,
    rf_tca_fit,
    rf_tca_transform,
)
from repro.obs import (
    DriftMonitor,
    MetricsRegistry,
    RequestTracer,
    Slo,
    SloEngine,
    Tracer,
    count_request_trees,
    sentinel,
    use_registry,
    use_tracer,
)
from repro.serve import (
    AdmissionGateway,
    AlignerServer,
    ModelStore,
    MomentStats,
    Request,
    StoreEntry,
    poisson_arrivals,
    run_open_loop,
    synth_requests,
)

DIM = 8
FIT_KW = dict(n_features=16, m=4, seed=0)


def _domain(seed, n=90, shift=0.7):
    rng = np.random.default_rng(seed)
    xs = rng.standard_normal((DIM, n)).astype(np.float32)
    xt = (rng.standard_normal((DIM, n - 7)) + shift).astype(np.float32)
    return xs, xt


def _server(capacity=4, **kw):
    return AlignerServer(capacity=capacity, min_bucket=4, max_bucket=32, **kw)


def _entry(seed=0):
    xs, xt = _domain(seed)
    return StoreEntry(state=rf_tca_fit(jnp.asarray(xs), jnp.asarray(xt), **FIT_KW))


# ---- model store ------------------------------------------------------------


def test_store_lru_eviction_at_capacity():
    store = ModelStore(capacity=2)
    for i in range(3):
        store.put(("s", f"t{i}"), _entry(i))
    assert len(store) == 2
    assert store.evictions == 1
    # pair 0 was least recently used -> evicted; its latest pointer is gone
    assert store.get(("s", "t0")) is None
    assert store.latest_version(("s", "t0")) is None
    assert store.get(("s", "t1")) is not None and store.get(("s", "t2")) is not None
    assert store.hits == 2 and store.misses == 1
    assert 0.0 <= store.hit_rate <= 1.0
    # a get refreshes recency: t1 survives the next insertion, t2 does not
    store.get(("s", "t1"))
    store.put(("s", "t3"), _entry(3))
    assert store.get(("s", "t1")) is not None
    assert store.get(("s", "t2")) is None


def test_store_version_invalidation():
    store = ModelStore(capacity=4)
    v0 = store.put(("a", "b"), _entry(0))
    assert v0 == 0
    # plain put overwrites the latest version (no invalidation)
    assert store.put(("a", "b"), _entry(1)) == 0
    assert store.invalidations == 0
    # bump stores latest+1 and drops the older version
    v1 = store.put(("a", "b"), _entry(2), bump=True)
    assert v1 == 1 and store.latest_version(("a", "b")) == 1
    assert store.invalidations == 1 and len(store) == 1
    # a reader pinned to the invalidated version misses, never goes stale
    assert store.get(("a", "b"), version=0) is None
    assert store.get(("a", "b"), version=1) is not None
    assert store.get(("a", "b")) is not None  # None -> newest
    # codecs are independent key spaces
    assert store.put(("a", "b"), _entry(3), codec="qint8") == 0
    assert store.latest_version(("a", "b"), "qint8") == 0
    assert store.latest_version(("a", "b")) == 1
    with pytest.raises(ValueError, match="capacity"):
        ModelStore(capacity=0)


# ---- batching dispatcher ----------------------------------------------------


def test_dispatcher_buckets_and_masked_padding():
    srv = _server()
    xs, xt = _domain(4)
    srv.fit_domain(("s", "t"), xs, xt, **FIT_KW)
    entry = srv.store.get(("s", "t"))
    assert srv.dispatcher.bucket_for(1) == 4
    assert srv.dispatcher.bucket_for(5) == 8
    assert srv.dispatcher.bucket_for(999) == 32  # clamped to the ladder top
    # ragged widths across one burst: results must match the direct transform
    rng = np.random.default_rng(7)
    reqs = [
        Request(x=rng.standard_normal((DIM, n)).astype(np.float32), key=("s", "t"))
        for n in (3, 5, 2, 7)
    ]
    done = srv.serve(reqs)
    assert len(done) == 4
    for req, out in done:
        ref = np.asarray(rf_tca_transform(entry.state, jnp.asarray(req.x)))
        assert out.shape == ref.shape
        np.testing.assert_allclose(out, ref, atol=1e-5)
    # a request wider than the top rung cannot be served in one dispatch
    srv.dispatcher.submit(Request(x=np.zeros((DIM, 33), np.float32), key=("s", "t")))
    with pytest.raises(ValueError, match="max_bucket"):
        srv.dispatcher.flush(entry)


def test_dispatcher_one_trace_per_bucket():
    srv = _server()
    xs, xt = _domain(5)
    srv.fit_domain(("s", "t"), xs, xt, **FIT_KW)
    before = sentinel.counts()
    srv.warmup(("s", "t"))  # compiles rungs 4, 8, 16, 32 exactly once each
    rng = np.random.default_rng(8)
    for n in (3, 4, 2, 7, 8, 20, 31, 1):  # re-hits every rung
        srv.serve([Request(x=rng.standard_normal((DIM, n)).astype(np.float32),
                           key=("s", "t"))])
    planes = tuple(f"serve.transform.b{b}" for b in (4, 8, 16, 32))
    sentinel.assert_stable(before, planes, expect=1)


def test_dispatcher_predict_mode():
    srv = _server()
    xs, xt = _domain(6)
    rng = np.random.default_rng(9)
    clf = {"w": rng.standard_normal((4, 3)).astype(np.float32),
           "b": rng.standard_normal(3).astype(np.float32)}
    srv.fit_domain(("s", "t"), xs, xt, classifier=clf, **FIT_KW)
    entry = srv.store.get(("s", "t"))
    x = rng.standard_normal((DIM, 5)).astype(np.float32)
    (req, logits), = srv.serve([Request(x=x, key=("s", "t"), mode="predict")])
    aligned = np.asarray(rf_tca_transform(entry.state, jnp.asarray(x)))
    ref = clf["w"].T @ aligned + clf["b"][:, None]
    np.testing.assert_allclose(logits, ref, atol=1e-5)
    with pytest.raises(ValueError, match="mode"):
        Request(x=x, mode="align")


# ---- live admission ---------------------------------------------------------


def test_admission_refit_free_and_matches_refit():
    srv = _server()
    xs, xt = _domain(10)
    srv.fit_domain(("s", "t"), xs, xt, **FIT_KW)
    v_before = srv.store.latest_version(("s", "t"))
    entry = srv.store.get(("s", "t"))
    rng = np.random.default_rng(11)
    x_new = rng.standard_normal((DIM, 40)).astype(np.float32)
    res = srv.admit(("s", "t"), x_new, role="source", sender=3)
    assert res.delivered and res.version == v_before
    # refit-free: no cached version changed, no refit ran
    assert srv.store.latest_version(("s", "t")) == v_before
    assert srv.refits == 0
    # stats are seeded with the fit moments (90 source cols) + the admission
    assert entry.stats.admitted == 1 and entry.stats.n_source == 90 + 40
    # the wire really carried both legs (CRC-framed bytes, no rejects)
    assert res.bytes_up > 0 and res.bytes_down > res.bytes_up
    # the admitted client's aligner agrees with a from-scratch fit <= 1e-3
    probe = rng.standard_normal((DIM, 13)).astype(np.float32)
    scratch = rf_tca_fit(jnp.asarray(xs), jnp.asarray(xt),
                         w_rf=f"fused:{srv.fused_seed}", **FIT_KW)
    got = np.asarray(rf_tca_transform(res.state, jnp.asarray(probe)))
    want = np.asarray(rf_tca_transform(scratch, jnp.asarray(probe)))
    assert float(np.max(np.abs(got - want))) <= 1e-3
    # and the served state never shipped omega: it is fused, re-derived
    assert res.state.omega is None and res.state.fused is not None


def test_admission_moment_merge_tracks_u():
    """Merging per-client moments incrementally equals the pooled statistic."""
    stats = MomentStats()
    rng = np.random.default_rng(12)
    chunks = [rng.standard_normal((16, n)) for n in (10, 25, 5)]
    for c in chunks:
        stats.merge(np.mean(c, axis=1), c.shape[1], role="source")
    tgt = rng.standard_normal((16, 30))
    stats.merge(-np.mean(tgt, axis=1), 30, role="target")
    pooled = np.mean(np.concatenate(chunks, axis=1), axis=1) - np.mean(tgt, axis=1)
    np.testing.assert_allclose(stats.u, pooled, atol=1e-12)
    assert stats.admitted == 4 and stats.n_source == 40 and stats.n_target == 30
    with pytest.raises(ValueError, match="role"):
        stats.merge(np.zeros(16), 1, role="both")
    with pytest.raises(ValueError, match="n_samples"):
        stats.merge(np.zeros(16), 0)


def test_admission_requires_fused_state_and_rejects_seed_replay():
    store = ModelStore()
    with pytest.raises(ValueError, match="seed_replay"):
        AdmissionGateway(store, transport=WireTransport(
            resolve_codecs("float32", w_rf="seed_replay")))
    # an omega-materialized state cannot be admitted against
    xs, xt = _domain(13)
    state = rf_tca_fit(jnp.asarray(xs), jnp.asarray(xt), **FIT_KW)
    assert state.fused is None
    srv = _server()
    srv.fit_domain(("s", "t"), xs, xt, w_rf=None, **FIT_KW)
    with pytest.raises(ValueError, match="fused"):
        srv.admit(("s", "t"), xs[:, :5])
    with pytest.raises(KeyError, match="fit_domain"):
        srv.get_or_fit(("never", "fitted"))


def test_fused_omega_memoized_across_serving():
    srv = _server()
    xs, xt = _domain(14)
    srv.fit_domain(("s", "t"), xs, xt, **FIT_KW)
    srv.warmup(("s", "t"))
    regen_before = fused_omega_cache_info()["regenerations"]
    rng = np.random.default_rng(15)
    for _ in range(6):
        srv.serve([Request(x=rng.standard_normal((DIM, 5)).astype(np.float32),
                           key=("s", "t"))])
    # the serving hot path hits the memo: zero regenerations after warmup
    assert fused_omega_cache_info()["regenerations"] == regen_before


# ---- load generator ---------------------------------------------------------


def test_loadgen_poisson_deterministic_and_open_loop():
    a1 = poisson_arrivals(100.0, 50, seed=3)
    a2 = poisson_arrivals(100.0, 50, seed=3)
    np.testing.assert_array_equal(a1, a2)
    assert np.all(np.diff(a1) > 0) and a1.shape == (50,)
    with pytest.raises(ValueError, match="rate"):
        poisson_arrivals(0.0, 5, seed=0)

    srv = _server()
    xs, xt = _domain(16)
    srv.fit_domain(("s", "t"), xs, xt, **FIT_KW)
    srv.warmup(("s", "t"))
    reqs = synth_requests([("s", "t")], dim=DIM, n_requests=40, seed=4,
                          cols_lo=2, cols_hi=8)
    res = run_open_loop(srv, reqs, rate=300.0, seed=5)
    summary = res.summary()
    assert summary["completed"] == 40  # open loop: every arrival is served
    assert summary["p99_ms"] >= summary["p50_ms"] > 0
    assert summary["throughput_rps"] > 0 and res.batches >= 1
    assert all(lat > 0 for lat in res.latencies.values())
    # the request mix is a pure function of the seed
    r1 = synth_requests([("s", "t")], dim=DIM, n_requests=5, seed=4)
    r2 = synth_requests([("s", "t")], dim=DIM, n_requests=5, seed=4)
    for a, b in zip(r1, r2):
        np.testing.assert_array_equal(a.x, b.x)
        assert a.key == b.key


def test_loadgen_cache_misses_under_many_pairs():
    """More pairs than store capacity: the load run survives in-path refits
    and the store reports a sub-unit hit rate."""
    srv = _server(capacity=2)
    pairs = [("s", f"t{i}") for i in range(3)]
    for i, pair in enumerate(pairs):
        xs, xt = _domain(20 + i)
        srv.fit_domain(pair, xs, xt, **FIT_KW)
    reqs = synth_requests(pairs, dim=DIM, n_requests=30, seed=6, cols_lo=2, cols_hi=6)
    res = run_open_loop(srv, reqs, rate=200.0, seed=7)
    assert res.summary()["completed"] == 30
    assert srv.refits > 0
    assert 0.0 < srv.store.hit_rate < 1.0


# ---- telemetry off vs on: bitwise degeneracy --------------------------------


def test_serve_telemetry_off_on_bitwise_identical():
    def run():
        srv = _server()
        xs, xt = _domain(30)
        srv.fit_domain(("s", "t"), xs, xt, **FIT_KW)
        reqs = synth_requests([("s", "t")], dim=DIM, n_requests=8, seed=8,
                              cols_lo=2, cols_hi=8)
        outs = [out for _, out in srv.serve(reqs)]
        adm = srv.admit(("s", "t"), xs[:, :11], role="source")
        outs.append(np.asarray(adm.state.w_rf))
        return outs

    plain = run()
    with use_registry(MetricsRegistry()), use_tracer(Tracer()):
        instrumented = run()
    for a, b in zip(plain, instrumented):
        np.testing.assert_array_equal(a, b)


# ---- request-level observability --------------------------------------------


def test_serve_observability_off_compiles_no_probe_planes():
    """Zero-overhead-off: without an attached drift monitor the dispatcher
    never touches the probed plane variants, and attaching a request tracer
    + SLO engine with no ambient tracer/registry leaves both the compiled
    planes and the served arrays bitwise untouched."""
    def outputs(srv):
        xs, xt = _domain(40)
        srv.fit_domain(("s", "t"), xs, xt, **FIT_KW)
        reqs = synth_requests([("s", "t")], dim=DIM, n_requests=10, seed=9,
                              cols_lo=2, cols_hi=8)
        return [out for _, out in srv.serve(reqs)]

    before = sentinel.counts()
    plain = outputs(_server(sentinel_prefix="off1"))
    srv2 = _server(sentinel_prefix="off2")
    srv2.attach(request_tracer=RequestTracer(rate=1.0), slo=SloEngine(
        [Slo("serve.latency", target=0.9, bound=1.0, window_fast_s=1.0,
             window_slow_s=4.0)]))
    wired = outputs(srv2)
    after = sentinel.counts()
    for a, b in zip(plain, wired):
        np.testing.assert_array_equal(a, b)
    probe_planes = [k for k, v in after.items()
                    if ".probe" in k and v > before.get(k, 0)]
    assert probe_planes == []  # telemetry off: plain planes only
    assert srv2.reqtrace.sampled_total == 0  # no ambient tracer -> declined


def test_serve_drift_probe_planes_trace_once_and_stay_bitwise():
    srv = _server(sentinel_prefix="dr1")
    xs, xt = _domain(41)
    srv.fit_domain(("s", "t"), xs, xt, **FIT_KW)
    srv.attach(drift=DriftMonitor(window=1, threshold=1e9))
    reqs = synth_requests([("s", "t")], dim=DIM, n_requests=10, seed=10,
                          cols_lo=2, cols_hi=8)
    before = sentinel.counts()
    srv.warmup(("s", "t"))
    done = srv.serve(reqs)
    planes = tuple(f"dr1.transform.b{b}.probe" for b in (4, 8, 16, 32))
    sentinel.assert_stable(before, planes, expect=1)
    # the probed planes' primary outputs are bitwise the plain planes' ...
    plain = _server(sentinel_prefix="dr1p")
    plain.fit_domain(("s", "t"), xs, xt, **FIT_KW)
    for (_, out), (_, ref) in zip(done, plain.serve(reqs)):
        np.testing.assert_array_equal(out, ref)
    # ... and the direct transform's to float32 rounding: XLA's CPU dot sums
    # a column in an order that depends on the batch width it is served in
    entry = srv.store.get(("s", "t"))
    for req, out in done:
        ref = np.asarray(rf_tca_transform(entry.state, jnp.asarray(req.x)))
        np.testing.assert_allclose(out, ref, rtol=1e-6, atol=1e-7)
    assert srv.drift.pairs() == [("s", "t")]


def test_serve_auto_refresh_on_drift_alert():
    rng = np.random.default_rng(42)
    clf = {"w": rng.standard_normal((4, 3)).astype(np.float32)}
    srv = _server(sentinel_prefix="dr2")
    xs, xt = _domain(43)
    srv.fit_domain(("s", "t"), xs, xt, classifier=clf, **FIT_KW)
    srv.attach(drift=DriftMonitor(alpha=1.0, window=1, k_consecutive=1,
                                  threshold=0.02))
    srv.admit(("s", "t"), xs[:, :9], role="source")
    assert srv.store.get(("s", "t")).stats.admitted == 1
    v0 = srv.store.latest_version(("s", "t"))
    # a shifted request: the first probed window crosses the threshold,
    # fires, and triggers exactly one moment-space refresh + version bump
    x_shift = (rng.standard_normal((DIM, 20)) + 3.0).astype(np.float32)
    for _ in range(4):  # the same post-drift distribution, re-served
        srv.virtual_now += 0.01
        srv.serve([Request(x=x_shift, key=("s", "t"))])
    assert srv.drift.fires == 1 and srv.moment_refreshes == 1
    assert srv.store.latest_version(("s", "t")) == v0 + 1
    entry = srv.store.get(("s", "t"))
    assert entry.classifier is clf  # carried across the refresh
    assert entry.stats.admitted == 0  # staleness counter reset
    # the reference re-pinned to the live moment: detection re-armed, so the
    # continued (now in-distribution) stream never re-fires
    rec = srv.drift.history[-1]
    assert not rec.fired and rec.mmd < srv.drift.pair_threshold(("s", "t"))


def test_loadgen_service_scale_validation_and_field():
    srv = _server()
    xs, xt = _domain(44)
    srv.fit_domain(("s", "t"), xs, xt, **FIT_KW)
    srv.warmup(("s", "t"))
    reqs = synth_requests([("s", "t")], dim=DIM, n_requests=10, seed=12,
                          cols_lo=2, cols_hi=6)
    res = run_open_loop(srv, reqs, rate=200.0, seed=13, service_scale=2.5)
    assert res.summary()["service_scale"] == 2.5
    assert res.summary()["completed"] == 10
    for bad in (0.0, -1.0, float("inf"), float("nan")):
        with pytest.raises(ValueError, match="service_scale"):
            run_open_loop(srv, reqs, rate=200.0, seed=13, service_scale=bad)


def test_loadgen_emits_request_trees_under_live_tracer():
    srv = _server()
    xs, xt = _domain(45)
    srv.fit_domain(("s", "t"), xs, xt, **FIT_KW)
    srv.attach(request_tracer=RequestTracer(rate=1.0))
    srv.warmup(("s", "t"))
    reqs = synth_requests([("s", "t")], dim=DIM, n_requests=7, seed=14,
                          cols_lo=2, cols_hi=8)
    tracer = Tracer()
    with use_tracer(tracer):
        run_open_loop(srv, reqs, rate=300.0, seed=15)
    assert count_request_trees(tracer.events) == 7
    assert srv.reqtrace.emitted == 7
    # rate 0 disables tracing entirely: no spans, no samples
    srv.attach(request_tracer=RequestTracer(rate=0.0))
    t2 = Tracer()
    with use_tracer(t2):
        run_open_loop(srv, reqs, rate=300.0, seed=16)
    assert count_request_trees(t2.events) == 0
    assert srv.reqtrace.sampled_total == 0
