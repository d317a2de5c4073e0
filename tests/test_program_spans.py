"""Program spans (``repro.obs.span``): the fit's eigensolve and the
dispatcher's legs, in the JAX profiler's trace and in an installed Tracer.

- a span is a ``TraceAnnotation`` whose args become the event's stats;
- with a Tracer installed it also writes the wall-clock B/E pair;
- every eigh path of the fit (stream, fused, dense) emits its leaf spans
  under ``rf_tca.fit``: the four of the host eigensolve below 2N = 16m, the
  wait and the device eigensolve above it; the in-program ``pure_callback``
  branch none; ``rf_tca.eigh_solves`` counts the solves by path;
- a serve call emits ``serve.call`` > assembly / padded dispatch > launch,
  device wait, fetch, with the batch's requests, columns and bucket;
- outputs are bitwise identical with spans recorded and without;
- the dispatcher's leg log is bounded and its registry keeps two counters.
"""
import glob
import sys

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core.rf_tca import rf_tca_fit
from repro.obs import (
    SPAN_NAMES,
    MetricsRegistry,
    Tracer,
    span,
    use_registry,
    use_tracer,
    validate_trace,
)
from repro.serve import AlignerServer, Request
from repro.serve import dispatcher as dispatcher_mod

rf_tca_mod = sys.modules["repro.core.rf_tca"]  # the package re-exports a function

DIM, N_FEATURES, M = 8, 16, 4
FIT_LEAVES = ["rf_tca.stats_wait", "rf_tca.cmat_to_host", "rf_tca.eigh",
              "rf_tca.vecs_to_device"]
DISPATCH_LEAVES = ["serve.launch", "serve.device_wait", "serve.fetch"]


def _domain(seed=0, n=90):
    rng = np.random.default_rng(seed)
    xs = rng.standard_normal((DIM, n)).astype(np.float32)
    xt = (rng.standard_normal((DIM, n - 7)) + 0.7).astype(np.float32)
    return xs, xt


def _server():
    srv = AlignerServer(capacity=2, min_bucket=4, max_bucket=32)
    xs, xt = _domain(1)
    srv.fit_domain(("s", "t"), xs, xt, n_features=N_FEATURES, m=M, seed=0)
    srv.fit_domain(("t", "s"), xt, xs, n_features=N_FEATURES, m=M, seed=1)
    return srv, xt


def _requests(xt):
    # a run of two requests on one pair, then one on the other: two dispatches
    return [Request(x=xt[:, :5], key=("s", "t")), Request(x=xt[:, 5:12], key=("s", "t")),
            Request(x=xt[:, 20:23], key=("t", "s"))]


def _profiled(tmp_path, fn):
    """Run ``fn`` under a profiler session; its result and the trace's host
    events [(name, start, end, stats)] whose names are program spans."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in SPAN_NAMES or ev.name == "probe":
                    events.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                                   dict(ev.stats)))
    return out, sorted(events, key=lambda e: (e[1], -e[2]))


def _tree(events):
    """[(name, depth, args)] in begin order from a Tracer's B/E events."""
    out, depth = [], 0
    for ev in events:
        if ev["ph"] == "B":
            out.append((ev["name"], depth, ev.get("args") or {}))
            depth += 1
        elif ev["ph"] == "E":
            depth -= 1
    return out


def test_span_writes_trace_annotation_with_args(tmp_path):
    def body():
        with span("probe", two_n=8, m=2):
            pass

    _, events = _profiled(tmp_path, body)
    assert [(n, stats) for n, _, _, stats in events] == [("probe", {"two_n": 8, "m": 2})]


def test_span_writes_wall_twin_to_installed_tracer():
    with use_tracer(Tracer()) as tracer:
        with span("outer", requests=3):
            with span("inner"):
                pass
    assert [(e["name"], e["ph"], e.get("args")) for e in tracer.events] == [
        ("outer", "B", {"requests": 3}), ("inner", "B", None),
        ("inner", "E", None), ("outer", "E", None)]
    assert validate_trace(tracer.events) == []
    # with no tracer installed the span is the annotation alone
    with span("outer", requests=3):
        pass
    assert len(tracer.events) == 4


FIT_PATHS = [
    {},  # the default streamed path
    {"w_rf": "fused:7"},  # seed-fused statistics, solve_w_rf_gram
    {"mode": "dense", "solver": "eigh"},  # explicit features, solve_w_rf_gram
]
DEVICE_N_FEATURES = 64  # 2N = 128 >= 16 m: the eigensolve runs on the device


@pytest.mark.parametrize("fit_kw,n_features", [
    *((kw, N_FEATURES) for kw in FIT_PATHS),
    *((kw, DEVICE_N_FEATURES) for kw in FIT_PATHS),
], ids=["stream", "fused", "dense", "stream-device", "fused-device", "dense-device"])
def test_fit_spans_cover_every_eigh_path(fit_kw, n_features):
    xs, xt = _domain()
    with use_tracer(Tracer()) as tracer:
        rf_tca_fit(xs, xt, n_features=n_features, m=M, **fit_kw)
    two_n = 2 * n_features
    tree = _tree(tracer.events)
    head = [
        ("rf_tca.fit", 0, {"n": xs.shape[1] + xt.shape[1], "p": DIM,
                           "n_features": n_features, "m": M}),
        ("rf_tca.stats_wait", 1, {}),
    ]
    if n_features == N_FEATURES:  # 2N < 16 m: the host path
        assert tree == head + [
            ("rf_tca.cmat_to_host", 1, {"bytes": 4 * two_n * two_n}),
            ("rf_tca.eigh", 1, {"two_n": two_n, "m": M, "path": "host"}),
            ("rf_tca.vecs_to_device", 1, {"bytes": 4 * (M + two_n * M)}),
        ]
        return
    # the device path: no copy of C and no upload, the products it took
    iters = tree[-1][2]["iters"]
    assert tree == head + [
        ("rf_tca.eigh", 1, {"two_n": two_n, "m": M, "block": rf_tca_mod.EIGH_BLOCK * M,
                            "iters": iters, "path": "device"}),
    ]
    assert 0 < iters < rf_tca_mod.EIGH_MAX_PRODUCTS
    assert iters % rf_tca_mod.EIGH_CHECK_EVERY == 0


def test_eigh_solves_counts_each_path(monkeypatch):
    xs, xt = _domain()
    with use_registry(MetricsRegistry()) as reg:
        rf_tca_fit(xs, xt, n_features=N_FEATURES, m=M)
        rf_tca_fit(xs, xt, n_features=DEVICE_N_FEATURES, m=M)
        rf_tca_fit(xs, xt, n_features=DEVICE_N_FEATURES, m=M, w_rf="fused:7")
        monkeypatch.setattr(rf_tca_mod, "EIGH_MAX_PRODUCTS", 1)
        with use_tracer(Tracer()) as tracer:
            rf_tca_fit(xs, xt, n_features=DEVICE_N_FEATURES, m=M)
    assert reg.snapshot()["rf_tca.eigh_solves"] == {
        "path=device": 2, "path=host": 1, "path=host_fallback": 1}
    # a fallback shows the device attempt, then the host solve with its copies
    two_n = 2 * DEVICE_N_FEATURES
    assert [(name, args) for name, _, args in _tree(tracer.events)][1:] == [
        ("rf_tca.stats_wait", {}),
        ("rf_tca.eigh", {"two_n": two_n, "m": M, "block": rf_tca_mod.EIGH_BLOCK * M,
                         "iters": 1, "path": "host_fallback"}),
        ("rf_tca.cmat_to_host", {"bytes": 4 * two_n * two_n}),
        ("rf_tca.eigh", {"two_n": two_n, "m": M, "path": "host_fallback"}),
        ("rf_tca.vecs_to_device", {"bytes": 4 * (M + two_n * M)}),
    ]


def test_in_program_eigh_callback_has_no_spans():
    # 5 m >= 2N: the jitted LOBPCG fit falls back to eigh as a pure_callback
    xs, xt = _domain()
    with use_tracer(Tracer()) as tracer:
        rf_tca_fit(xs, xt, n_features=8, m=M, solver="lobpcg")
    assert [name for name, _, _ in _tree(tracer.events)] == ["rf_tca.fit"]


def test_serve_spans_nest_and_carry_batch_args():
    srv, xt = _server()
    with use_tracer(Tracer()) as tracer:
        srv.serve(_requests(xt))
    dispatch = [("serve.padded_dispatch", 1, {})] + [(n, 2, {}) for n in DISPATCH_LEAVES]
    assert _tree(tracer.events) == [
        ("serve.call", 0, {"requests": 3}),
        ("serve.batch_assembly", 1, {"requests": 2, "cols": 12, "bucket": 16}),
        *dispatch,
        ("serve.batch_assembly", 1, {"requests": 1, "cols": 3, "bucket": 4}),
        *dispatch,
    ]
    assert validate_trace(tracer.events) == []


def test_spans_reach_the_profiler_trace_nested(tmp_path):
    xs, xt = _domain()
    srv, xq = _server()
    rf_tca_fit(xs, xt, n_features=N_FEATURES, m=M)  # compiled before the session

    def body():
        rf_tca_fit(xs, xt, n_features=N_FEATURES, m=M)
        srv.serve(_requests(xq))

    _, events = _profiled(tmp_path, body)
    names = [n for n, _, _, _ in events]
    assert names == (["rf_tca.fit"] + FIT_LEAVES + ["serve.call"]
                     + 2 * (["serve.batch_assembly", "serve.padded_dispatch"]
                            + DISPATCH_LEAVES))
    assert set(names) == set(SPAN_NAMES)
    by_name = {}
    for ev in events:
        by_name.setdefault(ev[0], []).append(ev)
    parents = {"rf_tca.fit": FIT_LEAVES,
               "serve.call": ["serve.batch_assembly", "serve.padded_dispatch"]}
    for parent, children in parents.items():
        (_, lo, hi, _), = by_name[parent]
        for child in children:
            assert all(lo <= s and e <= hi for _, s, e, _ in by_name[child])
    for i, (_, lo, hi, _) in enumerate(by_name["serve.padded_dispatch"]):
        for child in DISPATCH_LEAVES:
            _, s, e, _ = by_name[child][i]
            assert lo <= s and e <= hi
    assert by_name["rf_tca.eigh"][0][3] == {"two_n": 2 * N_FEATURES, "m": M, "path": "host"}
    assert [ev[3] for ev in by_name["serve.batch_assembly"]] == [
        {"requests": 2, "cols": 12, "bucket": 16}, {"requests": 1, "cols": 3, "bucket": 4}]


def test_outputs_bitwise_identical_with_spans_recorded(tmp_path):
    xs, xt = _domain()

    def run():
        state = rf_tca_fit(xs, xt, n_features=N_FEATURES, m=M)
        srv, xq = _server()
        served = [out for _, out in srv.serve(_requests(xq))]
        return [np.asarray(state.w_rf), np.asarray(state.eigvals), *served]

    plain = run()
    with use_tracer(Tracer()):
        recorded, _ = _profiled(tmp_path, run)
    assert len(plain) == len(recorded) == 5
    for a, b in zip(plain, recorded):
        np.testing.assert_array_equal(a, b)


def test_leg_log_keeps_the_newest_legs(monkeypatch):
    monkeypatch.setattr(dispatcher_mod, "LEG_LOG_MAX", 3)
    srv, xt = _server()
    for i in range(5):
        srv.serve([Request(x=xt[:, i:i + 2], key=("s", "t"))])
    legs = srv.dispatcher.take_legs()
    assert len(legs) == 3
    assert all(a >= 0 and d > 0 for a, d in legs)
    assert srv.dispatcher.take_legs() == []


def test_dispatch_registry_keeps_only_its_two_counters():
    srv, xt = _server()
    with use_registry(MetricsRegistry()) as reg:
        srv.serve(_requests(xt))
    snap = reg.snapshot()
    assert not {"serve.queue_depth", "serve.batch_requests", "serve.batch_fill",
                "serve.dispatch_s"} & set(snap)
    assert sum(snap["serve.requests"].values()) == 3
    assert sum(snap["serve.dispatches"].values()) == 2
