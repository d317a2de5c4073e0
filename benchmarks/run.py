"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines (one per measurement), plus a
section header per bench. See EXPERIMENTS.md for the claim-by-claim mapping.

    PYTHONPATH=src python -m benchmarks.run            # all benches
    PYTHONPATH=src python -m benchmarks.run --only fig3,table2
    PYTHONPATH=src python -m benchmarks.run --smoke    # CI: tiny fig3 + wire
    PYTHONPATH=src python -m benchmarks.run --profile --only async
        # wrap each bench in a wall-clock tracer, write trace_<name>.json

Four benches write machine-readable records at the repo root, tracked across
PRs: ``fig3`` -> ``BENCH_rf_tca.json`` (fit wall-times dense/stream/lobpcg,
speedups, peak-memory proxy, tiled large-N kernel agreement, seed-fused
kernel 0-ULP twin agreement + ensemble degeneracy + fused-vs-materialized
memory ladder + fused accuracy re-sweep with the N-anomaly resolution row,
round-engine per-round times serial/batched/ragged, accuracies), ``wire`` ->
``BENCH_comm.json`` (bytes-on-wire per payload per codec, accuracy-vs-loss-rate
and accuracy-vs-codec curves), ``async`` -> ``BENCH_async.json`` (fedsim
runtime: sync-vs-async degeneracy divergence, accuracy-vs-churn-rate with
staleness-weighted buffering vs drop-the-stragglers, accuracy-vs-buffer-size,
virtual time to target accuracy), ``fleet`` -> ``BENCH_fleet.json``
(rounds/sec + chunk-bounded working-set proxy vs K up to 1024+, server-ingress
bytes flat vs two-tier, two-tier-vs-flat divergence, accuracy vs edge codec),
``robust`` -> ``BENCH_robust.json`` (fault injection: zero-fault bitwise
degeneracy of the AggregationRule refactor, accuracy vs corruption rate and
vs Byzantine count for mean vs each robust rule, crash-recovery rollback vs
checkpoint interval), and ``obs`` -> ``BENCH_obs.json`` + ``trace_obs.json``
(telemetry: fully-on vs off rounds/sec gated at <= 5% slowdown, bitwise
off-vs-on degeneracy for both engines, jit-retrace sentinels at exactly one
trace per plane, and a churn + server-crash async run exported as a
Perfetto-viewable Chrome trace), and ``serve`` -> ``BENCH_serve.json``
(adaptation-as-a-service: p50/p99 latency + throughput vs offered Poisson
load, batch-size histograms, store hit rate under LRU pressure, the
refit-free live-admission gate at <= 1e-3, one jit trace per batch bucket,
request-tracing overhead <= 5% with bitwise off-vs-on degeneracy, SLO
burn-rate violations under overload + the quarantine-ledger objective, and
the drift-injection run: detection latency, auto-refresh version bumps,
chunked-refresh equivalence, and post-refresh accuracy recovery).

``--smoke`` reruns exactly those record-writing benches at tiny sizes and
schema-validates the emitted JSON (required keys present, wall-times positive,
agreement within tolerance) so the perf records cannot silently rot — this is
the CI ``bench-smoke`` job.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
import traceback
from pathlib import Path

from benchmarks import (
    bench_ablation,
    bench_accuracy,
    bench_async,
    bench_comm,
    bench_comm_wire,
    bench_fleet,
    bench_gamma,
    bench_hard_voting,
    bench_kernels,
    bench_laplace,
    bench_obs,
    bench_rf_tca,
    bench_robust,
    bench_robustness,
    bench_serve,
    bench_theory,
)
from repro.obs import Tracer, use_tracer, validate_trace_file

BENCHES = {
    "fig3": ("Fig.3 + Tables X-XIII: RF-TCA vs DA baselines", bench_rf_tca.run),
    "theory": ("Thm.1/2 + Cor.1 validation", bench_theory.run),
    "table2": ("Tables I/II: communication accounting", bench_comm.run),
    "wire": ("Wire format: bytes/payload/codec + loss & codec curves", bench_comm_wire.run),
    "async": ("Fedsim runtime: churn/staleness/buffer curves + degeneracy", bench_async.run),
    "fleet": ("Fleet scale: K-sweep, two-tier ingress, edge codecs", bench_fleet.run),
    "table3": ("Table III + Fig.4: drop/interval robustness", bench_robustness.run),
    "robust": ("Fault injection: corruption/Byzantine/crash-recovery", bench_robust.run),
    "table5": ("Tables IV-VI: federated DA leaderboard", bench_accuracy.run),
    "table8": ("Tables VIII/IX + Fig.5: ablations", bench_ablation.run),
    "appD": ("Appendix D: one-shot hard voting / asynchrony", bench_hard_voting.run),
    "fig6": ("Fig.6/Remark 3: gamma sensitivity", bench_gamma.run),
    "table14": ("App.D Tab.XIV/XV: Laplace vs Gaussian kernels", bench_laplace.run),
    "kernels": ("Pallas kernels vs oracles", bench_kernels.run),
    "obs": ("Telemetry: overhead gate, degeneracy, sentinels, trace export", bench_obs.run),
    "serve": ("Serving: Poisson load curves, batching, cache, live admission", bench_serve.run),
}


ROOT = Path(__file__).resolve().parent.parent


def _is_pos(v) -> bool:
    return isinstance(v, (int, float)) and not isinstance(v, bool) and v > 0 and math.isfinite(v)


class _SchemaErrors(list):
    """Collects dotted-path schema violations against a bench record."""

    def __init__(self, record: dict):
        super().__init__()
        self.record = record

    def need(self, path: str, pred=None) -> None:
        cur = self.record
        for part in path.split("."):
            if not isinstance(cur, dict) or part not in cur:
                self.append(f"missing key {path}")
                return
            cur = cur[part]
        if pred is not None and not pred(cur):
            self.append(f"bad value at {path}: {cur!r}")


def validate_rf_tca_record(record: dict) -> list[str]:
    """BENCH_rf_tca.json contract: keys present, wall-times positive, the
    tiled kernel within tolerance of its twin, ragged planes in agreement."""
    e = _SchemaErrors(record)
    acc01 = lambda d: isinstance(d, dict) and d and all(
        isinstance(v, (int, float)) and 0.0 <= v <= 1.0 for v in d.values()
    )
    for k in ("fit.dense_s", "fit.stream_s", "fit.lobpcg_s",
              "fit.speedup_stream_vs_dense", "fit.memory_proxy_bytes.dense",
              "fit.memory_proxy_bytes.stream", "large_n.tiled_pallas_s",
              "large_n.tiled_twin_s", "large_n.tile", "large_n.acc_bytes_tiled",
              "round_engine.serial", "round_engine.batched",
              "round_engine.speedup_batched_vs_serial", "ragged_rounds.serial_s",
              "ragged_rounds.batched_s"):
        e.need(k, _is_pos)
    e.need("large_n.rel_err_pallas_vs_twin", lambda v: 0.0 <= v <= 1e-4)
    e.need("ragged_rounds.max_param_divergence", lambda v: 0.0 <= v <= 1e-3)
    e.need("ragged_rounds.client_sizes", lambda v: isinstance(v, list) and len(set(v)) > 1)
    e.need("accuracy", acc01)
    # seed-fused gates: bit-for-bit vs the XLA generator twin in BOTH
    # layouts, ensemble=1 bitwise-degenerate to the single-draw path, and
    # the fused peak-memory proxy strictly below materialized from N >= 2048
    e.need("fused.ulp_untiled", lambda v: v == 0)
    e.need("fused.ulp_tiled", lambda v: v == 0)
    e.need("fused.ensemble1_max_abs_diff", lambda v: v == 0.0)
    e.need("fused.ensemble_rel_err_vs_oracle", lambda v: 0.0 <= v <= 1e-4)
    e.need("fused.fused_s", _is_pos)
    proxies = (record.get("fused") or {}).get("memory_proxy_bytes") or {}
    if not any(int(k) >= 2048 for k in proxies):
        e.append("fused.memory_proxy_bytes: no ladder entry at N >= 2048")
    for k, row in proxies.items():
        if int(k) >= 2048 and not (
            isinstance(row, dict)
            and _is_pos(row.get("fused"))
            and _is_pos(row.get("materialized"))
            and row["fused"] < row["materialized"]
        ):
            e.append(f"fused.memory_proxy_bytes.{k}: fused not strictly below "
                     f"materialized ({row!r})")
    e.need("accuracy_resweep.fused", acc01)
    e.need("accuracy_resweep.ensemble", acc01)
    e.need(
        "accuracy_resweep.anomaly_small_vs_large_n.status",
        lambda v: v in ("resolved", "persists"),
    )
    return list(e)


def validate_comm_record(record: dict) -> list[str]:
    """BENCH_comm.json contract: exact byte tables and accuracy curves."""
    e = _SchemaErrors(record)
    bytes_table = lambda d: isinstance(d, dict) and d and all(
        isinstance(kinds, dict) and kinds and all(_is_pos(b) for b in kinds.values())
        for kinds in d.values()
    )
    e.need("bytes_per_payload", bytes_table)
    for scale in ("1x", "4x"):
        e.need(f"w_rf_bytes_{scale}.float32", _is_pos)
        e.need(f"w_rf_bytes_{scale}.seed_replay", _is_pos)
    # the headline O(1) claim: seed-replay bytes must not grow with N
    if not self_consistent_seed_replay(record):
        e.append("w_rf seed_replay bytes grew between 1x and 4x N")
    e.need("identity.acc", lambda v: 0.0 <= v <= 1.0)
    e.need("identity.bytes", lambda d: isinstance(d, dict) and all(_is_pos(v) for v in d.values()))
    curve = lambda d: isinstance(d, dict) and d and all(
        isinstance(row, dict) and 0.0 <= row.get("acc", -1.0) <= 1.0 for row in d.values()
    )
    e.need("accuracy_vs_codec", curve)
    e.need("accuracy_vs_loss_rate", curve)
    return list(e)


def validate_async_record(record: dict) -> list[str]:
    """BENCH_async.json contract: degeneracy within tolerance, virtual times
    positive, churn/buffer accuracy curves well-formed."""
    e = _SchemaErrors(record)
    e.need("degeneracy.max_param_divergence", lambda v: 0.0 <= v <= 1e-3)
    for k in ("degeneracy.virtual_time_sync", "degeneracy.virtual_time_async",
              "degeneracy.flushes", "time_to_target.virtual_time_sync",
              "time_to_target.virtual_time_async", "time_to_target.target_acc"):
        e.need(k, _is_pos)
    e.need("degeneracy.staleness_max", lambda v: v == 0)  # full fresh buffers only
    acc_row = lambda r: isinstance(r, dict) and 0.0 <= r.get("acc", -1.0) <= 1.0 and _is_pos(
        r.get("virtual_time")
    )
    e.need("accuracy_vs_churn", lambda d: isinstance(d, dict) and d and all(
        acc_row(r.get("naive_sync")) and acc_row(r.get("async_buffered"))
        for r in d.values()
    ))
    e.need("accuracy_vs_buffer_size", lambda d: isinstance(d, dict) and d and all(
        acc_row(r) for r in d.values()
    ))
    e.need("async_beats_naive_at", lambda v: isinstance(v, list))
    return list(e)


def validate_fleet_record(record: dict) -> list[str]:
    """BENCH_fleet.json contract: the K-sweep sustains its sizes with the
    chunk-bounded working set, two-tier vs flat stays within tolerance, and
    server ingress is strictly below flat from K = 64 up."""
    e = _SchemaErrors(record)
    e.need("max_k", lambda v: v >= (64 if record.get("smoke") else 1024))
    e.need("scaling", lambda d: isinstance(d, dict) and len(d) >= 2)
    e.need("ingress", lambda d: isinstance(d, dict) and d)
    for key, row in (record.get("scaling") or {}).items():
        e.need(f"scaling.{key}.round_s", _is_pos)
        e.need(f"scaling.{key}.rounds_per_s", _is_pos)
        e.need(f"scaling.{key}.working_set_bytes_chunked", _is_pos)
        if row.get("chunk", 0) < row.get("k", 0):
            e.need(
                f"scaling.{key}.working_set_bytes_chunked",
                lambda v, row=row: v < row.get("working_set_bytes_full", 0),
            )
    for key, row in (record.get("ingress") or {}).items():
        if int(key) >= 64:
            e.need(
                f"ingress.{key}.two_tier_total",
                lambda v, row=row: _is_pos(v) and v < row.get("flat_total", 0),
            )
    e.need("two_tier.max_param_divergence", lambda v: 0.0 <= v <= 1e-3)
    e.need("edge_codec_curve", lambda d: isinstance(d, dict) and d and all(
        0.0 <= r.get("acc", -1.0) <= 1.0 and _is_pos(r.get("edge_uplink_bytes"))
        for r in d.values()
    ))
    return list(e)


def validate_robust_record(record: dict) -> list[str]:
    """BENCH_robust.json contract: the rule refactor is bitwise-degenerate
    with zero faults, at least one robust rule beats the plain mean at the
    heaviest corruption rate, and crash recovery rolls back no further than
    one checkpoint interval."""
    e = _SchemaErrors(record)
    e.need("degeneracy.max_param_divergence", lambda v: 0.0 <= v <= 1e-6)
    e.need("clean_baseline_acc", lambda v: 0.0 <= v <= 1.0)
    acc_row = lambda r: isinstance(r, dict) and "mean" in r and all(
        isinstance(v, (int, float)) and 0.0 <= v <= 1.0 for v in r.values()
    )
    e.need("corruption", lambda d: isinstance(d, dict) and d and all(
        isinstance(by_rate, dict) and by_rate and all(acc_row(r) for r in by_rate.values())
        for by_rate in d.values()
    ))
    e.need("byzantine", lambda d: isinstance(d, dict) and d and all(
        acc_row(r) for r in d.values()
    ))
    # the headline claim: a robust rule survives what poisons the mean
    for mode, by_rate in (record.get("corruption") or {}).items():
        if not isinstance(by_rate, dict) or not by_rate:
            continue
        worst = by_rate.get(max(by_rate, key=float))
        if isinstance(worst, dict) and "mean" in worst and len(worst) > 1:
            robust_best = max(v for k, v in worst.items() if k != "mean")
            if not robust_best > worst["mean"]:
                e.append(
                    f"corruption.{mode}: no robust rule beats mean at the "
                    f"heaviest rate ({worst!r})"
                )
    e.need("recovery", lambda d: isinstance(d, dict) and d)
    for key, row in (record.get("recovery") or {}).items():
        if not isinstance(row, dict):
            e.append(f"recovery[{key}]: not a dict")
            continue
        rb, iv = row.get("rollback_s"), row.get("checkpoint_interval_s", -1.0)
        if not (isinstance(rb, (int, float)) and 0.0 <= rb <= iv):
            e.append(f"recovery[{key}]: rollback_s {rb!r} not within interval {iv!r}")
        if row.get("recovered") is not True:
            e.append(f"recovery[{key}]: crashed run did not complete its flushes")
    return list(e)


def validate_obs_record(record: dict) -> list[str]:
    """BENCH_obs.json contract: telemetry fully on costs <= 5% rounds/sec,
    is bitwise-off when disabled (both engines), keeps every compiled plane
    at exactly one trace, and the exported churn + server-crash trace is a
    valid Chrome trace holding the whole virtual-time story."""
    e = _SchemaErrors(record)
    e.need("overhead.rounds_per_s_off", _is_pos)
    e.need("overhead.rounds_per_s_on", _is_pos)
    e.need("overhead.slowdown", lambda v: isinstance(v, (int, float)) and 0.0 <= v <= 0.05)
    e.need("degeneracy.batched_max_param_divergence", lambda v: v == 0.0)
    e.need("degeneracy.serial_max_param_divergence", lambda v: v == 0.0)
    e.need("sentinel.round_traces", lambda v: v == 1)
    e.need("sentinel.flush_traces", lambda v: v == 1)
    e.need("trace.n_events", _is_pos)
    e.need("trace.validation_errors", lambda v: v == [])
    e.need("trace.server_crashes", _is_pos)
    e.need("trace.request_trees", _is_pos)
    for span in ("compute", "uplink", "flush", "server_crash", "recovery",
                 "checkpoint", "eval"):
        e.need(f"trace.spans.{span}", _is_pos)
    # independently re-validate the trace file the record points at — it must
    # also hold at least one *complete* per-request span tree (all three
    # serving legs contained in their root span)
    trace_path = ROOT / str(record.get("trace", {}).get("file", "trace_obs.json"))
    if not trace_path.exists():
        e.append(f"{trace_path.name}: not written")
    else:
        e.extend(
            f"{trace_path.name}: {msg}"
            for msg in validate_trace_file(trace_path, require_request_trees=1)
        )
    return list(e)


def validate_serve_record(record: dict) -> list[str]:
    """BENCH_serve.json contract: positive latencies with p99 >= p50 at every
    offered load (>= 3 levels in the full run), positive saturation
    throughput, a cache hit rate in [0, 1], a nonempty batch histogram, the
    admission-equals-refit gate at <= 1e-3 with no version change and no
    refit, and exactly one jit trace per batch bucket.  The observability
    sections carry their own gates: request tracing fully on stays within
    the 5% overhead budget and bitwise-degenerate when off, the SLO engine
    fires at least one latency violation under overload (timeline entries
    holding both burn windows) plus one quarantine violation naming the
    poisoned member, and the drift run detects the injected shift with a
    positive latency, exactly one version bump per fire, a chunked-vs-oneshot
    refresh within 1e-3, and a recovered post-refresh accuracy."""
    e = _SchemaErrors(record)
    e.need("config.service_scale", _is_pos)
    min_levels = 1 if record.get("smoke") else 3
    curve = record.get("load_curve") or {}
    if not (isinstance(curve, dict) and len(curve) >= min_levels):
        e.append(f"load_curve: want >= {min_levels} offered-load levels, got {len(curve)}")
    for rate, row in curve.items():
        if not isinstance(row, dict):
            e.append(f"load_curve.{rate}: not a dict")
            continue
        for k in ("p50_ms", "p99_ms", "throughput_rps", "completed"):
            if not _is_pos(row.get(k)):
                e.append(f"load_curve.{rate}.{k}: {row.get(k)!r} not positive")
        if not row.get("p99_ms", 0) >= row.get("p50_ms", 0):
            e.append(f"load_curve.{rate}: p99 {row.get('p99_ms')!r} < p50 {row.get('p50_ms')!r}")
    e.need("saturation.throughput_rps", _is_pos)
    e.need("cache.hit_rate", lambda v: isinstance(v, (int, float)) and 0.0 <= v <= 1.0)
    e.need("batch_histogram.dispatches", _is_pos)
    e.need("batch_histogram.requests_per_dispatch", lambda d: isinstance(d, dict) and d)
    e.need("batch_histogram.bucket_widths", lambda d: isinstance(d, dict) and d)
    e.need("admission.max_divergence_vs_refit", lambda v: 0.0 <= v <= 1e-3)
    e.need("admission.store_version_changed", lambda v: v is False)
    e.need("admission.refit_ran", lambda v: v is False)
    e.need("admission.bytes_up", _is_pos)
    e.need("admission.bytes_down", _is_pos)
    e.need("sentinel.traces_per_bucket", lambda d: isinstance(d, dict) and d and all(
        v == 1 for v in d.values()
    ))
    # request-level observability: overhead/degeneracy gates + tree fidelity
    e.need("obs.slowdown", lambda v: isinstance(v, (int, float)) and 0.0 <= v <= 0.05)
    e.need("obs.degeneracy", lambda v: v == 0.0)
    e.need("obs.sample_rate", lambda v: isinstance(v, (int, float)) and 0.0 < v < 1.0)
    e.need("obs.request_tracing.complete_trees", _is_pos)
    e.need("obs.request_tracing.emitted", _is_pos)
    # SLO engine: overload must burn through the latency budget, and the
    # poisoned quarantine ledger must surface the guilty member
    e.need("slo.calm_p50_ms", _is_pos)
    e.need("slo.bound_ms", _is_pos)
    e.need("slo.n_violations", _is_pos)
    e.need("slo.quarantine.n_violations", _is_pos)
    e.need(
        "slo.quarantine.worst_member",
        lambda v: isinstance(v, str) and v.startswith("member=")
        and v.removeprefix("member=").isdigit(),
    )
    timeline = (record.get("slo") or {}).get("timeline")
    if not (isinstance(timeline, list) and timeline and all(
        isinstance(v, dict)
        and all(k in v for k in ("t", "objective", "burn_fast", "burn_slow",
                                 "window_fast_s", "window_slow_s"))
        for v in timeline
    )):
        e.append("slo.timeline: want >= 1 violation records carrying both "
                 f"burn windows, got {timeline!r}")
    # drift: injected shift detected, one bump per fire, refresh equivalent
    e.need("drift.injection_t", _is_pos)
    e.need("drift.detection_latency_s", _is_pos)
    e.need("drift.fires", _is_pos)
    drift = record.get("drift") or {}
    if drift.get("version_bumps") != drift.get("fires"):
        e.append(f"drift: version bumps {drift.get('version_bumps')!r} != "
                 f"fires {drift.get('fires')!r} (want exactly one refresh per fire)")
    e.need("drift.refresh_equivalence.max_divergence", lambda v: 0.0 <= v <= 1e-3)
    e.need("drift.accuracy.recovered", lambda v: v is True)
    e.need("drift.accuracy.stale_disc", _is_pos)
    e.need("drift.accuracy.refreshed_disc", _is_pos)
    return list(e)


def self_consistent_seed_replay(record: dict) -> bool:
    try:
        return (
            record["w_rf_bytes_4x"]["seed_replay"] <= record["w_rf_bytes_1x"]["seed_replay"]
        )
    except (KeyError, TypeError):
        return False


def run_smoke() -> None:
    """CI bench-smoke: tiny fig3 + wire + async + fleet runs, then
    schema-validate every emitted record."""
    for key, fn in (
        ("fig3", bench_rf_tca.run),
        ("wire", bench_comm_wire.run),
        ("async", bench_async.run),
        ("fleet", bench_fleet.run),
        ("robust", bench_robust.run),
        ("obs", bench_obs.run),
        ("serve", bench_serve.run),
    ):
        print(f"# --- smoke {key} ---", flush=True)
        t0 = time.time()
        fn(smoke=True)
        print(f"# smoke {key} done in {time.time()-t0:.1f}s", flush=True)
    errors = []
    for name, validate in (
        ("BENCH_rf_tca.json", validate_rf_tca_record),
        ("BENCH_comm.json", validate_comm_record),
        ("BENCH_async.json", validate_async_record),
        ("BENCH_fleet.json", validate_fleet_record),
        ("BENCH_robust.json", validate_robust_record),
        ("BENCH_obs.json", validate_obs_record),
        ("BENCH_serve.json", validate_serve_record),
    ):
        path = ROOT / name
        if not path.exists():
            errors.append(f"{name}: not written")
            continue
        errors += [f"{name}: {msg}" for msg in validate(json.loads(path.read_text()))]
    if errors:
        sys.exit("bench record schema violations:\n  " + "\n  ".join(errors))
    print(
        "# smoke: BENCH_rf_tca.json + BENCH_comm.json + BENCH_async.json + "
        "BENCH_fleet.json + BENCH_robust.json + BENCH_obs.json + "
        "BENCH_serve.json schemas OK",
        flush=True,
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument(
        "--smoke", action="store_true",
        help="tiny fig3+wire runs, then schema-validate the emitted JSON records",
    )
    ap.add_argument(
        "--profile", action="store_true",
        help="run each bench under a tracer and write trace_<name>.json "
        "(wall-clock span per bench + any virtual-time spans the fedsim "
        "schedulers emit while it runs); open at ui.perfetto.dev",
    )
    args = ap.parse_args()
    print("name,us_per_call,derived")
    if args.smoke:
        run_smoke()
        return
    selected = args.only.split(",") if args.only else list(BENCHES)
    failed = []
    for key in selected:
        title, fn = BENCHES[key]
        print(f"# --- {key}: {title} ---", flush=True)
        t0 = time.time()
        try:
            if args.profile:
                tracer = Tracer()
                with use_tracer(tracer), tracer.span(key):
                    fn()
                tracer.write(ROOT / f"trace_{key}.json")
                print(f"# wrote trace_{key}.json ({len(tracer.events)} events)", flush=True)
            else:
                fn()
        except Exception:  # noqa: BLE001
            failed.append(key)
            traceback.print_exc()
        print(f"# {key} done in {time.time()-t0:.1f}s", flush=True)
    if failed:
        sys.exit(f"benches failed: {failed}")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
