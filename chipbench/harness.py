"""The benchmark harness: runs one cell once and prints one result line.

Everything that belongs to one cell, configuration, driver or per-layer
metric is a file of its own, found by name:

- ``cells/<cell>.json``: the configuration's name, the driver's name, the
  traffic parameters, the traced window's length and the limits of the
  correctness check;
- ``configs/<config>.json``: the deployment's shapes, source, ``assumed``
  and ``reduced``;
- ``drivers/<driver>.py``: ``setup(ctx)``, ``window(state, seconds, ctx)``
  and ``check(state, record, ctx)`` of one entry point;
- ``metrics/<metric>.py``: ``read(ctx) -> float | None``, one per per-layer
  metric.  A reader that finds nothing to read returns None and its metric
  is left out of the line.

A run: set-up (data, the system's objects, a warm-up of every shape the
window uses), the measured window, the peak device memory, then the
comparison with the plain reference.  Set-up ends by freezing the heap it
leaves (``settle_heap``), so that the collector's full passes in the window
walk only what the window keeps.  With ``trace=1`` the window runs under
the profiler and the line carries the per-layer metrics instead of the
end-to-end ones.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import sys
import time
from pathlib import Path
from types import SimpleNamespace

BENCH_DIR = Path(__file__).resolve().parent
CHECKOUT = BENCH_DIR.parent
CACHE_DIR = CHECKOUT / ".jax_cache"  # fixed: the path is part of a cache hit's key
TRACE_DIR = CHECKOUT / ".chipbench_trace"


class NoChip(SystemExit):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_json(bench_dir: Path, kind: str, name: str) -> dict:
    path = Path(bench_dir) / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r} ({path})")
    return json.loads(path.read_text())


def load_module(bench_dir: Path, kind: str, name: str):
    path = Path(bench_dir) / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(f"chipbench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_readers(bench_dir: Path) -> dict:
    """{metric name: reader module} for every file under ``metrics/``."""
    return {
        p.stem: load_module(bench_dir, "metrics", p.stem)
        for p in sorted((Path(bench_dir) / "metrics").glob("*.py"))
    }


def enable_compile_cache() -> None:
    """Persistent compilation cache at the checkout's fixed path (unless
    JAX_COMPILATION_CACHE_DIR is set), with every program cached, however
    quick its compilation."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def devices_for(cell: dict, require_tpu: bool):
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"chipbench: no TPU, JAX found {devs[0].platform!r} devices")
    if len(devs) < int(cell["chips"]):
        raise NoChip(f"chipbench: the cell needs {cell['chips']} chips, found {len(devs)}")
    return devs[: int(cell["chips"])]


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks, default=0)


_COMPILES = {"n": 0, "listening": False}


def compile_count() -> int:
    """Programs built in this process since the first call, compiled or read
    from the persistent cache (JAX's backend-compile monitoring event)."""
    if not _COMPILES["listening"]:
        import jax

        def on_event(event: str, duration: float, **kw) -> None:
            if "backend_compile" in event:
                _COMPILES["n"] += 1

        jax.monitoring.register_event_duration_secs_listener(on_event)
        _COMPILES["listening"] = True
    return _COMPILES["n"]


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, bench_dir: Path = BENCH_DIR, require_tpu: bool = True,
             peaks: dict | None = None, trace_dir: Path | None = None,
             compile_cache: bool = True) -> dict:
    """One run of one cell; returns the result line as a dict.  The program
    runs as a user calls it: the harness sets no JAX option that changes its
    numerics."""
    cell = load_json(bench_dir, "cells", workload)
    config = load_json(bench_dir, "configs", cell["config"])
    driver = load_module(bench_dir, "drivers", cell["driver"])
    if compile_cache:
        enable_compile_cache()
    devices = devices_for(cell, require_tpu)
    return _run(driver, cell, config, devices, workload, seed, seconds, trace,
                t_start=t_start, bench_dir=bench_dir, peaks=peaks, trace_dir=trace_dir)


def _run(driver, cell, config, devices, workload, seed, seconds, trace, *, t_start,
         bench_dir, peaks, trace_dir):
    if peaks is None:
        from chipbench.lib.peaks import peaks_for

        peaks = peaks_for(devices[0].device_kind)
    import jax

    ctx = SimpleNamespace(
        workload=workload, seed=int(seed), cell=cell, config=config,
        params=cell["traffic"], devices=devices, peaks=peaks, annotate=_annotate,
        seconds=float(cell["trace_seconds"]) if trace else float(seconds),
    )
    compiles_before = compile_count()
    state = driver.setup(ctx)
    settle_heap()
    setup_s = time.perf_counter() - t_start
    compiles_at_window = compile_count()
    pauses = GcPauses()
    steal0 = host_steal_ms()
    reduced = None
    if trace:
        tdir = Path(trace_dir or TRACE_DIR) / workload
        shutil.rmtree(tdir, ignore_errors=True)
        jax.profiler.start_trace(str(tdir))
        try:
            with _annotate("chipbench.window"), pauses:
                record = driver.window(state, ctx.seconds, ctx)
        finally:
            jax.profiler.stop_trace()
        from chipbench.lib import trace as tr

        reduced = tr.reduce_xplane(tr.find_xplane(str(tdir)))
        if trace_dir is None:
            shutil.rmtree(tdir, ignore_errors=True)
    else:
        with pauses:
            record = driver.window(state, ctx.seconds, ctx)
    gc.unfreeze()
    steal1 = host_steal_ms()
    window_programs = compile_count() - compiles_at_window
    mem = memory_peak(devices)
    checks = driver.check(state, record, ctx)
    del state
    correct = all(v <= lim for v, lim in checks.values())

    if trace:
        rctx = SimpleNamespace(record=record["record"], trace=reduced, peaks=peaks,
                               cell=cell, config=config, workload=workload)
        metrics = {}
        for name, reader in metric_readers(bench_dir).items():
            value = reader.read(rctx)
            if value is not None:
                metrics[name] = {"value": float(value), "unit": reader.UNIT}
    else:
        metrics = {k: {"value": float(v), "unit": u} for k, (v, u) in record["metrics"].items()}
        metrics["setup_s"] = {"value": float(setup_s), "unit": "s"}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    result = {"correct": bool(correct), "attempted": int(record["attempted"]),
              "failed": int(record["failed"]), "metrics": metrics, "device": device}
    if trace:
        from chipbench.lib.trace import breakdown

        device["busy_s"] = reduced["busy_ns"] * 1e-9
        device["window_s"] = reduced["window_ns"] * 1e-9
        result["breakdown"] = breakdown(reduced)
    result["info"] = dict(record.get("info", {}), window_programs=window_programs,
                          setup_programs=compiles_at_window - compiles_before,
                          gc_collections=len(pauses.ms), gc_pause_ms_max=max(pauses.ms, default=0.0),
                          host_steal_ms=None if steal0 is None else steal1 - steal0)
    result["checks"] = {k: {"value": float(v), "limit": float(lim)}
                        for k, (v, lim) in checks.items()}
    return result


def host_steal_ms() -> float | None:
    """CPU time the hypervisor has held from this machine, summed over its
    CPUs (ms, from /proc/stat), or None where the system does not say."""
    try:
        with open("/proc/stat") as f:
            fields = f.readline().split()
        return float(fields[8]) * 1e3 / os.sysconf("SC_CLK_TCK")
    except (OSError, IndexError, ValueError):
        return None


def settle_heap() -> None:
    """Collect, then move every object set-up left into the collector's
    permanent generation.  A full collection walks every tracked object, some
    180k once JAX and the program are loaded; after the freeze it walks only
    what the window itself keeps."""
    gc.collect()
    gc.freeze()


class GcPauses:
    """The collector's pauses (ms) while the context is active."""

    def __init__(self):
        self.ms, self._t0 = [], None

    def _on(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.ms.append((time.perf_counter() - self._t0) * 1e3)
            self._t0 = None

    def __enter__(self):
        gc.callbacks.append(self._on)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._on)
        return False


def _annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def emit(result: dict, out=None, err=None) -> None:
    """The compared numbers as the last lines of stderr, then the result as
    the last line of stdout."""
    out, err = out or sys.stdout, err or sys.stderr
    for name, c in result["checks"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name} = {c['value']!r} limit {c['limit']!r} {verdict}", file=err)
    err.flush()
    print(json.dumps(result), file=out)
    out.flush()
