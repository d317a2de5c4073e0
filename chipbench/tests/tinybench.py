"""A copy of the benchmark with tiny cells, for the CPU tests.

``make(tmp_path)`` copies ``chipbench/`` into ``tmp_path`` and adds the
configuration ``tiny`` (p = 32, three classes, a few hundred samples per
domain) and one tiny cell per driver: ``tiny.fit``, ``tiny.rounds``,
``tiny.serve``.  ``run(bench, cell, ...)`` runs a cell once on the CPU,
without the look for a chip and without the persistent compilation cache.
"""
from __future__ import annotations

import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for _p in (ROOT / "src", ROOT):  # the system under test, and the benchmark
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

TINY_DOMAINS = {"amazon": 600, "dslr": 300, "webcam": 300}
TINY_TRAFFIC = {
    "fit": {"n_features": 16, "m": 4},
    "rounds": {"n_rff": 16, "m": 4},
    "serve": {"n_features": 16, "m": 4, "width_hi": 64},
}


def make(tmp_path: Path) -> Path:
    bench = Path(tmp_path) / "chipbench"
    shutil.copytree(ROOT / "chipbench", bench,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    config = json.loads((bench / "configs" / "office31.json").read_text())
    config.update(name="tiny", feature_dim=32, n_classes=3, domains=TINY_DOMAINS)
    write(bench, "configs", "tiny", config)
    for driver, traffic in TINY_TRAFFIC.items():
        cell = json.loads((bench / "cells" / f"office31.{driver}.json").read_text())
        cell.update(config="tiny", trace_seconds=0.3, traffic={**cell["traffic"], **traffic})
        write(bench, "cells", f"tiny.{driver}", cell)
    return bench


def write(bench: Path, kind: str, name: str, obj) -> None:
    path = Path(bench) / kind / f"{name}.json"
    path.write_text(json.dumps(obj))


def run(bench: Path, cell: str, *, seed: int = 2**31 + 17, seconds: float = 0.3,
        trace: bool = False) -> dict:
    from chipbench import harness
    from chipbench.lib.peaks import peaks_for

    return harness.run_cell(
        cell, seed, seconds, trace, t_start=time.perf_counter(), bench_dir=bench,
        require_tpu=False, peaks=peaks_for("TPU v5 lite"), compile_cache=False,
        trace_dir=Path(bench).parent / "trace" if trace else None,
    )
