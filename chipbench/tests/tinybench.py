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
# The tiny cells' limits, set from seeds at this size on the CPU (at the cells'
# sizes the limits come from chip runs).  Fit, 13 seeds: the program reads
# eig_rel <= 2.3e-6 and w_resid <= 3.0e-6, the control >= 5.7e-6 and >= 1.3e-5.
# Rounds, 44 seeds: the program reads grad_gap <= 1.8e-7, change_gap <= 1.9e-6,
# round_grad_gap <= 5.2e-7, round_grad_rel <= 1.7e-6 and round_change_gap
# <= 5.9e-6; the control, over 8 seeds, >= 3.0e-4, 3.0e-5, 1.3e-3, 2.9e-3 and
# 2.0e-4.
TINY_LIMITS = {"fit": {"eig_rel": 4e-6, "w_resid": 6e-6},
               "rounds": {"grad_gap": 2e-5, "change_gap": 1e-5, "round_grad_gap": 3e-5,
                          "round_grad_rel": 1e-4, "round_change_gap": 3e-5}}


def make(tmp_path: Path) -> Path:
    bench = Path(tmp_path) / "chipbench"
    shutil.copytree(ROOT / "chipbench", bench,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    config = json.loads((bench / "configs" / "office31.json").read_text())
    config.update(name="tiny", feature_dim=32, n_classes=3, domains=TINY_DOMAINS)
    write(bench, "configs", "tiny", config)
    for driver, traffic in TINY_TRAFFIC.items():
        cell = json.loads((bench / "cells" / f"office31.{driver}.json").read_text())
        cell.update(config="tiny", trace_seconds=0.3, traffic={**cell["traffic"], **traffic},
                    limits={**cell["limits"], **TINY_LIMITS.get(driver, {})})
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
