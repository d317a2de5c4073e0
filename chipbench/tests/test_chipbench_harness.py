"""The harness finds cells, configurations and metrics by name, and a whole
run (set-up, window, check) of each driver comes out correct at a tiny size
on the CPU."""
import json
import subprocess
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import tinybench  # noqa: E402

ROOT = tinybench.ROOT


@pytest.mark.parametrize("driver", ["fit", "rounds", "serve"])
def test_each_driver_runs_correct_at_tiny_size(tmp_path, driver):
    bench = tinybench.make(tmp_path)
    res = tinybench.run(bench, f"tiny.{driver}")
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert "setup_s" in res["metrics"] and len(res["metrics"]) >= 2
    assert list(res)[-1] == "checks"
    assert res["info"]["window_programs"] == 0


def test_cell_config_and_metric_found_from_files_alone(tmp_path):
    """A configuration, a cell and a per-layer metric dropped into their
    directories run with no other edit."""
    bench = tinybench.make(tmp_path)
    config = json.loads((bench / "configs" / "tiny.json").read_text())
    config.update(name="tiny2", domains={"amazon": 400, "dslr": 200, "webcam": 250})
    tinybench.write(bench, "configs", "tiny2", config)
    cell = json.loads((bench / "cells" / "tiny.fit.json").read_text())
    cell.update(config="tiny2")
    tinybench.write(bench, "cells", "tiny2.fit", cell)
    (bench / "metrics" / "fit.count.py").write_text(
        'UNIT = "fits"\n\n\ndef read(ctx):\n    return ctx.record.get("fits")\n')
    res = tinybench.run(bench, "tiny2.fit", trace=True)
    assert res["correct"], res["checks"]
    assert res["metrics"]["fit.count"]["value"] == res["attempted"]
    assert res["metrics"]["fit.count"]["unit"] == "fits"
    # readers that find nothing to read leave their metric out
    assert not any(k.startswith(("rounds.", "serve.")) for k in res["metrics"])
    assert res["device"]["window_s"] > 0 and "breakdown" in res


def test_traced_run_reports_per_layer_metrics_of_its_cell(tmp_path):
    bench = tinybench.make(tmp_path)
    res = tinybench.run(bench, "tiny.rounds", trace=True)
    assert set(res["metrics"]) <= {"rounds.device_ms", "rounds.idle_share"}
    assert "rounds.idle_share" in res["metrics"]
    assert res["breakdown"]["idle_gaps"][0][0].startswith("chipbench.")


def _run_py(args, cwd, env_extra):
    import os

    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=120)


def test_run_exits_nonzero_without_tpu():
    proc = _run_py(["chipbench/run.py", "--workload", "office31.fit", "--seed", "1",
                    "--seconds", "1", "--trace", "0"], ROOT, {})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_run_exits_nonzero_with_only_the_benchmark(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's files has
    no system under test: no result, a non-zero exit."""
    import shutil

    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    proc = _run_py(["chipbench/run.py", "--workload", "office31.fit", "--seed", "1",
                    "--seconds", "1", "--trace", "0"], tmp_path, {})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_every_cell_names_files_that_exist():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    d = ROOT / "chipbench"
    metrics = {p.stem for p in (d / "metrics").glob("*.py")}
    for w in bench["workloads"]:
        cell = json.loads((d / "cells" / f"{w['name']}.json").read_text())
        assert cell["config"] == w["config"]
        assert cell["chips"] == w["chips"]
        assert (d / "configs" / f"{cell['config']}.json").is_file()
        assert (d / "drivers" / f"{cell['driver']}.py").is_file()
    assert {m["name"] for m in bench["per_layer"]} <= metrics
