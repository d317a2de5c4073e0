"""A whole run with the timed path broken underneath comes out not correct:
one case for each fault a cell can have (one chip: no exchange between
chips to leave out), and one for each control, the reference one precision
below put in the program's place."""
import json
import sys
from pathlib import Path

import jax
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import tinybench  # noqa: E402

from chipbench.faults import CONTROLS, FAULTS  # noqa: E402

CASES = [(driver, fault) for driver, faults in FAULTS.items() for fault in faults]


@pytest.mark.parametrize("driver, fault", CASES, ids=[f"{d}-{f}" for d, f in CASES])
def test_broken_timed_path_is_not_correct(tmp_path, driver, fault):
    bench = tinybench.make(tmp_path)
    with FAULTS[driver][fault]():
        res = tinybench.run(bench, f"tiny.{driver}")
    assert res["correct"] is False, res["checks"]
    clean = tinybench.run(bench, f"tiny.{driver}", seed=5)
    assert clean["correct"], clean["checks"]


# The tiny fit's limits, set from 13 seeds at this size on the CPU: the program
# reads eig_rel <= 2.3e-6 and w_resid <= 3.0e-6, the control >= 5.7e-6 and
# >= 1.3e-5.  (At the cells' sizes the limits come from chip runs.)
TINY_LIMITS = {"fit": {"eig_rel": 4e-6, "w_resid": 6e-6}}


@pytest.mark.parametrize("driver", sorted(CONTROLS))
def test_control_in_the_programs_place_is_not_correct(tmp_path, driver):
    bench = tinybench.make(tmp_path)
    cell = json.loads((bench / "cells" / f"tiny.{driver}.json").read_text())
    cell["limits"].update(TINY_LIMITS.get(driver, {}))
    tinybench.write(bench, "cells", f"tiny.{driver}", cell)
    for seed in (3, 2**31 + 17):
        with CONTROLS[driver]():
            res = tinybench.run(bench, f"tiny.{driver}", seed=seed)
        assert res["correct"] is False, res["checks"]
        clean = tinybench.run(bench, f"tiny.{driver}", seed=seed)
        assert clean["correct"], clean["checks"]


def test_runs_leave_jax_settings_as_they_were():
    assert jax.config.jax_default_matmul_precision is None
