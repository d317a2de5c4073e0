"""A whole run with the timed path broken underneath comes out not correct:
one case for each fault a cell can have (one chip: no exchange between
chips to leave out), and one for each control, the reference one precision
below put in the program's place."""
import sys
from pathlib import Path

import jax
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import tinybench  # noqa: E402

from chipbench.faults import CONTROLS, FAULTS, WITNESSES  # noqa: E402

CASES = [(driver, fault) for driver, faults in FAULTS.items() for fault in faults]


@pytest.mark.parametrize("driver, fault", CASES, ids=[f"{d}-{f}" for d, f in CASES])
def test_broken_timed_path_is_not_correct(tmp_path, driver, fault):
    bench = tinybench.make(tmp_path)
    with FAULTS[driver][fault]():
        res = tinybench.run(bench, f"tiny.{driver}")
    assert res["correct"] is False, res["checks"]
    clean = tinybench.run(bench, f"tiny.{driver}", seed=5)
    assert clean["correct"], clean["checks"]


@pytest.mark.parametrize("driver", sorted(CONTROLS))
def test_control_in_the_programs_place_is_not_correct(tmp_path, driver):
    bench = tinybench.make(tmp_path)
    for seed in (3, 2**31 + 17):
        with CONTROLS[driver]():
            res = tinybench.run(bench, f"tiny.{driver}", seed=seed)
        assert res["correct"] is False, res["checks"]
        clean = tinybench.run(bench, f"tiny.{driver}", seed=seed)
        assert clean["correct"], clean["checks"]


def test_window_fault_is_caught_by_the_window_rounds_alone(tmp_path):
    """A round that goes wrong only after the set-up rounds passes every
    number of the set-up rounds and fails the window rounds' change."""
    bench = tinybench.make(tmp_path)
    with FAULTS["rounds"]["window_state_altered"]():
        res = tinybench.run(bench, "tiny.rounds")
    checks = res["checks"]
    for name in ("grad_gap", "change_gap", "bytes_gap"):
        assert checks[name]["value"] <= checks[name]["limit"], checks
    assert checks["round_change_gap"]["value"] > checks["round_change_gap"]["limit"], checks
    assert res["correct"] is False


def test_witness_sets_the_precision_only_while_active():
    with WITNESSES["rounds"]["highest"]():
        assert jax.config.jax_default_matmul_precision == "highest"
    assert jax.config.jax_default_matmul_precision is None


def test_runs_leave_jax_settings_as_they_were():
    assert jax.config.jax_default_matmul_precision is None
