"""Whole runs of one cell over several seeds in one process, with the timed
path as it is or with a control or a fault of ``chipbench/faults.py`` planted
under it: the readings the correctness limits are set from.

    python chipbench/control.py --workload <cell> --seeds 1,2,3 [--plant control] [--seconds 5]
    python chipbench/control.py --workload office31.serve --seeds 1 --sweep 600,800,1000

``--plant none`` (the default) runs the program as it is; ``control`` puts the
plain reference one precision below the configuration's in the program's
place; a name of ``WITNESSES`` runs the program under that witness; any other
name is a fault.  Prints one JSON line per seed with
``correct`` and every compared number beside its limit.  ``--sweep`` runs the
driver's open loop at several offered rates on one server instead.  The
benchmark's own runs never run this.
"""
import argparse
import json
import sys
import time
from pathlib import Path
from types import SimpleNamespace

CHECKOUT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--plant", default="none",
                    help="none, control, or a witness or fault of chipbench/faults.py")
    ap.add_argument("--seconds", type=float, default=5.0, help="window of each run")
    ap.add_argument("--sweep", default=None,
                    help="comma-separated offered rates: run the driver's sweep instead")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]
    from chipbench import harness
    from chipbench.faults import CONTROLS, FAULTS, WITNESSES

    cell = harness.load_json(harness.BENCH_DIR, "cells", args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    if args.sweep:
        sweep(args, cell, seeds)
        return 0
    plants = {"control": CONTROLS.get(cell["driver"]), **FAULTS.get(cell["driver"], {}),
              **WITNESSES.get(cell["driver"], {})}
    for seed in seeds:
        t0 = time.perf_counter()
        if args.plant == "none":
            res = harness.run_cell(args.workload, seed, args.seconds, False, t_start=t0)
        else:
            with plants[args.plant]():
                res = harness.run_cell(args.workload, seed, args.seconds, False, t_start=t0)
        print(json.dumps({"workload": args.workload, "seed": seed, "plant": args.plant,
                          "correct": res["correct"], "checks": res["checks"],
                          "metrics": res["metrics"], "info": res["info"],
                          "seconds": time.perf_counter() - t0}), flush=True)
    return 0


def sweep(args, cell, seeds):
    from chipbench import harness

    config = harness.load_json(harness.BENCH_DIR, "configs", cell["config"])
    driver = harness.load_module(harness.BENCH_DIR, "drivers", cell["driver"])
    harness.enable_compile_cache()
    devices = harness.devices_for(cell, require_tpu=True)
    rates = [float(r) for r in args.sweep.split(",")]
    for seed in seeds:
        ctx = SimpleNamespace(workload=args.workload, seed=seed, cell=cell, config=config,
                              params=cell["traffic"], devices=devices, peaks=None,
                              annotate=harness._annotate, seconds=args.seconds)
        for row in driver.sweep(ctx, rates, args.seconds):
            print(json.dumps({"workload": args.workload, "seed": seed, **row}), flush=True)


if __name__ == "__main__":
    sys.exit(main())
