"""Run one benchmark cell once on the chips of this machine.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints the result as one JSON object on the last line of standard output and
the compared numbers, each with its limit, as the last lines of standard
error.  Exits non-zero, printing no result, when JAX finds no TPU, fewer
chips than the cell asks for, a device missing from ``peaks.json``, or no
system under test beside the benchmark.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

CHECKOUT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the raw profiler trace here (default: read, then delete)")
    args = ap.parse_args(argv)
    if not (CHECKOUT / "src" / "repro").is_dir():
        print(f"chipbench: no system under test at {CHECKOUT / 'src' / 'repro'}",
              file=sys.stderr)
        return 2
    sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the TPU runtime logs under /tmp
    from chipbench import harness
    from chipbench.lib.peaks import UnknownDevice

    try:
        result = harness.run_cell(
            args.workload, args.seed, args.seconds, bool(args.trace), t_start=T_START,
            trace_dir=Path(args.trace_dir) if args.trace_dir else None,
        )
    except harness.NoChip as e:
        print(e, file=sys.stderr)
        return 3
    except UnknownDevice as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 4
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
