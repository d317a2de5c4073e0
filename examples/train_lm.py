"""Train an assigned-architecture LM with the FDA head active (eq. 12 on the
client=data-shard axis), asserting the loss decreases.

    PYTHONPATH=src python examples/train_lm.py                 # reduced (CPU)
    PYTHONPATH=src python examples/train_lm.py --arch smollm-135m --full

The reduced default finishes in ~2 min on CPU; --full runs the real config
(use the production mesh + dryrun-verified shardings for that).
"""
import argparse
import sys

sys.path.insert(0, "src")

from repro.launch import train as train_mod


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--steps", type=int, default=150)
    args = ap.parse_args()

    argv = ["--arch", args.arch, "--steps", str(args.steps), "--batch", "8",
            "--seq", "128", "--clients", "2", "--log-every", "25"]
    if not args.full:
        argv.append("--reduced")
    out = train_mod.main(argv)
    assert out["last"] < out["first"], "loss must decrease"
    print("OK: loss decreased", f"{out['first']:.3f} -> {out['last']:.3f}")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
