"""Serve a small model with batched requests: prefill + greedy decode.

    PYTHONPATH=src python examples/serve_batch.py --arch mamba2-2.7b
"""
import argparse
import sys

sys.path.insert(0, "src")

from repro.launch import serve as serve_mod


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--batch", type=int, default=4)
    args = ap.parse_args()
    out = serve_mod.main(
        ["--arch", args.arch, "--reduced", "--batch", str(args.batch),
         "--prompt-len", "32", "--gen", "16"]
    )
    print("OK: served", out["tokens"].shape, "tokens")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
