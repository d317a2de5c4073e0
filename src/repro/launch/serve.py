"""Serving driver: prefill a batch of prompts, then decode greedily.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --reduced \
        --prompt-len 64 --gen 32 --batch 4
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.models import LM, ShardRules

# every attention-cache leaf grows along axis 2 (the sequence axis), whether
# it is a plain KV pair, a windowed variant, or an MLA latent/rope column
_CACHE_GROW_KEYS = ("k", "v", "attn_k", "attn_v", "c", "kr")


def grow_cache(tree, extra: int, *, keys: tuple[str, ...] = _CACHE_GROW_KEYS):
    """Pad every cache leaf under a growable key by ``extra`` slots on the
    sequence axis (axis 2), recursing through nested dicts."""
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out[k] = grow_cache(v, extra, keys=keys)
        elif k in keys:
            pad = [(0, 0)] * v.ndim
            pad[2] = (0, extra)
            out[k] = jnp.pad(v, pad)
        else:
            out[k] = v
    return out


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = LM(cfg, ShardRules(model_size=1))
    key = jax.random.PRNGKey(0)
    params = model.init(key)

    total = args.prompt_len + args.gen
    prompts = jax.random.randint(key, (args.batch, args.prompt_len), 0, cfg.vocab_size)
    batch = {"tokens": prompts}
    if cfg.embeddings_in:
        emb = jax.random.normal(key, (args.batch, args.prompt_len, cfg.d_model)) * 0.02
        batch = {"embeddings": emb}
    if cfg.family == "vlm":
        batch["images"] = jnp.zeros((args.batch, cfg.n_image_tokens, cfg.d_image))

    t0 = time.time()
    prefill = jax.jit(model.prefill)
    logits, cache = prefill(params, batch)
    # grow attention caches to hold generated tokens
    cache = grow_cache(cache, args.gen)
    t_prefill = time.time() - t0

    decode = jax.jit(model.decode_step)
    tok = jnp.argmax(logits[:, : cfg.vocab_size], axis=-1)[:, None]
    out_tokens = [np.asarray(tok)]
    t0 = time.time()
    for i in range(args.gen - 1):
        db = {"tokens": tok}
        if cfg.embeddings_in:
            db = {"embeddings": jax.random.normal(key, (args.batch, 1, cfg.d_model)) * 0.02}
        logits, cache = decode(params, cache, db, jnp.int32(args.prompt_len + i))
        tok = jnp.argmax(logits[:, : cfg.vocab_size], axis=-1)[:, None]
        out_tokens.append(np.asarray(tok))
    t_decode = time.time() - t0
    gen = np.concatenate(out_tokens, axis=1)
    tps = args.batch * (args.gen - 1) / max(t_decode, 1e-9)
    print(f"prefill {args.prompt_len} toks x{args.batch}: {t_prefill:.2f}s")
    print(f"decode  {args.gen-1} steps x{args.batch}: {t_decode:.2f}s ({tps:,.1f} tok/s)")
    print("sample:", gen[0][:16])
    assert np.isfinite(gen).all()
    return {"prefill_s": t_prefill, "decode_s": t_decode, "tokens": gen}


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
