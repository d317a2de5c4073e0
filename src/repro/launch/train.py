"""Training driver: any assigned arch (full or reduced) on the host mesh.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-135m --reduced \
        --steps 200 --batch 8 --seq 128 --ckpt /tmp/ckpt

Full configs target the production mesh (see dryrun.py); --reduced trains the
smoke-scale variant end-to-end on CPU with loss-decrease checks. The FDA MMD
head is active whenever the data mesh has >1 client (or --clients is given).
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint as ckpt_lib
from repro.configs import get_config
from repro.data import TokenStream
from repro.launch.mesh import make_host_mesh
from repro.models import LM, ShardRules
from repro.optim import adamw, apply_updates, clip_by_global_norm, cosine_schedule


def build_train_step(model: LM, opt, n_clients: int):
    def train_step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: model.loss(p, batch, n_clients), has_aux=True
        )(params)
        grads, gnorm = clip_by_global_norm(grads, 1.0)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = apply_updates(params, updates)
        return params, opt_state, {**metrics, "loss": loss, "grad_norm": gnorm}

    return train_step


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--clients", type=int, default=0)
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--log-every", type=int, default=20)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    cfg = dataclasses.replace(cfg, remat=False) if args.reduced else cfg

    mesh = make_host_mesh()
    rules = ShardRules(model_size=int(mesh.shape["model"]), batch_axes=("data",))
    model = LM(cfg, rules)
    n_clients = args.clients or max(2, int(mesh.shape["data"]))
    if args.batch % n_clients:
        n_clients = 1

    key = jax.random.PRNGKey(0)
    params = model.init(key)
    opt = adamw(cosine_schedule(args.lr, warmup=10, total=args.steps), weight_decay=0.01)
    opt_state = opt.init(params)
    start_step = 0
    if args.ckpt:
        latest = ckpt_lib.latest_step(args.ckpt)
        if latest is not None:
            params = ckpt_lib.restore(args.ckpt, params)
            start_step = latest
            print(f"restored step {start_step} from {args.ckpt}")

    stream = TokenStream(cfg.vocab_size, args.batch, args.seq, seed=1)
    step_fn = jax.jit(build_train_step(model, opt, n_clients))

    losses = []
    t0 = time.time()
    for step in range(start_step, args.steps):
        batch_np = next(stream)
        batch = {k: jnp.asarray(v) for k, v in batch_np.items()}
        if cfg.embeddings_in:
            emb = jax.random.normal(
                jax.random.fold_in(key, step), (args.batch, args.seq, cfg.d_model)
            ) * 0.02
            batch = {"embeddings": emb, "labels": batch["labels"]}
        if cfg.family == "vlm":
            batch["images"] = jnp.zeros((args.batch, cfg.n_image_tokens, cfg.d_image))
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        losses.append(float(metrics["loss"]))
        if (step + 1) % args.log_every == 0:
            dt = (time.time() - t0) / args.log_every
            toks = args.batch * args.seq / dt
            print(
                f"step {step+1}: loss={losses[-1]:.4f} ce={float(metrics['ce']):.4f} "
                f"mmd={float(metrics['mmd']):.5f} gnorm={float(metrics['grad_norm']):.2f} "
                f"{toks:,.0f} tok/s"
            )
            t0 = time.time()
        if args.ckpt and (step + 1) % 100 == 0:
            ckpt_lib.save(args.ckpt, params, step=step + 1)
    if args.ckpt:
        ckpt_lib.save(args.ckpt, params, step=args.steps)
    first = float(np.mean(losses[:10])) if len(losses) >= 10 else losses[0]
    last = float(np.mean(losses[-10:]))
    print(f"loss: first10={first:.4f} last10={last:.4f} (improved={last < first})")
    return {"first": first, "last": last, "losses": losses}


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
