"""Seeded domains at a configuration's published sizes, made on the device.

Each domain is a class mixture with a cheap per-domain affine shift:

    x = a_d * (mu_y + noise * eps) + b_d,   then unit Euclidean norm,

with class means mu_c ~ N(0, I/p) * class_sep shared by every domain, labels
uniform over the classes, a_d = 1 + jitter * U(-1, 1)^p a diagonal scale and
b_d ~ N(0, I/p) * shift a translation of domain d.  Columns are samples and
have unit norm, as pooled ResNet-50 features are preprocessed in the FDA
papers.  All domains of a call come out of one jitted program, so set-up pays
one dispatch and no host eigensolve.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def base_key(seed: int) -> jax.Array:
    """A PRNG key for any whole seed up to 2**63 (both 32-bit halves count)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0xFFFFFFFF)


def derived_seed(seed: int, *path: int) -> int:
    """A 31-bit seed drawn from ``seed`` and a path of indices."""
    state = np.random.SeedSequence([int(seed), *map(int, path)]).generate_state(1)
    return int(state[0] & 0x7FFFFFFF)


@functools.partial(jax.jit, static_argnames=("sizes", "ids", "p", "n_classes", "gen"))
def _make(key, *, sizes: tuple[int, ...], ids: tuple[int, ...], p: int, n_classes: int,
          gen: tuple):
    class_sep, noise, jitter, shift = gen
    k_mu, k_dom = jax.random.split(key)
    mu = jax.random.normal(k_mu, (p, n_classes), jnp.float32) * (class_sep / np.sqrt(p))
    out = []
    for d, n in zip(ids, sizes):  # a domain's draw depends on its own index only
        k_y, k_e, k_a, k_b = jax.random.split(jax.random.fold_in(k_dom, d), 4)
        y = jax.random.randint(k_y, (n,), 0, n_classes, jnp.int32)
        eps = jax.random.normal(k_e, (p, n), jnp.float32) * (noise / np.sqrt(p))
        a = 1.0 + jitter * jax.random.uniform(k_a, (p, 1), jnp.float32, -1.0, 1.0)
        b = jax.random.normal(k_b, (p, 1), jnp.float32) * (shift / np.sqrt(p))
        x = a * (mu[:, y] + eps) + b
        x = x * jax.lax.rsqrt(jnp.sum(x * x, axis=0, keepdims=True))
        out.append((x, y))
    return tuple(out)


def make_domains(config: dict, names: list[str], seed: int) -> dict[str, tuple]:
    """{name: (x (p, n) float32, y (n,) int32)} on the default device for the
    named domains of ``config``, at the sizes the configuration states."""
    gen = config["generator"]
    order = list(config["domains"])
    sizes = tuple(int(config["domains"][n]) for n in names)
    made = _make(
        base_key(seed), sizes=sizes, ids=tuple(order.index(n) for n in names),
        p=int(config["feature_dim"]),
        n_classes=int(config["n_classes"]),
        gen=(float(gen["class_sep"]), float(gen["noise"]),
             float(gen["scale_jitter"]), float(gen["shift"])),
    )
    return dict(zip(names, made))
