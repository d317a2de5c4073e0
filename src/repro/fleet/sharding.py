"""Sharded + memory-bounded execution over the stacked client axis.

The batched round engine stacks per-client state on a leading K axis and
``vmap``s the local-step body across it — one compiled dispatch, but a
working set proportional to K.  At fleet scale (K in the thousands) that
O(K) working set is the ceiling, so this module provides the two axes the
fleet plane composes:

- :func:`chunked_vmap` — a drop-in ``vmap`` whose leading axis is consumed
  ``client_chunk`` rows at a time through ``lax.map``: only one chunk of
  activations/gradients is ever live, so the per-device working set of the
  local-step stage is O(chunk), not O(K).  ``chunk=None`` (or chunk >= K) is
  exactly ``jax.vmap`` — the unchunked program, bit for bit.  K that does not
  divide by the chunk is padded by repeating row 0 (finite values — zero rows
  would hit the extractor's unit-norm NaN gradient) and sliced back after.
- :func:`client_mesh` / :func:`sharded_client_map` — ``shard_map`` over a
  ``clients`` mesh axis: the stacked arrays are partitioned across devices,
  every shard runs the same (optionally chunked) per-client body on its K/D
  rows, and no collective is needed because the fleet plane's cross-client
  reductions happen in the edge/server merge, not in the local step.  On one
  host a 1-device mesh is the mocked-mesh path the bitwise equivalence tests
  run; the same code lowers to a real multi-device mesh unchanged.

:func:`working_set_proxy` is the measurable twin of the O(chunk) claim: the
largest intermediate the traced program materializes, read from the jaxpr —
the quantity ``BENCH_fleet.json`` records against K.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.extend.core import ClosedJaxpr, Jaxpr
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_mesh


def chunked_vmap(fn, in_axes, *, chunk: int | None):
    """``jax.vmap(fn, in_axes)`` evaluated ``chunk`` rows at a time.

    ``in_axes`` must be a tuple of ``0`` (mapped on the leading axis) or
    ``None`` (broadcast).  Outputs are assumed mapped on axis 0, like the
    engine's per-client bodies.  With ``chunk=None`` (or >= K) this *is*
    ``jax.vmap`` — same program, bitwise.  Otherwise the mapped inputs are
    reshaped to ``(K/chunk, chunk, ...)`` and fed through ``jax.lax.map``,
    so XLA holds one chunk of the body's intermediates at a time.
    """
    vf = jax.vmap(fn, in_axes=in_axes)
    if chunk is not None and chunk <= 0:
        raise ValueError(f"chunk must be a positive int or None, got {chunk}")

    def run(*args):
        if len(args) != len(in_axes):
            raise ValueError(f"{len(args)} args for in_axes of length {len(in_axes)}")
        mapped_leaves = [
            leaf
            for a, ax in zip(args, in_axes)
            if ax == 0
            for leaf in jax.tree_util.tree_leaves(a)
        ]
        if not mapped_leaves:
            raise ValueError("chunked_vmap needs at least one mapped (axis-0) argument")
        k = mapped_leaves[0].shape[0]
        if chunk is None or chunk >= k:
            return vf(*args)
        n_chunks = -(-k // chunk)
        pad = n_chunks * chunk - k

        def pack(x):
            if pad:
                x = jnp.concatenate([x, jnp.repeat(x[:1], pad, axis=0)], axis=0)
            return x.reshape((n_chunks, chunk) + x.shape[1:])

        packed = tuple(
            jax.tree_util.tree_map(pack, a) if ax == 0 else None
            for a, ax in zip(args, in_axes)
        )

        def body(sliced):
            full = tuple(
                s if ax == 0 else a for s, a, ax in zip(sliced, args, in_axes)
            )
            return vf(*full)

        out = jax.lax.map(body, packed)

        def unpack(x):
            x = x.reshape((n_chunks * chunk,) + x.shape[2:])
            return x[:k] if pad else x

        return jax.tree_util.tree_map(unpack, out)

    return run


def client_mesh(n_shards: int) -> Mesh:
    """A 1-D ``clients`` mesh over the first ``n_shards`` devices.  On a
    single-host CPU run ``n_shards=1`` is the mocked mesh; more devices come
    from ``XLA_FLAGS=--xla_force_host_platform_device_count`` or real TPUs."""
    devs = jax.devices()[:n_shards]
    if len(devs) < n_shards:
        raise ValueError(
            f"need {n_shards} devices for the clients mesh, have {len(devs)};"
            " set XLA_FLAGS=--xla_force_host_platform_device_count"
        )
    return make_mesh((n_shards,), ("clients",), devs)


def sharded_client_map(mesh: Mesh, fn, in_axes, *, chunk: int | None = None):
    """``shard_map`` the (chunked) per-client body over the ``clients`` axis.

    Mapped (axis-0) arguments are partitioned on their leading K axis across
    the mesh; broadcast (``None``) arguments are replicated.  Each shard runs
    :func:`chunked_vmap` on its local rows — the local-step stage has no
    cross-client dependency, so there is nothing to ``psum``; the cross-client
    work (edge/server merges) happens outside, on the gathered outputs.  K
    must divide by the mesh size (callers pad the stacked state once, not per
    round).
    """
    inner = chunked_vmap(fn, in_axes, chunk=chunk)
    spec = tuple(P("clients") if ax == 0 else P() for ax in in_axes)

    def run(*args):
        in_specs = tuple(
            jax.tree_util.tree_map(lambda _: s, a)
            if a is not None
            else s
            for a, s in zip(args, spec)
        )
        out = jax.eval_shape(inner, *args)
        out_specs = jax.tree_util.tree_map(lambda _: P("clients"), out)
        return jax.shard_map(
            inner, mesh=mesh, in_specs=in_specs, out_specs=out_specs
        )(*args)

    return run


def eqn_jaxprs(eqn):
    """The jaxprs an equation carries in its params (``scan``/``map``/
    ``cond``/``pjit`` bodies, a ``pallas_call`` kernel)."""
    for v in eqn.params.values():
        for item in v if isinstance(v, (tuple, list)) else (v,):
            if isinstance(item, ClosedJaxpr):
                yield item.jaxpr
            elif isinstance(item, Jaxpr):
                yield item


def sub_jaxprs(jaxpr):
    """Every jaxpr nested one level inside ``jaxpr``'s equations."""
    for eqn in jaxpr.eqns:
        yield from eqn_jaxprs(eqn)


def working_set_proxy(fn, *args) -> int:
    """Largest transient intermediate (bytes) the traced ``fn(*args)`` makes.

    Traces ``fn`` to a jaxpr and returns the byte size of the biggest array
    any *compute* primitive produces.  Equations that carry a sub-jaxpr
    (``lax.map``/``scan``/cond wrappers) are charged for their body's
    intermediates instead of their own stacked outputs, and pure
    data-movement primitives (reshape/transpose/concat/slice...) are skipped
    — the stacked carry and its repackings are persistent state (the
    (K, ...) parameters, identical under every chunk size), while the
    compute intermediates are the live activation set the ``client_chunk``
    scan exists to bound.  This is the memory-proxy twin of the O(chunk)
    claim, comparable across chunk sizes the way the kernel VMEM proxies of
    PR 3 are comparable across tiles.
    """
    jaxpr = jax.make_jaxpr(fn)(*args)

    data_movement = {
        "reshape", "broadcast_in_dim", "transpose", "squeeze", "expand_dims",
        "concatenate", "pad", "copy", "convert_element_type", "slice",
        "dynamic_slice", "gather", "rev",
    }

    def scan_eqns(jx) -> int:
        worst = 0
        for eqn in jx.eqns:
            subs = list(eqn_jaxprs(eqn))
            if subs:
                for sub in subs:
                    worst = max(worst, scan_eqns(sub))
                continue  # wrapper outputs are persistent carry, not live set
            if eqn.primitive.name in data_movement:
                continue  # repackings of persistent state, not live compute
            for var in eqn.outvars:
                aval = var.aval
                if hasattr(aval, "shape") and hasattr(aval, "dtype"):
                    size = int(aval.size) * aval.dtype.itemsize
                    worst = max(worst, size)
        return worst

    return scan_eqns(jaxpr.jaxpr)
