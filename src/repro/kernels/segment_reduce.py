"""Pallas TPU kernel: weighted segment-reduce for grouped moment merges.

The two-tier fleet plane merges K client payloads into E edge partials:

    out[e, :] = sum_k M[e, k] * w[k] * values[k, :]

with M the (E, K) 0/1 edge-membership matrix and w the per-client merge
weights (participation masks x staleness weights).  Expressed as a matmul of
the weighted membership ``WM = M * w`` against the stacked values, the MXU
does the segment reduction directly — no scatter, no sort — and the same
kernel serves every payload kind by flattening trailing dims into D.

Grid: ``(D/td, K/bk)`` with the client-block loop innermost; each step
accumulates ``WM[:, k-block] @ values[k-block, d-tile]`` into its (E_pad, td)
fp32 output block, which stays resident in VMEM across the K loop.  Tiling D
keeps the VMEM per instance at two (bk, td) value blocks whatever the payload
width — a flattened W_RF at N = 1024, m = 32 is D = 65,536.

``kernels.ref.segment_reduce_ref`` is the XLA twin (same contraction); the
fleet merge code uses the twin on non-TPU backends where interpret-mode
Pallas is slow, exactly like the streaming-Gram solver does.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _segment_reduce_kernel(wm_ref, v_ref, out_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += jax.lax.dot(
        wm_ref[...], v_ref[...], precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def segment_reduce_pallas(
    wm: jax.Array,  # (E_pad, K_pad) fp32 weighted membership M * w
    values: jax.Array,  # (K_pad, D_pad) fp32 stacked client payloads
    *,
    block_k: int = 128,
    block_d: int,
    interpret: bool,
) -> jax.Array:
    """(E_pad, D_pad) fp32 weighted segment sums; see module docstring."""
    e_pad, k_pad = wm.shape
    k_v, d_pad = values.shape
    bk = min(block_k, k_pad)
    td = min(block_d, d_pad)
    if k_v != k_pad or k_pad % bk or d_pad % td:
        raise ValueError(
            f"wm {wm.shape} / values {values.shape} must share K%{bk}==0, D%{td}==0"
        )
    return pl.pallas_call(
        _segment_reduce_kernel,
        grid=(d_pad // td, k_pad // bk),
        in_specs=[
            pl.BlockSpec((e_pad, bk), lambda d, k: (0, k)),
            pl.BlockSpec((bk, td), lambda d, k: (k, d)),
        ],
        out_specs=pl.BlockSpec((e_pad, td), lambda d, k: (0, d)),
        out_shape=jax.ShapeDtypeStruct((e_pad, d_pad), jnp.float32),
        interpret=interpret,
    )(wm, values)
