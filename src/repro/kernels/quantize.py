"""Pallas TPU kernel: fused stochastic-rounding quantize -> dequantize.

The wire codecs (``comm.codecs.QuantCodec``) simulate int8/int4 transmission
of federated payloads.  Inside the batched round engine that round trip is a
*fake-quant* op: ``x -> clip(floor(x/scale + u), -qmax, qmax) * scale`` with
``u ~ U[0,1)`` (unbiased stochastic rounding) and a per-tensor absmax scale.

This kernel fuses the divide / stochastic floor / clip / rescale into one
VMEM pass — the integer code tensor never exists in HBM (an eager
implementation materializes it plus the uniforms twice).  The uniforms are an
*input* so the kernel is bit-identical to its XLA twin
(``kernels.ref.fake_quant_ref``) and to the host codec given the same draws;
on a real TPU the in-kernel ``pltpu.prng_random_bits`` could generate them,
but the interpret-mode CPU lowering of the TPU PRNG primitives does not
exist, and a shared input keeps the twins exactly comparable.

Grid: (rows/block_r,) over a (rows, 128) layout; scale is a (1, 1) block
broadcast to every program instance.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _fake_quant_kernel(x_ref, u_ref, scale_ref, out_ref, *, qmax: int):
    scale = scale_ref[0, 0]
    # true divide, not multiply-by-reciprocal: the XLA twin and host codec
    # divide, and a reciprocal flips floor() at quantization-bin boundaries
    q = jnp.clip(jnp.floor(x_ref[...] / scale + u_ref[...]), -qmax, qmax)
    out_ref[...] = (q * scale).astype(out_ref.dtype)


def fake_quant_pallas(
    x: jax.Array,  # (rows, 128) fp32
    u: jax.Array,  # (rows, 128) fp32 uniforms in [0, 1)
    scale: jax.Array,  # (1, 1) fp32 per-tensor scale
    *,
    qmax: int,
    block_r: int = 8,
    interpret: bool,
) -> jax.Array:
    rows, cols = x.shape
    if cols != 128 or rows % block_r:
        raise ValueError(f"({rows}, {cols}) must be (k*{block_r}, 128)")
    return pl.pallas_call(
        functools.partial(_fake_quant_kernel, qmax=qmax),
        grid=(rows // block_r,),
        in_specs=[
            pl.BlockSpec((block_r, 128), lambda i: (i, 0)),
            pl.BlockSpec((block_r, 128), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_r, 128), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, cols), x.dtype),
        interpret=interpret,
    )(x, u, scale)
