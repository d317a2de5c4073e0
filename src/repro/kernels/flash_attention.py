"""Pallas TPU kernel: blockwise online-softmax attention (GQA, causal, window).

The framework's backbone hot spot. KV blocks stream HBM->VMEM along the
innermost grid axis while fp32 running (max, sum, acc) live in VMEM scratch;
the q-block output is written once on the last KV step. Causal/sliding-window
masks are computed from grid coordinates with iota — fully-masked blocks still
execute (Pallas grids are static) but contribute exp(-inf)=0.

Grid: (batch, q_heads, s_q/bq, s_k/bk). GQA is expressed in the k/v index_map:
kv_head = q_head // (h // kv), so no KV replication in HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
    *, scale: float, block_q: int, block_k: int, causal: bool, window: int, k_steps: int,
):
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)  # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)  # (bk, d)
    v = v_ref[0, 0].astype(jnp.float32)  # (bk, dv)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    s = s * scale  # (bq, bk)

    qi = pl.program_id(2)
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), jnp.bool_)
    if causal:
        mask &= q_pos >= k_pos
    if window:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = corr * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = corr * acc_ref[...] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(ki == k_steps - 1)
    def _write():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = out.astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array,  # (b, h, s, d)
    k: jax.Array,  # (b, kv, s, d)
    v: jax.Array,  # (b, kv, s, dv)
    *,
    causal: bool = True,
    window: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool,
) -> jax.Array:
    b, h, s, d = q.shape
    kv = k.shape[1]
    dv = v.shape[-1]
    g = h // kv
    bq = min(block_q, s)
    bk = min(block_k, s)
    if s % bq or s % bk:
        raise ValueError(f"seq {s} must tile by ({bq},{bk})")
    k_steps = s // bk
    grid = (b, h, s // bq, k_steps)
    scale = 1.0 / (d ** 0.5)

    kernel = functools.partial(
        _flash_kernel, scale=scale, block_q=bq, block_k=bk,
        causal=causal, window=window, k_steps=k_steps,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda bi, hi, qi, ki, g=g: (bi, hi // g, ki, 0)),
            pl.BlockSpec((1, 1, bk, dv), lambda bi, hi, qi, ki, g=g: (bi, hi // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, dv), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, dv), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
