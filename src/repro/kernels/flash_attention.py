"""Causal GQA flash attention — Pallas TPU kernel.

Online-softmax over (block_q x block_k) VMEM tiles; fp32 accumulators in
VMEM scratch; MXU-aligned tile sizes (multiples of 128 on the lane dim).
Layout: q (B, H, S, D); k/v (B, K, S, D); GQA mapping h -> h*K//H resolved
in the BlockSpec index maps, so no kv expansion ever materialises.

The grid's last dimension walks k-blocks ("arbitrary" semantics = sequential
on TPU) and carries running (m, l, acc) in scratch; causal upper blocks are
skipped with @pl.when.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *,
                  scale: float, causal: bool, block_q: int, block_k: int,
                  window: int):
    i = pl.program_id(2)  # q block
    j = pl.program_id(3)  # k block
    nj = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q_lo = i * block_q
    k_lo = j * block_k
    # skip blocks strictly above the diagonal (causal) or outside the window
    run = True
    if causal:
        run = k_lo <= q_lo + block_q - 1
    if window:
        run = jnp.logical_and(run, k_lo + block_k > q_lo - window)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)  # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask = kpos <= qpos
        if window:
            mask = jnp.logical_and(mask, kpos > qpos - window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_sc[...] = l_sc[...] * corr + jnp.sum(p, axis=1)
        acc_sc[...] = acc_sc[...] * corr[:, None] + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_sc[...] = m_new

    @pl.when(j == nj - 1)
    def _finish():
        denom = jnp.maximum(l_sc[...], 1e-30)[:, None]
        o_ref[0, 0] = (acc_sc[...] / denom).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    scale: float | None = None,
                    block_q: int = 256, block_k: int = 256,
                    interpret: bool = False) -> jax.Array:
    """q: (B, H, S, D); k/v: (B, K, S, D) -> (B, H, S, D)."""
    B, H, S, D = q.shape
    K = k.shape[1]
    assert H % K == 0, (H, K)
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    grid = (B, H, S // block_q, S // block_k)

    kern = functools.partial(_flash_kernel, scale=scale, causal=causal,
                             block_q=block_q, block_k=block_k, window=window)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, i, j: (b, h * K // H, j, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, i, j: (b, h * K // H, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
