"""Flash-decode — Pallas TPU kernel for one-token attention over a long KV
cache.

q packs all heads of one sequence into a single (H, D) MXU operand; the grid
walks KV blocks sequentially with running (m, l, acc) scratch, masking by
per-sequence position.  GQA is computed grouped — q reshaped (K, G, D)
against k (bk, K, D) — so kv never expands.  This is the kernel counterpart
of the sequence-sharded decode core in models/attention.py: on a real pod
each model rank runs it over its local KV shard and LSE-combines via psum.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *,
                   scale: float, block_k: int, groups: int):
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    pos = pos_ref[0]
    k_lo = j * block_k

    @pl.when(k_lo <= pos)
    def _body():
        q = q_ref[0].astype(jnp.float32)      # (H, D), H = K*G
        k = k_ref[0].astype(jnp.float32)      # (bk, K, D)
        v = v_ref[0].astype(jnp.float32)
        K = k.shape[1]
        qg = q.reshape(K, groups, q.shape[-1])
        # scores (K, G, bk)
        s = jax.lax.dot_general(qg, k, (((2,), (2,)), ((0,), (1,))),
                                preferred_element_type=jnp.float32) * scale
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(kpos <= pos, s, NEG_INF)
        sh = s.reshape(K * groups, block_k)   # (H, bk)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(sh, axis=1))
        p = jnp.exp(sh - m_new[:, None]).reshape(K, groups, block_k)
        corr = jnp.exp(m_prev - m_new)
        l_sc[...] = l_sc[...] * corr + jnp.sum(p, axis=2).reshape(-1)
        # (K, G, bk) x (bk, K, D) -> (K, G, D)
        o = jax.lax.dot_general(p, v, (((2,), (0,)), ((0,), (1,))),
                                preferred_element_type=jnp.float32)
        acc_sc[...] = acc_sc[...] * corr[:, None] + o.reshape(K * groups, -1)
        m_sc[...] = m_new

    @pl.when(j == nj - 1)
    def _finish():
        denom = jnp.maximum(l_sc[...], 1e-30)[:, None]
        o_ref[0] = (acc_sc[...] / denom).astype(o_ref.dtype)


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     positions: jax.Array, *, scale: float | None = None,
                     block_k: int = 512, interpret: bool = False) -> jax.Array:
    """q: (B, H, D); k/v: (B, S, K, D); positions: (B,) -> o (B, H, D)."""
    B, H, D = q.shape
    S, K = k.shape[1], k.shape[2]
    assert H % K == 0
    groups = H // K
    block_k = min(block_k, S)
    assert S % block_k == 0
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    grid = (B, S // block_k)

    kern = functools.partial(_decode_kernel, scale=scale, block_k=block_k,
                             groups=groups)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda b, j: (b,), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, H, D), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, block_k, K, D), lambda b, j: (b, j, 0, 0)),
            pl.BlockSpec((1, block_k, K, D), lambda b, j: (b, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, D), lambda b, j: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((H,), jnp.float32),
            pltpu.VMEM((H,), jnp.float32),
            pltpu.VMEM((H, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(positions, q, k, v)
