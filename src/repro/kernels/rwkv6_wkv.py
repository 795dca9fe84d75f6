"""RWKV6 WKV recurrence — Pallas TPU kernel.

State S (D_k x D_v) per (batch, head) lives in VMEM scratch across the
sequential time-block grid dimension; each block applies ``bt`` recurrence
steps with data-dependent per-channel decay:

    y_t = r_t . (S + (u*k_t) v_t^T);   S <- diag(w_t) S + k_t v_t^T

The in-block loop is a fori_loop over rows of the (bt, D) VMEM tiles —
outer products and (D,) x (D,D) contractions hit the MXU/VPU directly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, s_out_ref,
                s_sc, *, block_t: int):
    t = pl.program_id(2)
    nt = pl.num_programs(2)

    @pl.when(t == 0)
    def _init():
        s_sc[...] = s0_ref[0, 0].astype(jnp.float32)

    u = u_ref[0].astype(jnp.float32)  # (D,)

    def step(i, s):
        rt = r_ref[0, i, 0].astype(jnp.float32)  # (D,)
        kt = k_ref[0, i, 0].astype(jnp.float32)
        vt = v_ref[0, i, 0].astype(jnp.float32)
        wt = w_ref[0, i, 0].astype(jnp.float32)
        at = kt[:, None] * vt[None, :]           # (Dk, Dv)
        y = (rt[None, :] @ (s + (u * kt)[:, None] * vt[None, :]))[0]
        y_ref[0, i, 0] = y.astype(y_ref.dtype)
        return wt[:, None] * s + at

    s_sc[...] = jax.lax.fori_loop(0, block_t, step, s_sc[...])

    @pl.when(t == nt - 1)
    def _finish():
        s_out_ref[0, 0] = s_sc[...].astype(s_out_ref.dtype)


def rwkv6_wkv(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
              u: jax.Array, s0: jax.Array, *, block_t: int = 64,
              interpret: bool = False):
    """r/k/v/w: (B, T, H, D); u: (H, D); s0: (B, H, D, D).

    Returns (y (B, T, H, D), s_final (B, H, D, D)).
    """
    B, T, H, D = r.shape
    block_t = min(block_t, T)
    assert T % block_t == 0
    grid = (B, H, T // block_t)

    kern = functools.partial(_wkv_kernel, block_t=block_t)
    seq_spec = pl.BlockSpec((1, block_t, 1, D), lambda b, h, t: (b, t, h, 0))
    y, s_f = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            seq_spec, seq_spec, seq_spec, seq_spec,
            pl.BlockSpec((1, D), lambda b, h, t: (h, 0)),
            pl.BlockSpec((1, 1, D, D), lambda b, h, t: (b, h, 0, 0)),
        ],
        out_specs=[
            seq_spec,
            pl.BlockSpec((1, 1, D, D), lambda b, h, t: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(r.shape, r.dtype),
            jax.ShapeDtypeStruct(s0.shape, jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((D, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, w, u, s0)
    return y, s_f
