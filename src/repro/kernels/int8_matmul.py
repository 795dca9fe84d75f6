"""w8a8 int8 GEMM with per-row/per-channel scales — Pallas TPU kernel.

This is the TAPAS instance-configurator's quantization knob realised on
TPU: v5e has no FP8, so bf16 -> int8 symmetric quantization is the
MXU-native low-precision path.  int32 accumulation in VMEM scratch over the
sequential K-block grid dim; scales applied once at the final block.
Tiles are 128-aligned for the MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _int8_mm_kernel(x_ref, w_ref, sx_ref, sw_ref, o_ref, acc_sc, *,
                    out_dtype):
    kb = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        acc_sc[...] = jnp.zeros_like(acc_sc)

    acc_sc[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(kb == nk - 1)
    def _finish():
        sx = sx_ref[...].astype(jnp.float32)  # (bm, 1)
        sw = sw_ref[...].astype(jnp.float32)  # (1, bn)
        o_ref[...] = (acc_sc[...].astype(jnp.float32) * sx * sw).astype(out_dtype)


def int8_matmul(x_q: jax.Array, w_q: jax.Array, sx: jax.Array, sw: jax.Array,
                *, block_m: int = 256, block_n: int = 256, block_k: int = 512,
                out_dtype=jnp.bfloat16, interpret: bool = False) -> jax.Array:
    """x_q: (M, K) int8; w_q: (K, N) int8; sx: (M, 1) f32; sw: (1, N) f32."""
    M, K = x_q.shape
    N = w_q.shape[1]
    block_m = min(block_m, M)
    block_n = min(block_n, N)
    block_k = min(block_k, K)
    assert M % block_m == 0 and N % block_n == 0 and K % block_k == 0
    grid = (M // block_m, N // block_n, K // block_k)

    kern = functools.partial(_int8_mm_kernel, out_dtype=out_dtype)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kb: (i, kb)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kb: (kb, j)),
            pl.BlockSpec((block_m, 1), lambda i, j, kb: (i, 0)),
            pl.BlockSpec((1, block_n), lambda i, j, kb: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kb: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x_q, w_q, sx, sw)


def quantize_rows(x: jax.Array):
    """Symmetric per-row int8 quantization: returns (x_q, scale (M,1) f32)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    x_q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    return x_q, scale


def quantize_cols(w: jax.Array):
    """Symmetric per-output-channel int8 quantization: (w_q, scale (1,N))."""
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=0, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    w_q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    return w_q, scale
