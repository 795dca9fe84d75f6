"""jit'd public wrappers for the Pallas kernels.

``interpret`` defaults to True off-TPU so the same call sites work in this
CPU container (interpret mode executes the kernel body exactly) and compile
to real Mosaic kernels on TPU.
"""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.decode_attention import decode_attention as _decode
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.int8_matmul import (int8_matmul as _int8_mm,
                                       quantize_cols, quantize_rows)
from repro.kernels.paged_decode_attention import \
    paged_decode_attention as _paged_decode
from repro.kernels.paged_decode_attention import \
    paged_decode_attention_lse as _paged_decode_lse
from repro.kernels.paged_decode_attention import \
    paged_prefill_attention as _paged_prefill
from repro.kernels.paged_decode_attention import combine_lse
from repro.kernels.paged_decode_attention import \
    paged_verify_attention as _paged_verify
from repro.kernels.rwkv6_wkv import rwkv6_wkv as _wkv


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


@partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k",
                                   "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, block_q=256,
                    block_k=256, interpret=None):
    if interpret is None:
        interpret = _default_interpret()
    return _flash(q, k, v, causal=causal, window=window, block_q=block_q,
                  block_k=block_k, interpret=interpret)


@partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention(q, k, v, positions, *, block_k=512, interpret=None):
    if interpret is None:
        interpret = _default_interpret()
    return _decode(q, k, v, positions, block_k=block_k, interpret=interpret)


@partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(q, k_pool, v_pool, layer, block_tables, positions,
                           *, interpret=None):
    """Decode attention over the layer-stacked pool (L, n_blocks, bs, K, D)
    at ``layer``."""
    if interpret is None:
        interpret = _default_interpret()
    return _paged_decode(q, k_pool, v_pool, layer, block_tables, positions,
                         interpret=interpret)


@partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_lse(q, k_pool, v_pool, block_tables, positions,
                               owned, *, interpret=None):
    """Per-KV-shard paged decode: (o, lse) over the owned blocks only;
    merge shards with ``combine_lse``."""
    if interpret is None:
        interpret = _default_interpret()
    return _paged_decode_lse(q, k_pool, v_pool, block_tables, positions,
                             owned, interpret=interpret)


@partial(jax.jit, static_argnames=("interpret",))
def paged_prefill_attention(q, k_pool, v_pool, block_tables, starts, *,
                            interpret=None):
    if interpret is None:
        interpret = _default_interpret()
    return _paged_prefill(q, k_pool, v_pool, block_tables, starts,
                          interpret=interpret)


@partial(jax.jit, static_argnames=("interpret",))
def paged_verify_attention(q, k_pool, v_pool, block_tables, positions, *,
                           interpret=None):
    """Verify K+1 query positions per lane in one paged-attention pass."""
    if interpret is None:
        interpret = _default_interpret()
    return _paged_verify(q, k_pool, v_pool, block_tables, positions,
                         interpret=interpret)


@partial(jax.jit, static_argnames=("block_t", "interpret"))
def rwkv6_wkv(r, k, v, w, u, s0, *, block_t=64, interpret=None):
    if interpret is None:
        interpret = _default_interpret()
    return _wkv(r, k, v, w, u, s0, block_t=block_t, interpret=interpret)


@partial(jax.jit, static_argnames=("interpret",))
def int8_matmul_quantized(x, w, *, interpret=None):
    """Quantize bf16/f32 operands on the fly and run the w8a8 GEMM."""
    if interpret is None:
        interpret = _default_interpret()
    x_q, sx = quantize_rows(x)
    w_q, sw = quantize_cols(w)
    return _int8_mm(x_q, w_q, sx, sw, interpret=interpret)


@partial(jax.jit, static_argnames=("interpret",))
def int8_matmul(x_q, w_q, sx, sw, *, interpret=None):
    if interpret is None:
        interpret = _default_interpret()
    return _int8_mm(x_q, w_q, sx, sw, interpret=interpret)


__all__ = ["flash_attention", "decode_attention", "paged_decode_attention",
           "paged_decode_attention_lse", "combine_lse",
           "paged_prefill_attention", "paged_verify_attention", "rwkv6_wkv",
           "int8_matmul", "int8_matmul_quantized", "quantize_rows",
           "quantize_cols"]
