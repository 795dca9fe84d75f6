"""Paged flash-decode — Pallas TPU kernel over a block-table KV pool.

The KV cache lives in one global pool of fixed-size blocks
(``n_blocks, block_size, K, D``); each sequence owns a per-request *block
table* mapping its logical KV blocks to physical pool blocks (vLLM-style
PagedAttention).  The grid walks (sequence, logical block); the physical
block to DMA is resolved in the BlockSpec index map from the scalar-
prefetched block table (SMEM), so the kernel body is the same running
(m, l, acc) online softmax as the dense flash-decode in
``decode_attention.py`` — only the gather changed.

q packs all heads of one sequence into a single (H, D) MXU operand and GQA
is computed grouped — q reshaped (K, G, D) against k (bs, K, D) — so kv is
never expanded.  Logical blocks past the sequence's length are skipped with
``@pl.when``; their index-map entries must still name a valid physical
block, so callers pad unused block-table slots with 0 (the pool reserves
block 0 as a parking block that no live sequence owns).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _paged_decode_kernel(bt_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                         m_sc, l_sc, acc_sc, *, scale: float,
                         block_size: int, groups: int):
    b = pl.program_id(0)
    j = pl.program_id(1)          # logical block index within the sequence
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    pos = pos_ref[b]
    k_lo = j * block_size

    @pl.when(k_lo <= pos)
    def _body():
        q = q_ref[0].astype(jnp.float32)      # (H, D), H = K*G
        k = k_ref[...].astype(jnp.float32)    # (bs, K, D) — physical block
        v = v_ref[...].astype(jnp.float32)
        K = k.shape[1]
        qg = q.reshape(K, groups, q.shape[-1])
        # scores (K, G, bs)
        s = jax.lax.dot_general(qg, k, (((2,), (2,)), ((0,), (1,))),
                                preferred_element_type=jnp.float32) * scale
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(kpos <= pos, s, NEG_INF)
        sh = s.reshape(K * groups, block_size)  # (H, bs)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(sh, axis=1))
        p = jnp.exp(sh - m_new[:, None]).reshape(K, groups, block_size)
        corr = jnp.exp(m_prev - m_new)
        l_sc[...] = l_sc[...] * corr + jnp.sum(p, axis=2).reshape(-1)
        # (K, G, bs) x (bs, K, D) -> (K, G, D)
        o = jax.lax.dot_general(p, v, (((2,), (0,)), ((0,), (1,))),
                                preferred_element_type=jnp.float32)
        acc_sc[...] = acc_sc[...] * corr[:, None] + o.reshape(K * groups, -1)
        m_sc[...] = m_new

    @pl.when(j == nj - 1)
    def _finish():
        denom = jnp.maximum(l_sc[...], 1e-30)[:, None]
        o_ref[0] = (acc_sc[...] / denom).astype(o_ref.dtype)


def _paged_prefill_kernel(bt_ref, st_ref, q_ref, k_ref, v_ref, o_ref,
                          m_sc, l_sc, acc_sc, *, scale: float,
                          block_size: int, groups: int, chunk: int):
    b = pl.program_id(0)
    j = pl.program_id(1)          # logical block index within the sequence
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    start = st_ref[b]
    k_lo = j * block_size

    # the chunk's own KV is already in the pool; blocks past the chunk's
    # last query position contribute nothing and are skipped entirely
    @pl.when(k_lo <= start + chunk - 1)
    def _body():
        q = q_ref[0].astype(jnp.float32)      # (C, H, D), H = K*G
        k = k_ref[...].astype(jnp.float32)    # (bs, K, D) — physical block
        v = v_ref[...].astype(jnp.float32)
        K = k.shape[1]
        qg = q.reshape(chunk, K, groups, -1).transpose(1, 0, 2, 3) \
              .reshape(K, chunk * groups, -1)
        # scores (K, C*G, bs)
        s = jax.lax.dot_general(qg, k, (((2,), (2,)), ((0,), (1,))),
                                preferred_element_type=jnp.float32) * scale
        cidx = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) // groups
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(kpos <= start + cidx, s, NEG_INF)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=2))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        l_sc[...] = l_sc[...] * corr + jnp.sum(p, axis=2)
        # (K, C*G, bs) x (bs, K, D) -> (K, C*G, D)
        o = jax.lax.dot_general(p, v, (((2,), (0,)), ((0,), (1,))),
                                preferred_element_type=jnp.float32)
        acc_sc[...] = acc_sc[...] * corr[..., None] + o
        m_sc[...] = m_new

    @pl.when(j == nj - 1)
    def _finish():
        denom = jnp.maximum(l_sc[...], 1e-30)[..., None]
        o = acc_sc[...] / denom               # (K, C*G, D)
        K = o.shape[0]
        o = o.reshape(K, chunk, groups, -1).transpose(1, 0, 2, 3) \
             .reshape(chunk, K * groups, -1)
        o_ref[0] = o.astype(o_ref.dtype)


def _paged_decode_lse_kernel(bt_ref, pos_ref, own_ref, q_ref, k_ref, v_ref,
                             o_ref, lse_ref, m_sc, l_sc, acc_sc, *,
                             scale: float, block_size: int, groups: int):
    b = pl.program_id(0)
    j = pl.program_id(1)          # logical block index within the sequence
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    pos = pos_ref[b]
    k_lo = j * block_size

    @pl.when((k_lo <= pos) & (own_ref[b, j] != 0))
    def _body():
        q = q_ref[0].astype(jnp.float32)      # (H, D), H = K*G
        k = k_ref[...].astype(jnp.float32)    # (bs, K, D) — physical block
        v = v_ref[...].astype(jnp.float32)
        K = k.shape[1]
        qg = q.reshape(K, groups, q.shape[-1])
        s = jax.lax.dot_general(qg, k, (((2,), (2,)), ((0,), (1,))),
                                preferred_element_type=jnp.float32) * scale
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(kpos <= pos, s, NEG_INF)
        sh = s.reshape(K * groups, block_size)  # (H, bs)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(sh, axis=1))
        p = jnp.exp(sh - m_new[:, None]).reshape(K, groups, block_size)
        corr = jnp.exp(m_prev - m_new)
        l_sc[...] = l_sc[...] * corr + jnp.sum(p, axis=2).reshape(-1)
        o = jax.lax.dot_general(p, v, (((2,), (0,)), ((0,), (1,))),
                                preferred_element_type=jnp.float32)
        acc_sc[...] = acc_sc[...] * corr[:, None] + o.reshape(K * groups, -1)
        m_sc[...] = m_new

    @pl.when(j == nj - 1)
    def _finish():
        l = l_sc[...]
        o_ref[0] = (acc_sc[...]
                    / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)
        lse = jnp.where(l > 0.0, m_sc[...] + jnp.log(
            jnp.maximum(l, 1e-30)), NEG_INF)
        lse_ref[0] = lse.reshape(1, -1)


def paged_decode_attention_lse(q: jax.Array, k_pool: jax.Array,
                               v_pool: jax.Array, block_tables: jax.Array,
                               positions: jax.Array, owned: jax.Array, *,
                               scale: float | None = None,
                               interpret: bool = False):
    """Paged decode attention over a *partial* pool, with the LSE exposed.

    The per-KV-shard building block of the block-stripe sharded pool
    (``models/attention._paged_decode_core``): each shard runs this over
    its local stripe and the shards' outputs merge exactly via
    ``combine_lse`` — the same max/sum softmax merge ``_flash_decode_core``
    does with pmax/psum.

    ``owned``: (B, T) nonzero where this shard holds the table's block;
    unowned slots are skipped entirely (never DMA'd), so callers may clip
    their localized table ids into range without masking the contents.
    Returns ``(o, lse)``: o (B, H, D) softmax-normalised over the owned
    blocks only, lse (B, H) float32 ``m + log(l)`` (``NEG_INF`` where the
    shard saw no key), so the combine is exact in one weighted sum.
    """
    B, H, D = q.shape
    bs, K = k_pool.shape[1], k_pool.shape[2]
    T = block_tables.shape[1]
    assert H % K == 0
    groups = H // K
    scale = scale if scale is not None else 1.0 / math.sqrt(D)

    kern = functools.partial(_paged_decode_lse_kernel, scale=scale,
                             block_size=bs, groups=groups)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,    # block_tables, positions, owned in SMEM
        grid=(B, T),
        in_specs=[
            pl.BlockSpec((1, H, D), lambda b, j, bt, pos, own: (b, 0, 0)),
            pl.BlockSpec((None, bs, K, D),
                         lambda b, j, bt, pos, own: (bt[b, j], 0, 0, 0)),
            pl.BlockSpec((None, bs, K, D),
                         lambda b, j, bt, pos, own: (bt[b, j], 0, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, H, D), lambda b, j, bt, pos, own: (b, 0, 0)),
            # (1, 1, H): the last two block dims equal the array's, which
            # Mosaic's (8, 128) tiling rule accepts for any H
            pl.BlockSpec((1, 1, H), lambda b, j, bt, pos, own: (b, 0, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((H,), jnp.float32),
            pltpu.VMEM((H,), jnp.float32),
            pltpu.VMEM((H, D), jnp.float32),
        ],
    )
    o, lse = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((B, 1, H), jnp.float32)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), positions.astype(jnp.int32),
      owned.astype(jnp.int32), q, k_pool, v_pool)
    return o, lse[:, 0]


def combine_lse(os: jax.Array, lses: jax.Array) -> jax.Array:
    """Merge per-shard ``paged_decode_attention_lse`` outputs exactly.

    os: (S, B, H, D) per-shard normalised outputs; lses: (S, B, H).
    Weights each shard by ``exp(lse_s - max_s lse)`` times its own
    denominator share — algebraically identical to one softmax over the
    union of the shards' keys.
    """
    m = jnp.max(lses, axis=0)                       # (B, H)
    w = jnp.exp(lses - m[None])                     # (S, B, H)
    denom = jnp.maximum(jnp.sum(w, axis=0), 1e-30)  # (B, H)
    o = jnp.sum(os.astype(jnp.float32) * w[..., None], axis=0) / denom[..., None]
    return o.astype(os.dtype)


def paged_prefill_attention(q: jax.Array, k_pool: jax.Array,
                            v_pool: jax.Array, block_tables: jax.Array,
                            starts: jax.Array, *,
                            scale: float | None = None,
                            interpret: bool = False) -> jax.Array:
    """Chunked-prefill attention over a paged KV pool.

    q: (B, C, H, D) — C chunk queries per sequence, query c sitting at
    absolute position ``starts[b] + c``; k_pool/v_pool: (n_blocks, bs, K,
    D) with the chunk's own KV already written; block_tables: (B, T)
    int32 physical ids (pad unused slots with 0); starts: (B,) ->
    o (B, C, H, D).  Same online-softmax walk as the decode kernel with a
    (C*G)-row score tile per KV head and a per-row causal mask
    ``kpos <= starts + c``; pool blocks past the chunk's last query are
    never DMA'd.
    """
    B, C, H, D = q.shape
    bs, K = k_pool.shape[1], k_pool.shape[2]
    T = block_tables.shape[1]
    assert H % K == 0
    groups = H // K
    scale = scale if scale is not None else 1.0 / math.sqrt(D)

    kern = functools.partial(_paged_prefill_kernel, scale=scale,
                             block_size=bs, groups=groups, chunk=C)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,    # block_tables, starts land in SMEM
        grid=(B, T),
        in_specs=[
            pl.BlockSpec((1, C, H, D), lambda b, j, bt, st: (b, 0, 0, 0)),
            pl.BlockSpec((None, bs, K, D),
                         lambda b, j, bt, st: (bt[b, j], 0, 0, 0)),
            pl.BlockSpec((None, bs, K, D),
                         lambda b, j, bt, st: (bt[b, j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, C, H, D),
                               lambda b, j, bt, st: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((K, C * groups), jnp.float32),
            pltpu.VMEM((K, C * groups), jnp.float32),
            pltpu.VMEM((K, C * groups, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), starts.astype(jnp.int32),
      q, k_pool, v_pool)


def paged_verify_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, block_tables: jax.Array,
                           positions: jax.Array, *,
                           scale: float | None = None,
                           interpret: bool = False) -> jax.Array:
    """Multi-query-per-lane decode attention (speculative verify).

    q: (B, Q, H, D) — the current input token plus K draft tokens per
    lane, query i at absolute position ``positions[b] + i``, all verified
    against the block table in one pass; k_pool/v_pool: (n_blocks, bs, K,
    D) with the Q tokens' own KV already written; block_tables: (B, T)
    (pad unused slots with 0); positions: (B,) -> o (B, Q, H, D).

    The mask walk is exactly chunked prefill with ``starts == positions``
    (query i sees kpos <= positions + i), so the same online-softmax
    kernel body serves both entry points; only the calling convention —
    decode-style positions instead of prefill starts — differs.
    """
    return paged_prefill_attention(q, k_pool, v_pool, block_tables,
                                   positions, scale=scale,
                                   interpret=interpret)


def paged_decode_attention(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                           block_tables: jax.Array, positions: jax.Array, *,
                           scale: float | None = None,
                           interpret: bool = False) -> jax.Array:
    """One-token attention over a paged KV pool.

    q: (B, H, D); k_pool/v_pool: (n_blocks, bs, K, D);
    block_tables: (B, T) int32 physical block ids (pad unused slots with 0);
    positions: (B,) last valid cache index per sequence -> o (B, H, D).
    """
    B, H, D = q.shape
    bs, K = k_pool.shape[1], k_pool.shape[2]
    T = block_tables.shape[1]
    assert H % K == 0
    groups = H // K
    scale = scale if scale is not None else 1.0 / math.sqrt(D)

    kern = functools.partial(_paged_decode_kernel, scale=scale,
                             block_size=bs, groups=groups)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,    # block_tables, positions land in SMEM
        grid=(B, T),
        in_specs=[
            pl.BlockSpec((1, H, D), lambda b, j, bt, pos: (b, 0, 0)),
            pl.BlockSpec((None, bs, K, D),
                         lambda b, j, bt, pos: (bt[b, j], 0, 0, 0)),
            pl.BlockSpec((None, bs, K, D),
                         lambda b, j, bt, pos: (bt[b, j], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, D), lambda b, j, bt, pos: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H,), jnp.float32),
            pltpu.VMEM((H,), jnp.float32),
            pltpu.VMEM((H, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), positions.astype(jnp.int32),
      q, k_pool, v_pool)
