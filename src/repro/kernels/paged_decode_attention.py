"""Paged attention — Pallas TPU kernels over a block-table KV pool.

The KV cache lives in one global pool of fixed-size blocks; each sequence
owns a per-request *block table* mapping its logical KV blocks to physical
pool blocks (vLLM-style PagedAttention).  Callers pad unused block-table
slots with 0 (the pool reserves block 0 as a parking block that no live
sequence owns).

``paged_decode_attention`` is the decode step's kernel.  It reads the
layer-stacked pool ``(L, n_blocks, bs, K, D)`` the model's layer scan
carries, at a scalar-prefetched layer, left in HBM: each lane's grid step
walks only the compute blocks (several pool blocks each) that its position
reaches, with its own double-buffered async copies, and runs the online
softmax over them.  GQA never expands K/V: the block's rows of every kv
head go to the MXU as one ``(positions x K, D)`` operand against all H
query rows, and each query head keeps only the score columns of its own
group's kv head (h // G).  Splitting the heads apart instead needs strided
loads, since a bf16 pool block packs two heads per 32-bit row; the product
over the whole block loads the same K/V tiles into the MXU.

The prefill, verify and per-shard LSE kernels take one layer's pool
``(n_blocks, bs, K, D)``: their grids walk (sequence, logical block), the
physical block to DMA resolved in the BlockSpec index map from the
scalar-prefetched table, with blocks past the sequence skipped by
``@pl.when`` — one pool block per grid step.  q packs all heads of one
sequence into a single MXU operand, reshaped (K, G, D) against k
(bs, K, D).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


# a decode compute block holds at most this many bytes of K (and of V) and
# this many positions: 256 positions at qwen3-1.7b widths, the fastest of
# 128-1,024 on a v5e
_BLOCK_BYTES = 512 * 1024
_BLOCK_ROWS = 512


def _pages_per_block(block_size: int, n_table: int, page_bytes: int) -> int:
    """Pool blocks per compute block: the most that divides the table width
    within ``_BLOCK_BYTES`` and ``_BLOCK_ROWS``."""
    fits = [p for p in range(1, n_table + 1)
            if n_table % p == 0 and p * page_bytes <= _BLOCK_BYTES
            and p * block_size <= _BLOCK_ROWS]
    return max(fits, default=1)


def _paged_decode_kernel(layer_ref, bt_ref, pos_ref, q_ref, k_hbm, v_hbm,
                         o_ref, k_buf, v_buf, sems, slot_ref, *,
                         scale: float, block_size: int, pages: int,
                         groups: int, n_table: int):
    b = pl.program_id(0)
    n_lanes = pl.num_programs(0)
    layer = layer_ref[0]
    cb = pages * block_size                     # positions per compute block
    K, D = k_buf.shape[-2], k_buf.shape[-1]
    H = q_ref.shape[1]
    rows = cb * K                               # (position, kv head) rows

    def page_copies(lane, blk, slot):
        """(live, k copy, v copy) for each pool block of compute block
        ``blk`` of ``lane``: live iff the lane's position reaches it."""
        last_page = pos_ref[lane] // block_size
        out = []
        for j in range(pages):
            page = blk * pages + j
            phys = bt_ref[lane * n_table + jnp.minimum(page, n_table - 1)]
            out.append((page <= last_page,
                        pltpu.make_async_copy(k_hbm.at[layer, phys],
                                              k_buf.at[slot, j],
                                              sems.at[0, slot]),
                        pltpu.make_async_copy(v_hbm.at[layer, phys],
                                              v_buf.at[slot, j],
                                              sems.at[1, slot])))
        return out

    def start(lane, blk, slot):
        for live, kc, vc in page_copies(lane, blk, slot):
            @pl.when(live)
            def _():
                kc.start()
                vc.start()

    def wait(lane, blk, slot):
        for live, kc, vc in page_copies(lane, blk, slot):
            @pl.when(live)
            def _():
                kc.wait()
                vc.wait()

    # the copy chain runs across lanes: the first grid step starts lane
    # 0's first block, and every block prefetches the next live one
    @pl.when(b == 0)
    def _first():
        slot_ref[0] = 0
        start(0, 0, 0)

    pos = pos_ref[b]
    n_blk = pos // cb + 1                       # compute blocks pos reaches
    q = q_ref[0]                                # (H, D), H = K * G
    # score column c is (position c // K, kv head c % K); query head h
    # reads only its own group's kv head h // G
    col = jax.lax.broadcasted_iota(jnp.int32, (H, rows), 1)
    own = (col % K) == (jax.lax.broadcasted_iota(jnp.int32, (H, rows), 0)
                        // groups)
    col_pos = col // K

    def body(i, carry):
        m, l, acc, slot = carry
        last = i == n_blk - 1
        nxt_lane = jnp.where(last, b + 1, b)

        @pl.when(nxt_lane < n_lanes)
        def _prefetch():
            start(nxt_lane, jnp.where(last, 0, i + 1), 1 - slot)

        wait(b, i, slot)
        reach = pos - i * cb                    # last visible row position

        # pool blocks past pos were not copied: clear their V rows so that
        # stale buffer contents cannot reach the product as 0 * NaN
        @pl.when(last)
        def _clear_tail():
            r = jax.lax.broadcasted_iota(jnp.int32, (pages, block_size, K, D),
                                         0) * block_size \
                + jax.lax.broadcasted_iota(jnp.int32,
                                           (pages, block_size, K, D), 1)
            v_buf[slot] = jnp.where(r <= reach, v_buf[slot],
                                    jnp.zeros((), v_buf.dtype))

        k = k_buf[slot].reshape(rows, D)        # pool dtype to the MXU
        v = v_buf[slot].reshape(rows, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(own & (col_pos <= reach), s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=1, keepdims=True)
        acc = acc * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l, acc, 1 - slot

    m0 = jnp.full((H, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((H, 1), jnp.float32)
    acc0 = jnp.zeros((H, D), jnp.float32)
    _, l, acc, slot = jax.lax.fori_loop(0, n_blk, body,
                                        (m0, l0, acc0, slot_ref[0]))
    slot_ref[0] = slot
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


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
                           layer, block_tables: jax.Array,
                           positions: jax.Array, *,
                           scale: float | None = None,
                           interpret: bool = False) -> jax.Array:
    """One-token attention over the layer-stacked paged KV pool.

    q: (B, H, D); k_pool/v_pool: (L, n_blocks, bs, K, D), left in HBM and
    read at ``layer`` (a scalar, traced or not) without slicing a layer
    out; block_tables: (B, T) int32 physical block ids (pad unused slots
    with 0); positions: (B,) last valid cache index per sequence ->
    o (B, H, D).

    One grid step per lane walks the compute blocks its position reaches,
    each several pool blocks (``_pages_per_block``), fetched with
    double-buffered async copies of ``k_pool[layer, table[b, i]]``; the
    copy chain crosses lanes, so the first block of lane b + 1 is in flight
    while lane b finishes.  Pool blocks past a lane's position are neither
    copied nor computed: a parked lane (position 0) costs one pool block.
    K and V reach the MXU in the pool's dtype with float32 accumulation;
    the online softmax state is float32.
    """
    B, H, D = q.shape
    _, _, bs, K, _ = k_pool.shape
    T = block_tables.shape[1]
    assert H % K == 0
    groups = H // K
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    pages = _pages_per_block(bs, T, bs * K * D * k_pool.dtype.itemsize)

    kern = functools.partial(_paged_decode_kernel, scale=scale,
                             block_size=bs, pages=pages, groups=groups,
                             n_table=T)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,    # layer, block tables, positions in SMEM
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, H, D), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, H, D), lambda b, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, pages, bs, K, D), k_pool.dtype),
            pltpu.VMEM((2, pages, bs, K, D), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        # the copy chain carries from one lane's grid step to the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      block_tables.astype(jnp.int32).reshape(-1),
      positions.astype(jnp.int32), q, k_pool, v_pool)
