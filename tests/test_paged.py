"""Paged-KV serving stack: kernel equivalence vs the dense flash-decode,
PagedCachePool allocator invariants, and slot-vs-paged engine equivalence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import ops, ref
from repro.models import build_model, local_plan
from repro.serving import Engine, EngineKnobs, PagedCachePool, Request

# whole-module: kernel sweeps + live engines (CI sim job);
# leakcheck = tracer escapes fail at the leak site (tapaslint runtime)
pytestmark = [pytest.mark.slow, pytest.mark.leakcheck]


def arr(rng, *s, dtype=jnp.float32):
    return jnp.asarray(rng.standard_normal(s), dtype)


# ---------------------------------------------------------------------------
# kernel: paged == dense at equal logical context
# ---------------------------------------------------------------------------

def _stacked(rng, pool, layer=1, layers=3):
    """A layer-stacked pool holding ``pool`` at ``layer``, noise elsewhere:
    the decode kernel reads the layer it is given and no other."""
    noise = arr(rng, layers, *pool.shape, dtype=pool.dtype)
    return noise.at[layer].set(pool)


@pytest.mark.parametrize("B,H,K,D,bs,T,positions", [
    (2, 8, 2, 64, 16, 8, None),       # GQA 4:1, sequences span 8 blocks
    (1, 4, 4, 128, 32, 4, None),      # MHA
    (3, 4, 1, 64, 16, 8, None),       # MQA
    # 256-position compute blocks: two parked lanes (position 0, table
    # all 0), a context ending on a compute-block edge and one 1 past it
    (4, 8, 4, 128, 16, 32, (0, 0, 255, 256)),
    (2, 8, 4, 128, 16, 32, (511, 300)),   # a full table
], ids=["gqa4", "mha", "mqa", "parked+edges", "full-table"])
def test_paged_decode_matches_ref(B, H, K, D, bs, T, positions):
    """The stacked-pool kernel at layer 1 against the float32 oracle on
    that layer, and against the XLA gather path the serving step runs
    off TPU."""
    from repro.models.attention import paged_attention
    rng = np.random.default_rng(B * 10 + T)
    n_blocks = 1 + B * T
    kp, vp = arr(rng, n_blocks, bs, K, D), arr(rng, n_blocks, bs, K, D)
    q = arr(rng, B, H, D)
    # ragged positions, scrambled (non-contiguous) physical block layout
    if positions is None:
        pos = rng.integers(0, T * bs, B)
    else:
        pos = np.asarray(positions)
    ids = rng.permutation(np.arange(1, n_blocks))[: B * T].reshape(B, T)
    ids[pos == 0] = 0                  # parked lanes point at block 0
    bt, pos = jnp.asarray(ids, jnp.int32), jnp.asarray(pos, jnp.int32)
    kst, vst = _stacked(rng, kp), _stacked(rng, vp)
    o = ops.paged_decode_attention(q, kst, vst, 1, bt, pos)
    o_ref = ref.paged_decode_attention_ref(q, kp, vp, bt, pos)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=1e-5)
    o_xla = paged_attention(q, kst, vst, 1, bt, pos, scale=1 / np.sqrt(D),
                            kv_idx=jnp.arange(H) // (H // K))
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_xla), atol=1e-5)


def test_paged_decode_matches_dense_flash_decode():
    """Same KV content laid out paged vs contiguous -> identical output."""
    rng = np.random.default_rng(0)
    B, H, K, D, bs = 2, 8, 2, 64, 32
    S = 128
    T = S // bs
    k, v = arr(rng, B, S, K, D), arr(rng, B, S, K, D)
    q = arr(rng, B, H, D)
    pos = jnp.asarray([37, 101], jnp.int32)
    o_dense = ops.decode_attention(q, k, v, pos, block_k=bs)
    # scatter the same content into a scrambled pool
    perm = rng.permutation(np.arange(1, 1 + B * T))
    kp = jnp.zeros((1 + B * T, bs, K, D), k.dtype)
    vp = jnp.zeros_like(kp)
    bt = perm.reshape(B, T)
    kp = kp.at[bt.reshape(-1)].set(k.reshape(B * T, bs, K, D))
    vp = vp.at[bt.reshape(-1)].set(v.reshape(B * T, bs, K, D))
    o_paged = ops.paged_decode_attention(q, _stacked(rng, kp),
                                         _stacked(rng, vp), 1,
                                         jnp.asarray(bt, jnp.int32), pos)
    np.testing.assert_allclose(np.asarray(o_paged), np.asarray(o_dense),
                               atol=1e-5)


def test_paged_decode_masks_future():
    """Entries past the position (within the last live block) are masked."""
    rng = np.random.default_rng(1)
    B, H, K, D, bs, T = 1, 2, 2, 32, 16, 4
    kp, vp = arr(rng, 1 + T, bs, K, D), arr(rng, 1 + T, bs, K, D)
    q = arr(rng, B, H, D)
    bt = jnp.arange(1, T + 1, dtype=jnp.int32)[None]
    pos = jnp.asarray([21], jnp.int32)
    o1 = ops.paged_decode_attention(q, kp[None], vp[None], 0, bt, pos)
    kp2 = kp.at[2, 6:].set(999.0).at[3].set(999.0).at[4].set(999.0)
    vp2 = vp.at[2, 6:].set(999.0).at[3].set(999.0).at[4].set(999.0)
    o2 = ops.paged_decode_attention(q, kp2[None], vp2[None], 0, bt, pos)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-6)


@pytest.mark.parametrize("B,C,H,K,D,bs,T", [
    (2, 8, 8, 2, 64, 16, 8),   # GQA 4:1, 8-token chunk
    (1, 16, 4, 4, 64, 16, 4),  # MHA, chunk spans a block boundary
    (3, 4, 4, 1, 32, 8, 8),    # MQA
])
def test_paged_prefill_matches_ref(B, C, H, K, D, bs, T):
    """Chunk queries at ragged start offsets over a scrambled pool."""
    rng = np.random.default_rng(B * 100 + C)
    n_blocks = 1 + B * T
    kp, vp = arr(rng, n_blocks, bs, K, D), arr(rng, n_blocks, bs, K, D)
    q = arr(rng, B, C, H, D)
    starts = jnp.asarray(rng.integers(0, T * bs - C, B), jnp.int32)
    ids = rng.permutation(np.arange(1, n_blocks))[: B * T].reshape(B, T)
    bt = jnp.asarray(ids, jnp.int32)
    o = ops.paged_prefill_attention(q, kp, vp, bt, starts)
    o_ref = ref.paged_prefill_attention_ref(q, kp, vp, bt, starts)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=1e-5)


def test_paged_prefill_chunk_equals_decode_steps():
    """A C-token chunk attends exactly like C successive decode steps
    whose KV is already in place (same pool, same block tables)."""
    rng = np.random.default_rng(5)
    B, H, K, D, bs, T = 2, 4, 2, 32, 8, 4
    C = 6
    n_blocks = 1 + B * T
    kp, vp = arr(rng, n_blocks, bs, K, D), arr(rng, n_blocks, bs, K, D)
    q = arr(rng, B, C, H, D)
    starts = jnp.asarray([5, 11], jnp.int32)
    bt = jnp.asarray(rng.permutation(np.arange(1, n_blocks))
                     .reshape(B, T), jnp.int32)
    o_chunk = ops.paged_prefill_attention(q, kp, vp, bt, starts)
    for c in range(C):
        o_one = ops.paged_decode_attention(q[:, c], kp[None], vp[None], 0,
                                           bt, starts + c)
        np.testing.assert_allclose(np.asarray(o_chunk[:, c]),
                                   np.asarray(o_one), atol=1e-5)


@pytest.mark.parametrize("B,Q,H,K,D,bs,T", [
    (2, 5, 8, 2, 64, 16, 8),   # GQA 4:1, K=4 speculation (Q = K + 1)
    (1, 2, 4, 4, 64, 16, 4),   # MHA, K=1
    (3, 5, 4, 1, 32, 8, 8),    # MQA
])
def test_paged_verify_matches_ref(B, Q, H, K, D, bs, T):
    """Speculative verify: Q candidate queries per lane, query i at
    absolute position positions[b] + i, against the mask-walk oracle."""
    rng = np.random.default_rng(B * 1000 + Q)
    n_blocks = 1 + B * T
    kp, vp = arr(rng, n_blocks, bs, K, D), arr(rng, n_blocks, bs, K, D)
    q = arr(rng, B, Q, H, D)
    pos = jnp.asarray(rng.integers(0, T * bs - Q, B), jnp.int32)
    bt = jnp.asarray(rng.permutation(np.arange(1, n_blocks))
                     [: B * T].reshape(B, T), jnp.int32)
    o = ops.paged_verify_attention(q, kp, vp, bt, pos)
    o_ref = ref.paged_verify_attention_ref(q, kp, vp, bt, pos)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=1e-5)


def test_paged_verify_equals_decode_steps():
    """Verify query i == one decode step at positions + i with the KV
    already in place: the multi-query pass and the sequential chain see
    the same causal context."""
    rng = np.random.default_rng(7)
    B, Q, H, K, D, bs, T = 2, 5, 4, 2, 32, 8, 4
    n_blocks = 1 + B * T
    kp, vp = arr(rng, n_blocks, bs, K, D), arr(rng, n_blocks, bs, K, D)
    q = arr(rng, B, Q, H, D)
    pos = jnp.asarray([6, 13], jnp.int32)
    bt = jnp.asarray(rng.permutation(np.arange(1, n_blocks))
                     .reshape(B, T), jnp.int32)
    o = ops.paged_verify_attention(q, kp, vp, bt, pos)
    for i in range(Q):
        o_one = ops.paged_decode_attention(q[:, i], kp[None], vp[None], 0,
                                           bt, pos + i)
        np.testing.assert_allclose(np.asarray(o[:, i]), np.asarray(o_one),
                                   atol=1e-5)


# ---------------------------------------------------------------------------
# PagedCachePool allocator invariants
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_model():
    cfg = get_config("llama2-7b").smoke_config()
    return build_model(cfg, local_plan(param_dtype=jnp.bfloat16))


def _fake_prefill(model, batch, seq, value=1.0):
    cfg = model.cfg
    shape = (cfg.num_layers, batch, seq, cfg.n_kv_heads, cfg.head_dim)
    return {"attn": {"k": jnp.full(shape, value, jnp.bfloat16),
                     "v": jnp.full(shape, 2 * value, jnp.bfloat16)}}


def test_pool_alloc_release_invariants(tiny_model):
    pool = PagedCachePool(tiny_model, n_lanes=3, max_seq=64, block_size=8)
    total = pool.n_blocks - 1          # block 0 reserved for parking
    assert len(pool.free_blocks) == total

    pool.insert(10, _fake_prefill(tiny_model, 1, 20), 0, 20)   # 3 blocks
    pool.insert(11, _fake_prefill(tiny_model, 1, 8), 0, 8)     # 1 block
    assert pool.used_blocks == 4
    held = pool.blocks_of[10] + pool.blocks_of[11]
    assert len(set(held)) == len(held), "double-allocated block"
    assert 0 not in held, "parking block must never be allocated"
    # block tables point parked slots at 0 and live slots at owned blocks
    lane = pool.lane_of[10]
    assert list(pool.block_tables[lane][:3]) == pool.blocks_of[10]
    assert all(b == 0 for b in pool.block_tables[lane][3:])

    pool.release(10)
    assert pool.used_blocks == 1
    assert len(pool.free_blocks) == total - 1
    # released blocks are reusable: fill the pool completely
    while pool.can_admit(16):
        pool.insert(100 + pool.used_blocks, _fake_prefill(tiny_model, 1, 16),
                    0, 16)
    assert not pool.free_lanes or len(pool.free_blocks) < pool.blocks_for(17)


def test_pool_insert_writes_only_touched_blocks(tiny_model):
    """O(blocks-touched) admission: untouched blocks keep their contents
    bit-for-bit (no whole-pool rewrite)."""
    pool = PagedCachePool(tiny_model, n_lanes=2, max_seq=32, block_size=8)
    pool.insert(1, _fake_prefill(tiny_model, 1, 16, value=3.0), 0, 16)
    before = np.asarray(pool.cache["attn"]["k"]).copy()
    blks1 = list(pool.blocks_of[1])
    pool.insert(2, _fake_prefill(tiny_model, 1, 9, value=5.0), 0, 9)
    after = np.asarray(pool.cache["attn"]["k"])
    touched = set(pool.blocks_of[2])
    for b in range(pool.n_blocks):
        if b not in touched:
            np.testing.assert_array_equal(after[:, b], before[:, b])
    # and request 1's blocks still hold its values
    for b in blks1:
        assert float(after[:, b].max()) == 3.0


def test_pool_append_allocation_and_preemption_path(tiny_model):
    pool = PagedCachePool(tiny_model, n_lanes=2, max_seq=32, block_size=8,
                          n_blocks=4)   # 3 usable blocks
    pool.insert(1, _fake_prefill(tiny_model, 1, 8), 0, 8)    # 1 block full
    pool.insert(2, _fake_prefill(tiny_model, 1, 8), 0, 8)    # 1 block full
    # both need an append block; only one is left -> one victim
    victims = pool.ensure_append_blocks([2, 1])
    assert victims == [1]
    assert len(pool.blocks_of[2]) == 2
    pool.release(1)
    assert pool.ensure_append_blocks([2]) == []


# ---------------------------------------------------------------------------
# engine: slot-based and paged serving produce identical streams
# ---------------------------------------------------------------------------

def _run_engine(model, params, vocab, *, paged, n_blocks=None, seed=0,
                n_req=5, max_new=6):
    eng = Engine(model, params, max_seq=64, n_slots=3,
                 knobs=EngineKnobs(max_batch=3), paged=paged, block_size=8,
                 n_blocks=n_blocks)
    rng = np.random.default_rng(seed)
    for _ in range(n_req):
        plen = int(rng.integers(4, 20))
        eng.submit(Request(prompt=[int(t) for t in rng.integers(0, vocab, plen)],
                           max_new_tokens=max_new))
    stats = eng.run()
    outs = [tuple(r.output) for r in sorted(stats.completed,
                                            key=lambda r: r.req_id)]
    return outs, stats


def test_engine_slot_vs_paged_identical(tiny_model):
    params = tiny_model.init(jax.random.PRNGKey(0))
    vocab = tiny_model.cfg.vocab_size
    outs_slot, st_slot = _run_engine(tiny_model, params, vocab, paged=False)
    outs_paged, st_paged = _run_engine(tiny_model, params, vocab, paged=True)
    assert outs_slot == outs_paged
    assert len(outs_paged) == 5
    # batched admission: fewer jitted prefill launches than requests
    assert st_paged.prefill_batches < st_slot.prefill_batches


def test_engine_paged_preemption_recompute(tiny_model):
    """A pool too small to hold all actives preempts + recomputes, and the
    token streams still match the roomy-pool run exactly."""
    params = tiny_model.init(jax.random.PRNGKey(1))
    vocab = tiny_model.cfg.vocab_size
    roomy, _ = _run_engine(tiny_model, params, vocab, paged=True, seed=3,
                           max_new=12)
    tight, st = _run_engine(tiny_model, params, vocab, paged=True, seed=3,
                            max_new=12, n_blocks=8)
    assert tight == roomy
    assert st.preemptions > 0


def test_engine_paged_pool_fully_reclaimed(tiny_model):
    params = tiny_model.init(jax.random.PRNGKey(0))
    eng = Engine(tiny_model, params, max_seq=64, n_slots=2,
                 knobs=EngineKnobs(max_batch=2), paged=True, block_size=8)
    for i in range(3):
        eng.submit(Request(prompt=[1, 2, 3, 4], max_new_tokens=4))
    eng.run()
    assert eng.pool.used_blocks == 0
    assert sorted(eng.pool.free_lanes) == [0, 1]
    assert (eng.pool.block_tables == 0).all()


# ---------------------------------------------------------------------------
# profiles bridge: engine-measured table
# ---------------------------------------------------------------------------

def test_measure_from_engine_calibrates_entry():
    from repro.core import profiles as P
    mp = P.measure_from_engine(batches=(1, 2), freqs=(1.0,),
                               n_requests=3, max_new=4, prompt_len=6)
    assert len(mp.rows) == 4      # 2 variants x 2 batches x 1 freq
    assert all(r["tok_per_s"] > 0 for r in mp.rows)
    assert mp.calibration["source"] == "engine-measured"
    # entries ride the unchanged ProfileEntry/_entry API
    assert max(e.goodput for e in mp.entries) == 1.0
    P.calibrate(mp)
    try:
        assert P._CAL["source"] == "engine-measured"
        e = P._entry(P.NOMINAL)
        assert e.goodput == 1.0    # nominal is the normalization point
        assert P._entry(P.NOMINAL.__class__(
            freq=1.0, tp=8, batch=1, size="70b", quant="bf16")).goodput \
            == pytest.approx(mp.calibration["batch_eff"][1], rel=1e-6)
    finally:
        P.reset_calibration()
