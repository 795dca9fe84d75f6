"""Device-resident decode hot path: fused multi-step decode parity,
prefix-shared block refcount invariants, chunked-prefill interleaving, and
the persistent device-buffer mirrors."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model, local_plan
from repro.serving import Engine, EngineKnobs, PagedCachePool, Request

# whole-module: every test drives a live jitted engine (CI sim job);
# leakcheck = tracer escapes fail at the leak site (tapaslint runtime)
pytestmark = [pytest.mark.slow, pytest.mark.leakcheck]


@pytest.fixture(scope="module")
def tiny_model():
    cfg = get_config("llama2-7b").smoke_config()
    return build_model(cfg, local_plan(param_dtype=jnp.bfloat16))


@pytest.fixture(scope="module")
def tiny_params(tiny_model):
    return tiny_model.init(jax.random.PRNGKey(0))


def _submit_load(eng, vocab, *, n_req=5, max_new=6, seed=0, shared=0,
                 stagger=0):
    rng = np.random.default_rng(seed)
    head = [int(t) for t in rng.integers(0, vocab, shared)]
    for i in range(n_req):
        plen = int(rng.integers(4, 20))
        tail = [int(t) for t in rng.integers(0, vocab, plen)]
        eng.submit(Request(prompt=head + tail,
                           max_new_tokens=max_new + stagger * i))


def _streams(stats):
    return [tuple(r.output) for r in sorted(stats.completed,
                                            key=lambda r: r.req_id)]


def _engine(model, params, **kw):
    kw.setdefault("max_seq", 64)
    kw.setdefault("n_slots", 3)
    kw.setdefault("block_size", 8)
    kw.setdefault("paged", True)
    kw.setdefault("knobs", EngineKnobs(max_batch=kw["n_slots"]))
    return Engine(model, params, **kw)


# ---------------------------------------------------------------------------
# fused multi-step decode: parity vs the per-step path
# ---------------------------------------------------------------------------

def test_fused_decode_matches_per_step_path(tiny_model, tiny_params):
    """N fused steps == N independent decode_step_paged launches: identical
    tokens and matching logits (model-level, one lane active + one parked)."""
    model, params = tiny_model, tiny_params
    vocab = model.cfg.vocab_size
    bs, T, n_lanes, max_seq = 8, 8, 2, 64
    pool = PagedCachePool(model, n_lanes, max_seq, block_size=bs)
    rng = np.random.default_rng(0)
    prompt = [int(t) for t in rng.integers(0, vocab, 11)]

    logits, cache = jax.jit(model.prefill)(
        params, jnp.asarray([prompt], jnp.int32))
    tok0 = int(jnp.argmax(logits[0, :vocab]))
    pool.insert(1, cache, 0, len(prompt))
    pool.ensure_append_blocks([1], horizon=5)

    n = 5
    # per-step reference: N sequential single-token launches
    step = jax.jit(model.decode_step_paged)
    cache_a = jax.tree.map(jnp.copy, pool.cache)
    toks_ref, tok, pos = [], tok0, len(prompt)
    tables = pool.tables()
    for _ in range(n):
        lg, cache_a = step(params, cache_a,
                           jnp.asarray([tok, 0], jnp.int32),
                           jnp.asarray([pos, 0], jnp.int32), tables)
        tok = int(jnp.argmax(lg[0, :vocab]))
        toks_ref.append(tok)
        pos += 1
    # fused: one launch, horizon N
    out = model.decode_multi_paged(
        params, jax.tree.map(jnp.copy, pool.cache),
        jnp.asarray([tok0, 0], jnp.int32),
        jnp.asarray([len(prompt), 0], jnp.int32), tables,
        jnp.asarray([True, False]), jnp.asarray([100, 0], jnp.int32),
        jnp.asarray([-1, -1], jnp.int32), num_steps=n, max_len=max_seq)
    toks_f, emitted, last_logits, (_, pos_f, act_f, _), _ = out
    assert [int(t) for t in np.asarray(toks_f)[:, 0]] == toks_ref
    assert bool(np.asarray(emitted)[:, 0].all())
    assert not np.asarray(emitted)[:, 1].any()          # parked lane silent
    assert int(np.asarray(pos_f)[0]) == len(prompt) + n
    np.testing.assert_allclose(np.asarray(last_logits[0, :vocab], np.float32),
                               np.asarray(lg[0, :vocab], np.float32),
                               atol=1e-6)


def test_engine_horizon_streams_identical(tiny_model, tiny_params):
    """Engine-level: horizon-8 fused serving produces exactly the per-step
    token streams, with ~horizon-fold fewer decode host syncs."""
    vocab = tiny_model.cfg.vocab_size
    runs = {}
    for hz in (1, 8):
        eng = _engine(tiny_model, tiny_params, horizon=hz)
        _submit_load(eng, vocab, max_new=12, stagger=2)
        stats = eng.run()
        runs[hz] = (_streams(stats), stats)
    assert runs[1][0] == runs[8][0]
    assert len(runs[8][0]) == 5
    assert runs[8][1].decode_syncs * 2 <= runs[1][1].decode_syncs


def test_fused_decode_respects_eos_and_budget(tiny_model, tiny_params):
    """Mid-horizon finishes (eos / budget) stop emission on the right token
    even though the device loop keeps spinning."""
    vocab = tiny_model.cfg.vocab_size
    eng = _engine(tiny_model, tiny_params, horizon=8)
    rng = np.random.default_rng(3)
    prompt = [int(t) for t in rng.integers(0, vocab, 9)]
    # discover the greedy stream, then replay with eos set to a mid token
    eng.submit(Request(prompt=list(prompt), max_new_tokens=10))
    free = _streams(eng.run())[0]
    eos = free[4]
    eng2 = _engine(tiny_model, tiny_params, horizon=8)
    eng2.submit(Request(prompt=list(prompt), max_new_tokens=10, eos_id=eos))
    got = _streams(eng2.run())[0]
    # stops exactly at the FIRST occurrence of the eos token
    assert got == free[: free.index(eos) + 1]


# ---------------------------------------------------------------------------
# prefix sharing: refcount invariants
# ---------------------------------------------------------------------------

def test_prefix_refcount_invariants(tiny_model):
    pool = PagedCachePool(tiny_model, n_lanes=3, max_seq=64, block_size=8)
    toks = list(range(20))                 # 2 full blocks + 4 tail tokens
    lane = pool.admit_prefill(1, len(toks), [])
    assert lane is not None
    assert pool.lengths[lane] == 0         # nothing valid until prefill
    pool.register_prefix(1, toks)
    assert len(pool.prefix_index) == 2     # only FULL blocks are published

    shared = pool.shared_prefix(toks)
    assert shared == pool.blocks_of[1][:2]
    before = pool.used_blocks
    pool.admit_prefill(2, len(toks), shared)
    # 3 blocks needed for ctx+1, two reused -> only one fresh allocation
    assert pool.used_blocks == before + 1
    assert all(pool.ref[b] == 2 for b in shared)

    # release with a live sharer keeps the shared blocks and the index
    pool.release(1)
    assert all(pool.ref[b] == 1 for b in shared)
    assert len(pool.prefix_index) == 2
    assert all(b not in pool.free_blocks for b in shared)
    # last release frees them and prunes the index
    pool.release(2)
    assert pool.used_blocks == 0
    assert not pool.prefix_index and not pool.key_of
    assert (pool.ref[1:] == 0).all()


def test_prefix_sharing_engine_streams_and_savings(tiny_model, tiny_params):
    """Prefix-shared serving yields identical tokens while prefilling
    fewer tokens (the shared head is skipped)."""
    vocab = tiny_model.cfg.vocab_size
    base = _engine(tiny_model, tiny_params)
    _submit_load(base, vocab, shared=17, max_new=6, stagger=3)
    st0 = base.run()
    shr = _engine(tiny_model, tiny_params, prefix_share=True,
                  prefill_chunk=16, horizon=4)
    _submit_load(shr, vocab, shared=17, max_new=6, stagger=3)
    st1 = shr.run()
    assert _streams(st0) == _streams(st1)
    assert shr.pool.shared_block_hits > 0
    assert st1.prefill_tokens < st0.prefill_tokens
    assert shr.pool.used_blocks == 0       # everything reclaimed


def test_pending_share_dedups_same_wave_admissions(tiny_model, tiny_params):
    """Two requests with an identical prompt head submitted in the same
    wave: the second waits on the first's in-flight prefill and attaches
    to its blocks (register-at-admit), instead of both writing the head."""
    vocab = tiny_model.cfg.vocab_size
    rng = np.random.default_rng(3)
    head = [int(t) for t in rng.integers(0, vocab, 24)]  # 3 full blocks @8
    tails = [[int(t) for t in rng.integers(0, vocab, 4 + i)]
             for i in range(3)]

    def serve(prefix_share):
        eng = _engine(tiny_model, tiny_params, max_seq=128, n_slots=4,
                      knobs=EngineKnobs(max_batch=4),
                      prefix_share=prefix_share, prefill_chunk=16)
        for t in tails:                    # one wave, identical heads
            eng.submit(Request(prompt=head + t, max_new_tokens=4))
        stats = eng.run()
        return eng, stats

    base, st0 = serve(False)
    shr, st1 = serve(True)
    assert _streams(st0) == _streams(st1)
    # the two waiters deferred admission, then attached to the 3 head
    # blocks the first request prefilled — none of them recomputed it
    assert shr.pool.pending_share_waits > 0
    assert shr.pool.shared_block_hits >= 6
    assert st1.prefill_tokens <= st0.prefill_tokens - 2 * len(head)
    assert shr.pool.used_blocks == 0       # everything reclaimed
    assert not shr.pool.pending_index and not shr.pool.pending_of


def test_pending_claims_cleared_on_release(tiny_model):
    """A preempted/failed prefill releases its pending chain-key claims so
    waiters cannot deadlock on a dead owner."""
    pool = PagedCachePool(tiny_model, n_lanes=3, max_seq=64, block_size=8)
    toks = list(range(20))                 # 2 full blocks + tail
    assert pool.admit_prefill(1, len(toks), []) is not None
    pool.register_pending(1, toks)
    assert pool.pending_shared(toks, have=0)
    pool.release(1)                        # preemption path
    assert not pool.pending_shared(toks, have=0)
    assert not pool.pending_index and not pool.pending_of


# ---------------------------------------------------------------------------
# chunked prefill: interleaving + TBT non-regression
# ---------------------------------------------------------------------------

def test_chunked_prefill_streams_identical(tiny_model, tiny_params):
    vocab = tiny_model.cfg.vocab_size
    a = _engine(tiny_model, tiny_params)
    _submit_load(a, vocab, seed=5)
    b = _engine(tiny_model, tiny_params, prefill_chunk=8)
    _submit_load(b, vocab, seed=5)
    assert _streams(a.run()) == _streams(b.run())


def test_chunked_prefill_interleaves_decode(tiny_model, tiny_params):
    """While a long prompt streams in chunk by chunk, already-active
    requests keep producing decode tokens every scheduler step (the long
    prefill never blocks decode for more than one chunk)."""
    vocab = tiny_model.cfg.vocab_size
    eng = _engine(tiny_model, tiny_params, max_seq=128, prefill_chunk=8,
                  n_slots=2, knobs=EngineKnobs(max_batch=2))
    rng = np.random.default_rng(0)
    eng.submit(Request(prompt=[int(t) for t in rng.integers(0, vocab, 6)],
                       max_new_tokens=40))
    eng.step(now=0.0)                      # short request starts decoding
    assert len(eng.active) == 1
    long_prompt = [int(t) for t in rng.integers(0, vocab, 60)]
    eng.submit(Request(prompt=long_prompt, max_new_tokens=4))
    decode_during_prefill = 0
    steps = 0
    while eng.prefilling or eng.queue:
        produced = eng.step(now=float(steps + 1))
        if eng.prefilling:
            decode_during_prefill += produced
        steps += 1
        assert steps < 100
    # 60 tokens / 8-token chunks = several steps of overlap, with the short
    # request emitting on every one of them
    assert decode_during_prefill >= 5


def test_chunked_prefill_tbt_non_regression(tiny_model, tiny_params):
    """Wall-clock TBT of a decoding request spanning a long admission:
    chunked prefill caps the stall at ~one chunk, so the worst inter-token
    gap must not exceed the monolithic-prefill gap (generous 1.5x margin
    for CI noise)."""
    vocab = tiny_model.cfg.vocab_size
    rng = np.random.default_rng(1)
    short = [int(t) for t in rng.integers(0, vocab, 6)]
    long_prompt = [int(t) for t in rng.integers(0, vocab, 480)]

    def worst_gap(chunk):
        eng = _engine(tiny_model, tiny_params, max_seq=512, n_slots=2,
                      knobs=EngineKnobs(max_batch=2), prefill_chunk=chunk)
        # warmup pass: compile every prefill/decode shape this config hits
        eng.submit(Request(prompt=list(short), max_new_tokens=20))
        eng.step()
        eng.submit(Request(prompt=list(long_prompt), max_new_tokens=2))
        eng.run(max_steps=200)
        # measured pass: a decoding victim spans the long admission
        eng.submit(Request(prompt=list(short), max_new_tokens=60))
        eng.step()
        victim = next(iter(eng.active.values()))
        # anchor the stamp window *before* the long prompt goes in: the
        # admission stall lands in the very first step, and np.diff
        # discards everything before the first stamp, so without this
        # anchor the monolithic stall would fall in a blind spot and the
        # comparison would reduce to scheduler noise
        stamps = [time.perf_counter()]
        eng.submit(Request(prompt=list(long_prompt), max_new_tokens=2))
        seen = len(victim.output)
        for _ in range(200):
            eng.step()
            if len(victim.output) > seen:
                seen = len(victim.output)
                stamps.append(time.perf_counter())
            if victim.done and not (eng.queue or eng.prefilling
                                    or eng.active):
                break
        return max(np.diff(stamps)) if len(stamps) > 2 else 0.0

    # wall-clock comparison: a background stall (GC, a noisy CI neighbor)
    # during either pass flips the verdict, so retry a bounded number of
    # times and pass on the first clean measurement
    for attempt in range(3):
        monolithic = worst_gap(None)
        chunked = worst_gap(32)
        if chunked <= monolithic * 1.5:
            break
    assert chunked <= monolithic * 1.5, \
        f"after {attempt + 1} attempts: {chunked} !<= 1.5 * {monolithic}"


# ---------------------------------------------------------------------------
# persistent device mirrors + misc satellites
# ---------------------------------------------------------------------------

def test_device_mirrors_track_host_state(tiny_model, tiny_params):
    """tables()/positions()/last_tokens_dev() stay consistent with the
    numpy source of truth through admit / decode / release, without bulk
    re-uploads (the mirror object is updated incrementally)."""
    vocab = tiny_model.cfg.vocab_size
    eng = _engine(tiny_model, tiny_params, horizon=4)
    _submit_load(eng, vocab, n_req=4, max_new=8)
    steps = 0
    while eng.queue or eng.active:
        eng.step(now=float(steps))
        steps += 1
        pool = eng.pool
        np.testing.assert_array_equal(np.asarray(pool.tables()),
                                      pool.block_tables)
        np.testing.assert_array_equal(np.asarray(pool.positions()),
                                      pool.lengths)
        np.testing.assert_array_equal(np.asarray(pool.last_tokens_dev()),
                                      pool.last_tokens)
    assert eng.pool.used_blocks == 0


def test_bucket_clamps_to_max_seq(tiny_model, tiny_params):
    """Oversized contexts are rejected (never bucketed past the cache) and
    legal ones near the cap bucket to max_seq, not past it."""
    from repro.serving.engine import _bucket
    assert _bucket(70, hi=96) == 96
    assert _bucket(70, hi=128) == 128
    assert _bucket(7) == 16
    vocab = tiny_model.cfg.vocab_size
    eng = _engine(tiny_model, tiny_params)
    rng = np.random.default_rng(0)
    eng.submit(Request(prompt=[int(t) for t in rng.integers(0, vocab, 64)],
                       max_new_tokens=4))     # == max_seq: can never fit
    eng.submit(Request(prompt=[int(t) for t in rng.integers(0, vocab, 5)],
                       max_new_tokens=4))
    stats = eng.run()
    assert stats.rejected == 1
    assert len(stats.completed) == 2
    served = [r for r in stats.completed if r.output]
    assert len(served) == 1 and len(served[0].output) == 4


def test_stats_bounded_and_goodput_incremental(tiny_model, tiny_params):
    from repro.serving.engine import EngineStats
    st = EngineStats()
    for i in range(612):
        st.record_step(0.5)
    assert st.n_steps == 612
    assert st.step_time_total == pytest.approx(0.5 * 612)

    vocab = tiny_model.cfg.vocab_size
    eng = _engine(tiny_model, tiny_params, horizon=2)
    _submit_load(eng, vocab)
    eng.run()
    g1 = eng.goodput(ttft_slo=50, tbt_slo=50)
    acc = eng.stats._good_acc[(50, 50)]
    assert acc[0] == len(eng.stats.completed)         # folded exactly once
    assert eng.goodput(ttft_slo=50, tbt_slo=50) == g1  # cached, no rescan
    assert g1 > 0
