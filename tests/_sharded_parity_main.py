"""Multi-device half of tests/test_sharded.py.

Runs in a subprocess with ``XLA_FLAGS=--xla_force_host_platform_device_count=4``
(the in-process test session pins a single CPU device; device count is
fixed at jax import, so sharded checks need their own interpreter).
Prints one "ok <name>" line per passing check and exits nonzero on the
first failure — the parent test asserts on the ok-lines.
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = \
        (flags + " --xla_force_host_platform_device_count=4").strip()

import numpy as np          # noqa: E402
import jax                  # noqa: E402
import jax.numpy as jnp     # noqa: E402
from functools import partial               # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.configs.base import get_config                    # noqa: E402
from repro.models.attention import (_paged_decode_core,      # noqa: E402
                                    _paged_prefill_core)
from repro.models.sharding import shard_map_or_call          # noqa: E402
from repro.serving import EngineSpec, serving_plan           # noqa: E402
from repro.serving.request import Request                    # noqa: E402


def ok(name):
    print(f"ok {name}", flush=True)


def check_core_parity():
    """Sharded decode/prefill cores match the unsharded oracle to 1e-6
    at shard in {2, 4}; pool scatters are bit-exact."""
    rng = np.random.default_rng(0)
    B, H, K, hd, bs, T = 3, 4, 2, 16, 8, 4
    kv_idx = jnp.asarray(np.arange(H) % K)
    for shards in (2, 4):
        nb = shards * 4
        q = jnp.asarray(rng.normal(size=(B, H, hd)), jnp.float32)
        kp = jnp.asarray(rng.normal(size=(nb, bs, K, hd)), jnp.float32)
        vp = jnp.asarray(rng.normal(size=(nb, bs, K, hd)), jnp.float32)
        tables = jnp.asarray(
            rng.permutation(np.arange(1, nb))[:B * T].reshape(B, T)
            if nb - 1 >= B * T else rng.integers(1, nb, (B, T)), jnp.int32)
        positions = jnp.asarray([13, 7, 24], jnp.int32)
        kn = jnp.asarray(rng.normal(size=(B, K, hd)), jnp.float32)
        vn = jnp.asarray(rng.normal(size=(B, K, hd)), jnp.float32)
        core = partial(_paged_decode_core, scale=0.25, kv_idx=kv_idx)
        o_ref, kp_ref, vp_ref = core(None, q, kp, vp, tables, positions,
                                     kn, vn)
        plan = serving_plan(shards, param_dtype=jnp.float32)
        o_s, kp_s, vp_s = shard_map_or_call(
            plan, core,
            (P(None), P("model"), P("model"), P(None), P(None), P(None),
             P(None)),
            (P(None), P("model"), P("model")),
            q, kp, vp, tables, positions, kn, vn)
        assert float(jnp.max(jnp.abs(o_s - o_ref))) < 1e-6, shards
        assert float(jnp.max(jnp.abs(kp_s - kp_ref))) == 0.0
        assert float(jnp.max(jnp.abs(vp_s - vp_ref))) == 0.0

        C = 8
        qf = jnp.asarray(rng.normal(size=(B, C, H, hd)), jnp.float32)
        kf = jnp.asarray(rng.normal(size=(B, C, K, hd)), jnp.float32)
        vf = jnp.asarray(rng.normal(size=(B, C, K, hd)), jnp.float32)
        starts = jnp.asarray([0, 5, 11], jnp.int32)
        lengths = jnp.asarray([8, 6, 3], jnp.int32)
        coreP = partial(_paged_prefill_core, scale=0.25, kv_idx=kv_idx)
        o_ref, kp_ref, vp_ref = coreP(None, qf, kp, vp, tables, starts,
                                      lengths, kf, vf)
        o_s, kp_s, vp_s = shard_map_or_call(
            plan, coreP,
            (P(None), P("model"), P("model"), P(None), P(None), P(None),
             P(None), P(None)),
            (P(None), P("model"), P("model")),
            qf, kp, vp, tables, starts, lengths, kf, vf)
        assert float(jnp.max(jnp.abs(o_s - o_ref))) < 1e-6, shards
        assert float(jnp.max(jnp.abs(kp_s - kp_ref))) == 0.0
        assert float(jnp.max(jnp.abs(vp_s - vp_ref))) == 0.0
    ok("core_parity")


def _run_streams(eng, n=3, new=12):
    reqs = [Request(prompt=[3 + i, 7, 11, 13 + i], max_new_tokens=new)
            for i in range(n)]
    for r in reqs:
        eng.submit(r)
    for _ in range(80):
        if not (eng.queue or eng.active or eng.prefilling):
            break
        eng.step()
    return [list(r.output) for r in reqs]


def check_engine_streams():
    """End-to-end: a shards=2 engine reproduces the shards=1 greedy
    streams on the smoke config (same seed, same requests)."""
    cfg = get_config("llama2-7b").smoke_config()
    base = dict(max_seq=64, n_slots=4, block_size=8, seed=0)
    ref = _run_streams(EngineSpec(cfg, shards=1, **base).build())
    s2 = _run_streams(EngineSpec(cfg, shards=2, **base).build())
    assert ref == s2, (ref, s2)
    assert all(len(o) == 12 for o in ref)
    ok("engine_streams")


def check_pool_invariants():
    """Free-list/refcount invariants hold with a sharded pool: blocks
    allocated on submit are returned on completion, per shard stripe."""
    cfg = get_config("llama2-7b").smoke_config()
    eng = EngineSpec(cfg, shards=2, max_seq=64, n_slots=4,
                     block_size=8, seed=0).build()
    pool = eng.pool
    assert pool.n_blocks % pool.shards == 0
    free0 = len(pool.free_blocks)
    lanes0 = len(pool.free_lanes)
    _run_streams(eng)
    assert len(pool.free_blocks) == free0, (free0, len(pool.free_blocks))
    assert len(pool.free_lanes) == lanes0
    assert int(pool.ref[1:].sum()) == 0          # block 0 is the parking block
    assert sorted(pool.free_blocks) == list(range(1, pool.n_blocks))
    ok("pool_invariants")


def check_set_shards():
    """Live reshard: params transfer verbatim and streams still match;
    incompatible degrees reject with a reason instead of crashing."""
    cfg = get_config("llama2-7b").smoke_config()   # n_kv_heads=2
    base = dict(max_seq=64, n_slots=4, block_size=8, seed=0)
    ref = _run_streams(EngineSpec(cfg, shards=1, **base).build())
    eng = EngineSpec(cfg, shards=1, **base).build()
    assert eng.can_shard(2) is None
    eng.set_shards(2)
    assert eng.shards == 2 and eng.stats.shard_swaps == 1
    assert _run_streams(eng) == ref
    assert eng.can_shard(4) is not None       # n_kv_heads=2 % 4 != 0
    assert eng.can_shard(100) is not None     # more shards than devices
    try:
        eng.set_shards(4)
    except ValueError:
        pass
    else:
        raise AssertionError("set_shards(4) should reject on kv heads")
    assert eng.shards == 2                    # unchanged after rejection
    ok("set_shards")


def check_born_sharded():
    """``EngineSpec.build`` at shards=2 creates params and pool in their
    shardings: every leaf matches ``param_shardings()`` /
    ``paged_cache_shardings()``, and no array held by one device is
    copied onto the mesh on the way; the mesh's axes are Auto."""
    from jax.sharding import AxisType
    cfg = get_config("llama2-7b").smoke_config()
    moved = []
    real_put = jax.device_put

    def watched_put(x, *a, **kw):
        moved.extend(leaf.shape for leaf in jax.tree.leaves(x)
                     if isinstance(leaf, jax.Array) and leaf.size > 1
                     and len(leaf.sharding.device_set) == 1)
        return real_put(x, *a, **kw)

    jax.device_put = watched_put
    try:
        eng = EngineSpec(cfg, shards=2, max_seq=64, n_slots=4,
                         block_size=8).build()
    finally:
        jax.device_put = real_put
    assert not moved, f"single-device arrays copied onto the mesh: {moved}"
    m = eng.model
    assert m.plan.mesh.axis_types == (AxisType.Auto,) * 2
    for tree, want in ((eng.params, m.param_shardings()),
                       (eng.pool.cache, m.paged_cache_shardings())):
        same = jax.tree.map(
            lambda a, s: a.sharding.is_equivalent_to(s, a.ndim), tree, want)
        assert all(jax.tree.leaves(same)), same
    ok("born_sharded")


def check_sharded_fleet():
    """An EngineFleet of sharded engines serves a pumped workload."""
    from repro.serving import EngineFleet
    cfg = get_config("llama2-7b").smoke_config()
    spec = EngineSpec(cfg, shards=2, max_seq=64, n_slots=4, block_size=8)
    fleet = EngineFleet(spec, n_engines=2, steps_per_tick=3)
    bks = [fleet.make_backend() for _ in range(8)]
    for t in range(3):
        for bk in bks:
            bk.pump(now=float(t), load=1.0)
        fleet.flush(now=float(t))
    fleet.drain(now_h=3.0)
    per_srv = [sum(len(r.output) for r in bk.issued) for bk in bks]
    assert all(n > 0 for n in per_srv), per_srv
    ok("sharded_fleet")


if __name__ == "__main__":
    assert jax.device_count() >= 4, jax.device_count()
    check_core_parity()
    check_engine_streams()
    check_pool_invariants()
    check_set_shards()
    check_born_sharded()
    check_sharded_fleet()
    print("ALL_OK", flush=True)
    sys.exit(0)
