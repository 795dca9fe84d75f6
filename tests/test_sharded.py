"""Tensor-sharded paged pool + fleet-of-engines coverage.

The multi-device checks (LSE-combine parity vs the unsharded oracle,
bit-identical streams across a live ``set_shards``, per-shard pool
invariants, a sharded ``EngineFleet``) need more than the session's
single pinned CPU device, so they run ``tests/_sharded_parity_main.py``
in a subprocess with ``--xla_force_host_platform_device_count=4``; this
module asserts on its ok-lines and covers everything that works on one
device in-process: the shard-compat validation, the LSE-outputs Pallas
kernel, and the batched pump across 100 simulated servers.
"""
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

_ROOT = pathlib.Path(__file__).resolve().parent.parent


def test_sharded_parity_multi_device():
    env = dict(os.environ)
    env["PYTHONPATH"] = str(_ROOT / "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run(
        [sys.executable, str(_ROOT / "tests" / "_sharded_parity_main.py")],
        capture_output=True, text=True, env=env, timeout=1200)
    assert proc.returncode == 0, \
        f"sharded parity subprocess failed:\n{proc.stdout}\n{proc.stderr}"
    for name in ("core_parity", "engine_streams", "pool_invariants",
                 "set_shards", "born_sharded", "sharded_fleet"):
        assert f"ok {name}" in proc.stdout, \
            f"missing check {name!r}:\n{proc.stdout}"
    assert "ALL_OK" in proc.stdout


def test_shard_compat_validation():
    from repro.configs.base import get_config
    from repro.serving import shard_compat

    cfg = get_config("llama2-7b").smoke_config()   # n_kv_heads == 2
    assert shard_compat(1, cfg) is None
    err = shard_compat(4, cfg)
    assert err is not None and "kv" in err.lower()
    # degree above the visible device budget is the engine's (not the
    # config's) problem; the config check is purely about head counts
    assert shard_compat(2, cfg) is None


def test_engine_spec_rejects_unshardable_variant():
    from repro.configs.base import get_config
    from repro.serving import EngineSpec

    cfg = get_config("llama2-7b").smoke_config()
    bad = cfg.replace(n_kv_heads=3, name="odd-kv")
    spec = EngineSpec(cfg, shards=2, variants=(("odd", bad),))
    with pytest.raises(ValueError, match="odd"):
        spec.validate()


def test_paged_decode_lse_kernel_matches_full_pool():
    """Per-shard LSE kernel outputs merge exactly to the full-pool kernel
    (the TPU-kernel counterpart of ``_paged_decode_core``'s psum merge)."""
    import jax.numpy as jnp
    from repro.kernels.ops import (combine_lse, paged_decode_attention,
                                   paged_decode_attention_lse)

    rng = np.random.default_rng(0)
    B, H, K, D, bs, nb, T = 3, 8, 4, 16, 8, 16, 4
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((nb, bs, K, D)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((nb, bs, K, D)), jnp.float32)
    bt = jnp.asarray(rng.permutation(np.arange(1, nb))[:B * T]
                     .reshape(B, T), jnp.int32)
    pos = jnp.asarray([5, 17, 30], jnp.int32)
    ref = paged_decode_attention(q, kp[None], vp[None], 0, bt, pos)
    for shards in (2, 4):
        nb_loc = nb // shards
        os_, lses = [], []
        for r in range(shards):
            local = bt - r * nb_loc
            owned = ((local >= 0) & (local < nb_loc)).astype(jnp.int32)
            safe = jnp.clip(local, 0, nb_loc - 1)
            o, lse = paged_decode_attention_lse(
                q, kp[r * nb_loc:(r + 1) * nb_loc],
                vp[r * nb_loc:(r + 1) * nb_loc], safe, pos, owned)
            os_.append(o)
            lses.append(lse)
        got = combine_lse(jnp.stack(os_), jnp.stack(lses))
        err = float(jnp.max(jnp.abs(got - ref)))
        assert err < 1e-6, f"shards={shards}: max err {err}"


def _smoke_spec(**kw):
    from repro.configs.base import get_config
    from repro.serving import EngineSpec
    cfg = get_config("llama2-7b").smoke_config()
    return EngineSpec(cfg, max_seq=64, n_slots=4, block_size=8, **kw)


def test_fleet_batched_pump_fairness_100_servers():
    """100 simulated servers on two engines sharing one weight copy:
    every server gets service and equal load means near-equal tokens."""
    from repro.serving import EngineFleet

    fleet = EngineFleet(_smoke_spec(), n_engines=2, steps_per_tick=8,
                        backend_kw=dict(requests_per_load=1.0, prompt_len=4,
                                        max_new_tokens=2))
    backends = [fleet.make_backend() for _ in range(100)]
    p0 = fleet.engines[0].variants["full"][1]
    assert all(e.variants["full"][1] is p0 for e in fleet.engines)
    for tick in range(2):
        for bk in backends:
            assert bk.pump(now=float(tick) / 6.0, load=1.0) == 0
        fleet.flush(now=float(tick) / 6.0)
    fleet.drain(now_h=1.0, max_steps=2000)
    tokens = np.array([sum(len(r.output) for r in bk.issued)
                       for bk in backends], float)
    assert (tokens > 0).all(), "a pumped server was never served"
    cov = float(tokens.std() / tokens.mean())
    assert cov <= 0.25, f"per-server token CoV too high: {cov:.3f}"
    assert fleet.flushes == 2


def test_cluster_sim_flushes_fleet_backends():
    """ClusterSim's two-phase sync: fleet backends submit at pump time and
    the simulator flushes each distinct fleet once per tick, reporting
    engine-measured goodput for the attached servers."""
    from repro.core.datacenter import DCConfig
    from repro.core.simulator import TAPAS, ClusterSim, SimConfig
    from repro.serving import EngineFleet

    fleet = EngineFleet(_smoke_spec(), n_engines=2, steps_per_tick=4,
                        backend_kw=dict(requests_per_load=3.0, prompt_len=4,
                                        max_new_tokens=2))
    sim = ClusterSim(SimConfig(
        dc=DCConfig(n_rows=2, racks_per_row=2, servers_per_rack=4),
        horizon_h=3.0, tick_min=10.0, seed=3, policy=TAPAS,
        occupancy=0.95, demand_scale=1.0))
    attached = {}
    measured = 0
    while sim.tick < sim.ticks:
        st = sim.step()
        for srv in np.flatnonzero(st.kind == 2):
            if int(srv) not in attached:
                bk = fleet.make_backend()
                sim.attach_backend(int(srv), bk)
                attached[int(srv)] = bk
        measured += sum(1 for srv in attached
                        if st.measured_goodput.get(srv, 0.0) > 0.0)
    assert attached, "drill placed no SaaS servers"
    assert fleet.flushes > 0, "simulator never flushed the fleet"
    assert measured > 0, "no attached server reported measured goodput"
    fleet.drain(now_h=2.0)
    assert any(len(r.output) > 0 for bk in attached.values()
               for r in bk.issued)
