"""End-to-end behaviour: training convergence, serving engine, checkpoint
restart (fault tolerance), elastic re-meshing, launch drivers."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.elastic import plan_remesh
from repro.models import build_model, local_plan
from repro.serving import Engine, EngineKnobs, Request
from repro.training.checkpoint import (latest_step, restore_checkpoint,
                                       save_checkpoint)
from repro.training.data import DataConfig, TokenPipeline
from repro.training.optimizer import AdamWConfig
from repro.training.train_step import init_opt_state, make_train_step

# whole-module: end-to-end training/serving runs (CI sim job)
pytestmark = pytest.mark.slow


def test_training_reduces_loss():
    from repro.launch.train import main
    out = main(["--arch", "qwen3-1.7b", "--smoke", "--steps", "15",
                "--batch", "8", "--seq", "64", "--lr", "3e-3"])
    assert out["last_loss"] < out["first_loss"] - 0.1


def test_serve_driver_completes_requests():
    from jax.experimental.compilation_cache import compilation_cache

    from repro.launch.serve import main
    cache_dir = jax.config.jax_compilation_cache_dir
    try:
        out = main(["--arch", "llama2-7b", "--smoke", "--requests", "5",
                    "--slots", "3", "--max-new", "8"])
    finally:   # the driver turns the persistent cache on; undo it here
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        compilation_cache.reset_cache()
    assert out["completed"] == 5
    assert out["decode_tokens"] > 0


def test_engine_continuous_batching():
    cfg = get_config("llama2-7b").smoke_config()
    model = build_model(cfg, local_plan(param_dtype=jnp.bfloat16))
    params = model.init(jax.random.PRNGKey(0))
    eng = Engine(model, params, max_seq=64, n_slots=2,
                 knobs=EngineKnobs(max_batch=2))
    rng = np.random.default_rng(0)
    for i in range(5):
        eng.submit(Request(prompt=list(rng.integers(0, cfg.vocab_size, 6)),
                           max_new_tokens=4, customer="custA"))
    stats = eng.run()
    assert len(stats.completed) == 5
    for r in stats.completed:
        assert all(0 <= t < cfg.vocab_size for t in r.output)


def test_engine_variant_swap():
    """Instance Configurator's model-size knob: swap to a smaller variant."""
    cfg_big = get_config("llama2-7b").smoke_config()
    cfg_small = cfg_big.replace(num_layers=1, d_ff=64, name="llama2-tiny")
    plan = local_plan(param_dtype=jnp.bfloat16)
    m_big = build_model(cfg_big, plan)
    m_small = build_model(cfg_small, plan)
    eng = Engine(m_big, m_big.init(jax.random.PRNGKey(0)), max_seq=64,
                 n_slots=2)
    eng.add_variant("small", m_small, m_small.init(jax.random.PRNGKey(1)))
    eng.submit(Request(prompt=[1, 2, 3], max_new_tokens=3))
    eng.run()
    eng.set_variant("small")
    eng.submit(Request(prompt=[4, 5, 6], max_new_tokens=3))
    stats = eng.run()
    assert len(stats.completed) == 2


def test_checkpoint_roundtrip(tmp_path):
    cfg = get_config("qwen3-1.7b").smoke_config()
    model = build_model(cfg, local_plan(param_dtype=jnp.float32))
    params = model.init(jax.random.PRNGKey(0))
    opt = init_opt_state(params)
    save_checkpoint(tmp_path, 7, (params, opt), meta={"arch": cfg.name})
    assert latest_step(tmp_path) == 7
    (p2, o2), manifest = restore_checkpoint(tmp_path, (params, opt))
    assert manifest["step"] == 7
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_resume_deterministic(tmp_path):
    """Train 6 steps straight == train 3, checkpoint, restore, train 3."""
    cfg = get_config("deepseek-7b").smoke_config()
    model = build_model(cfg, local_plan(param_dtype=jnp.float32))
    opt_cfg = AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=10)
    step = jax.jit(make_train_step(model, opt_cfg))

    def run(n_start, n_end, params, opt, pipe):
        m = None
        for _ in range(n_start, n_end):
            x, y = pipe.next_batch()
            params, opt, m = step(params, opt, x, y)
        return params, opt, m

    dc = DataConfig(cfg.vocab_size, 4, 32, seed=3)
    p0 = model.init(jax.random.PRNGKey(0))
    o0 = init_opt_state(p0)
    pa, oa, ma = run(0, 6, p0, o0, TokenPipeline(dc))

    pipe = TokenPipeline(dc)
    pb, ob, _ = run(0, 3, p0, o0, pipe)
    save_checkpoint(tmp_path, 3, (pb, ob))
    (pr, onr), _ = restore_checkpoint(tmp_path, (pb, ob))
    pipe2 = TokenPipeline(dc, step=3)
    pc, oc, mc = run(3, 6, pr, onr, pipe2)
    np.testing.assert_allclose(float(ma["loss"]), float(mc["loss"]),
                               rtol=1e-4, atol=1e-5)


def test_checkpoint_atomic_ignores_torn_tmp(tmp_path):
    cfg = get_config("qwen3-1.7b").smoke_config()
    model = build_model(cfg, local_plan())
    params = model.init(jax.random.PRNGKey(0))
    save_checkpoint(tmp_path, 1, params)
    (tmp_path / ".tmp_dead").mkdir()  # simulated torn write
    p2, manifest = restore_checkpoint(tmp_path, params)
    assert manifest["step"] == 1


@pytest.mark.parametrize("survivors,expect_model,expect_data", [
    (512, 16, 32), (496, 16, 31), (256, 16, 16), (17, 16, 1), (8, 8, 1),
    (3, 2, 1),
])
def test_elastic_remesh_policy(survivors, expect_model, expect_data):
    d = plan_remesh(survivors)
    assert d.model == expect_model
    assert d.data == expect_data
    assert d.usable <= survivors
    assert d.usable == d.data * d.model


def test_data_pipeline_checkpointable():
    dc = DataConfig(vocab_size=100, batch=2, seq_len=16, seed=1)
    p1 = TokenPipeline(dc)
    b1 = [p1.next_batch() for _ in range(4)]
    p2 = TokenPipeline(dc, step=2)
    b2 = p2.next_batch()
    np.testing.assert_array_equal(np.asarray(b1[2][0]), np.asarray(b2[0]))
