"""The harness end to end on the CPU at a tiny size: a window of open-loop
traffic through ``EngineSpec.build()`` and ``Engine.step()``, then the
comparison that decides ``correct``.  A sound run passes; the float8
control and a broken timed path fail; ``bench/run.py`` refuses to run
without an accelerator."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

from bench import generate as G
from bench import harness as H
from bench_tiny import tiny_chat, tiny_config

ROOT = pathlib.Path(__file__).resolve().parents[2]
SEED = 2**31 + 4242
# At this size a sound run's widest gap reads about 1e-3 and the float8
# control's about 6e-2 (CPU); the test limit sits between them.
TINY_LIMIT = 0.01


def serve(cfg, mix, seed, seconds=2.0, rate=8.0):
    eng = H.build_engine(cfg, seed)
    H.warm_up(eng, cfg, mix, seed)
    compiles = H.CompileCounter()
    sched = G.generate(mix, vocab=cfg["vocab_size"], seed=seed,
                       seconds=seconds, rate_per_s=rate)
    compiles.on = True
    run = H.drive(eng, sched, seconds)
    compiles.on = False
    seqs = H.sample_for_check(run, 8, seed)
    del eng
    H.free()
    return run, seqs, compiles.n


@pytest.fixture(scope="module")
def sound():
    return serve(tiny_config(), tiny_chat(), SEED)


def test_sound_run_is_correct(sound):
    run, seqs, compiles = sound
    assert compiles == 0            # the warm-up covered every shape
    e2e = H.end_to_end(run, {"ttft": 1e4, "itl": 1e4})
    assert e2e["requests"] == len(run["tracks"]) > 5
    for name in ("ttft_p95_ms", "itl_p95_ms", "goodput_tokens_per_s",
                 "output_tokens_per_s"):
        assert e2e[name] > 0
    assert any(len(p) + len(o) == max(len(a) + len(b) for a, b in seqs)
               for p, o in seqs)
    got = H.check(tiny_config(), tiny_chat(), seqs, SEED, limit=TINY_LIMIT)
    assert got["ok"] and got["tokens"] > 50, got


@pytest.mark.parametrize("workload", [w["name"] for w in json.loads(
    (ROOT / "BENCHMARK.json").read_text())["workloads"]])
def test_run_gives_every_end_to_end_metric(sound, workload):
    cell = H.load_cell(workload)
    run, _, _ = sound
    e2e = H.end_to_end(run, cell.params.get("slo_ms"),
                       want=[m["name"] for m in cell.end_to_end])
    e2e["setup_s"] = 1.0
    got = H.result_metrics(cell.end_to_end, e2e)
    assert list(got) == [m["name"] for m in cell.end_to_end]
    assert all(v["value"] > 0 for v in got.values())
    with pytest.raises(KeyError):
        H.result_metrics(cell.end_to_end + [{"name": "queue_wait_p95_ms",
                                             "unit": "ms"}], e2e)


def test_float8_control_fails(sound):
    _, seqs, _ = sound
    got = H.check(tiny_config(), tiny_chat(), seqs, SEED, limit=TINY_LIMIT,
                  control=True)
    assert not got["ok"] and got["max_logit_gap"] > 3 * TINY_LIMIT, got


def test_check_samples_only_greedy_requests():
    from types import SimpleNamespace as R
    done = [R(req_id=i, outcome="accepted", prompt=[1] * (10 + i),
              output=[2] * 5, temperature=0.8 if i % 2 else 0.0)
            for i in range(12)]
    done.append(R(req_id=99, outcome="rejected", prompt=[1] * 90,
                  output=[2], temperature=0.0))
    seqs = H.sample_for_check({"completed": done}, 4, SEED)
    assert len(seqs) == 4
    assert len(seqs[0][0]) == 20            # the longest greedy request
    assert all(len(p) % 2 == 0 for p, _ in seqs)


def _unchanged_state(orig):
    def broken(self, params, cache, *a, **kw):
        out = orig(self, params, cache, *a, **kw)
        return out[:-1] + (cache,)       # the decode step's KV is lost
    return broken


def _altered_token(orig):
    def broken(self, *a, **kw):
        toks, *rest = orig(self, *a, **kw)
        return (toks + 1) % self.cfg.vocab_size, *rest
    return broken


@pytest.mark.parametrize("fault", [_unchanged_state, _altered_token],
                         ids=["state_unchanged", "token_altered"])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    from repro.models.transformer import Model
    monkeypatch.setattr(Model, "decode_multi_paged",
                        fault(Model.decode_multi_paged))
    _, seqs, _ = serve(tiny_config(), tiny_chat(), SEED + 1)
    got = H.check(tiny_config(), tiny_chat(), seqs, SEED + 1,
                  limit=TINY_LIMIT)
    assert not got["ok"], got


def _run_py(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen3-1.7b.chat",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(out: str) -> bool:
    last = out.strip().splitlines()[-1:] or [""]
    try:
        json.loads(last[0])
    except ValueError:
        return True
    return False


def test_run_refuses_the_cpu():
    p = _run_py(ROOT)
    assert p.returncode != 0 and _no_result(p.stdout), p.stderr[-2000:]
    assert "no accelerator" in p.stderr


def test_run_fails_with_the_benchmark_alone(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for d in bench["paths"]:
        shutil.copytree(ROOT / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(tmp_path)
    assert p.returncode != 0 and _no_result(p.stdout)
