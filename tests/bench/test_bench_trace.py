"""The trace reduction (bench/trace.py) and the per-layer readers
(bench/layer.py) on a small hand-made trace with known answers."""
from types import SimpleNamespace

import pytest

from bench import layer as Lyr
from bench import trace as T
from bench_tiny import load

MS = 1_000_000


def small_trace():
    """Two 10 ms steps on chip 0 and chip 1 inside a 40 ms window."""
    ops0 = [("%while.9 = (s32[]) while((s32[]) %t)", 0, 30 * MS),  # spans
            ("%fusion.1 = bf16[8] fusion(bf16[8] %p), calls=%f",
             1 * MS, 4 * MS),                    # decode, 1-5 ms
            ("all-reduce.2", 5 * MS, 1 * MS),    # decode, 5-6 ms
            ("fusion.3", 21 * MS, 2 * MS),       # prefill, 21-23 ms
            ("fusion.1", 24 * MS, 5 * MS)]       # decode, 24-29 ms
    mods0 = [("jit_decode_multi_paged(3)", 1 * MS, 5 * MS),
             ("jit_prefill_chunk_paged(4)", 21 * MS, 2 * MS),
             ("jit_decode_multi_paged(3)", 24 * MS, 5 * MS)]
    ops1 = [("fusion.1", 2 * MS, 4 * MS), ("fusion.1", 24 * MS, 4 * MS)]
    mods1 = [("jit_decode_multi_paged(3)", 2 * MS, 4 * MS),
             ("jit_decode_multi_paged(3)", 24 * MS, 4 * MS)]
    host = [("bench.window", 0, 40 * MS),
            ("bench.step", 0, 10 * MS),
            ("bench.step", 20 * MS, 10 * MS),
            ("bench.decode", 23 * MS, 7 * MS),
            ("not.ours", 0, 1 * MS)]
    return T.from_planes([
        ("/device:TPU:0", [("XLA Ops", ops0), ("XLA Modules", mods0),
                           ("Steps", [("s", 0, 1)])]),
        ("/device:TPU:1", [("XLA Ops", ops1), ("XLA Modules", mods1)]),
        ("/host:CPU", [("python3", host)]),
    ])


def test_reduction_of_a_small_trace():
    tr = small_trace()
    assert sorted(tr.devices) == [0, 1]
    # the loop's event spans its body and is not an operation of its own;
    # an operation keeps its instruction name
    assert [n for _, _, n in tr.devices[0].ops][:2] == ["%fusion.1",
                                                        "all-reduce.2"]
    assert tr.window() == (0, 40 * MS)
    assert [s[2] for s in tr.spans].count("bench.step") == 2
    assert "not.ours" not in [s[2] for s in tr.spans]
    b0 = T.busy(tr.devices[0], 0, 40 * MS)
    assert b0 == [(1 * MS, 6 * MS), (21 * MS, 23 * MS), (24 * MS, 29 * MS)]
    assert T.module_time(tr.devices[0], "decode_multi_paged", 0,
                         40 * MS) == (10 * MS, 2)
    dec = T.union((s, e) for s, e, n in tr.devices[0].modules
                  if "decode" in n)
    assert T.collective_time(tr.devices[0], dec) == 1 * MS
    gaps = T.idle_gaps(b0, tr.spans, 0, 40 * MS, top=2)
    assert gaps == [("bench.between_steps", 0.015),     # 6-21 ms
                    ("bench.between_steps", 0.011)]     # 29-40 ms
    names = [n for n, _ in T.idle_gaps(b0, tr.spans, 0, 40 * MS)]
    assert names[2:] == ["bench.step", "bench.decode"]  # 0-1, 23-24 ms


def ctx(tr):
    cfg = load("configs", "qwen3-1.7b")
    rec = SimpleNamespace(decodes=[[100] * 8, [101] * 8], prefills=[],
                          step_s=[0.010, 0.010],
                          counters0={"prefill_tokens": 0},
                          counters1={"prefill_tokens": 2000})
    return SimpleNamespace(config=cfg, chips=2, trace=tr,
                           window=tr.window(), recorder=rec,
                           peak={"bf16_flops": 197e12,
                                 "hbm_bytes_per_s": 819e9},
                           counters=({"shared_block_hits": 10},
                                     {"shared_block_hits": 74}),
                           admitted_prompt_tokens=4096, notes={})


def test_layer_readers_on_the_small_trace():
    run = ctx(small_trace())
    # steps 0-10 and 20-30 ms; chip 0 busy 5 + 7 ms in them, chip 1 4 + 4
    assert Lyr.step_idle_share(run) == pytest.approx(
        100 * ((20 - 12) + (20 - 8)) / 2 / 20)
    # chip 0: 2 launches, 10 ms; chip 1: 2 launches, 8 ms
    assert Lyr.decode_step_ms(run) == pytest.approx((5 + 4) / 2)
    # 2 ms of prefill on chip 0, none on chip 1, per 2 ktok
    assert Lyr.prefill_ms_per_ktok(run) == pytest.approx(0.5)
    assert Lyr.prefix_hit_share(run) == pytest.approx(100 * 64 * 16 / 4096)
    share = Lyr.decode_roofline(run)
    assert 0 < share < 100
    assert run.notes["decode_roofline_bound"] == "memory"
    assert 0 < Lyr.mfu(run) < 100


def test_readers_return_nothing_without_a_trace():
    run = ctx(small_trace())
    run.trace, run.window = None, None
    for fn in (Lyr.step_idle_share, Lyr.decode_step_ms,
               Lyr.prefill_ms_per_ktok, Lyr.decode_roofline):
        assert fn(run) is None
