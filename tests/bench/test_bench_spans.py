"""The program-span readers (bench/spans.py) on a small hand-made trace
with known answers, and their silence where the program has no spans."""
from types import SimpleNamespace

import pytest

from bench import spans as S
from bench import trace as T

MS = 1_000_000


def ms(a, b):
    return a * MS, (b - a) * MS


def small_planes(engine=True):
    """Chip 0 runs a prefill (2.5-4 ms) and two decode launches (5.5-8.5,
    24.5-28.5 ms) inside a 40 ms window; the host runs three steps in it
    (the third does no work) and one after it."""
    ops = [("%while.9 = (s32[]) while((s32[]) %t)", *ms(5, 9)),  # spans
           ("fusion.3", *ms(2.5, 4)), ("fusion.1", *ms(5.5, 7)),
           ("scatter.2", *ms(7, 8)), ("copy.4", *ms(8, 8.5)),
           ("fusion.1", *ms(24.5, 27)), ("fusion.5", *ms(27, 28.5))]
    mods = [("jit_prefill_chunk_paged(4)", *ms(2.5, 4)),
            ("jit_decode_multi_paged(3)", *ms(5.5, 8.5)),
            ("jit_decode_multi_paged(3)", *ms(24.5, 28.5))]
    host = [("bench.window", *ms(0, 40), {}),
            ("bench.step", *ms(0, 10), {})]
    if engine:
        host += [
            ("engine.step", *ms(0, 10), {"_r": 1, "step_num": 1,
                                         "queue": 1, "active": 0}),
            ("engine.admit", *ms(0, 1), {"requests": 1, "queue_wait_ms": 2.5,
                                         "prompt_tokens": 100,
                                         "shared_tokens": 0}),
            ("engine.prefill", *ms(1, 4), {"rows": 1, "tokens": 100,
                                           "finished": 1}),
            ("engine.prefill.prepare", *ms(1, 2), {}),
            ("engine.prefill.launch", *ms(2, 3), {}),
            ("engine.prefill.sync", *ms(3, 4), {}),
            ("engine.decode", *ms(4, 10), {"lanes": 1}),
            ("engine.decode.prepare", *ms(4, 5), {}),
            ("engine.decode.launch", *ms(5, 6), {}),
            ("engine.decode.sync", *ms(6, 9), {}),
            ("engine.decode.commit", *ms(9, 10), {}),
            ("engine.step", *ms(20, 30), {"step_num": 2}),
            ("engine.admit", *ms(20, 21), {"requests": 0,
                                           "queue_wait_ms": 0.0}),
            ("engine.decode", *ms(21, 30), {"lanes": 1}),
            ("engine.decode.prepare", *ms(21, 23), {}),
            ("engine.decode.launch", *ms(23, 24), {}),
            ("engine.decode.sync", *ms(24, 29), {}),
            ("engine.decode.commit", *ms(29, 30), {}),
            ("engine.step", *ms(32, 33), {"step_num": 3}),      # no work
            ("engine.admit", *ms(32, 32.5), {"requests": 2,
                                             "queue_wait_ms": 1.5}),
            ("engine.step", *ms(41, 44), {"step_num": 4}),      # after
            ("engine.admit", *ms(41, 42), {"requests": 5,
                                           "queue_wait_ms": 100.0}),
            ("engine.decode.launch", *ms(42, 43), {}),
        ]
    return [("/device:TPU:0", [("XLA Ops", ops), ("XLA Modules", mods)]),
            ("/host:CPU", [("python3", host)])]


def run_of(planes):
    tr = T.from_planes([(p, [(ln, [e[:3] for e in evs])
                             for ln, evs in lines]) for p, lines in planes])
    return SimpleNamespace(trace=tr, window=tr.window(), notes={},
                           engine_spans=S.from_planes(planes))


def test_span_tree_of_the_small_trace():
    sp = S.from_planes(small_planes())
    steps = sp.named("engine.step", 0, 40 * MS)
    assert [s.args["step_num"] for s in steps] == [1, 2, 3]
    assert "_r" not in steps[0].args          # the profiler's own stats
    assert [s.name for s in sp.inside(steps[1])] == [
        "engine.admit", "engine.decode", "engine.decode.prepare",
        "engine.decode.launch", "engine.decode.sync",
        "engine.decode.commit"]
    assert all(s.name.startswith("engine.") for s in sp.spans)


def test_step_host_ms_and_idle_by_phase():
    run = run_of(small_planes())
    # steps 1 and 2 launched: 10 - (1 + 3) and 10 - 5 ms of host work
    assert S.step_host_ms(run) == pytest.approx(5.5)
    idle = run.notes["idle_by_phase"]
    want_ms = {"outside_step": 19, "engine.decode.prepare": 3,
               "engine.admit": 2.5, "engine.decode.commit": 2,
               "engine.decode.launch": 1.5, "engine.decode.sync": 1.5,
               "engine.prefill.prepare": 1, "engine.prefill.launch": 0.5,
               "engine.step": 0.5}
    assert {k: v["s"] * 1e3 for k, v in idle.items()} == pytest.approx(
        want_ms)
    assert list(idle)[0] == "outside_step"              # largest first
    assert sum(v["pct"] for v in idle.values()) == pytest.approx(100)
    assert idle["engine.admit"]["pct"] == pytest.approx(100 * 2.5 / 31.5)


def test_queue_wait_ms():
    run = run_of(small_planes())
    # three admissions in the window: 1, 0 and 2 requests
    assert S.queue_wait_ms(run) == pytest.approx((2.5 + 1.5) / 3)
    assert run.notes["queue_wait_requests"] == 3


@pytest.mark.parametrize("reader", [S.step_host_ms, S.queue_wait_ms])
def test_readers_give_nothing_without_engine_spans(reader):
    run = run_of(small_planes(engine=False))
    assert reader(run) is None
    assert run.notes == {}
    run = run_of(small_planes())
    run.trace, run.window = None, None
    assert reader(run) is None


def test_load_reads_nothing_from_an_empty_directory(tmp_path):
    assert S.load(tmp_path) is None
    run = SimpleNamespace(window=(0, 1), notes={}, engine_spans=None)
    assert S.step_host_ms(run) is None
