"""The traffic generator (bench/generate.py): deterministic for a seed,
the same set of sizes for every seed, and the stated distributions."""
import copy

import numpy as np
import pytest

from bench import generate as G
from bench_tiny import load

VOCAB = 151936


def bursty():
    """The chat mix with gamma gaps of CV 3, as a bursty cell states it."""
    mix = copy.deepcopy(load("traffic", "chat"))
    mix["arrivals"] = {"cv": 3.0}
    return mix


def long_short():
    """Two populations in one queue, one of them sampled, as a later cell
    states it."""
    return {"arrivals": {"cv": 1.0},
            "populations": [
                {"share": 0.25,
                 "prompt": {"median": 1800, "sigma": 0.1, "min": 1536,
                            "max": 2000},
                 "output": {"median": 64, "sigma": 0.5, "min": 16,
                            "max": 128}},
                {"share": 0.75, "temperature": 0.8, "top_k": 50,
                 "prompt": {"median": 128, "sigma": 0.8, "min": 16,
                            "max": 512},
                 "output": {"median": 128, "sigma": 0.6, "min": 16,
                            "max": 512}}]}


MIXES = {"chat": lambda: load("traffic", "chat"), "bursty": bursty,
         "long_short": long_short}


def sched(mix, seed, seconds=50.0, rate=4.0):
    return G.generate(MIXES[mix](), vocab=VOCAB, seed=seed,
                      seconds=seconds, rate_per_s=rate)


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_deterministic_for_a_seed(mix):
    a, b = sched(mix, 2**31 + 7), sched(mix, 2**31 + 7)
    assert [(x.due_s, x.prompt, x.max_new, x.temperature) for x in a] == \
        [(x.due_s, x.prompt, x.max_new, x.temperature) for x in b]
    c = sched(mix, 2**31 + 8)
    assert [x.prompt for x in a] != [x.prompt for x in c]


def test_stratified_blocks_cover_every_stratum():
    rng = np.random.default_rng(3)
    u = G.stratified_quantiles(64, rng)
    for i in range(0, 64, 16):
        assert sorted((u[i:i + 16] * 16).astype(int)) == list(range(16))
    grid = np.sort(u)
    assert np.allclose(grid, (np.arange(64) + 0.5) / 64)


def test_chat_lengths_match_the_mix():
    pop = load("traffic", "chat")["populations"][0]
    a = sched("chat", 11, seconds=400.0)
    p = np.array([len(x.prompt) for x in a])
    o = np.array([x.max_new for x in a])
    assert p.min() >= pop["prompt"]["min"] and p.max() <= pop["prompt"]["max"]
    assert o.min() >= pop["output"]["min"] and o.max() <= pop["output"]["max"]
    assert p.max() + o.max() < 2048         # fits the engine's context
    assert abs(np.median(p) - pop["prompt"]["median"]) <= 16
    assert abs(np.median(o) - pop["output"]["median"]) <= 8
    assert all(x.temperature == 0.0 for x in a)
    # the same set of sizes on every seed, in another order
    runs = [sched("chat", s, seconds=1e4, rate=0.0160) for s in (1, 2)]
    assert len(runs[0]) == len(runs[1]) == 160
    assert sorted(len(x.prompt) for x in runs[0]) == \
        sorted(len(x.prompt) for x in runs[1])
    assert [len(x.prompt) for x in runs[0]] != \
        [len(x.prompt) for x in runs[1]]


def test_poisson_rate_and_window():
    a = sched("chat", 5, seconds=50.0, rate=4.0)
    due = np.array([x.due_s for x in a])
    assert due[0] == 0.0 and due.max() < 50.0
    assert np.all(np.diff(due) >= 0)
    assert 170 <= len(a) <= 210
    gaps = np.diff(due)
    assert abs(np.mean(gaps) - 0.25) < 0.03
    assert abs(np.std(gaps) / np.mean(gaps) - 1.0) < 0.15


def test_gamma_gaps_have_the_stated_burstiness():
    u = (np.arange(4096) + 0.5) / 4096
    for cv in (0.5, 1.0, 3.0):
        g = G.gamma_gaps(u, 0.25, cv)
        assert abs(np.mean(g) - 0.25) < 0.01
        assert abs(np.std(g) / np.mean(g) - cv) < 0.05 * cv
    a = sched("bursty", 6, seconds=400.0, rate=1.0)
    gaps = np.diff([x.due_s for x in a])
    assert np.std(gaps) / np.mean(gaps) > 2.0


def test_populations_keep_their_shares_lengths_and_sampling():
    a = sched("long_short", 12, seconds=64.0, rate=4.0)
    assert len(a) >= 240
    long = [x for x in a if len(x.prompt) >= 1536]
    short = [x for x in a if len(x.prompt) <= 512]
    assert len(long) + len(short) == len(a)
    assert abs(len(long) / len(a) - 0.25) < 0.02
    assert all(x.temperature == 0.0 and x.top_k == 0 for x in long)
    assert all(x.temperature == 0.8 and x.top_k == 50 for x in short)
    bad = long_short()
    bad["populations"][0]["share"] = 0.5
    with pytest.raises(ValueError):
        G.generate(bad, vocab=VOCAB, seed=1, seconds=10.0, rate_per_s=1.0)
