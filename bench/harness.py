"""The harness: everything a cell needs, found by name.

A cell of ``BENCHMARK.json`` names a configuration (``bench/configs/
<config>.json``: published sizes and the engine it runs in), a traffic mix
(``bench/traffic/<traffic>.json``, read by ``bench/generate.py``) and has
its own load, service limits and correctness limit in
``bench/cells/<cell>.json``.  Per-layer metrics are readers under
``bench/metrics/<metric>.py``.  Nothing here names a cell.

A configuration names its model family (``"family"``, ``dense_gqa``
where absent), and ``bench/families/<family>.py`` holds all that is
particular to it: its sizes (``dims``, with the vocabulary ``v`` the
traffic draws from), its seeded tensors (``weights``), the program's
``ArchConfig`` (``arch``) and parameter tree (``to_program``), its
float32 ``Reference`` and its FLOP and byte counts (``decode_step``,
``prefill``, ``kv_bytes_per_token``).  Nothing here names a family's
tensor or architecture field.

The program under test is built through ``EngineSpec.build()`` and driven
through ``Engine.step()``; its weights are made from the seed
(``bench/weights.py``), in one jitted call into the program's own
parameter layout.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import pathlib
import re
import time
from collections import deque
from contextlib import nullcontext
from dataclasses import dataclass, field
from types import SimpleNamespace

import numpy as np

from bench import generate
from bench import weights as W

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH_DIR = pathlib.Path(__file__).resolve().parent
FAMILY_DIR = BENCH_DIR / "families"
DEFAULT_FAMILY = "dense_gqa"


# ---------------------------------------------------------------------------
# the benchmark's files
# ---------------------------------------------------------------------------

def _json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config: dict            # bench/configs/<config>.json
    mix: dict               # bench/traffic/<traffic>.json
    params: dict            # bench/cells/<cell>.json
    end_to_end: list        # BENCHMARK.json entries this cell reports
    per_layer: list


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    bench = _json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    cfgs = {c["name"]: c for c in bench["configs"]}
    config = _json(root / cfgs[w["config"]]["file"])
    family(config)          # an unknown family fails here, before set-up

    def mine(m):
        return "workloads" not in m or name in m["workloads"]

    return Cell(name=name, chips=int(w["chips"]), config=config,
                mix=_json(BENCH_DIR / "traffic" / f"{w['traffic']}.json"),
                params=_json(BENCH_DIR / "cells" / f"{name}.json"),
                end_to_end=[m for m in bench["end_to_end"] if mine(m)],
                per_layer=[m for m in bench["per_layer"] if mine(m)])


def _load(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str):
    """``read(run) -> float | None`` of ``bench/metrics/<name>.py``."""
    return _load(BENCH_DIR / "metrics" / f"{name}.py",
                 f"bench_metric_{name}").read


def family(config: dict):
    """The module ``bench/families/<family>.py`` of a configuration."""
    name = config.get("family", DEFAULT_FAMILY)
    path = FAMILY_DIR / f"{name}.py"
    if not path.is_file():
        raise ValueError(
            f"{config.get('name')}: no model family {name!r}; families "
            f"present: {sorted(p.stem for p in FAMILY_DIR.glob('*.py'))}")
    return _load(path, f"bench_family_{name}")


def vocab(config: dict) -> int:
    """The vocabulary a configuration's traffic draws its token ids from."""
    return family(config).dims(config)["v"]


# ---------------------------------------------------------------------------
# JAX set-up and the device
# ---------------------------------------------------------------------------

CACHE_DIR = ROOT / ".jax_cache"


def configure_jax() -> None:
    """Persistent compilation cache at a fixed path inside the checkout,
    every program cached however quickly it compiled."""
    import jax
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_info(chips: int) -> dict:
    """The accelerator as JAX reports it; raises where there is none or
    too few chips (the benchmark never falls back to the CPU)."""
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise RuntimeError("JAX found no accelerator (platform 'cpu')")
    if len(devs) < chips:
        raise RuntimeError(f"the cell needs {chips} chips, JAX found "
                           f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(chips: int) -> int | None:
    import jax
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


# ---------------------------------------------------------------------------
# the program under test
# ---------------------------------------------------------------------------

def arch_for(config: dict):
    """The program's ``ArchConfig`` for a configuration file, as its
    family makes it from the registered model ``config["model"]``."""
    from repro.configs import get_config
    return family(config).arch(config, get_config(config["model"]))


def program_params(model, config: dict, seed: int):
    """The benchmark's weights for ``seed`` in the program's parameter
    layout and shardings, made on the device in one jitted call."""
    import jax
    fam = family(config)
    dm = fam.dims(config)

    def make(words):
        return fam.to_program(fam.weights(W.seed_key(words), dm), dm, model)

    words = W.seed_words(seed)
    want = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    got = jax.eval_shape(make, words)
    if (jax.tree.structure(want) != jax.tree.structure(got)
            or jax.tree.leaves(jax.tree.map(
                lambda a, b: (a.shape, a.dtype) != (b.shape, b.dtype),
                want, got)).count(True)):
        raise ValueError(f"the program's parameter layout is not the one "
                         f"{fam.__file__} writes the weights into")
    shardings = model.param_shardings()
    kw = {} if model.plan.mesh is None else {"out_shardings": shardings}
    return jax.jit(make, **kw)(words)


def build_engine(config: dict, seed: int):
    """Engine for a configuration through ``EngineSpec.build()``, serving
    the benchmark's weights."""
    from repro.models.transformer import Model
    from repro.serving import EngineSpec
    spec = EngineSpec(arch_for(config), seed=int(seed) % (2 ** 31),
                      **config["engine"])
    model = Model(spec.cfg, spec.plan())
    params = program_params(model, config, seed)
    holder = SimpleNamespace(variants={"full": (model, params)}, drafters={})
    return spec.build(share=holder)


def _busy(eng) -> bool:
    return bool(eng.queue or eng.active or eng.prefilling)


def drain(eng, max_steps: int = 100_000) -> None:
    steps = 0
    while _busy(eng):
        eng.step()
        steps += 1
        if steps > max_steps:
            raise RuntimeError("engine did not drain")


def sampling_modes(mix: dict) -> list:
    """The distinct ``(temperature, top_k)`` of a mix's populations."""
    return sorted({(float(p.get("temperature", 0.0)), int(p.get("top_k", 0)))
                   for p in mix["populations"]})


def warm_up(eng, config: dict, mix: dict, seed: int) -> int:
    """Compile (or load from the cache) every shape the window can use:
    for each sampling mode of the mix, a prefill launch of every row
    bucket by every chunk bucket, the first-token pick after each, and the
    decode step.  Returns the number of warm-up requests served."""
    from repro.serving import Request
    e = config["engine"]
    top = e.get("prefill_chunk") or e["max_seq"]
    lengths = [16]
    while lengths[-1] * 2 <= top:
        lengths.append(lengths[-1] * 2)
    rows = [1]
    while rows[-1] * 2 <= e["n_slots"]:
        rows.append(rows[-1] * 2)
    v = vocab(config)
    rng = np.random.default_rng([int(seed), 0x5752])
    served = 0
    for temperature, top_k in sampling_modes(mix):
        for b in rows:
            for c in lengths:
                for _ in range(b):
                    eng.submit(Request(
                        prompt=rng.integers(0, v, c).tolist(),
                        max_new_tokens=2, temperature=temperature,
                        top_k=top_k))
                drain(eng)
                served += b
    return served


def counters(eng) -> dict:
    """The engine's work counters."""
    s = eng.stats
    return {"prefill_tokens": s.prefill_tokens,
            "decode_tokens": s.decode_tokens,
            "shared_block_hits": eng.pool.shared_block_hits}


class CompileCounter:
    """Counts XLA compilations (cache hits excluded) from a point on."""

    def __init__(self):
        import jax
        self.n = 0
        self.on = False
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, secs: float, **_) -> None:
        if self.on and event.endswith("backend_compile_duration"):
            self.n += 1


# ---------------------------------------------------------------------------
# the measured window
# ---------------------------------------------------------------------------

@dataclass
class Track:
    """One request as its client saw it: due time and token stamps, in
    seconds from the window's start."""
    item: generate.Item
    req: object
    stamps: list = field(default_factory=list)


class Recorder:
    """Work and spans of the traced stretch of a ``--trace 1`` run.

    Wraps the engine's admission, prefill and decode calls in profiler
    spans (where the engine has them) and records what each decode launch
    and each completed prefill computed, from the pool's public state."""

    def __init__(self, eng, config):
        import jax
        self.jax = jax
        self.eng, self.config = eng, config
        self.active = False
        self.decodes: list = []       # contexts of each decode launch
        self.prefills: list = []      # (start, end) of each finished prefill
        self.shared: dict = {}        # rid -> tokens already in the pool
        self.step_s: list = []        # host seconds of steps that did work
        self.counters0 = self.counters1 = None
        for attr, label in (("_admit", "bench.admit"),
                            ("_prefill_tick", "bench.prefill"),
                            ("_decode_paged", "bench.decode")):
            fn = getattr(eng, attr, None)
            if fn is not None:
                setattr(eng, attr, self._wrap(fn, label))

    def _wrap(self, fn, label):
        def wrapped(*a, **kw):
            if not self.active:
                return fn(*a, **kw)
            if label == "bench.decode":
                pool = self.eng.pool
                self.decodes.append([int(pool.lengths[pool.lane_of[r]])
                                     for r in self.eng.active])
            before = {}
            if label == "bench.prefill":
                pool = self.eng.pool
                for rid, req in self.eng.prefilling.items():
                    self.shared.setdefault(
                        rid, int(pool.lengths[pool.lane_of[rid]]))
                    before[rid] = len(req.prompt) + len(req.output)
            with self.jax.profiler.TraceAnnotation(label):
                out = fn(*a, **kw)
            for rid, n in before.items():
                if rid not in self.eng.prefilling:
                    self.prefills.append((self.shared.pop(rid), n))
            return out
        return wrapped

    def counters(self) -> dict:
        return counters(self.eng)


TRACE_FROM = 0.4     # the traced stretch starts at this share of the window
TRACE_SECONDS = 6.0  # and lasts this long (at most half the window)


def drive(eng, schedule: list, seconds: float, *, trace_dir=None,
          recorder: Recorder | None = None) -> dict:
    """Run the open-loop window: submit each request when it falls due,
    step the engine whenever it has work, stamp every output token at the
    return of the step that produced it.  Stops at the first step return
    past ``seconds``.  With ``trace_dir``, traces ``TRACE_SECONDS`` from
    ``TRACE_FROM * seconds`` on: traces are large and slow to read, and a
    few hundred steps give the per-layer numbers."""
    import jax
    from repro.serving import Request
    pending = deque(schedule)
    tracks: dict = {}
    completed0 = len(eng.stats.completed)
    seen = completed0
    lag, used = [], []
    trace_t0 = TRACE_FROM * seconds
    trace_t1 = trace_t0 + min(TRACE_SECONDS, 0.5 * seconds)
    tracing = False
    window = None
    t0 = time.perf_counter()
    now = 0.0
    while now < seconds:
        if trace_dir is not None and not tracing and trace_t0 <= now \
                and recorder.counters0 is None:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
            window = jax.profiler.TraceAnnotation("bench.window")
            window.__enter__()
            recorder.counters0 = recorder.counters()
            recorder.active = tracing = True
        elif tracing and now >= trace_t1:
            recorder.active = tracing = False
            recorder.counters1 = recorder.counters()
            window.__exit__(None, None, None)
            jax.profiler.stop_trace()
        while pending and pending[0].due_s <= now:
            it = pending.popleft()
            req = Request(prompt=list(it.prompt), max_new_tokens=it.max_new,
                          temperature=it.temperature, top_k=it.top_k)
            eng.submit(req)
            tracks[req.req_id] = Track(it, req)
            lag.append(now - it.due_s)
        if _busy(eng):
            span = jax.profiler.TraceAnnotation("bench.step") if tracing \
                else nullcontext()
            s0 = time.perf_counter()
            work0 = eng.stats.prefill_tokens + eng.stats.decode_tokens
            with span:
                eng.step(now)
            t = time.perf_counter()
            if tracing and (eng.stats.prefill_tokens
                            + eng.stats.decode_tokens) != work0:
                recorder.step_s.append(t - s0)
            now = t - t0
            done = eng.stats.completed
            for req in list(eng.active.values()) + done[seen:]:
                tr = tracks.get(req.req_id)
                if tr is not None:
                    tr.stamps.extend([now] * (len(req.output)
                                              - len(tr.stamps)))
            seen = len(done)
            used.append(eng.pool.used_blocks)
        else:
            nxt = pending[0].due_s if pending else seconds
            time.sleep(max(0.0, min(nxt, seconds) - now))
            now = time.perf_counter() - t0
    if tracing:
        recorder.active = False
        recorder.counters1 = recorder.counters()
        window.__exit__(None, None, None)
        jax.profiler.stop_trace()
    return {"end_s": now, "tracks": list(tracks.values()),
            "completed": eng.stats.completed[completed0:],
            "lag_s": lag, "queued": len(eng.queue),
            "pool_used_share": [u / (eng.pool.n_blocks - 1) for u in used]}


# ---------------------------------------------------------------------------
# end-to-end metrics (host clock)
# ---------------------------------------------------------------------------

PERCENTILE = re.compile(r"(ttft|itl)_p(\d+)_ms")


def end_to_end(run: dict, slo_ms: dict | None, want=()) -> dict:
    """Every end-to-end number a window gives, with each percentile named
    in ``want`` (``ttft_p<q>_ms``, ``itl_p<q>_ms``) besides the usual ones.
    TTFT runs from the due time to the first token's stamp; a request with
    no token by the window's end counts at (end - due).  Gaps are between
    consecutive stamps of one request.  Goodput counts the in-window tokens
    of requests that kept both limits (TTFT, and the mean gap between
    their tokens)."""
    end = run["end_s"]
    ttft, gaps = [], []
    good = total = 0
    for tr in run["tracks"]:
        st = tr.stamps
        first = st[0] if st else end
        ttft.append(first - tr.item.due_s)
        gaps.extend(np.diff(st).tolist())
        total += len(st)
        if slo_ms and st:
            mean_gap = (st[-1] - st[0]) / (len(st) - 1) if len(st) > 1 else 0
            if (ttft[-1] * 1e3 <= slo_ms["ttft"]
                    and mean_gap * 1e3 <= slo_ms["itl"]):
                good += len(st)
    out = {"output_tokens_per_s": total / end if end > 0 else 0.0,
           "requests": len(ttft), "gaps": len(gaps)}
    if ttft:
        out["ttft_mean_ms"] = float(np.mean(ttft)) * 1e3
    series = {"ttft": ttft, "itl": gaps}
    names = {f"{k}_p{q}_ms" for k in series for q in (50, 75, 90, 95, 99)}
    for name in sorted(names | set(want)):
        m = PERCENTILE.fullmatch(name)
        if m and series[m[1]]:
            out[name] = float(np.percentile(series[m[1]], int(m[2]))) * 1e3
    if slo_ms:
        out["goodput_tokens_per_s"] = good / end if end > 0 else 0.0
        out["slo_attainment"] = (
            sum(1 for tr in run["tracks"] if tr.stamps
                and (tr.stamps[0] - tr.item.due_s) * 1e3 <= slo_ms["ttft"])
            / max(len(run["tracks"]), 1))
    if run["pool_used_share"]:
        out["pool_used_peak_pct"] = 100.0 * max(run["pool_used_share"])
        out["pool_used_mean_pct"] = 100.0 * float(np.mean(
            run["pool_used_share"]))
    if run["lag_s"]:
        out["generator_lag_p95_ms"] = float(np.percentile(run["lag_s"],
                                                          95)) * 1e3
    return out


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------

def result_metrics(metrics: list, values: dict) -> dict:
    """The result line's ``metrics``: every metric of ``metrics`` (entries
    of ``BENCHMARK.json``) with its value and unit; a metric the run did
    not give is an error, never a line without it."""
    missing = [m["name"] for m in metrics if m["name"] not in values]
    if missing:
        raise KeyError(f"the run gave no value for {missing}")
    return {m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
            for m in metrics}


def sample_for_check(run: dict, k: int, seed: int) -> list:
    """The longest finished greedy request and other greedy ones drawn
    from the seed: up to ``k`` (prompt, served tokens) pairs.  Sampled
    requests have no reference token to compare."""
    done = [r for r in run["completed"] if r.outcome == "accepted"
            and r.temperature <= 0 and len(r.output) > 0]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.prompt) + len(r.output),
                                       r.req_id))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed), 0x434B])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False) \
        if rest else []
    chosen = [longest] + [rest[i] for i in sorted(pick)]
    return [(list(r.prompt), list(r.output)) for r in chosen]


def check(config: dict, mix: dict, seqs: list, seed: int, *,
          limit: float | None, control: bool = False,
          batch: int = 8) -> dict:
    """Compare served tokens with the family's float32 reference: the
    widest gap by which a served token's logit lies below the reference's
    best."""
    e = config["engine"]
    ref = family(config).Reference(config, seed)
    gaps = []
    for i in range(0, len(seqs), batch):
        part = seqs[i:i + batch]
        part = part + [([0], [])] * (batch - len(part))
        gaps.extend(ref.judge(part, pad_to=e["max_seq"],
                              judge_len=max(p["output"]["max"]
                                            for p in mix["populations"]),
                              control=control)[:len(seqs[i:i + batch])])
    tokens = int(sum(len(g) for g in gaps))
    worst = float(max((float(np.max(g)) for g in gaps if len(g)),
                      default=math.inf))
    ok = bool(tokens > 0 and limit is not None and worst <= limit)
    return {"max_logit_gap": worst, "limit": limit, "tokens": tokens,
            "requests": len(seqs), "ok": ok}


def free() -> None:
    """Collect the program's state (the caller has dropped its references)
    before the reference runs."""
    import jax
    gc.collect()
    jax.clear_caches()
    gc.collect()
