#!/usr/bin/env python3
"""Chip smoke test: the serving path at published widths on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips: the sharded path only

One chip: qwen3-1.7b (28 layers, d_model 2048, 16/8 heads, vocab 151,936;
random bf16 weights from ``--seed``) is built through ``EngineSpec`` and
serves seeded requests of 64-1,500 prompt tokens, two of them sharing a
1,024-token prefix, in four decode modes: per-step, fused horizon 8,
chunked prefill with prefix sharing, and n-gram speculation (K=4).  The
same engine then runs inside a ``ClusterSim`` through ``EngineBackend``
until a TAPAS ``reconfigure()`` knob change lands on it and its measured
goodput comes back in ``ClusterState.measured_goodput``.

Four chips: deepseek-7b (30 layers, d_model 4096, 32 KV heads, vocab
102,400) served at ``shards=4`` with parameters and pool created sharded,
then resharded live to ``shards=2`` mid-stream.

Correctness, every phase: each emitted greedy token must equal the argmax
of the dense full forward (``Model.logits``, teacher-forced on the
emitted stream, under the engine's own plan) wherever that argmax's
margin over the runner-up exceeds ``TOL_REL * |top logit|``; streams of
the same requests must agree across modes except at a first divergent
position whose margin is inside that tolerance.

Each phase prints JSON lines; the last line of stdout is
``{"ok": ..., "device": {"platform", "kind", "count"}}``.  The script
exits non-zero when JAX finds no TPU (it never falls back to the CPU) or
when any check fails.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time
from dataclasses import dataclass

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402
import numpy as np              # noqa: E402

from repro.configs import get_config                        # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.serving import EngineSpec, Request               # noqa: E402

# Both the engine and the dense reference keep activations in bfloat16
# (8 significant bits) and round them at different points: paged gathers
# versus one causal pass, chunked versus whole prefill, a K+1-row verify
# batch versus one row.  One rounding moves a logit z by up to 2**-8 * |z|;
# over 28-30 layers several such differences reach the head.  A top-2
# margin within 2**-5 * |z| (eight roundings) is therefore a tie that
# either path may break either way; above it the argmax must agree.
TOL_REL = 2.0 ** -5


@dataclass(frozen=True)
class Workload:
    """Request mix of a phase: prompt lengths (two of them extended from
    one shared prefix) and the decode budget per request."""
    prompt_lens: tuple = (64, 150, 333, 700, 1100, 1500)
    shared_prefix: int = 1024
    shared_tails: tuple = (40, 90)
    max_new: int = 32

    def prompts(self, vocab: int, seed: int) -> list:
        rng = np.random.default_rng(seed)
        draw = lambda n: [int(t) for t in rng.integers(0, vocab, n)]  # noqa: E731
        out = [draw(n) for n in self.prompt_lens]
        if self.shared_prefix:
            head = draw(self.shared_prefix)
            out += [head + draw(t) for t in self.shared_tails]
        return out


def emit(**row) -> None:
    print(json.dumps(row), flush=True)


class CompileClock:
    """Sums XLA backend-compile seconds reported through jax.monitoring."""

    def __init__(self):
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event.endswith("backend_compile_duration"):
            self.total += secs


def tree_bytes(tree) -> int:
    return sum(int(x.nbytes) for x in jax.tree.leaves(tree))


def peak_device_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def check_placement(eng) -> bool:
    """Parameters and pool sit exactly where the plan puts them."""
    model = eng.model
    if model.plan.mesh is None:
        one = {jax.devices()[0]}
        return all(x.sharding.device_set == one
                   for x in jax.tree.leaves((eng.params, eng.pool.cache)))
    same = lambda a, s: a.sharding.is_equivalent_to(s, a.ndim)  # noqa: E731
    return all(jax.tree.leaves((
        jax.tree.map(same, eng.params, model.param_shardings()),
        jax.tree.map(same, eng.pool.cache, model.paged_cache_shardings()))))


def make_reference(model):
    """Dense full forward under the model's own plan: per position, the
    argmax, the top logit and its margin over the runner-up."""
    v = model.cfg.vocab_size

    @jax.jit
    def ref(params, tokens):
        z = model.logits(params, tokens)[0, :, :v].astype(jnp.float32)
        top = jnp.argmax(z, axis=-1)
        z1 = jnp.max(z, axis=-1)
        z2 = jnp.max(jnp.where(jnp.arange(v) == top[:, None], -jnp.inf, z),
                     axis=-1)
        return top, z1, z1 - z2

    return ref


class Reference:
    """Teacher-forced dense-forward verdicts, cached per emitted stream."""

    def __init__(self, model, params, max_seq: int):
        self.fn = make_reference(model)
        self.params = params
        self.max_seq = max_seq
        self.cache: dict = {}

    def __call__(self, prompt: list, out: list):
        key = (tuple(prompt), tuple(out))
        if key not in self.cache:
            seq = prompt + out[:-1]
            toks = np.zeros((1, self.max_seq), np.int32)
            toks[0, :len(seq)] = seq
            top, z1, margin = (np.asarray(a) for a in
                               self.fn(self.params, jnp.asarray(toks)))
            pos = np.arange(len(prompt) - 1, len(seq))
            self.cache[key] = (top[pos], z1[pos], margin[pos])
        return self.cache[key]


def judge(ref: Reference, prompts: list, reqs: list, max_new: int):
    """Streams of served requests, and a row saying whether each request
    completed and each token agrees with the dense forward."""
    streams = [list(r.output) for r in reqs]
    row = dict(completed=sum(r.outcome == "accepted"
                             and len(r.output) == max_new for r in reqs),
               requests=len(reqs), decode_tokens=sum(map(len, streams)),
               checked=0, under_tol=0, mismatch=0, flips_under_tol=0)
    for p, out in zip(prompts, streams):
        top, z1, margin = ref(p, out)
        sure = margin > TOL_REL * np.abs(z1)
        wrong = top != np.asarray(out)
        row["checked"] += int(sure.sum())
        row["under_tol"] += int((~sure).sum())
        row["mismatch"] += int((sure & wrong).sum())
        row["flips_under_tol"] += int((~sure & wrong).sum())
    row["passed"] = (row["completed"] == len(reqs)
                     and row["mismatch"] == 0)
    return streams, row


def describe(eng) -> dict:
    cfg = eng.model.cfg
    return dict(arch=cfg.name, shards=eng.shards, layers=cfg.num_layers,
                d_model=cfg.d_model, heads=cfg.n_heads,
                kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
                vocab=cfg.vocab_size, lanes=eng.n_slots,
                max_seq=eng.max_seq, block_size=eng.block_size,
                pool_blocks=eng.pool.n_blocks,
                param_bytes=tree_bytes(eng.params),
                pool_bytes=tree_bytes(eng.pool.cache))


def serve(eng, prompts: list, max_new: int, *, steps_before=None,
          before=None) -> list:
    """Serve ``prompts`` greedily to completion; ``before(eng)`` runs
    once after ``steps_before`` scheduler steps."""
    reqs = [Request(prompt=list(p), max_new_tokens=max_new) for p in prompts]
    for r in reqs:
        eng.submit(r)
    steps = 0
    while eng.queue or eng.active or eng.prefilling:
        if before is not None and steps == steps_before:
            before(eng)
        eng.step(now=float(steps))
        steps += 1
        if steps > 20_000:
            raise RuntimeError("engine did not drain")
    return reqs


def compare_modes(ref: Reference, prompts: list, base: list, other: list,
                  mode: str) -> tuple[int, int]:
    """First divergence per request against the base stream; returns
    (divergences, divergences above tolerance)."""
    n_div = n_bad = 0
    for i, (p, a, b) in enumerate(zip(prompts, base, other)):
        if a == b:
            continue
        k = next(j for j in range(min(len(a), len(b))) if a[j] != b[j])
        _, z1, margin = ref(p, a)
        tol = TOL_REL * abs(float(z1[k]))
        n_div += 1
        n_bad += float(margin[k]) > tol
        emit(phase="divergence", mode=mode, request=i, position=len(p) + k,
             margin=float(margin[k]), tol=tol,
             within_tol=bool(float(margin[k]) <= tol))
    return n_div, n_bad


MODES = (
    ("per_step", dict(horizon=1)),
    ("fused_h8", dict(horizon=8)),
    ("chunked_prefix_share", dict(prefill_chunk=256, prefix_share=True)),
    ("ngram_spec_k4", dict(draft="ngram", spec_k=4)),
)


def phase_modes(spec: EngineSpec, wl: Workload, clock: CompileClock,
                seed: int):
    """The four decode modes on one engine spec, sharing one weight copy.
    Returns (passed, last engine) — the engine feeds the TAPAS phase."""
    prompts = wl.prompts(spec.cfg.vocab_size, seed)
    eng = ref = base = None
    passed = True
    for name, kw in MODES:
        c0, t0 = clock.total, time.perf_counter()
        # built beside the previous mode's engine, which is dropped after
        eng = spec.replace(**kw).build(share=eng)
        gc.collect()
        placed = check_placement(eng)
        reqs = serve(eng, prompts, wl.max_new)
        wall = time.perf_counter() - t0
        if ref is None:
            emit(phase="build", **describe(eng))
            ref = Reference(eng.model, eng.params, spec.max_seq)
        streams, row = judge(ref, prompts, reqs, wl.max_new)
        emit(phase="mode", mode=name, placed=placed,
             prompt_tokens=sum(map(len, prompts)),
             prefill_tokens=eng.stats.prefill_tokens,
             shared_block_hits=eng.pool.shared_block_hits,
             accepted_draft_tokens=eng.stats.accepted_tokens,
             compile_s=round(clock.total - c0, 3),
             wall_s_incl_compile=round(wall, 3),
             peak_device_bytes=peak_device_bytes(), **row)
        ok = placed and row["passed"]
        if base is None:
            base = streams
        else:
            n_div, n_bad = compare_modes(ref, prompts, base, streams, name)
            emit(phase="mode_vs_per_step", mode=name,
                 divergent_requests=n_div, above_tol=n_bad)
            ok = ok and n_bad == 0
        passed = passed and ok
    return passed, eng


def phase_loop(eng, *, max_ticks: int = 48) -> bool:
    """Attach the live engine to a ClusterSim as one SaaS server and step
    until a reconfigure knob change reaches it and its measured goodput
    returns into the cluster state."""
    from repro.core.datacenter import DCConfig
    from repro.core.scenario import DemandSurge, FailureEvent, Scenario
    from repro.core.simulator import TAPAS, ClusterSim, SimConfig
    from repro.serving import EngineBackend

    sim = ClusterSim(SimConfig(
        dc=DCConfig(n_rows=2, racks_per_row=2, servers_per_rack=4,
                    region="hot"),
        horizon_h=8.0, tick_min=10.0, seed=1, policy=TAPAS, occupancy=0.95,
        demand_scale=1.0,
        scenario=Scenario((
            FailureEvent(kind="thermal", start_h=2.0, end_h=6.0, target=0),
            DemandSurge(start_h=2.0, end_h=6.0, scale=1.4)))))
    backend = srv = None
    synced = None
    result = dict(phase="tapas_loop", server=None, knob_change=None,
                  measured_goodput=0.0, ticks=0)
    while sim.tick < min(sim.ticks, max_ticks):
        state = sim.step()
        result["ticks"] = sim.tick
        if backend is None:
            saas = np.flatnonzero(state.kind == 2)
            if len(saas):
                srv = int(saas[np.argmax(state.risk[saas])])
                backend = EngineBackend(eng, seed=0, requests_per_load=1.0,
                                        prompt_len=6, max_new_tokens=4)
                sim.attach_backend(srv, backend)
                result["server"] = srv
            continue
        knobs = (eng.knobs.freq_scale, eng.knobs.max_batch)
        if synced is None:
            synced = knobs          # the attach-time sync of the instance
            continue
        if knobs != synced and result["knob_change"] is None:
            result["knob_change"] = {"tick": sim.tick,
                                     "from": list(synced), "to": list(knobs)}
        gp = float(state.measured_goodput.get(srv, 0.0))
        if result["knob_change"] is not None and gp > 0.0:
            result["measured_goodput"] = gp
            break
    result.update(applied=len(backend.applied) if backend else 0,
                  completed=len(eng.stats.completed))
    result["passed"] = bool(result["knob_change"] is not None
                            and result["measured_goodput"] > 0.0)
    emit(**result)
    return result["passed"]


def phase_sharded(spec: EngineSpec, wl: Workload, clock: CompileClock,
                  seed: int, *, reshard_to: int, reshard_after: int) -> bool:
    """Serve at ``spec.shards``; serve the same requests again and reshard
    live to ``reshard_to`` after ``reshard_after`` steps.  Both runs are
    held to the dense forward under their own mesh, and to each other."""
    prompts = wl.prompts(spec.cfg.vocab_size, seed)
    c0 = clock.total
    eng = spec.build()
    placed = check_placement(eng)
    emit(phase="build", placed=placed, **describe(eng))
    runs, ref = [], None
    for label, kw in ((f"shards{spec.shards}", {}),
                      (f"shards{spec.shards}_to_{reshard_to}",
                       dict(steps_before=reshard_after,
                            before=lambda e: e.set_shards(reshard_to)))):
        ref = None      # release its hold on the previous layout's params
        gc.collect()
        reqs = serve(eng, prompts, wl.max_new, **kw)
        ref = Reference(eng.model, eng.params, spec.max_seq)
        streams, row = judge(ref, prompts, reqs, wl.max_new)
        emit(phase="sharded", run=label, shards=eng.shards,
             shard_swaps=eng.stats.shard_swaps,
             preemptions=eng.stats.preemptions,
             compile_s=round(clock.total - c0, 3), **row)
        c0 = clock.total
        runs.append((streams, row["passed"]))
    placed_after = check_placement(eng) and eng.shards == reshard_to
    n_div, n_bad = compare_modes(ref, prompts, runs[0][0], runs[1][0],
                                 f"reshard_to_{reshard_to}")
    emit(phase="sharded_streams", placed_after_reshard=placed_after,
         divergent_requests=n_div, above_tol=n_bad)
    return bool(placed and placed_after and n_bad == 0
                and all(ok for _, ok in runs))


def require_tpu(count: int) -> dict:
    if jax.default_backend() != "tpu":
        sys.exit(f"chip_smoke: JAX backend is {jax.default_backend()!r}, "
                 f"not 'tpu'; this check runs on the chip only")
    devs = jax.devices()
    if len(devs) < count:
        sys.exit(f"chip_smoke: needs {count} TPU chips, found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    device = require_tpu(args.chips)
    emit(phase="device", **device, tol_rel=TOL_REL,
         compile_cache=enable_compile_cache())
    clock = CompileClock()
    t0 = time.perf_counter()
    if args.chips == 1:
        spec = EngineSpec(get_config("qwen3-1.7b"), n_slots=16,
                          max_seq=2048, block_size=16, seed=args.seed)
        ok_modes, eng = phase_modes(spec, Workload(), clock, args.seed)
        ok_loop = phase_loop(eng)
        ok = ok_modes and ok_loop
    else:
        spec = EngineSpec(get_config("deepseek-7b"), shards=4, n_slots=8,
                          max_seq=1024, block_size=16, seed=args.seed)
        wl = Workload(prompt_lens=(64, 300, 700, 900), shared_prefix=0,
                      max_new=16)
        ok = phase_sharded(spec, wl, clock, args.seed, reshard_to=2,
                           reshard_after=6)
    emit(phase="total", compile_s=round(clock.total, 3),
         wall_s=round(time.perf_counter() - t0, 3))
    print(json.dumps({"ok": bool(ok), "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
