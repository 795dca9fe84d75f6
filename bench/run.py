#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's engine through ``EngineSpec.build()`` with weights made
from ``--seed``, warms up every shape the cell's traffic can use (set-up,
timed as ``setup_s``), then drives ``Engine.step()`` open-loop for
``--seconds`` with the cell's traffic, generated from the seed.  After the
window the program's state is freed and a sample of the finished requests
is held against the float32 reference of the configuration's model family
(``bench/families/<family>.py``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device`` and,
traced, ``breakdown``; its last key, ``checks``, gives each number
compared beside its limit, as do the last lines of standard error.

Exits non-zero, printing no result, when JAX finds no accelerator or
fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse         # noqa: E402
import bisect           # noqa: E402
import json             # noqa: E402
import pathlib          # noqa: E402
import shutil           # noqa: E402
import sys              # noqa: E402
from collections import defaultdict  # noqa: E402
from types import SimpleNamespace    # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness as H      # noqa: E402
from bench import trace as T        # noqa: E402
from bench.generate import generate  # noqa: E402
from bench.peaks import peak        # noqa: E402

TRACE_DIR = ROOT / ".bench_trace"


def breakdown(tr: T.Trace, lo: int, hi: int) -> dict:
    """Top device operations (named by module) and longest idle gaps
    (named by the harness span they fall in) on chip 0."""
    dev = tr.devices.get(0)
    if dev is None:
        return {"device_ops": [], "idle_gaps": []}
    starts = [m[0] for m in dev.modules]
    tot = defaultdict(int)
    for s, e, n in dev.ops:
        if e <= lo or s >= hi:
            continue
        i = bisect.bisect_right(starts, s) - 1
        mod = dev.modules[i][2].split("(")[0] \
            if i >= 0 and dev.modules[i][1] >= e else "?"
        tot[f"{mod}/{n}"] += min(e, hi) - max(s, lo)
    ops = sorted(tot.items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[n, ns / 1e9] for n, ns in ops],
            "idle_gaps": [list(g) for g in
                          T.idle_gaps(T.busy(dev, lo, hi), tr.spans, lo, hi)]}


def per_layer(cell: H.Cell, ctx: SimpleNamespace) -> dict:
    out = {}
    for m in cell.per_layer:
        v = H.metric_reader(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = H.load_cell(args.workload)
    H.configure_jax()
    try:
        device = H.device_info(cell.chips)
    except RuntimeError as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 2
    compiles = H.CompileCounter()
    t_build = time.perf_counter()
    eng = H.build_engine(cell.config, args.seed)
    t_warm = time.perf_counter()
    H.warm_up(eng, cell.config, cell.mix, args.seed)
    t_warm_end = time.perf_counter()
    schedule = generate(cell.mix, vocab=H.vocab(cell.config),
                        seed=args.seed, seconds=args.seconds,
                        rate_per_s=cell.params.get("rate_per_s"))
    recorder = trace_dir = None
    if args.trace:
        trace_dir = TRACE_DIR / args.workload
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
        recorder = H.Recorder(eng, cell.config)
    snap = H.counters(eng)
    setup_s = time.perf_counter() - T_START

    compiles.on = True
    run = H.drive(eng, schedule, args.seconds, trace_dir=trace_dir,
                  recorder=recorder)
    compiles.on = False

    counters = (snap, H.counters(eng))
    queued = {r.req_id for r in eng.queue}
    admitted = sum(len(tr.req.prompt) for tr in run["tracks"]
                   if tr.req.req_id not in queued)
    failed = sum(1 for r in run["completed"] if r.outcome != "accepted")
    mem = H.memory_peak_bytes(cell.chips)
    check_cfg = cell.params["check"]
    seqs = H.sample_for_check(run, check_cfg["requests"], args.seed)
    e2e = H.end_to_end(run, cell.params.get("slo_ms"),
                       want=[m["name"] for m in cell.end_to_end])
    if recorder is not None:
        recorder.eng = None
    del eng
    run["completed"] = None
    H.free()

    device["memory_peak_bytes"] = mem
    result = {"correct": False, "attempted": len(run["tracks"]),
              "failed": failed, "metrics": {}, "device": device}
    if args.trace:
        tr = T.load(trace_dir)
        win = tr.window()
        ctx = SimpleNamespace(config=cell.config, chips=cell.chips,
                              peak=peak(device["kind"]), trace=tr,
                              window=win, recorder=recorder,
                              counters=counters,
                              admitted_prompt_tokens=admitted, notes={})
        result["metrics"] = per_layer(cell, ctx)
        if win is not None:
            lo, hi = win
            busy = [sum(e - s for s, e in T.busy(tr.devices[i], lo, hi))
                    for i in range(cell.chips) if i in tr.devices]
            device["busy_s"] = sum(busy) / len(busy) / 1e9 if busy else 0.0
            device["window_s"] = (hi - lo) / 1e9
            result["breakdown"] = breakdown(tr, lo, hi)
        result["notes"] = ctx.notes
    else:
        e2e["setup_s"] = setup_s
        result["metrics"] = H.result_metrics(cell.end_to_end, e2e)
    result["info"] = {k: v for k, v in e2e.items()
                      if k not in result["metrics"]}
    result["info"].update(window_compiles=compiles.n,
                          queued_at_end=run["queued"], setup_s=setup_s,
                          start_s=t_build - T_START,
                          build_s=t_warm - t_build,
                          warm_up_s=t_warm_end - t_warm)

    chk = H.check(cell.config, cell.mix, seqs, args.seed,
                  limit=check_cfg["max_logit_gap"])
    result["correct"] = chk["ok"]
    result["checks"] = {
        "max_logit_gap": {"value": chk["max_logit_gap"],
                          "limit": chk["limit"]},
        "compared_tokens": {"value": chk["tokens"], "limit": "> 0"},
    }
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
