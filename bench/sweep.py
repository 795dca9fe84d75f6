#!/usr/bin/env python3
"""Find a cell's knee once, on the chip: the highest offered rate at which
90% of requests keep the TTFT limit and no backlog grows.  (The few
requests that fall due during the window's last step are always queued at
its end; ``queued_at_end`` shows a backlog only where it grows with the
rate.)

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 2,3,4,5 [--out sweep.json]

One process: the cell's engine is built and warmed once.  First the
unloaded service times are read (one request of the mix's median prompt
length alone: its time to first token, and the mean gap of its decode
steps),
which set the limits the DistServe way: 5x the unloaded TTFT and 5x the
unloaded step time.  Then each rate gets its own window of the cell's
traffic (seed + its index), after which the engine is drained untimed.
The cell's ``rate_per_s`` is then set by hand to about 4/5 of the knee.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness as H      # noqa: E402
from bench.generate import generate  # noqa: E402


def unloaded(eng, cell, seed: int) -> dict:
    """TTFT of one prompt of the mix's median length and the mean decode
    gap, alone."""
    import numpy as np
    from repro.serving import Request
    rng = np.random.default_rng([seed, 0x554E])
    vocab = H.vocab(cell.config)
    mix = generate(cell.mix, vocab=vocab, seed=seed,
                   seconds=1000.0, rate_per_s=1.0)
    n = int(np.median([len(it.prompt) for it in mix]))
    out = []
    for _ in range(3):
        req = Request(prompt=rng.integers(0, vocab, n).tolist(),
                      max_new_tokens=64)
        t0 = time.perf_counter()
        eng.submit(req)
        stamps = []
        while H._busy(eng):
            eng.step()
            stamps.extend([time.perf_counter() - t0]
                          * (len(req.output) - len(stamps)))
        gaps = np.diff(stamps[1:])
        out.append((stamps[0], float(np.mean(gaps))))
    ttft, step = (float(np.median([o[i] for o in out])) for i in (0, 1))
    return {"ttft_ms": ttft * 1e3, "step_ms": step * 1e3,
            "slo_ms": {"ttft": 5 * ttft * 1e3, "itl": 5 * step * 1e3}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    cell = H.load_cell(args.workload)
    H.configure_jax()
    device = H.device_info(cell.chips)
    eng = H.build_engine(cell.config, args.seed)
    H.warm_up(eng, cell.config, cell.mix, args.seed)
    base = unloaded(eng, cell, args.seed)
    print(json.dumps({"unloaded": base, "device": device}), flush=True)
    rows = []
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        sched = generate(cell.mix, vocab=H.vocab(cell.config),
                         seed=args.seed + 1 + i, seconds=args.seconds,
                         rate_per_s=rate)
        run = H.drive(eng, sched, args.seconds)
        row = {"rate_per_s": rate, "queued_at_end": run["queued"],
               **H.end_to_end(run, base["slo_ms"],
                               want=[m["name"] for m in cell.end_to_end])}
        rows.append(row)
        print(json.dumps(row), flush=True)
        H.drain(eng)
    if args.out:
        path = pathlib.Path(args.out)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps({"cell": args.workload,
                                    "unloaded": base, "rates": rows},
                                   indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
