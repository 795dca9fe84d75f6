#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        --seconds <s> [--out calib.jsonl]

For each seed, in one process: the cell's engine is built with that
seed's weights and warmed up, serves the cell's traffic for a window of
``--seconds`` (the same path and load as ``bench/run.py``), and its state
is freed.  Then the sample that a run would compare is held against the
float32 reference (the program's reading: the widest gap of a served
token below the reference's best logit) and against the float8 control
at the same positions (the gap of the token the float8 pass puts
first).  One JSON line per seed.

The limit in ``bench/cells/<cell>.json`` sits above the largest program
reading and below the smallest control reading (see PERF.md).
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


def reading(cell, seed: int, seconds: float) -> dict:
    t0 = time.perf_counter()
    eng = H.build_engine(cell.config, seed)
    H.warm_up(eng, cell.config, cell.mix, seed)
    setup = time.perf_counter() - t0
    sched = generate(cell.mix, vocab=H.vocab(cell.config), seed=seed,
                     seconds=seconds, rate_per_s=cell.params.get("rate_per_s"))
    run = H.drive(eng, sched, seconds)
    seqs = H.sample_for_check(run, cell.params["check"]["requests"], seed)
    e2e = H.end_to_end(run, cell.params.get("slo_ms"),
                       want=[m["name"] for m in cell.end_to_end])
    del eng
    run["completed"] = None
    H.free()
    row = {"seed": seed, "setup_s": setup,
           "requests": [len(p) + len(o) for p, o in seqs],
           "e2e": e2e}
    prog = H.check(cell.config, cell.mix, seqs, seed, limit=None)
    ctrl = H.check(cell.config, cell.mix, seqs, seed, limit=None,
                   control=True)
    row.update(program=prog["max_logit_gap"], tokens=prog["tokens"],
               control=ctrl["max_logit_gap"])
    row["wall_s"] = time.perf_counter() - t0
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    cell = H.load_cell(args.workload)
    H.configure_jax()
    H.device_info(cell.chips)
    out = open(args.out, "a") if args.out else None
    try:
        for seed in (int(s) for s in args.seeds.split(",")):
            row = json.dumps({"cell": args.workload,
                              **reading(cell, seed, args.seconds)})
            print(row, flush=True)
            if out:
                out.write(row + "\n")
                out.flush()
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
