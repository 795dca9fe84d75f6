"""Serving driver: continuous-batched generation through ``EngineSpec``.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b
    PYTHONPATH=src python -m repro.launch.serve --arch llama2-7b --smoke \
        --requests 12

Builds the stack the way every caller does (``EngineSpec`` -> ``Engine``
-> ``PagedCachePool``), so without ``--smoke`` it serves the published
widths with random weights on whatever device JAX finds, and prints that
device first.
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.serving import EngineSpec, Request


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama2-7b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--max-batch", type=int, default=None,
                    help="TAPAS batch knob (default: --slots)")
    ap.add_argument("--freq-scale", type=float, default=1.0,
                    help="TAPAS frequency knob (1.0 = nominal clock)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="paged KV pool block size (tokens)")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke_config()
    enable_compile_cache()
    dev = jax.devices()[0]
    print({"platform": dev.platform, "kind": dev.device_kind,
           "count": jax.device_count()})
    # fresh construction from CLI flags, not a copy of another spec
    eng = EngineSpec(cfg, max_seq=args.max_seq, n_slots=args.slots,  # tapaslint: disable=TL004
                     max_batch=args.max_batch,
                     block_size=args.block_size).build()
    eng.knobs.freq_scale = args.freq_scale

    rng = np.random.default_rng(0)
    for i in range(args.requests):
        plen = int(rng.integers(4, 24))
        eng.submit(Request(
            prompt=list(rng.integers(0, cfg.vocab_size, plen)),
            max_new_tokens=args.max_new, customer=f"cust{i % 3}",
            arrival_s=0.0))
    stats = eng.run()
    gp = eng.goodput(ttft_slo=50.0, tbt_slo=5.0)
    out = {
        "completed": len(stats.completed),
        "decode_tokens": stats.decode_tokens,
        "prefill_tokens": stats.prefill_tokens,
        "prefill_batches": stats.prefill_batches,
        "preemptions": stats.preemptions,
        "goodput_tok_per_step": round(gp, 3),
    }
    print(out)
    return out


if __name__ == "__main__":
    main()
