"""Child process of test_bench_sharded.py: four virtual CPU devices, a
tiny tensor-sharded engine (``shards=4``) served through the harness, then
held against the reference; with ``fault`` the decode step's cross-chip
softmax merge (the LSE max/sum exchange) is left out.  Prints one JSON
line with the check."""
import json
import os
import pathlib
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src"), str(pathlib.Path(__file__).parent)]

from bench_tiny import tiny_chat, tiny_config  # noqa: E402
from bench import generate as G  # noqa: E402
from bench import harness as H   # noqa: E402


def main(mode: str) -> None:
    if mode == "fault":
        from repro.models import attention as A
        core = A._paged_decode_core

        def no_exchange(axis, *a, **kw):
            return core(None, *a, **kw)

        A._paged_decode_core = no_exchange
    cfg = tiny_config(shards=4, prefix_share=False)
    cfg.update(num_attention_heads=8, num_key_value_heads=8, head_dim=8)
    seed = 2**31 + 77
    eng = H.build_engine(cfg, seed)
    assert eng.shards == 4
    mix = tiny_chat()
    H.warm_up(eng, cfg, mix, seed)
    run = H.drive(eng, G.generate(mix, vocab=cfg["vocab_size"], seed=seed,
                                  seconds=2.0, rate_per_s=8.0), 2.0)
    seqs = H.sample_for_check(run, 8, seed)
    del eng
    H.free()
    print(json.dumps(H.check(cfg, mix, seqs, seed, limit=0.01)))


if __name__ == "__main__":
    main(sys.argv[1])
