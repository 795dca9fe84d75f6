"""Tiny configurations and mixes for the CPU tests of the chip benchmark
(``bench/``): the published files with their widths cut, so the harness,
the generator and the reference run in seconds."""
import copy
import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parents[2] / "bench"


def load(kind: str, name: str) -> dict:
    with open(BENCH / kind / f"{name}.json") as f:
        return json.load(f)


def tiny_config(name: str = "qwen3-1.7b", **engine) -> dict:
    cfg = copy.deepcopy(load("configs", name))
    cfg.update(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
               num_attention_heads=4, num_key_value_heads=2, head_dim=16,
               vocab_size=512)
    cfg["engine"] = dict(cfg["engine"], n_slots=4, max_seq=128,
                         prefill_chunk=32, **engine)
    return cfg


def tiny_chat() -> dict:
    mix = copy.deepcopy(load("traffic", "chat"))
    mix["populations"][0].update(
        prompt=dict(median=24, sigma=0.8, min=4, max=64),
        output=dict(median=12, sigma=0.6, min=2, max=48))
    return mix
