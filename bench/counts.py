"""The work the algorithm needs, from a configuration file's sizes.

Counts are of the model, not of an implementation: a matrix product of
an (n, k) by a (k, m) operand is ``2 n k m`` operations whatever kernel
runs it, and the bytes a decode step needs are every weight once, the
keys and values of each active lane's real context, and the new token's
keys and values.  Padding, gathers of unused blocks and copies of the
pool are not work.  Weights and the KV cache are bfloat16 (2 bytes).
"""
from __future__ import annotations

from bench.weights import dims

BYTES = 2


def layer_matmul_params(dm: dict) -> int:
    d, h, k, hd, f = dm["d"], dm["h"], dm["k"], dm["hd"], dm["f"]
    return d * h * hd * 2 + d * k * hd * 2 + 3 * d * f


def head_params(dm: dict) -> int:
    return dm["d"] * dm["v"]


def matmul_params(cfg: dict) -> int:
    """Parameters that take part in a matrix product per token: every
    layer's projections and MLP, and the output head (the tied embedding
    counts once, as the head; the embedding lookup is a gather)."""
    dm = dims(cfg)
    return dm["layers"] * layer_matmul_params(dm) + head_params(dm)


def weight_bytes(cfg: dict) -> int:
    """Bytes of every weight a decode step reads once: the matrix
    products' weights and the norms (the embedding rows it gathers are a
    few KiB and left out)."""
    dm = dims(cfg)
    norms = dm["layers"] * (2 * dm["d"] + (2 * dm["hd"] if dm["qk_norm"]
                                           else 0)) + dm["d"]
    return (matmul_params(cfg) + norms) * BYTES


def kv_bytes_per_token(cfg: dict) -> int:
    dm = dims(cfg)
    return dm["layers"] * 2 * dm["k"] * dm["hd"] * BYTES


def attn_flops(dm: dict, n_keys: int) -> int:
    """Scores and the weighted sum of one query over ``n_keys`` keys."""
    return 4 * dm["layers"] * dm["h"] * dm["hd"] * n_keys


def decode_step(cfg: dict, contexts: list) -> tuple[float, float]:
    """(FLOPs, bytes) of one decode step over active lanes whose contexts
    (tokens already in the cache) are ``contexts``."""
    dm = dims(cfg)
    flops = sum(2 * matmul_params(cfg) + attn_flops(dm, c + 1)
                for c in contexts)
    kv = kv_bytes_per_token(cfg)
    byts = weight_bytes(cfg) + sum(c * kv for c in contexts) \
        + len(contexts) * kv
    return float(flops), float(byts)


def prefill(cfg: dict, start: int, end: int, *, head: bool) -> float:
    """FLOPs of computing prompt positions ``start..end-1`` (the earlier
    ones are cached), with the head at the last position if ``head``."""
    dm = dims(cfg)
    n = end - start
    per_layer = 2 * layer_matmul_params(dm) * dm["layers"] * n
    # position p attends to p + 1 keys
    keys = (start + 1 + end) * n // 2
    return float(per_layer + attn_flops(dm, 1) * keys
                 + (2 * head_params(dm) if head else 0))
