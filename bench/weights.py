"""Model weights made from ``--seed`` by the benchmark, not by the program.

The seed machinery that every model family (``bench/families/``) makes
its tensors with, and the dense GQA family's own tensors.  One canonical
set per (configuration, seed), named as in the published checkpoints of
dense GQA models, with shapes::

    embed (vocab, d)        lm_head (d, vocab)  [untied models only]
    final_norm (d,)
    layer l: norm1 (d,), norm2 (d,), wq (d, H*hd), wk (d, K*hd),
             wv (d, K*hd), wo (H*hd, d), q_norm (hd,), k_norm (hd,)
             [qk-norm models only], w_gate (d, f), w_up (d, f),
             w_down (f, d)

Every tensor has its own key, folded from the seed, the layer and the
tensor's name, so one layer can be made alone (the reference does, layer
by layer) and equal to the same layer made inside a batch of all layers
(the served weights are, in one jitted call).  Values are drawn in
float32 and rounded to bfloat16, the type they are served in; the
reference computes on exactly those rounded values.

This module imports nothing of the program.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

DTYPE = jnp.bfloat16


def dims(cfg: dict) -> dict:
    """The sizes the forward needs, from a configuration file's keys."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return dict(d=d, h=h, k=cfg["num_key_value_heads"],
                hd=cfg.get("head_dim") or d // h,
                f=cfg["intermediate_size"], v=cfg["vocab_size"],
                layers=cfg["num_hidden_layers"],
                theta=float(cfg["rope_theta"]),
                eps=float(cfg["rms_norm_eps"]),
                qk_norm=bool(cfg.get("qk_norm", False)),
                tied=bool(cfg["tie_word_embeddings"]))


def _tag(name: str) -> int:
    return zlib.crc32(name.encode()) & 0x7FFFFFFF


def seed_key(seed_words):
    """A key from the two 32-bit words of a seed (seeds may pass 2**32)."""
    lo, hi = seed_words[0], seed_words[1]
    key = jax.random.PRNGKey(lo)
    key = jax.random.fold_in(key, hi)
    return jax.random.fold_in(key, _tag("weights"))


def seed_words(seed: int):
    """Host-side split of a whole-number seed into uint32 words."""
    import numpy as np
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return np.asarray([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF],
                      np.uint32)


def _normal(key, name, shape, std):
    k = jax.random.fold_in(key, _tag(name))
    return (jax.random.normal(k, shape, jnp.float32) * std).astype(DTYPE)


def _norm_w(key, name, n):
    k = jax.random.fold_in(key, _tag(name))
    return (1.0 + 0.1 * jax.random.normal(k, (n,), jnp.float32)).astype(DTYPE)


def layer(key, l, dm: dict) -> dict:
    """Layer ``l``'s weights in bfloat16 (``l`` may be traced)."""
    d, h, k, hd, f = dm["d"], dm["h"], dm["k"], dm["hd"], dm["f"]
    lk = jax.random.fold_in(key, l)
    w = {
        "norm1": _norm_w(lk, "norm1", d),
        "norm2": _norm_w(lk, "norm2", d),
        "wq": _normal(lk, "wq", (d, h * hd), d ** -0.5),
        "wk": _normal(lk, "wk", (d, k * hd), d ** -0.5),
        "wv": _normal(lk, "wv", (d, k * hd), d ** -0.5),
        "wo": _normal(lk, "wo", (h * hd, d), (h * hd) ** -0.5),
        "w_gate": _normal(lk, "w_gate", (d, f), d ** -0.5),
        "w_up": _normal(lk, "w_up", (d, f), d ** -0.5),
        "w_down": _normal(lk, "w_down", (f, d), f ** -0.5),
    }
    if dm["qk_norm"]:
        w["q_norm"] = _norm_w(lk, "q_norm", hd)
        w["k_norm"] = _norm_w(lk, "k_norm", hd)
    return w


def outer(key, dm: dict) -> dict:
    """Embedding, final norm and (untied models) the output head."""
    w = {"embed": _normal(key, "embed", (dm["v"], dm["d"]), 0.02),
         "final_norm": _norm_w(key, "final_norm", dm["d"])}
    if not dm["tied"]:
        w["lm_head"] = _normal(key, "lm_head", (dm["d"], dm["v"]),
                               dm["d"] ** -0.5)
    return w


def all_layers(key, dm: dict, make=layer) -> dict:
    """Every layer's weights, as ``make(key, l, dm)`` gives one layer,
    stacked on a leading layer axis."""
    return jax.vmap(lambda l: make(key, l, dm))(
        jnp.arange(dm["layers"], dtype=jnp.int32))
