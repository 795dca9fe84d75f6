"""The dense GQA decoder family (Llama, Qwen3): RMSNorm, grouped-query
causal attention with optional qk-norm, rotary embeddings, SwiGLU MLP,
tied or untied head.  A configuration file without a ``"family"`` key
belongs here.

Its sizes and seeded tensors are in ``bench/weights.py``, its float32
forward in ``bench/reference.py`` and its FLOP and byte counts in
``bench/counts.py``; this file maps them onto the program (``ArchConfig``
fields and the parameter tree) and hands the readers the counts of a
traced run.
"""
from __future__ import annotations

from bench import counts
from bench import weights as W
from bench.reference import Reference  # noqa: F401  (the float32 forward)

dims = W.dims
kv_bytes_per_token = counts.kv_bytes_per_token

# The program's ArchConfig fields that make it the decoder
# bench/reference.py computes.
COMPUTES = dict(attn_kind="gqa", family="dense", norm_kind="rms",
                norm_plus_one=False, embed_scale=False, activation="silu",
                mlp_kind="glu", n_experts=0, causal=True, input_kind="tokens")


def weights(key, dm: dict) -> dict:
    """Every tensor in the checkpoint's layout: ``layers`` (each layer's
    tensors stacked on a leading layer axis), ``embed``, ``final_norm``
    and, untied, ``lm_head``."""
    return {"layers": W.all_layers(key, dm), **W.outer(key, dm)}


def arch(config: dict, base):
    """The registered model ``base`` with the file's sizes, checked to be
    the decoder the reference computes."""
    dm = dims(config)
    a = base.replace(
        num_layers=dm["layers"], d_model=dm["d"], n_heads=dm["h"],
        n_kv_heads=dm["k"], head_dim=dm["hd"], d_ff=dm["f"],
        vocab_size=dm["v"], qk_norm=dm["qk_norm"],
        tie_embeddings=dm["tied"], rope_theta=dm["theta"])
    bad = {k: (getattr(a, k), v) for k, v in COMPUTES.items()
           if getattr(a, k) != v}
    if bad:
        raise ValueError(f"{config['name']}: the program's {a.name} is "
                         f"not the decoder bench/reference.py computes: {bad}")
    return a


def to_program(w: dict, dm: dict, model) -> dict:
    """``weights`` in the program's parameter tree (``models/
    transformer.py``): heads split out of the projections, the vocabulary
    padded to the plan's multiple."""
    import jax.numpy as jnp
    n, d, h, k, hd = dm["layers"], dm["d"], dm["h"], dm["k"], dm["hd"]
    v_pad = model.plan.v_pad(model.cfg)
    lay = w["layers"]
    attn = {"w_q": lay["wq"].reshape(n, d, h, hd),
            "w_k": lay["wk"].reshape(n, d, k, hd),
            "w_v": lay["wv"].reshape(n, d, k, hd),
            "w_o": lay["wo"].reshape(n, h, hd, d)}
    if dm["qk_norm"]:
        attn["q_norm"], attn["k_norm"] = lay["q_norm"], lay["k_norm"]
    p = {"embed": jnp.pad(w["embed"], ((0, v_pad - dm["v"]), (0, 0))),
         "layers": {"norm1": {"scale": lay["norm1"]},
                    "norm2": {"scale": lay["norm2"]},
                    "attn": attn,
                    "mlp": {"w_gate": lay["w_gate"], "w_up": lay["w_up"],
                            "w_down": lay["w_down"]}},
         "final_norm": {"scale": w["final_norm"]}}
    if not dm["tied"]:
        p["unembed"] = jnp.pad(w["lm_head"], ((0, 0), (0, v_pad - dm["v"])))
    return p


def decode_step(run, contexts: list) -> tuple[float, float]:
    """(FLOPs, bytes) of one decode launch over lanes with ``contexts``."""
    return counts.decode_step(run.config, contexts)


def prefill(run, start: int, end: int, head: bool) -> float:
    """FLOPs of prompt positions ``start..end-1``, with the head if
    ``head``."""
    return counts.prefill(run.config, start, end, head=head)
