"""A sparse-expert model family for the harness's drop-in test, written
into a temporary families directory as ``tiny_moe.py``.

The dense GQA attention of ``bench/reference.py`` with a routed SwiGLU
expert FFN in place of the MLP (Qwen3-MoE): a float32 softmax router over
every expert, the top ``num_experts_per_tok`` renormalised, every expert
held on the chip.  The program runs it through ``models/moe.py``.  Keys as
in the published Qwen3-MoE ``config.json``: ``num_experts``,
``num_experts_per_tok``, ``moe_intermediate_size``, ``norm_topk_prob``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import counts
from bench import reference as R
from bench import weights as W

COMPUTES = dict(attn_kind="gqa", family="moe", norm_kind="rms",
                norm_plus_one=False, embed_scale=False, activation="silu",
                mlp_kind="glu", causal=True, input_kind="tokens",
                router_renorm=True)


def dims(config: dict) -> dict:
    dm = W.dims(config)
    dm.update(e=config["num_experts"], top_k=config["num_experts_per_tok"],
              fe=config["moe_intermediate_size"],
              renorm=bool(config["norm_topk_prob"]))
    return dm


def layer(key, l, dm: dict) -> dict:
    """Layer ``l``: the dense layer's attention and norms, a router
    (d, E) and the experts' projections stacked on a leading expert axis."""
    w = W.layer(key, l, dm)
    for name in ("w_gate", "w_up", "w_down"):
        del w[name]
    lk = jax.random.fold_in(key, l)
    d, e, fe = dm["d"], dm["e"], dm["fe"]
    w["router"] = W._normal(lk, "router", (d, e), d ** -0.5)
    w["experts.gate"] = W._normal(lk, "experts.gate", (e, d, fe), d ** -0.5)
    w["experts.up"] = W._normal(lk, "experts.up", (e, d, fe), d ** -0.5)
    w["experts.down"] = W._normal(lk, "experts.down", (e, fe, d), fe ** -0.5)
    return w


def weights(key, dm: dict) -> dict:
    return {"layers": W.all_layers(key, dm, layer), **W.outer(key, dm)}


def arch(config: dict, base):
    dm = dims(config)
    a = base.replace(
        num_layers=dm["layers"], d_model=dm["d"], n_heads=dm["h"],
        n_kv_heads=dm["k"], head_dim=dm["hd"], d_ff=dm["f"],
        vocab_size=dm["v"], qk_norm=dm["qk_norm"],
        tie_embeddings=dm["tied"], rope_theta=dm["theta"],
        n_experts=dm["e"], top_k=dm["top_k"], expert_d_ff=dm["fe"],
        router_renorm=dm["renorm"])
    bad = {k: (getattr(a, k), v) for k, v in COMPUTES.items()
           if getattr(a, k) != v}
    if bad:
        raise ValueError(f"{config['name']}: {a.name} is not the model "
                         f"tiny_moe's reference computes: {bad}")
    return a


def to_program(w: dict, dm: dict, model) -> dict:
    n, d, h, k, hd = dm["layers"], dm["d"], dm["h"], dm["k"], dm["hd"]
    v_pad = model.plan.v_pad(model.cfg)
    e_pad = model.plan.e_pad(model.cfg) - dm["e"]
    lay = w["layers"]

    def experts(t):
        return jnp.pad(t, ((0, 0), (0, e_pad), (0, 0), (0, 0)))

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
                    "moe": {"router": lay["router"].astype(jnp.float32),
                            "w_gate": experts(lay["experts.gate"]),
                            "w_up": experts(lay["experts.up"]),
                            "w_down": experts(lay["experts.down"])}},
         "final_norm": {"scale": w["final_norm"]}}
    if not dm["tied"]:
        p["unembed"] = jnp.pad(w["lm_head"], ((0, 0), (0, v_pad - dm["v"])))
    return p


def _layer(x, w, dm, quant):
    x = R._attention(x, w, dm, quant)
    y = R._rms(x, w["norm2"], dm["eps"])
    probs = jax.nn.softmax(R._mm(y, w["router"], quant), axis=-1)
    top, idx = jax.lax.top_k(probs, dm["top_k"])
    if dm["renorm"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    gate = jnp.sum(jax.nn.one_hot(idx, dm["e"]) * top[..., None], axis=-2)

    def expert(wg, wu, wd):
        return R._mm(jax.nn.silu(R._mm(y, wg, quant)) * R._mm(y, wu, quant),
                     wd, quant)

    out = jax.vmap(expert)(w["experts.gate"], w["experts.up"],
                           w["experts.down"])            # (E, B, S, d)
    return x + jnp.einsum("bse,ebsd->bsd", gate, out)


class Reference(R.Reference):
    dims = staticmethod(dims)
    layer_weights = staticmethod(layer)
    layer_forward = staticmethod(_layer)


kv_bytes_per_token = counts.kv_bytes_per_token


def _token_params(dm: dict) -> int:
    """Weights a token multiplies by: attention projections, the router,
    its top-k experts, and the head."""
    d, h, k, hd = dm["d"], dm["h"], dm["k"], dm["hd"]
    per_layer = 2 * d * h * hd + 2 * d * k * hd + d * dm["e"] \
        + dm["top_k"] * 3 * d * dm["fe"]
    return dm["layers"] * per_layer + d * dm["v"]


def decode_step(run, contexts: list) -> tuple[float, float]:
    """FLOPs of the experts each token reaches; bytes of the fewest
    weights any step reads (one token's experts), and the KV."""
    dm = dims(run.config)
    flops = sum(2 * _token_params(dm) + counts.attn_flops(dm, c + 1)
                for c in contexts)
    kv = kv_bytes_per_token(run.config)
    byts = _token_params(dm) * counts.BYTES + (sum(contexts)
                                               + len(contexts)) * kv
    return float(flops), float(byts)


def prefill(run, start: int, end: int, head: bool) -> float:
    dm = dims(run.config)
    n = end - start
    body = _token_params(dm) - dm["d"] * dm["v"]
    keys = (start + 1 + end) * n // 2
    return float(2 * body * n + counts.attn_flops(dm, 1) * keys
                 + (2 * dm["d"] * dm["v"] if head else 0))
