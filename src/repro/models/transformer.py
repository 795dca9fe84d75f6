"""Model assembly: per-family blocks + scan-over-layers + Model API.

``jax.lax.scan`` over stacked per-layer parameters keeps HLO size (and
compile time on this 1-core container) O(1) in depth.  The same block
functions serve train, prefill and decode; decode uses the shard_map cores
from attention.py / moe.py.

Cross-entropy runs inside shard_map over the vocab-sharded unembedding with
a checkpointed chunk scan, so the (T, V) logits are never materialised
globally (V_loc chunks only) — this is what keeps gemma's 256k vocab inside
HBM at train time.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S
from repro.models.sharding import ShardPlan, shard_map_or_call

Params = dict[str, Any]
NEG_INF = -1e30
MOE_AUX_WEIGHT = 0.01


def _norm(x, w, cfg: ArchConfig):
    if cfg.norm_kind == "layer":
        return L.layer_norm(x, w["scale"], w["bias"])
    return L.rms_norm(x, w["scale"], plus_one=cfg.norm_plus_one)


def _norm_init(cfg: ArchConfig, dt) -> Params:
    scale = jnp.zeros if cfg.norm_plus_one else jnp.ones
    p = {"scale": scale((cfg.d_model,), dt)}
    if cfg.norm_kind == "layer":
        p["bias"] = jnp.zeros((cfg.d_model,), dt)
    return p


def _norm_axes(cfg: ArchConfig) -> Params:
    p = {"scale": ("embed_act",)}
    if cfg.norm_kind == "layer":
        p["bias"] = ("embed_act",)
    return p


# ---------------------------------------------------------------------------
# per-layer init / axes
# ---------------------------------------------------------------------------

def init_layer(key, cfg: ArchConfig, plan: ShardPlan) -> Params:
    dt = plan.param_dtype
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p: Params = {"norm1": _norm_init(cfg, dt), "norm2": _norm_init(cfg, dt)}
    if cfg.rwkv:
        p["tmix"] = S.init_rwkv_tmix(k1, cfg, plan)
        p["cmix"] = S.init_rwkv_cmix(k2, cfg, plan)
        return p
    if cfg.attn_kind == "mla":
        p["attn"] = A.init_mla(k1, cfg, plan)
    else:
        p["attn"] = A.init_gqa(k1, cfg, plan)
    if cfg.family == "hybrid":
        p["mamba"] = S.init_mamba(k2, cfg, plan)
    if cfg.n_experts:
        p["moe"] = M.init_moe(k3, cfg, plan)
    elif cfg.mlp_kind == "gelu2":
        p["mlp"] = L.gelu_mlp_init(k4, cfg.d_model, cfg.d_ff, dtype=dt)
    else:
        p["mlp"] = L.mlp_init(k4, cfg.d_model, cfg.d_ff, dtype=dt)
    return p


def layer_axes(cfg: ArchConfig, plan: ShardPlan) -> Params:
    ax: Params = {"norm1": _norm_axes(cfg), "norm2": _norm_axes(cfg)}
    if cfg.rwkv:
        ax["tmix"] = S.rwkv_tmix_axes(cfg, plan)
        ax["cmix"] = S.rwkv_cmix_axes(cfg, plan)
        return ax
    if cfg.attn_kind == "mla":
        ax["attn"] = A.mla_axes(cfg, plan)
    else:
        ax["attn"] = A.gqa_axes(cfg, plan)
    if cfg.family == "hybrid":
        ax["mamba"] = S.mamba_axes(cfg, plan)
    if cfg.n_experts:
        ax["moe"] = M.moe_axes(cfg, plan)
    elif cfg.mlp_kind == "gelu2":
        ax["mlp"] = {"w_in": ("embed", "ffn"), "b_in": ("ffn",),
                     "w_out": ("ffn", "embed"), "b_out": ("embed_act",)}
    else:
        ax["mlp"] = {"w_gate": ("embed", "ffn"), "w_up": ("embed", "ffn"),
                     "w_down": ("ffn", "embed")}
    return ax


# ---------------------------------------------------------------------------
# block forward (train / prefill): x (B, S, d)
# ---------------------------------------------------------------------------

def block_forward(x, lp: Params, positions, cfg: ArchConfig, plan: ShardPlan,
                  *, want_cache: bool, state: Params | None = None):
    """Returns (x, cache_or_None, aux)."""
    aux = jnp.zeros((), jnp.float32)
    if cfg.rwkv:
        st = state or {}
        y, tmix_state = S.rwkv_tmix_forward(lp["tmix"], _norm(x, lp["norm1"], cfg),
                                            cfg, plan, st.get("tmix"))
        x = x + y
        y, cmix_state = S.rwkv_cmix_forward(lp["cmix"], _norm(x, lp["norm2"], cfg),
                                            cfg, plan, st.get("cmix"))
        x = x + y
        cache = {"tmix": tmix_state, "cmix": cmix_state} if want_cache else None
        return x, cache, aux

    h = _norm(x, lp["norm1"], cfg)
    if cfg.attn_kind == "mla":
        attn_out, attn_cache = A.mla_forward(lp["attn"], h, positions, cfg, plan,
                                             want_cache=want_cache)
    else:
        attn_out, attn_cache = A.gqa_forward(lp["attn"], h, positions, cfg, plan,
                                             want_cache=want_cache)
    if cfg.family == "hybrid":
        st = state or {}
        mamba_out, mamba_state = S.mamba_forward(lp["mamba"], h, cfg, plan,
                                                 st.get("mamba"))
        x = x + 0.5 * (attn_out + mamba_out)
    else:
        x = x + attn_out
        mamba_state = None

    h = _norm(x, lp["norm2"], cfg)
    if cfg.n_experts:
        y, aux = M.moe_ffn(lp["moe"], h, cfg, plan)
    elif cfg.mlp_kind == "gelu2":
        y = L.gelu_mlp(h, {k: v.astype(plan.compute_dtype) for k, v in lp["mlp"].items()})
        y = plan.constrain(y, ("batch", "seq", "embed_act"), cfg)
    else:
        y = L.glu_mlp(h, {k: v.astype(plan.compute_dtype) for k, v in lp["mlp"].items()},
                      activation=cfg.activation)
        y = plan.constrain(y, ("batch", "seq", "embed_act"), cfg)
    x = x + y

    cache = None
    if want_cache:
        cache = {"attn": attn_cache}
        if mamba_state is not None:
            cache["mamba"] = mamba_state
    return x, cache, aux


# ---------------------------------------------------------------------------
# block decode: x (B, d), per-layer cache
# ---------------------------------------------------------------------------

def block_decode(x, lp: Params, lc: Params, positions, cfg: ArchConfig,
                 plan: ShardPlan):
    """Returns (x, new_cache)."""
    if cfg.rwkv:
        x3 = x[:, None]
        y, tmix_state = S.rwkv_tmix_forward(lp["tmix"], _norm(x3, lp["norm1"], cfg),
                                            cfg, plan, lc["tmix"])
        x3 = x3 + y
        y, cmix_state = S.rwkv_cmix_forward(lp["cmix"], _norm(x3, lp["norm2"], cfg),
                                            cfg, plan, lc["cmix"])
        x3 = x3 + y
        return x3[:, 0], {"tmix": tmix_state, "cmix": cmix_state}

    h = _norm(x, lp["norm1"], cfg)
    if cfg.attn_kind == "mla":
        attn_out, attn_cache = A.mla_decode(lp["attn"], h, lc["attn"], positions,
                                            cfg, plan)
    else:
        attn_out, attn_cache = A.gqa_decode(lp["attn"], h, lc["attn"], positions,
                                            cfg, plan)
    if cfg.family == "hybrid":
        mamba_out, mamba_state = S.mamba_decode(lp["mamba"], h, lc["mamba"], cfg, plan)
        x = x + 0.5 * (attn_out + mamba_out)
    else:
        x = x + attn_out
        mamba_state = None

    h = _norm(x, lp["norm2"], cfg)
    if cfg.n_experts:
        y, _ = M.moe_ffn(lp["moe"], h[:, None], cfg, plan)
        y = y[:, 0]
    elif cfg.mlp_kind == "gelu2":
        y = L.gelu_mlp(h, {k: v.astype(plan.compute_dtype) for k, v in lp["mlp"].items()})
    else:
        y = L.glu_mlp(h, {k: v.astype(plan.compute_dtype) for k, v in lp["mlp"].items()},
                      activation=cfg.activation)
    x = x + y
    new_cache = {"attn": attn_cache}
    if mamba_state is not None:
        new_cache["mamba"] = mamba_state
    return x, new_cache


def block_prefill_paged(x, lp: Params, cache: Params, layer, starts,
                        lengths, block_tables, cfg: ArchConfig,
                        plan: ShardPlan):
    """Chunked-prefill variant of ``block_forward`` over the paged pool.

    x: (B, C, d) — one chunk of C prompt tokens per row starting at
    absolute position ``starts[b]``; cache is the whole layer-stacked
    pool and ``layer`` this block's index into it.  The attention
    scatter/gather goes through the per-sequence block table, so the
    chunk sees all previously written context (earlier chunks, shared
    prefix blocks) plus itself causally.
    """
    with jax.named_scope("attention"):
        h = _norm(x, lp["norm1"], cfg)
        attn_out, attn_cache = A.gqa_prefill_paged(
            lp["attn"], h, cache["attn"], layer, starts, lengths,
            block_tables, cfg, plan)
        x = x + attn_out
    with jax.named_scope("mlp"):
        h = _norm(x, lp["norm2"], cfg)
        if cfg.n_experts:
            y, _ = M.moe_ffn(lp["moe"], h, cfg, plan)
        elif cfg.mlp_kind == "gelu2":
            y = L.gelu_mlp(h, {k: v.astype(plan.compute_dtype) for k, v in lp["mlp"].items()})
            y = plan.constrain(y, ("batch", "seq", "embed_act"), cfg)
        else:
            y = L.glu_mlp(h, {k: v.astype(plan.compute_dtype) for k, v in lp["mlp"].items()},
                          activation=cfg.activation)
            y = plan.constrain(y, ("batch", "seq", "embed_act"), cfg)
        x = x + y
    return x, {"attn": attn_cache}


def block_decode_paged(x, lp: Params, cache: Params, layer, positions,
                       block_tables, cfg: ArchConfig, plan: ShardPlan):
    """Paged-pool variant of ``block_decode`` (plain-GQA families only).

    cache is the whole layer-stacked pool and ``layer`` this block's index
    into it; the attention write/gather goes through the per-sequence
    block table.
    """
    with jax.named_scope("attention"):
        h = _norm(x, lp["norm1"], cfg)
        attn_out, attn_cache = A.gqa_decode_paged(
            lp["attn"], h, cache["attn"], layer, positions, block_tables,
            cfg, plan)
        x = x + attn_out
    with jax.named_scope("mlp"):
        h = _norm(x, lp["norm2"], cfg)
        if cfg.n_experts:
            y, _ = M.moe_ffn(lp["moe"], h[:, None], cfg, plan)
            y = y[:, 0]
        elif cfg.mlp_kind == "gelu2":
            y = L.gelu_mlp(h, {k: v.astype(plan.compute_dtype) for k, v in lp["mlp"].items()})
        else:
            y = L.glu_mlp(h, {k: v.astype(plan.compute_dtype) for k, v in lp["mlp"].items()},
                          activation=cfg.activation)
        x = x + y
    return x, {"attn": attn_cache}


# ---------------------------------------------------------------------------
# vocab-sharded embedding / loss
# ---------------------------------------------------------------------------

def _embed_core(axis, table, ids):
    v_loc = table.shape[0]
    off = (jax.lax.axis_index(axis) * v_loc) if axis is not None else 0
    local = ids - off
    valid = (local >= 0) & (local < v_loc)
    e = jnp.take(table, jnp.clip(local, 0, v_loc - 1), axis=0)
    e = jnp.where(valid[..., None], e, 0)
    if axis is not None:
        e = jax.lax.psum(e, axis)
    return e


def embed_lookup(table, ids, cfg: ArchConfig, plan: ShardPlan):
    dp = plan.dp_axes if plan.dp_axes else None
    specs_in = (P("model", None), P(dp, None) if ids.ndim == 2 else P(dp))
    out = P(dp, None, None) if ids.ndim == 2 else P(dp, None)
    return shard_map_or_call(plan, _embed_core, specs_in, out,
                             table.astype(plan.compute_dtype), ids)


def _xent_core(axis, x, w_u, labels, *, vocab_size: int, n_chunks: int):
    """Chunked, checkpointed cross-entropy on a vocab shard.

    x: (T_loc, d); w_u: (d, V_loc); labels: (T_loc,). Returns summed loss.
    """
    t = x.shape[0]
    v_loc = w_u.shape[1]
    off = (jax.lax.axis_index(axis) * v_loc) if axis is not None else 0
    cols = off + jnp.arange(v_loc)
    col_valid = cols < vocab_size
    chunk = t // n_chunks

    @jax.checkpoint
    def chunk_loss(xc, lc):
        logits = jnp.einsum("td,dv->tv", xc, w_u,
                            preferred_element_type=jnp.float32)
        logits = jnp.where(col_valid[None, :], logits, NEG_INF)
        # stability shift: stop_gradient BEFORE pmax (pmax has no JVP rule;
        # the shift cancels in the gradient anyway)
        m = jax.lax.stop_gradient(jnp.max(logits, axis=-1))
        if axis is not None:
            m = jax.lax.pmax(m, axis)
        se = jnp.sum(jnp.exp(logits - m[:, None]), axis=-1)
        if axis is not None:
            se = jax.lax.psum(se, axis)
        lse = jnp.log(se) + m
        lab_local = lc - off
        lab_valid = (lab_local >= 0) & (lab_local < v_loc)
        lab_logit = jnp.take_along_axis(
            logits, jnp.clip(lab_local, 0, v_loc - 1)[:, None], axis=1)[:, 0]
        lab_logit = jnp.where(lab_valid, lab_logit, 0.0)
        if axis is not None:
            lab_logit = jax.lax.psum(lab_logit, axis)
        return jnp.sum(lse - lab_logit)

    def body(acc, inp):
        xc, lc = inp
        return acc + chunk_loss(xc, lc), None

    xs = x.reshape(n_chunks, chunk, -1)
    ls = labels.reshape(n_chunks, chunk)
    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xs, ls))
    return total


def sharded_xent(x, w_u, labels, cfg: ArchConfig, plan: ShardPlan,
                 *, n_chunks: int = 8):
    """Mean next-token loss; x: (B, S, d), labels: (B, S)."""
    B, Sq, d = x.shape
    t = B * Sq
    dp = plan.dp_axes if plan.dp_axes else None
    xf = x.reshape(t, d)
    lf = labels.reshape(t)
    while n_chunks > 1 and t // max(plan.dp, 1) % n_chunks:
        n_chunks //= 2

    def core(axis, xc, wc, lc):
        s = _xent_core(axis, xc, wc, lc, vocab_size=cfg.vocab_size,
                       n_chunks=n_chunks)
        if axis is not None and dp is not None:
            s = jax.lax.psum(s, dp)  # sum per-data-shard partials
        return s

    in_specs = (P(dp, None), P(None, "model"), P(dp))
    total = shard_map_or_call(plan, core, in_specs, P(), xf,
                              w_u.astype(plan.compute_dtype), lf)
    return total / t


# ---------------------------------------------------------------------------
# sampling / speculative-decode helpers
# ---------------------------------------------------------------------------

# Salts folded into per-lane PRNG keys so every sampling event at one
# sequence index draws from a distinct stream.  Keys fold the ABSOLUTE
# sequence index of the token being decided, which makes streams
# replay-stable across preemption and horizon re-splits.
SALT_SAMPLE = 0   # non-speculative draws (scan step / first prefill token)
SALT_DRAFT = 1    # drafter proposal draws
SALT_ACCEPT = 2   # rejection-sampling accept uniforms
SALT_BONUS = 3    # residual / bonus draws after the accepted prefix


def lane_keys(seeds):
    """Per-lane base PRNG keys from int32 seeds: (B,) -> (B, 2) uint32."""
    return jax.vmap(jax.random.PRNGKey)(seeds)


def event_keys(base_keys, seq_idx, salt):
    """``fold(fold(base, seq_idx), salt)`` per lane.

    base_keys: (B, 2); seq_idx: (B,) or (B, Q) absolute sequence index of
    the token the event decides.  Returns keys of seq_idx.shape + (2,).
    """
    seq_idx = jnp.asarray(seq_idx, jnp.uint32)
    salt_arr = jnp.full(seq_idx.shape, salt, jnp.uint32)
    if seq_idx.ndim == 2:
        keys = jnp.broadcast_to(base_keys[:, None, :],
                                seq_idx.shape + (base_keys.shape[-1],))
        fold = jax.vmap(jax.vmap(jax.random.fold_in))
    else:
        keys = base_keys
        fold = jax.vmap(jax.random.fold_in)
    return fold(fold(keys, seq_idx), salt_arr)


def uniform_lanes(keys):
    """One U[0, 1) draw per key; keys: (..., 2) raw PRNG key data."""
    flat = keys.reshape(-1, keys.shape[-1])
    u = jax.vmap(jax.random.uniform)(flat)
    return u.reshape(keys.shape[:-1])


def sampling_dist(logits, temps, top_ks):
    """Per-lane warped sampling distribution over the real vocab.

    logits: (..., V) already sliced to the real vocab; temps/top_ks:
    (...,).  Lanes with ``temps <= 0`` get a ONE-HOT argmax distribution,
    so the single rejection-sampling path degenerates bit-exactly to
    greedy acceptance; ``top_ks <= 0`` disables top-k truncation.
    Returns float32 probs.
    """
    logits = logits.astype(jnp.float32)
    V = logits.shape[-1]
    greedy = temps <= 0.0
    top = jnp.sort(logits, axis=-1)[..., ::-1]
    kth = jnp.take_along_axis(
        top, jnp.clip(top_ks - 1, 0, V - 1)[..., None], axis=-1)
    keep = (logits >= kth) | (top_ks <= 0)[..., None]
    t = jnp.where(greedy, 1.0, jnp.maximum(temps, 1e-6))[..., None]
    probs = jax.nn.softmax(jnp.where(keep, logits / t, NEG_INF), axis=-1)
    one_hot = jax.nn.one_hot(jnp.argmax(logits, axis=-1), V,
                             dtype=jnp.float32)
    return jnp.where(greedy[..., None], one_hot, probs)


def sample_from_dist(keys, probs, greedy):
    """One token per lane from ``probs``; greedy lanes take the argmax
    EXACTLY (categorical over a one-hot is only almost-surely the argmax).

    keys: (..., 2); probs: (..., V); greedy: (...,) bool -> (...) int32.
    """
    flat_k = keys.reshape(-1, keys.shape[-1])
    flat_p = probs.reshape(-1, probs.shape[-1])
    drawn = jax.vmap(
        lambda k, p: jax.random.categorical(k, jnp.log(p + 1e-30)))(
            flat_k, flat_p).reshape(probs.shape[:-1])
    return jnp.where(greedy, jnp.argmax(probs, axis=-1),
                     drawn).astype(jnp.int32)


def rejection_choose(base_keys, pos_eff, drafts, q_dists, p_dists, greedy,
                     n_valid):
    """Standard speculative rejection sampling, vectorised per lane.

    drafts: (B, K) proposal tokens with proposal dists ``q_dists``
    (B, K, V); ``p_dists``: (B, K+1, V) target dists for every slot.
    Draft j is accepted iff ``u_j * q_j(d_j) < p_j(d_j)`` with u_j ~
    U[0, 1) keyed on the token's absolute slot index (SALT_ACCEPT); the
    token at the first rejected slot is drawn from the renormalised
    residual ``max(p - q, 0)`` (SALT_BONUS), falling back to p when the
    residual vanishes (q == p); slot K has q = 0, so its "residual" is
    the plain bonus draw from p.  The emitted-token marginal at every
    consumed slot equals p exactly; greedy lanes (one-hot dists) accept
    iff the draft is the argmax and correct with the argmax.

    Returns ``(n_acc (B,) accepted-prefix length, capped at
    max(n_valid - 1, 0) so the bonus slot stays in range, cand_out
    (B, K+1) the would-be emitted token per slot)``.
    """
    B, spec_k = drafts.shape
    K1 = spec_k + 1
    V = p_dists.shape[-1]
    p_d = jnp.take_along_axis(p_dists[:, :spec_k],
                              drafts[..., None], axis=2)[..., 0]
    q_d = jnp.take_along_axis(q_dists, drafts[..., None], axis=2)[..., 0]
    slot_idx = pos_eff[:, None] + 1 + jnp.arange(spec_k)[None, :]
    u = uniform_lanes(event_keys(base_keys, slot_idx, SALT_ACCEPT))
    accept = u * q_d < p_d
    n_acc = jnp.cumprod(accept.astype(jnp.int32), axis=1).sum(axis=1)
    n_acc = jnp.minimum(n_acc, jnp.maximum(n_valid - 1, 0))

    q_ext = jnp.concatenate([q_dists, jnp.zeros((B, 1, V), jnp.float32)],
                            axis=1)
    resid = jnp.maximum(p_dists - q_ext, 0.0)
    rsum = resid.sum(axis=-1, keepdims=True)
    resid = jnp.where(rsum > 1e-9, resid / jnp.maximum(rsum, 1e-30),
                      p_dists)
    emit_idx = pos_eff[:, None] + 1 + jnp.arange(K1)[None, :]
    corr = sample_from_dist(event_keys(base_keys, emit_idx, SALT_BONUS),
                            resid, jnp.broadcast_to(greedy[:, None], (B, K1)))
    j = jnp.arange(K1)[None, :]
    d_ext = jnp.concatenate([drafts, jnp.zeros((B, 1), jnp.int32)], axis=1)
    cand_out = jnp.where(j < n_acc[:, None], d_ext, corr)
    return n_acc, cand_out


def ngram_propose(hist, positions, *, k: int, n: int = 2):
    """Prompt-lookup drafting (drafter-free speculation).

    Match the n-token suffix ending at ``positions`` against every earlier
    window of the sequence history and propose the k tokens that followed
    the MOST RECENT match; with no match (or too little history) repeat
    the current last token.  hist: (B, S) int32, ``hist[b, i]`` = i-th
    sequence token, valid through ``positions[b]``; returns (B, k) int32.
    """
    B, S = hist.shape
    last = jnp.take_along_axis(hist, jnp.clip(positions, 0, S - 1)[:, None],
                               axis=1)
    nw = S - n
    if nw <= 0:
        return jnp.broadcast_to(last, (B, k)).astype(jnp.int32)
    windows = jnp.stack([hist[:, j:nw + j] for j in range(n)], axis=-1)
    suf_idx = jnp.clip(positions[:, None] - (n - 1) + jnp.arange(n)[None, :],
                       0, S - 1)
    suffix = jnp.take_along_axis(hist, suf_idx, axis=1)
    starts = jnp.arange(nw)
    match = jnp.all(windows == suffix[:, None, :], axis=-1)
    # the window must END strictly before the suffix itself (start <=
    # pos - n), which also keeps its continuation a known token
    match &= starts[None, :] <= positions[:, None] - n
    best = jnp.max(jnp.where(match, starts[None, :], -1), axis=1)
    cont_idx = jnp.minimum(best[:, None] + n + jnp.arange(k)[None, :],
                           positions[:, None])
    drafts = jnp.take_along_axis(hist, jnp.clip(cont_idx, 0, S - 1), axis=1)
    return jnp.where(best[:, None] >= 0, drafts, last).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------

class Model:
    """One assigned architecture bound to a shard plan."""

    def __init__(self, cfg: ArchConfig, plan: ShardPlan):
        self.cfg = cfg
        self.plan = plan

    # ----- params -----
    def init(self, rng) -> Params:
        cfg, plan = self.cfg, self.plan
        dt = plan.param_dtype
        k_embed, k_layers, k_out = jax.random.split(rng, 3)
        v_pad = plan.v_pad(cfg)
        p: Params = {}
        if cfg.input_kind == "tokens":
            emb = L.embed_init(k_embed, (cfg.vocab_size, cfg.d_model), dtype=dt)
            p["embed"] = jnp.pad(emb, ((0, v_pad - cfg.vocab_size), (0, 0)))
        keys = jax.random.split(k_layers, cfg.num_layers)
        p["layers"] = jax.vmap(lambda k: init_layer(k, cfg, plan))(keys)
        p["final_norm"] = _norm_init(cfg, dt)
        if not (cfg.tie_embeddings and cfg.input_kind == "tokens"):
            w = L.dense_init(k_out, (cfg.d_model, cfg.vocab_size), dtype=dt)
            p["unembed"] = jnp.pad(w, ((0, 0), (0, v_pad - cfg.vocab_size)))
        return p

    def param_axes(self) -> Params:
        cfg, plan = self.cfg, self.plan
        ax: Params = {}
        if cfg.input_kind == "tokens":
            ax["embed"] = ("vocab", "embed")
        lax_ = layer_axes(cfg, plan)
        ax["layers"] = jax.tree.map(lambda a: ("layers",) + a, lax_,
                                    is_leaf=lambda x: isinstance(x, tuple))
        ax["final_norm"] = _norm_axes(cfg)
        if not (cfg.tie_embeddings and cfg.input_kind == "tokens"):
            ax["unembed"] = ("embed", "vocab")
        return ax

    def param_shardings(self):
        return self.plan.tree_shardings(self.param_axes(), self.cfg)

    def init_placed(self, seed: int) -> Params:
        """``init(PRNGKey(seed))`` compiled with the plan's parameter
        shardings as its output layout, so each leaf is drawn, cast and
        stored where it lives: no eager f32 draw of the stacked layers and
        no copy through device 0 (the key too is made inside the program
        from the host seed).  Bitwise equal to the eager ``init``."""
        return _born_on_device(
            lambda s: self.init(jax.random.PRNGKey(s)),
            self.param_shardings(), np.uint32(seed))

    def _unembed_w(self, params: Params):
        if self.cfg.tie_embeddings and self.cfg.input_kind == "tokens":
            return params["embed"].T
        return params["unembed"]

    # ----- input embedding -----
    def _embed_inputs(self, params, inputs):
        cfg, plan = self.cfg, self.plan
        if cfg.input_kind == "embeds":
            x = inputs.astype(plan.compute_dtype)
        else:
            x = embed_lookup(params["embed"], inputs, cfg, plan)
        if cfg.embed_scale:
            x = x * jnp.asarray(math.sqrt(cfg.d_model), plan.compute_dtype)
        return plan.constrain(x, ("batch", "seq", "embed_act"), cfg)

    # ----- forward (train/prefill trunk) -----
    def _trunk(self, params, x, positions, *, want_cache: bool):
        cfg, plan = self.cfg, self.plan

        def body(carry, lp):
            x, aux = carry
            x, cache, aux_l = block_forward(x, lp, positions, cfg, plan,
                                            want_cache=want_cache)
            return (x, aux + aux_l), cache

        if plan.remat == "full":
            body = jax.checkpoint(body)
        (x, aux), caches = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                        params["layers"])
        x = _norm(x, params["final_norm"], cfg)
        return x, caches, aux

    def loss(self, params, inputs, labels):
        cfg, plan = self.cfg, self.plan
        x = self._embed_inputs(params, inputs)
        Sq = x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(Sq)[None, :], x.shape[:2])
        x, _, aux = self._trunk(params, x, positions, want_cache=False)
        ce = sharded_xent(x, self._unembed_w(params), labels, cfg, plan)
        return ce + MOE_AUX_WEIGHT * aux

    def logits(self, params, inputs):
        """Full-sequence logits (small inputs / tests only)."""
        cfg, plan = self.cfg, self.plan
        x = self._embed_inputs(params, inputs)
        Sq = x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(Sq)[None, :], x.shape[:2])
        x, _, _ = self._trunk(params, x, positions, want_cache=False)
        return self._head(params, x)

    def _head(self, params, x):
        cfg, plan = self.cfg, self.plan
        w = self._unembed_w(params).astype(plan.compute_dtype)
        logits = jnp.einsum("...d,dv->...v", x, w)
        cols = jnp.arange(logits.shape[-1])
        return jnp.where(cols < cfg.vocab_size, logits, NEG_INF)

    # ----- serving -----
    @property
    def supports_paged(self) -> bool:
        """Paged KV serving needs plain GQA attention (MLA latent, SWA ring
        and mamba/rwkv recurrent state keep the slot-based pool)."""
        cfg = self.cfg
        return (not cfg.rwkv and cfg.family != "hybrid"
                and cfg.attn_kind == "gqa" and cfg.causal
                and cfg.input_kind == "tokens")

    def paged_decode_path(self, pool_dtype=jnp.bfloat16) -> str:
        """``"pallas"`` or ``"xla"``: the attention ``decode_step_paged``
        is built with over a pool of ``pool_dtype`` on this backend."""
        return A.paged_decode_path(self.cfg, self.plan, pool_dtype)

    def prefill(self, params, inputs):
        """Returns (last-token logits (B, V_pad), cache stacked over layers)."""
        cfg, plan = self.cfg, self.plan
        x = self._embed_inputs(params, inputs)
        Sq = x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(Sq)[None, :], x.shape[:2])
        x, caches, _ = self._trunk(params, x, positions, want_cache=True)
        logits = self._head(params, x[:, -1])
        return logits, caches

    def prefill_ragged(self, params, inputs, lengths):
        """Batched prefill over right-padded prompts of one bucket shape.

        inputs: (B, S_bucket) token ids, row b valid for its first
        lengths[b] tokens; returns logits at each row's true last token
        (B, V_pad) + the stacked cache.  Padded tail positions attend only
        causally so rows' valid prefixes are exact; their cache entries are
        garbage past lengths[b] and masked downstream by position.
        """
        cfg, plan = self.cfg, self.plan
        x = self._embed_inputs(params, inputs)
        Sq = x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(Sq)[None, :], x.shape[:2])
        x, caches, _ = self._trunk(params, x, positions, want_cache=True)
        last = jnp.take_along_axis(
            x, (lengths - 1)[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        return self._head(params, last), caches

    def decode_step(self, params, cache, tokens, positions):
        """One token per sequence. tokens: (B,), positions: (B,)."""
        cfg, plan = self.cfg, self.plan
        if cfg.input_kind == "embeds":
            raise ValueError(f"{cfg.name} is encoder-only: no decode step")
        x = embed_lookup(params["embed"], tokens, cfg, plan)
        if cfg.embed_scale:
            x = x * jnp.asarray(math.sqrt(cfg.d_model), plan.compute_dtype)

        def body(x, inp):
            lp, lc = inp
            x, new_lc = block_decode(x, lp, lc, positions, cfg, plan)
            return x, new_lc

        x, new_cache = jax.lax.scan(body, x, (params["layers"], cache))
        x = _norm(x, params["final_norm"], cfg)
        logits = self._head(params, x)
        return logits, new_cache

    def decode_step_paged(self, params, cache, tokens, positions,
                          block_tables):
        """One token per lane over the paged pool.  tokens/positions: (B,);
        block_tables: (B, T) physical block ids per lane.

        The layer scan carries the whole stacked pool and hands each layer
        its index, not a slice: every layer scatters into and gathers from
        the one carried buffer, which the donated argument then aliases.
        (Scanning over the pool as ``xs``/``ys`` instead slices each layer
        out and restacks it into a new buffer, copying the pool per call.)
        """
        cfg, plan = self.cfg, self.plan
        x = embed_lookup(params["embed"], tokens, cfg, plan)
        if cfg.embed_scale:
            x = x * jnp.asarray(math.sqrt(cfg.d_model), plan.compute_dtype)

        def body(carry, inp):
            x, cache = carry
            lp, layer = inp
            return block_decode_paged(x, lp, cache, layer, positions,
                                      block_tables, cfg, plan), None

        (x, new_cache), _ = jax.lax.scan(
            body, (x, cache), (params["layers"], jnp.arange(cfg.num_layers)))
        with jax.named_scope("head"):
            x = _norm(x, params["final_norm"], cfg)
            logits = self._head(params, x)
        return logits, new_cache

    def decode_multi_paged(self, params, cache, tokens, positions,
                           block_tables, active, budgets, eos_ids,
                           num_steps: int, max_len: int,
                           temps=None, top_ks=None, seeds=None):
        """Fused multi-step greedy decode over the paged pool.

        Runs ``num_steps`` decode iterations inside one jitted
        ``lax.scan`` horizon — embed, trunk, greedy sampling (argmax), KV
        append, position advance and finished-flag computation all stay on
        device; the host only reads ``(out_tokens, emitted)`` when the
        horizon drains (one sync per N steps instead of per step).

        tokens/positions: (B,) per-lane state at entry; active: (B,) bool
        decode mask (parked / still-prefilling lanes False); budgets: (B,)
        tokens each lane may still produce; eos_ids: (B,) int32 (-1 = no
        eos).  Lanes that finish mid-horizon are steered to the parking
        block (position 0, table row 0) so they never touch live blocks.
        Blocks for every position a lane can reach within the horizon must
        be allocated before entry (``PagedCachePool.ensure_append_blocks``
        with the same horizon).

        With ``temps``/``top_ks``/``seeds`` (all (B,)) set, sampling
        replaces argmax per lane: each step's token is drawn from the
        temperature/top-k-warped distribution with a key folded from the
        lane seed and the token's absolute sequence index (replay-stable);
        lanes with ``temps[b] <= 0`` still take the exact argmax.  The
        default ``None`` builds the identical graph as before.

        Returns ``(out_tokens (N, B), emitted (N, B) bool — token [i, b]
        valid iff emitted, last_logits (B, V_pad), (tokens, positions,
        active, budgets) final state, cache)``.
        """
        cfg, plan = self.cfg, self.plan
        v_pad = params["embed"].shape[0] if "embed" in params else \
            self._unembed_w(params).shape[1]
        logits0 = jnp.zeros((tokens.shape[0], v_pad), plan.compute_dtype)
        base_keys = lane_keys(seeds) if temps is not None else None

        def one_step(carry, _):
            # dead tail steps (every lane drained) skip the model at
            # runtime, so the engine can always launch `horizon` steps —
            # one jit variant — without paying for the unused tail
            with jax.named_scope("horizon_cond"):
                return jax.lax.cond(jnp.any(carry[3]), live_step,
                                    parked_step, carry)

        def parked_step(carry):
            B = carry[1].shape[0]
            return carry, (jnp.zeros((B,), jnp.int32),
                           jnp.zeros((B,), bool))

        def live_step(carry):
            cache, tokens, positions, active, budgets, _ = carry
            pos_eff = jnp.where(active, positions, 0)
            bt_eff = jnp.where(active[:, None], block_tables, 0)
            logits, cache = self.decode_step_paged(
                params, cache, tokens, pos_eff, bt_eff)
            with jax.named_scope("sample"):
                if temps is None:
                    nxt = jnp.argmax(logits[:, : cfg.vocab_size],
                                     axis=-1).astype(jnp.int32)
                else:
                    dist = sampling_dist(logits[:, : cfg.vocab_size], temps,
                                         top_ks)
                    keys = event_keys(base_keys, positions + 1, SALT_SAMPLE)
                    nxt = sample_from_dist(keys, dist, temps <= 0.0)
                emitted = active
                budgets = budgets - emitted.astype(jnp.int32)
                done = emitted & ((budgets <= 0) | (nxt == eos_ids)
                                  | (positions + 1 >= max_len))
                tokens = jnp.where(emitted, nxt, tokens)
                positions = positions + emitted.astype(jnp.int32)
                active = active & ~done
            carry = (cache, tokens, positions, active, budgets,
                     logits.astype(logits0.dtype))
            return carry, (nxt, emitted)

        carry0 = (cache, tokens, positions, active, budgets, logits0)
        (cache, tokens, positions, active, budgets, last_logits), \
            (out_tokens, emitted) = jax.lax.scan(
                one_step, carry0, None, length=num_steps)
        return (out_tokens, emitted, last_logits,
                (tokens, positions, active, budgets), cache)

    def decode_verify_paged(self, params, cache, tokens, positions,
                            block_tables, n_valid):
        """Target forward over Q candidate tokens per lane in ONE paged
        pass (speculative verify): the current input token plus K drafts,
        token i at absolute position ``positions[b] + i``.

        The Q candidates fold into the lane axis — candidate i becomes a
        pseudo-lane at position ``positions[b] + i`` sharing lane b's
        block-table row — and run through ``decode_step_paged`` verbatim.
        All Q keys scatter into the pool before the (position-masked)
        attention gather, so candidate i sees candidates < i causally;
        because every op is the exact decode-step graph (just a larger
        batch) the per-position logits are BITWISE equal to sequential
        single-token decode — greedy speculative streams match plain
        decode exactly, not just to tolerance.  (The standalone
        multi-query kernel ``ops.paged_verify_attention`` computes the
        same attention in one prefill-style pass; it is kept as the
        general-purpose form but differs from the decode kernel by bf16
        ulps, which is why the model path folds instead.)

        Tokens at index >= ``n_valid[b]`` are routed to the parking block
        (their logits are garbage — callers must ignore them).  Returns
        (logits (B, Q, V_pad) — ``logits[b, i]`` predicts position
        ``positions[b] + i + 1`` — and the cache).
        """
        B, Q = tokens.shape
        j = jnp.arange(Q, dtype=jnp.int32)
        valid = j[None, :] < n_valid[:, None]                        # (B, Q)
        pos = jnp.where(valid, positions[:, None] + j[None, :], 0)
        tab = jnp.where(valid[..., None], block_tables[:, None, :], 0)
        logits, new_cache = self.decode_step_paged(
            params, cache, tokens.reshape(B * Q), pos.reshape(B * Q),
            tab.reshape(B * Q, tab.shape[-1]))
        return logits.reshape(B, Q, logits.shape[-1]), new_cache

    def decode_spec_paged(self, drafter, params, cache, d_params, d_cache,
                          hist, tokens, positions, block_tables, active,
                          budgets, eos_ids, temps, top_ks, seeds, *,
                          num_steps: int, spec_k: int, max_len: int,
                          ngram: int = 2):
        """Fused speculative decode over the paged pool.

        Each of ``num_steps`` rounds proposes ``spec_k`` draft tokens per
        lane — from ``drafter`` (a paired smaller Model whose paged cache
        ``d_cache`` shares this pool's block tables) or, when ``drafter``
        is None, by n-gram prompt-lookup over the sequence history
        ``hist`` — then verifies all spec_k + 1 positions in ONE target
        pass (``decode_verify_paged``) and advances each lane by its
        accepted prefix plus one corrected/bonus token via standard
        rejection sampling (accept draft d iff ``u * q(d) < p(d)``;
        residual ``max(p - q, 0)`` renormalised on rejection; bonus from
        p on full acceptance).  Greedy lanes (``temps <= 0``) use one-hot
        distributions, so acceptance degenerates to exact argmax
        agreement and the emitted stream is bit-identical to
        ``decode_multi_paged``.

        Rejected tails need no KV rollback: position p is always the
        next-write slot, so a stale slot is rewritten the moment that
        position is consumed again as an input token; positions, budgets
        and the history only advance by emitted tokens.  Blocks for the
        worst case (spec_k + 1 writes per round) must be pre-allocated
        (``ensure_append_blocks`` with the padded horizon).

        Returns ``(out_tokens (N, B, spec_k+1), emitted (N, B, spec_k+1)
        bool, n_acc (N, B) accepted drafts per round, (tokens, positions,
        active, budgets) final state, cache, d_cache, hist)``.
        """
        cfg, plan = self.cfg, self.plan
        B = tokens.shape[0]
        K1 = spec_k + 1
        base_keys = lane_keys(seeds)
        greedy = temps <= 0.0
        hist_w = hist.shape[1]

        def one_round(carry, _):
            # once every lane has drained its budget the remaining rounds
            # of the fixed-length scan skip the model entirely (lax.cond
            # executes one branch at runtime), so the engine can launch a
            # constant number of rounds — one jit variant, no retraces —
            # without paying for dead tail rounds
            return jax.lax.cond(jnp.any(carry[5]), live_round, parked_round,
                                carry)

        def parked_round(carry):
            return carry, (jnp.zeros((B, K1), jnp.int32),
                           jnp.zeros((B, K1), bool),
                           jnp.zeros((B,), jnp.int32))

        def live_round(carry):
            cache, d_cache, hist, tokens, positions, active, budgets = carry
            pos_eff = jnp.where(active, positions, 0)
            bt_eff = jnp.where(active[:, None], block_tables, 0)

            # ---- propose spec_k draft tokens + their proposal dists q
            if drafter is None:
                drafts = ngram_propose(hist, pos_eff, k=spec_k, n=ngram)
                q_dists = jax.nn.one_hot(drafts, cfg.vocab_size,
                                         dtype=jnp.float32)
                new_d_cache = d_cache
            else:
                def d_step(dc, j):
                    d_cache, cur = dc
                    p_j = jnp.minimum(pos_eff + j, max_len - 1)
                    lg, d_cache = drafter.decode_step_paged(
                        d_params, d_cache, cur, p_j, bt_eff)
                    qd = sampling_dist(lg[:, : cfg.vocab_size], temps,
                                       top_ks)
                    keys = event_keys(base_keys, pos_eff + j + 1,
                                      SALT_DRAFT)
                    nxt = sample_from_dist(keys, qd, greedy)
                    return (d_cache, nxt), (nxt, qd)

                (new_d_cache, d_last), (drafts, q_dists) = jax.lax.scan(
                    d_step, (d_cache, tokens), jnp.arange(spec_k))
                # backfill d_K so the drafter cache has no hole next round
                p_b = jnp.minimum(pos_eff + spec_k, max_len - 1)
                _, new_d_cache = drafter.decode_step_paged(
                    d_params, new_d_cache, d_last, p_b, bt_eff)
                drafts = drafts.transpose(1, 0)
                q_dists = q_dists.transpose(1, 0, 2)

            # ---- verify all spec_k + 1 positions in one target pass
            cand_in = jnp.concatenate([tokens[:, None], drafts], axis=1)
            n_valid = jnp.where(active,
                                jnp.clip(max_len - pos_eff, 0, K1), 0)
            logits, cache = self.decode_verify_paged(
                params, cache, cand_in, pos_eff, bt_eff, n_valid)
            p_dists = sampling_dist(
                logits[..., : cfg.vocab_size],
                jnp.broadcast_to(temps[:, None], (B, K1)),
                jnp.broadcast_to(top_ks[:, None], (B, K1)))

            # ---- rejection-sample the accepted prefix + correction/bonus
            n_acc, cand_out = rejection_choose(
                base_keys, pos_eff, drafts, q_dists, p_dists, greedy,
                n_valid)
            j = jnp.arange(K1)[None, :]

            # ---- stop flags, replicating decode_multi_paged's per-step
            # semantics: token slot j is this round's (j+1)-th emission
            stop = ((budgets[:, None] - (j + 1) <= 0)
                    | (cand_out == eos_ids[:, None])
                    | (pos_eff[:, None] + j + 1 >= max_len))
            stopped_before = (jnp.cumsum(stop.astype(jnp.int32), axis=1)
                              - stop.astype(jnp.int32)) > 0
            emit = active[:, None] & (j <= n_acc[:, None]) & ~stopped_before
            done = jnp.any(emit & stop, axis=1)
            m = emit.sum(axis=1)

            # ---- advance lane state by the emitted run
            last_tok = jnp.take_along_axis(
                cand_out, jnp.maximum(m - 1, 0)[:, None], axis=1)[:, 0]
            tokens = jnp.where(m > 0, last_tok, tokens)
            positions = positions + m
            budgets = budgets - m
            active = active & ~done
            upd_idx = jnp.where(emit, pos_eff[:, None] + j + 1, hist_w)
            hist = jax.vmap(
                lambda h, i, v: h.at[i].set(v, mode="drop"))(
                    hist, upd_idx, cand_out)

            carry = (cache, new_d_cache, hist, tokens, positions, active,
                     budgets)
            return carry, (cand_out, emit, n_acc)

        carry0 = (cache, d_cache, hist, tokens, positions, active, budgets)
        (cache, d_cache, hist, tokens, positions, active, budgets), \
            (out_tokens, emitted, n_accs) = jax.lax.scan(
                one_round, carry0, None, length=num_steps)
        return (out_tokens, emitted, n_accs,
                (tokens, positions, active, budgets), cache, d_cache, hist)

    def prefill_chunk_paged(self, params, cache, tokens, starts, lengths,
                            block_tables):
        """One chunked-prefill step over the paged pool.

        tokens: (B, C) — the next C context tokens per prefilling row, row
        b valid for its first lengths[b] tokens; starts: (B,) absolute
        position of tokens[:, 0] (tokens before ``starts`` — earlier
        chunks or prefix-shared blocks — must already sit in the pool).
        Returns (logits at each row's last valid chunk token (B, V_pad),
        cache).  Rows admitted mid-way through a longer prompt simply call
        this again with ``starts`` advanced; decode TBT is never blocked
        for longer than one chunk.  The layer scan carries the stacked pool
        as ``decode_step_paged`` does.
        """
        cfg, plan = self.cfg, self.plan
        x = self._embed_inputs(params, tokens)

        def body(carry, inp):
            x, cache = carry
            lp, layer = inp
            return block_prefill_paged(x, lp, cache, layer, starts, lengths,
                                       block_tables, cfg, plan), None

        (x, new_cache), _ = jax.lax.scan(
            body, (x, cache), (params["layers"], jnp.arange(cfg.num_layers)))
        with jax.named_scope("head"):
            x = _norm(x, params["final_norm"], cfg)
            last = jnp.take_along_axis(
                x, jnp.maximum(lengths - 1, 0)[:, None, None].astype(
                    jnp.int32), axis=1)[:, 0]
            return self._head(params, last), new_cache

    # ----- grads -----
    def canonicalize_grads(self, grads: Params) -> Params:
        """Zero pad-head grads / tie padded-kv-copy grads so the padded model
        stays exactly equivalent to the published architecture."""
        cfg, plan = self.cfg, self.plan
        H, h_pad = cfg.n_heads, plan.h_pad(cfg)
        lay = dict(grads["layers"])

        def zero_tail(w, axis):
            idx = [slice(None)] * w.ndim
            idx[axis] = slice(H, None)
            return w.at[tuple(idx)].set(0)

        if cfg.rwkv and h_pad != H:
            t = dict(lay["tmix"])
            for name in ("w_r", "w_k", "w_v", "w_g"):
                t[name] = zero_tail(t[name], 2)
            t["w_o"] = zero_tail(t["w_o"], 1)
            t["decay_b"] = zero_tail(t["decay_b"], 2)
            for name in ("decay_base", "u_bonus", "ln_x"):
                t[name] = zero_tail(t[name], 1)
            lay["tmix"] = t
        elif "attn" in lay:
            if cfg.attn_kind == "mla":
                if h_pad != H:
                    a = dict(lay["attn"])
                    for name in ("w_uq", "w_uk", "w_uv"):
                        a[name] = zero_tail(a[name], 2)
                    a["w_o"] = zero_tail(a["w_o"], 1)
                    lay["attn"] = a
            else:
                lay["attn"] = A.canonicalize_gqa_grads(lay["attn"], cfg, plan)
        out = dict(grads)
        out["layers"] = lay
        return out

    # ----- cache -----
    def init_cache(self, batch: int, seq_len: int, dtype=jnp.bfloat16):
        cfg, plan = self.cfg, self.plan
        single, _ = self._cache_template(batch, seq_len, dtype)
        return jax.tree.map(
            lambda a: jnp.zeros((cfg.num_layers,) + a.shape, a.dtype), single)

    def init_paged_cache(self, n_blocks: int, block_size: int,
                         dtype=jnp.bfloat16):
        """Layer-stacked paged KV pool: leaves (L, n_blocks, bs, K, hd).
        Under a sharded plan each leaf is laid out over the mesh (the
        ``kv_blocks`` axis stripes physical block ids across ranks)."""
        cfg, plan = self.cfg, self.plan

        def zeros():
            c, _ = A.init_paged_attn_cache(cfg, plan, n_blocks, block_size,
                                           dtype)
            return jax.tree.map(
                lambda a: jnp.zeros((cfg.num_layers,) + a.shape, a.dtype),
                {"attn": c})

        # zeroed in place on each rank's stripe (or the default device)
        return _born_on_device(zeros, self.paged_cache_shardings())

    def paged_cache_axes(self):
        """Logical axes of the layer-stacked paged pool leaves."""
        _, ax = A.init_paged_attn_cache(self.cfg, self.plan,
                                        max(self.plan.tp, 1), 1, jnp.bfloat16)
        return jax.tree.map(lambda a: ("layers",) + a, {"attn": ax},
                            is_leaf=lambda x: isinstance(x, tuple))

    def paged_cache_shardings(self):
        if self.plan.mesh is None:
            return None
        return self.plan.tree_shardings(self.paged_cache_axes(), self.cfg)

    def cache_axes(self):
        _, ax = self._cache_template(1, 8, jnp.bfloat16)
        return jax.tree.map(lambda a: ("layers",) + a, ax,
                            is_leaf=lambda x: isinstance(x, tuple))

    def cache_shardings(self):
        return self.plan.tree_shardings(self.cache_axes(), self.cfg)

    def _cache_template(self, batch, seq_len, dtype):
        cfg, plan = self.cfg, self.plan
        if cfg.rwkv:
            return S.init_rwkv_state(cfg, plan, batch, dtype)
        c, ax = A.init_attn_cache(cfg, plan, batch, seq_len, dtype)
        cache = {"attn": c}
        axes = {"attn": ax}
        if cfg.family == "hybrid":
            ms, max_ = S.init_mamba_state(cfg, plan, batch, dtype)
            cache["mamba"] = ms
            axes["mamba"] = max_
        return cache, axes


def _born_on_device(fn, shardings, *args):
    """Run ``fn`` compiled with ``shardings`` as its output layout (the
    default device when None), so its results are created where they live
    instead of built on one device and copied."""
    kw = {} if shardings is None else {"out_shardings": shardings}
    return jax.jit(fn, **kw)(*args)


def build_model(name_or_cfg, plan: ShardPlan) -> Model:
    from repro.configs import get_config
    cfg = name_or_cfg if isinstance(name_or_cfg, ArchConfig) else get_config(name_or_cfg)
    return Model(cfg, plan)
