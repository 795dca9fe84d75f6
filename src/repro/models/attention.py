"""Attention for every assigned family: GQA (+qk_norm), MLA, SWA, encoder.

Train/prefill use an XLA-native flash-equivalent: a statically unrolled
q-chunk loop that only materialises (chunk x klen) score blocks, giving
exact causal FLOPs and bounded VMEM-sized temporaries (this mirrors what the
Pallas ``flash_attention`` kernel does on real TPUs; see kernels/).

Decode uses sequence-sharded flash-decode: the KV cache is sharded along
the sequence dim over the ``model`` axis, every device computes a partial
softmax over its KV slice for *all* heads, and partials are combined with
the LSE trick via psum (collective bytes per layer: O(B*H*hd), tiny).
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.kernels.paged_decode_attention import \
    paged_decode_attention as paged_decode_kernel
from repro.models import layers as L
from repro.models.sharding import ShardPlan, shard_map_or_call

Params = dict[str, Any]
NEG_INF = -1e30


# ---------------------------------------------------------------------------
# init + logical axes
# ---------------------------------------------------------------------------

def init_gqa(key, cfg: ArchConfig, plan: ShardPlan) -> Params:
    d, hd = cfg.d_model, cfg.head_dim
    h_pad, k = plan.h_pad(cfg), cfg.n_kv_heads
    dt = plan.param_dtype
    ks = jax.random.split(key, 4)
    w_q = L.dense_init(ks[0], (d, cfg.n_heads, hd), dtype=dt)
    w_q = jnp.pad(w_q, ((0, 0), (0, h_pad - cfg.n_heads), (0, 0)))
    w_o = L.dense_init(ks[3], (cfg.n_heads, hd, d), in_axis=1, dtype=dt)
    w_o = jnp.pad(w_o, ((0, h_pad - cfg.n_heads), (0, 0), (0, 0)))
    w_k = L.dense_init(ks[1], (d, k, hd), dtype=dt)
    w_v = L.dense_init(ks[2], (d, k, hd), dtype=dt)
    if plan.kv_padded(cfg):
        copies = plan.k_pad(cfg) // k  # slot j <-> real head j // copies
        w_k = jnp.repeat(w_k, copies, axis=1)
        w_v = jnp.repeat(w_v, copies, axis=1)
    p = {"w_q": w_q, "w_k": w_k, "w_v": w_v, "w_o": w_o}
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dt)
        p["k_norm"] = jnp.ones((hd,), dt)
    return p


def canonicalize_gqa_grads(g: Params, cfg: ArchConfig, plan: ShardPlan) -> Params:
    """Keep padded params exactly equivalent to the unpadded model:
    zero pad-q-head grads; average (tie) padded kv-copy grads.

    Grad arrays are layer-stacked: leading 'layers' dim.
    """
    g = dict(g)
    H, h_pad = cfg.n_heads, plan.h_pad(cfg)
    if h_pad != H:
        g["w_q"] = g["w_q"].at[:, :, H:, :].set(0)
        g["w_o"] = g["w_o"].at[:, H:, :, :].set(0)
    if plan.kv_padded(cfg):
        k = cfg.n_kv_heads
        copies = plan.k_pad(cfg) // k
        for name in ("w_k", "w_v"):
            w = g[name]  # (L, d, K_pad, hd); slot j <-> real j // copies
            shp = w.shape
            w = w.reshape(shp[0], shp[1], k, copies, shp[3])
            w = jnp.broadcast_to(w.mean(axis=3, keepdims=True), w.shape)
            g[name] = w.reshape(shp)
    return g


def gqa_axes(cfg: ArchConfig, plan: ShardPlan) -> Params:
    ax = {
        "w_q": ("embed", "heads", "qk_dim"),
        "w_k": ("embed", "kv_heads", "qk_dim"),
        "w_v": ("embed", "kv_heads", "qk_dim"),
        "w_o": ("heads", "qk_dim", "embed"),
    }
    if cfg.qk_norm:
        ax["q_norm"] = ("qk_dim",)
        ax["k_norm"] = ("qk_dim",)
    return ax


def init_mla(key, cfg: ArchConfig, plan: ShardPlan) -> Params:
    d = cfg.d_model
    h_pad = plan.h_pad(cfg)
    rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    nope, ropeD, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    dt = plan.param_dtype
    ks = jax.random.split(key, 7)

    def padh(w, axis):
        pad = [(0, 0)] * w.ndim
        pad[axis] = (0, h_pad - cfg.n_heads)
        return jnp.pad(w, pad)

    return {
        "w_dq": L.dense_init(ks[0], (d, rq), dtype=dt),
        "w_uq": padh(L.dense_init(ks[1], (rq, cfg.n_heads, nope + ropeD), dtype=dt), 1),
        "w_dkv": L.dense_init(ks[2], (d, rkv), dtype=dt),
        "w_kr": L.dense_init(ks[3], (d, ropeD), dtype=dt),
        "w_uk": padh(L.dense_init(ks[4], (rkv, cfg.n_heads, nope), dtype=dt), 1),
        "w_uv": padh(L.dense_init(ks[5], (rkv, cfg.n_heads, vd), dtype=dt), 1),
        "w_o": padh(L.dense_init(ks[6], (cfg.n_heads, vd, d), in_axis=1, dtype=dt), 0),
        "q_norm": jnp.ones((rq,), dt),
        "kv_norm": jnp.ones((rkv,), dt),
    }


def mla_axes(cfg: ArchConfig, plan: ShardPlan) -> Params:
    return {
        "w_dq": ("embed", "lora"),
        "w_uq": ("lora", "heads", "qk_dim"),
        "w_dkv": ("embed", "lora"),
        "w_kr": ("embed", "qk_dim"),
        "w_uk": ("lora", "heads", "qk_dim"),
        "w_uv": ("lora", "heads", "v_dim"),
        "w_o": ("heads", "v_dim", "embed"),
        "q_norm": ("lora",),
        "kv_norm": ("lora",),
    }


# ---------------------------------------------------------------------------
# shared helpers
# ---------------------------------------------------------------------------

def kv_index(cfg: ArchConfig, h_pad: int, k_pad: int | None = None) -> jnp.ndarray:
    """Constant q-head -> kv-slot map; pad heads point at slot 0.

    With padded kv (k_pad == tp > n_kv) the map is h * k_pad // n_heads,
    which is monotone and shard-aligned (slot j holds a copy of real head
    j * n_kv // k_pad; see DESIGN.md §3).
    """
    k = k_pad or cfg.n_kv_heads
    if k == cfg.n_kv_heads:
        idx = [h * cfg.n_kv_heads // cfg.n_heads for h in range(cfg.n_heads)]
    else:
        idx = [h * k // cfg.n_heads for h in range(cfg.n_heads)]
    idx += [0] * (h_pad - cfg.n_heads)
    return jnp.asarray(idx, jnp.int32)


def _expand_kv(k: jax.Array, kv_idx: jax.Array, n_heads: int) -> jax.Array:
    """(..., K, hd) -> (..., H, hd) via constant-index gather (GQA)."""
    if k.shape[-2] == n_heads:
        return k
    return jnp.take(k, kv_idx, axis=-2)


def _pick_chunk(b_loc: int, h_loc: int, s: int, budget: int) -> int:
    """Largest power-of-two chunk whose fp32 score block fits the budget."""
    c = 1024
    while c > 128 and b_loc * h_loc * c * s * 4 > budget:
        c //= 2
    while s % c:
        c //= 2
    return max(c, 1)


def _attn_block(q, k, v, mask, scale):
    """One (chunk x klen) attention block; fp32 softmax."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


# ---------------------------------------------------------------------------
# train / prefill attention cores
# ---------------------------------------------------------------------------

def causal_attention(q, k, v, *, scale: float, plan: ShardPlan,
                     cfg: ArchConfig) -> jax.Array:
    """Flash-style causal attention: nested scans over uniform (cq x ck)
    tiles with online softmax.

    Uniform tile shapes let XLA reuse one score buffer across every scan
    step (the unrolled growing-klen variant kept O(S/c) distinct buffers
    live and blew past HBM at 32k).  Above-diagonal tiles are masked, not
    skipped — the XLA path pays ~2x causal attention FLOPs; the Pallas
    ``flash_attention`` kernel (kernels/) skips them with @pl.when on TPU.
    """
    B, S, H, D = q.shape
    Dv = v.shape[-1]  # MLA: value head dim differs from the qk dim
    if S <= 1024:
        mask = (jnp.arange(S)[None, :] <= jnp.arange(S)[:, None])[None, None]
        return _attn_block(q, k, v, mask, scale)
    if plan.attn_exact_causal:
        return _causal_pair_scan(q, k, v, scale=scale, c=plan.attn_cq)
    cq = ck = plan.attn_cq
    while S % cq:
        cq //= 2
    ck = cq
    nq, nk = S // cq, S // ck
    qs = q.reshape(B, nq, cq, H, D).transpose(1, 0, 2, 3, 4)
    ks = k.reshape(B, nk, ck, H, D).transpose(1, 0, 2, 3, 4)
    vs = v.reshape(B, nk, ck, H, Dv).transpose(1, 0, 2, 3, 4)

    def q_step(_, qi_i):
        qi, i = qi_i  # (B, cq, H, D)

        def k_step(carry, kv_j):
            m, l, acc = carry
            kj, vj, j = kv_j
            s = jnp.einsum("bqhd,bkhd->bhqk", qi, kj,
                           preferred_element_type=jnp.float32) * scale
            qpos = i * cq + jnp.arange(cq)
            kpos = j * ck + jnp.arange(ck)
            mask = (kpos[None, :] <= qpos[:, None])[None, None]
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p.astype(vj.dtype), vj,
                preferred_element_type=jnp.float32)
            return (m_new, l, acc), None

        m0 = jnp.full((B, H, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, cq), jnp.float32)
        a0 = jnp.zeros((B, H, cq, Dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            k_step, (m0, l0, a0), (ks, vs, jnp.arange(nk)))
        o = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, o.transpose(0, 2, 1, 3).astype(q.dtype)  # (B, cq, H, D)

    _, outs = jax.lax.scan(q_step, None, (qs, jnp.arange(nq)))
    return outs.transpose(1, 0, 2, 3, 4).reshape(B, S, H, Dv)


def _causal_pair_scan(q, k, v, *, scale: float, c: int) -> jax.Array:
    """Exact-FLOPs flash attention: one scan over the n(n+1)/2 lower-triangle
    (q-block, k-block) pairs — above-diagonal tiles are never read or
    computed, unlike the masked nested scan (§Perf iteration 1).

    Pairs are ordered row-major (i ascending, j = 0..i), so the running
    (m, l, acc) carry resets at j == 0 and row i's output is complete at the
    diagonal; the out buffer is updated every step and the diagonal write
    (the last one per row) wins.
    """
    B, S, H, D = q.shape
    Dv = v.shape[-1]
    while S % c:
        c //= 2
    n = S // c
    i_idx = jnp.asarray([i for i in range(n) for _ in range(i + 1)])
    j_idx = jnp.asarray([j for i in range(n) for j in range(i + 1)])

    def step(carry, ij):
        m, l, acc, out = carry
        i, j = ij
        reset = (j == 0)
        m = jnp.where(reset, NEG_INF, m)
        l = jnp.where(reset, 0.0, l)
        acc = jnp.where(reset, 0.0, acc)
        qi = jax.lax.dynamic_slice_in_dim(q, i * c, c, 1)
        kj = jax.lax.dynamic_slice_in_dim(k, j * c, c, 1)
        vj = jax.lax.dynamic_slice_in_dim(v, j * c, c, 1)
        s = jnp.einsum("bqhd,bkhd->bhqk", qi, kj,
                       preferred_element_type=jnp.float32) * scale
        qpos = i * c + jnp.arange(c)
        kpos = j * c + jnp.arange(c)
        mask = (kpos[None, :] <= qpos[:, None])[None, None]
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p.astype(vj.dtype), vj,
            preferred_element_type=jnp.float32)
        o = (acc / jnp.maximum(l, 1e-30)[..., None]).transpose(0, 2, 1, 3)
        out = jax.lax.dynamic_update_slice_in_dim(out, o.astype(out.dtype),
                                                  i * c, 1)
        return (m_new, l, acc, out), None

    m0 = jnp.full((B, H, c), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, c), jnp.float32)
    a0 = jnp.zeros((B, H, c, Dv), jnp.float32)
    out0 = jnp.zeros((B, S, H, Dv), q.dtype)
    (_, _, _, out), _ = jax.lax.scan(step, (m0, l0, a0, out0),
                                     (i_idx, j_idx))
    return out


def encoder_attention(q, k, v, *, scale: float, plan: ShardPlan,
                      cfg: ArchConfig) -> jax.Array:
    """Bidirectional attention (encoder-only archs); scan over q chunks."""
    B, S, H, D = q.shape
    b_loc = max(B // max(plan.dp, 1), 1)
    h_loc = max(H // max(plan.tp, 1), 1)
    chunk = _pick_chunk(b_loc, h_loc, S, plan.attn_temp_budget)
    n = S // chunk
    if n == 1:
        return _attn_block(q, k, v, jnp.bool_(True)[None, None, None, None], scale)
    qs = q.reshape(B, n, chunk, H, D).transpose(1, 0, 2, 3, 4)

    def body(_, qi):
        return None, _attn_block(qi, k, v, jnp.bool_(True)[None, None, None, None], scale)

    _, o = jax.lax.scan(body, None, qs)
    return o.transpose(1, 0, 2, 3, 4).reshape(B, S, H, D)


def swa_attention(q, k, v, *, window: int, scale: float, plan: ShardPlan,
                  cfg: ArchConfig) -> jax.Array:
    """Banded (sliding-window) causal attention, O(S * window)."""
    B, S, H, D = q.shape
    if S <= window:  # window covers everything: plain causal is identical
        return causal_attention(q, k, v, scale=scale, plan=plan, cfg=cfg)
    chunk = min(max(window, 128), S)
    while S % chunk:
        chunk //= 2
    if S <= window + chunk or S <= 2048:
        # small enough: one explicit causal+window masked block
        qpos = jnp.arange(S)[:, None]
        kpos = jnp.arange(S)[None, :]
        mask = ((kpos <= qpos) & (kpos > qpos - window))[None, None]
        return _attn_block(q, k, v, mask, scale)
    n = S // chunk
    win = window + chunk  # each q chunk sees [i*chunk - window, i*chunk + chunk)
    # gather k windows: idx[i, t] = i*chunk - window + t (clamped; masked below)
    base = jnp.arange(n)[:, None] * chunk
    idx = base + jnp.arange(-window, chunk)[None, :]
    valid_idx = idx >= 0
    idx_c = jnp.clip(idx, 0, S - 1)
    kw = jnp.take(k, idx_c, axis=1)  # (B, n, win, Hk, D)
    vw = jnp.take(v, idx_c, axis=1)
    qs = q.reshape(B, n, chunk, H, D)
    qpos = base[:, :, None] + jnp.arange(chunk)[None, None, :]  # (1? n, chunk)
    qpos = (jnp.arange(n)[:, None] * chunk + jnp.arange(chunk)[None, :])
    kpos = idx  # (n, win)
    causal = kpos[:, None, :] <= qpos[:, :, None]
    inwin = kpos[:, None, :] > qpos[:, :, None] - window
    mask = (causal & inwin & valid_idx[:, None, :])[None, :, None]  # (1,n,1,chunk,win)
    s = jnp.einsum("bnqhd,bnkhd->bnhqk", qs, kw,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bnhqk,bnkhd->bnqhd", p.astype(vw.dtype), vw)
    return o.reshape(B, S, H, D)


# ---------------------------------------------------------------------------
# GQA forward (train / prefill)
# ---------------------------------------------------------------------------

def gqa_forward(p: Params, x: jax.Array, positions: jax.Array,
                cfg: ArchConfig, plan: ShardPlan, *, want_cache: bool):
    """x: (B, S, d) -> (out (B, S, d), cache | None)."""
    dt = plan.compute_dtype
    h_pad = plan.h_pad(cfg)
    q = jnp.einsum("bsd,dhk->bshk", x, p["w_q"].astype(dt))
    k = jnp.einsum("bsd,dgk->bsgk", x, p["w_k"].astype(dt))
    v = jnp.einsum("bsd,dgk->bsgk", x, p["w_v"].astype(dt))
    if cfg.qk_norm:
        q = L.rms_norm(q, p["q_norm"])
        k = L.rms_norm(k, p["k_norm"])
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    q = plan.constrain(q, ("batch", "seq", "heads", None), cfg)
    kv_ax = "kv_heads"
    k = plan.constrain(k, ("batch", "seq", kv_ax, None), cfg)
    v = plan.constrain(v, ("batch", "seq", kv_ax, None), cfg)
    cache = None
    if want_cache:
        k_out, v_out = k, v
        if plan.kv_padded(cfg):
            # dedup padded copies: slot r*copies is copy-0 of real head r
            copies = plan.k_pad(cfg) // cfg.n_kv_heads
            k_out, v_out = k[:, :, ::copies], v[:, :, ::copies]
        if cfg.attn_kind == "swa" and cfg.window:
            # ring-buffer tail: slot (p % W) holds position p, p in [S-W, S)
            S = k_out.shape[1]
            W = min(cfg.window, S)
            tail = jnp.arange(S - W, S)
            slot = tail % W
            k_ring = jnp.zeros((k_out.shape[0], W) + k_out.shape[2:], k_out.dtype)
            v_ring = jnp.zeros_like(k_ring)
            k_ring = k_ring.at[:, slot].set(k_out[:, S - W:])
            v_ring = v_ring.at[:, slot].set(v_out[:, S - W:])
            cache = {"k": k_ring, "v": v_ring}
        else:
            cache = {
                "k": plan.constrain(k_out, ("batch", "cache_seq", "kv_cache_heads", None), cfg),
                "v": plan.constrain(v_out, ("batch", "cache_seq", "kv_cache_heads", None), cfg),
            }
    idx = kv_index(cfg, h_pad, plan.k_pad(cfg))
    ke = _expand_kv(k, idx, h_pad)
    ve = _expand_kv(v, idx, h_pad)
    scale = 1.0 / math.sqrt(cfg.head_dim)
    if not cfg.causal:
        o = encoder_attention(q, ke, ve, scale=scale, plan=plan, cfg=cfg)
    elif cfg.attn_kind == "swa" and cfg.window:
        o = swa_attention(q, ke, ve, window=cfg.window, scale=scale, plan=plan, cfg=cfg)
    else:
        o = causal_attention(q, ke, ve, scale=scale, plan=plan, cfg=cfg)
    out = jnp.einsum("bshk,hkd->bsd", o, p["w_o"].astype(dt))
    return plan.constrain(out, ("batch", "seq", "embed_act"), cfg), cache


# ---------------------------------------------------------------------------
# MLA forward (train / prefill)
# ---------------------------------------------------------------------------

def mla_forward(p: Params, x: jax.Array, positions: jax.Array,
                cfg: ArchConfig, plan: ShardPlan, *, want_cache: bool):
    dt = plan.compute_dtype
    nope, ropeD = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    # --- queries (low-rank) ---
    cq = L.rms_norm(jnp.einsum("bsd,dr->bsr", x, p["w_dq"].astype(dt)), p["q_norm"])
    q = jnp.einsum("bsr,rhk->bshk", cq, p["w_uq"].astype(dt))
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = L.apply_rope(q_rope, positions, cfg.rope_theta)
    # --- latent kv ---
    ckv = L.rms_norm(jnp.einsum("bsd,dr->bsr", x, p["w_dkv"].astype(dt)), p["kv_norm"])
    kr = L.apply_rope(jnp.einsum("bsd,dk->bsk", x, p["w_kr"].astype(dt))[:, :, None, :],
                      positions, cfg.rope_theta)[:, :, 0]  # (B,S,ropeD), shared
    cache = None
    if want_cache:
        cache = {
            "ckv": plan.constrain(ckv, ("batch", "cache_seq", None), cfg),
            "kr": plan.constrain(kr, ("batch", "cache_seq", None), cfg),
        }
    # --- expand latent to per-head k/v (prefill-optimal form) ---
    k_nope = jnp.einsum("bsr,rhk->bshk", ckv, p["w_uk"].astype(dt))
    v = jnp.einsum("bsr,rhk->bshk", ckv, p["w_uv"].astype(dt))
    k = jnp.concatenate([k_nope, jnp.broadcast_to(kr[:, :, None, :], k_nope.shape[:2] + (k_nope.shape[2], ropeD))], axis=-1)
    qf = jnp.concatenate([q_nope, q_rope], axis=-1)
    qf = plan.constrain(qf, ("batch", "seq", "heads", None), cfg)
    k = plan.constrain(k, ("batch", "seq", "heads", None), cfg)
    v = plan.constrain(v, ("batch", "seq", "heads", None), cfg)
    scale = 1.0 / math.sqrt(nope + ropeD)
    o = causal_attention(qf, k, v, scale=scale, plan=plan, cfg=cfg)
    out = jnp.einsum("bshk,hkd->bsd", o, p["w_o"].astype(dt))
    return plan.constrain(out, ("batch", "seq", "embed_act"), cfg), cache


# ---------------------------------------------------------------------------
# decode: sequence-sharded flash-decode
# ---------------------------------------------------------------------------

def _flash_decode_core(axis, q, k_cache, v_cache, k_new, v_new, positions,
                       kv_idx, scale):
    """Runs per-device on an S-shard of the cache.

    q: (B, H, hd) full heads; k_cache/v_cache: (B, S_loc, K, hd);
    k_new/v_new: (B, K, hd); positions: (B,).  Returns (o, k_cache, v_cache).
    """
    B, S_loc = k_cache.shape[0], k_cache.shape[1]
    off = (jax.lax.axis_index(axis) * S_loc) if axis is not None else 0
    local = positions - off
    valid_w = (local >= 0) & (local < S_loc)
    safe = jnp.clip(local, 0, S_loc - 1)
    bidx = jnp.arange(B)
    old_k = k_cache[bidx, safe]
    old_v = v_cache[bidx, safe]
    k_cache = k_cache.at[bidx, safe].set(
        jnp.where(valid_w[:, None, None], k_new, old_k))
    v_cache = v_cache.at[bidx, safe].set(
        jnp.where(valid_w[:, None, None], v_new, old_v))

    ke = _expand_kv(k_cache, kv_idx, q.shape[1])  # (B, S_loc, H, hd)
    ve = _expand_kv(v_cache, kv_idx, q.shape[1])
    s = jnp.einsum("bhd,bshd->bhs", q, ke,
                   preferred_element_type=jnp.float32) * scale
    kpos = off + jnp.arange(S_loc)
    mask = kpos[None, None, :] <= positions[:, None, None]
    s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1)  # (B, H)
    if axis is not None:
        m = jax.lax.pmax(m, axis)
    pexp = jnp.exp(s - m[..., None])
    l = jnp.sum(pexp, axis=-1)
    num = jnp.einsum("bhs,bshd->bhd", pexp.astype(ve.dtype), ve,
                     preferred_element_type=jnp.float32)
    if axis is not None:
        l = jax.lax.psum(l, axis)
        num = jax.lax.psum(num, axis)
    o = num / jnp.maximum(l, 1e-30)[..., None]
    return o.astype(q.dtype), k_cache, v_cache


def _decode_qkv(p: Params, x: jax.Array, positions: jax.Array,
                cfg: ArchConfig, plan: ShardPlan):
    """Shared one-token GQA projection: q (B, H, hd) + the new token's
    real-head k/v (B, K, hd), with qk_norm/rope/padded-copy-drop applied."""
    dt = plan.compute_dtype
    q = jnp.einsum("bd,dhk->bhk", x, p["w_q"].astype(dt))
    k_new = jnp.einsum("bd,dgk->bgk", x, p["w_k"].astype(dt))
    v_new = jnp.einsum("bd,dgk->bgk", x, p["w_v"].astype(dt))
    if cfg.qk_norm:
        q = L.rms_norm(q, p["q_norm"])
        k_new = L.rms_norm(k_new, p["k_norm"])
    q = L.apply_rope(q[:, None], positions[:, None], cfg.rope_theta)[:, 0]
    k_new = L.apply_rope(k_new[:, None], positions[:, None], cfg.rope_theta)[:, 0]
    if plan.kv_padded(cfg):
        # decode caches store real heads; drop padded copies of the new token
        copies = plan.k_pad(cfg) // cfg.n_kv_heads
        k_new, v_new = k_new[:, ::copies], v_new[:, ::copies]
    return q, k_new, v_new


def gqa_decode(p: Params, x: jax.Array, cache: Params, positions: jax.Array,
               cfg: ArchConfig, plan: ShardPlan):
    """x: (B, d) one token per sequence -> (out (B, d), new cache)."""
    dt = plan.compute_dtype
    h_pad = plan.h_pad(cfg)
    q, k_new, v_new = _decode_qkv(p, x, positions, cfg, plan)
    idx = kv_index(cfg, h_pad)
    scale = 1.0 / math.sqrt(cfg.head_dim)

    if cfg.attn_kind == "swa" and cfg.window:
        return _swa_decode(p, q, k_new, v_new, cache, positions, cfg, plan, idx, scale)

    dp = plan.dp_axes if plan.dp_axes else None
    in_specs = (P(dp, None, None), P(dp, "model", None, None),
                P(dp, "model", None, None), P(dp, None, None),
                P(dp, None, None), P(dp))
    out_specs = (P(dp, None, None), P(dp, "model", None, None),
                 P(dp, "model", None, None))
    o, k_c, v_c = shard_map_or_call(
        plan, lambda ax, *a: _flash_decode_core(ax, *a, kv_idx=idx, scale=scale),
        in_specs, out_specs, q, cache["k"], cache["v"], k_new, v_new, positions)
    out = jnp.einsum("bhk,hkd->bd", o, p["w_o"].astype(dt))
    return plan.constrain(out, ("batch", "embed_act"), cfg), {"k": k_c, "v": v_c}


def _swa_decode(p, q, k_new, v_new, cache, positions, cfg, plan, kv_idx, scale):
    """Ring-buffer sliding-window decode; window cache replicated over model."""
    dt = plan.compute_dtype
    W = cache["k"].shape[1]
    B = q.shape[0]
    bidx = jnp.arange(B)
    slot = positions % W
    k_c = cache["k"].at[bidx, slot].set(k_new)
    v_c = cache["v"].at[bidx, slot].set(v_new)
    ke = _expand_kv(k_c, kv_idx, q.shape[1])
    ve = _expand_kv(v_c, kv_idx, q.shape[1])
    s = jnp.einsum("bhd,bshd->bhs", q, ke,
                   preferred_element_type=jnp.float32) * scale
    slots = jnp.arange(W)
    valid = (slots[None, :] <= positions[:, None]) | (positions[:, None] >= W)
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    prob = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhs,bshd->bhd", prob.astype(ve.dtype), ve)
    out = jnp.einsum("bhk,hkd->bd", o.astype(dt), p["w_o"].astype(dt))
    return plan.constrain(out, ("batch", "embed_act"), cfg), {"k": k_c, "v": v_c}


def paged_decode_path(cfg: ArchConfig, plan: ShardPlan, pool_dtype) -> str:
    """The attention a paged decode step's program is built with:
    ``"pallas"`` (``kernels/paged_decode_attention``) or ``"xla"``
    (``paged_attention``, and ``_paged_decode_core`` on a sharded pool).

    The kernel serves a single-device plan on TPU where query head h reads
    kv head h // G (no padded q or kv heads), the head dim fills whole
    128-lane rows, the kv heads fill whole packed rows of the pool's dtype
    (Mosaic cannot slice a lone bf16 head out of a pool block), and the
    pool holds the compute dtype.  Everything else keeps the XLA gather.
    """
    single = plan.mesh is None or plan.mesh.size == 1
    if not single or jax.default_backend() != "tpu":
        return "xla"
    pool_dtype = jnp.dtype(pool_dtype)
    packing = max(4 // pool_dtype.itemsize, 1)
    plain = plan.h_pad(cfg) == cfg.n_heads and not plan.kv_padded(cfg)
    if (plain and cfg.head_dim % 128 == 0
            and cfg.n_kv_heads % packing == 0
            and jnp.dtype(plan.compute_dtype) == pool_dtype):
        return "pallas"
    return "xla"


def paged_attention(q, k_pool, v_pool, layer, block_tables, positions, *,
                    scale: float, kv_idx: jax.Array) -> jax.Array:
    """Decode attention over a paged KV pool (XLA gather path).

    q: (B, H, hd); k_pool/v_pool: the layer-stacked (L, n_blocks, bs, K,
    hd), read at ``layer``; block_tables: (B, T) physical block ids;
    positions: (B,).

    The per-sequence logical view is gathered from the pool through the
    block table, then masked by position: every lane reads all T blocks of
    its table whatever its context.  ``gqa_decode_paged`` runs this where
    ``paged_decode_path`` says ``"xla"`` (off TPU, padded heads, other
    head sizes); on one TPU chip it runs the Pallas kernel
    ``kernels/paged_decode_attention.py``, which reads only the blocks a
    lane's position reaches.  This path is the kernel's reference.
    """
    B, H = q.shape[:2]
    bs, K = k_pool.shape[2], k_pool.shape[3]
    T = block_tables.shape[1]
    k = k_pool[layer, block_tables].reshape(B, T * bs, K, -1)
    v = v_pool[layer, block_tables].reshape(B, T * bs, K, -1)
    ke = _expand_kv(k, kv_idx, H)
    ve = _expand_kv(v, kv_idx, H)
    s = jnp.einsum("bhd,bshd->bhs", q, ke,
                   preferred_element_type=jnp.float32) * scale
    mask = jnp.arange(T * bs)[None, None, :] <= positions[:, None, None]
    s = jnp.where(mask, s, NEG_INF)
    prob = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhs,bshd->bhd", prob.astype(ve.dtype), ve,
                   preferred_element_type=jnp.float32)
    return o.astype(q.dtype)


def paged_prefill_attention(q, k_pool, v_pool, layer, block_tables, starts,
                            *, scale: float, kv_idx: jax.Array) -> jax.Array:
    """Chunk-prefill attention over a paged KV pool (XLA gather path).

    q: (B, C, H, hd) — a chunk of C query tokens per sequence whose first
    token sits at absolute position ``starts[b]``; k_pool/v_pool: the
    layer-stacked (L, n_blocks, bs, K, hd), read at ``layer``;
    block_tables: (B, T).  The chunk's own KV must
    already be written into the pool (see ``gqa_prefill_paged``), so one
    gather serves both the cached context and the within-chunk causal
    part: position kpos is visible to chunk token c iff
    kpos <= starts + c.  The serving path runs this XLA gather on every
    backend; the Pallas counterpart
    (``kernels/paged_decode_attention.paged_prefill_attention``) is
    tested and compile-tested for TPU but not called here.
    """
    B, C, H = q.shape[:3]
    bs, K = k_pool.shape[2], k_pool.shape[3]
    T = block_tables.shape[1]
    k = k_pool[layer, block_tables].reshape(B, T * bs, K, -1)
    v = v_pool[layer, block_tables].reshape(B, T * bs, K, -1)
    ke = _expand_kv(k, kv_idx, H)
    ve = _expand_kv(v, kv_idx, H)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, ke,
                   preferred_element_type=jnp.float32) * scale
    qpos = starts[:, None] + jnp.arange(C)[None, :]            # (B, C)
    mask = jnp.arange(T * bs)[None, None, :] <= qpos[:, :, None]
    s = jnp.where(mask[:, None], s, NEG_INF)
    prob = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", prob.astype(ve.dtype), ve,
                   preferred_element_type=jnp.float32)
    return o.astype(q.dtype)


def _pool_index(layer, *idx):
    """Index into a pool stripe: one layer's ``(nb_loc, bs, K, hd)`` when
    ``layer`` is None, else the layer-stacked ``(L, nb_loc, bs, K, hd)``
    at ``layer`` — one gather or scatter into the stacked buffer, never a
    slice of the layer first."""
    return idx if layer is None else (layer, *idx)


def _paged_decode_core(axis, q, k_pool, v_pool, block_tables, positions,
                       k_new, v_new, layer=None, *, scale, kv_idx):
    """Per-shard paged decode on a block-stripe of the pool, LSE-combined.

    Rank r owns physical blocks ``[r*nb_loc, (r+1)*nb_loc)``: the new
    token's KV scatter uses an out-of-range sentinel with ``mode="drop"``
    so exactly the owning rank writes, the gather masks unowned table
    entries, and the softmax merges across ranks via the same
    max/sum-reduce (pmax/psum) idiom as ``_flash_decode_core``.

    q: (B, H, hd); k_pool/v_pool: (nb_loc, bs, K, hd) local stripe, or
    the layer-stacked (L, nb_loc, bs, K, hd) at ``layer``; block_tables:
    (B, T) *global* block ids; k_new/v_new: (B, K, hd).
    """
    B, H = q.shape[:2]
    nb_loc, bs, K = k_pool.shape[-4:-1]
    off = (jax.lax.axis_index(axis) * nb_loc) if axis is not None else 0
    blk = jnp.take_along_axis(block_tables, (positions // bs)[:, None],
                              axis=1)[:, 0]
    o_in_b = positions % bs
    local_b = blk - off
    owned = (local_b >= 0) & (local_b < nb_loc)
    safe_b = jnp.where(owned, local_b, nb_loc)     # OOB on unowned -> dropped
    with jax.named_scope("kv_append"):
        at = _pool_index(layer, safe_b, o_in_b)
        k_pool = k_pool.at[at].set(k_new.astype(k_pool.dtype), mode="drop")
        v_pool = v_pool.at[at].set(v_new.astype(v_pool.dtype), mode="drop")
    T = block_tables.shape[1]
    local_t = block_tables - off
    t_owned = (local_t >= 0) & (local_t < nb_loc)  # (B, T)
    safe_t = jnp.clip(local_t, 0, nb_loc - 1)
    k = k_pool[_pool_index(layer, safe_t)].reshape(B, T * bs, K, -1)
    v = v_pool[_pool_index(layer, safe_t)].reshape(B, T * bs, K, -1)
    ke = _expand_kv(k, kv_idx, H)
    ve = _expand_kv(v, kv_idx, H)
    s = jnp.einsum("bhd,bshd->bhs", q, ke,
                   preferred_element_type=jnp.float32) * scale
    mask = (jnp.arange(T * bs)[None, None, :] <= positions[:, None, None]) \
        & jnp.repeat(t_owned, bs, axis=1)[:, None, :]
    s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1)
    if axis is not None:
        m = jax.lax.pmax(m, axis)
    pexp = jnp.exp(s - m[..., None])
    l = jnp.sum(pexp, axis=-1)
    num = jnp.einsum("bhs,bshd->bhd", pexp.astype(ve.dtype), ve,
                     preferred_element_type=jnp.float32)
    if axis is not None:
        l = jax.lax.psum(l, axis)
        num = jax.lax.psum(num, axis)
    o = num / jnp.maximum(l, 1e-30)[..., None]
    return o.astype(q.dtype), k_pool, v_pool


def _paged_prefill_core(axis, q, k_pool, v_pool, block_tables, starts,
                        lengths, k, v, layer=None, *, scale, kv_idx):
    """Per-shard chunk prefill on a block-stripe of the pool, LSE-combined.

    q: (B, C, H, hd); k_pool/v_pool as in ``_paged_decode_core``; k/v:
    (B, C, K, hd) the chunk's new KV (rope applied, real heads); the
    scatter-then-gather ordering inside the core keeps within-chunk causal
    attention exact on the rank that owns each block.
    """
    B, C, H = q.shape[:3]
    nb_loc, bs, K = k_pool.shape[-4:-1]
    off = (jax.lax.axis_index(axis) * nb_loc) if axis is not None else 0
    positions = starts[:, None] + jnp.arange(C)[None, :]
    valid = jnp.arange(C)[None, :] < lengths[:, None]
    safe_pos = jnp.where(valid, positions, 0)
    blk = jnp.take_along_axis(block_tables, safe_pos // bs, axis=1)
    blk = jnp.where(valid, blk, 0)
    o_in_b = jnp.where(valid, safe_pos % bs, 0)
    local_b = blk - off
    owned_w = valid & (local_b >= 0) & (local_b < nb_loc)
    safe_b = jnp.where(owned_w, local_b, nb_loc)   # OOB on unowned -> dropped
    with jax.named_scope("kv_append"):
        at = _pool_index(layer, safe_b.reshape(-1), o_in_b.reshape(-1))
        k_pool = k_pool.at[at].set(
            k.reshape(B * C, K, -1).astype(k_pool.dtype), mode="drop")
        v_pool = v_pool.at[at].set(
            v.reshape(B * C, K, -1).astype(v_pool.dtype), mode="drop")
    T = block_tables.shape[1]
    local_t = block_tables - off
    t_owned = (local_t >= 0) & (local_t < nb_loc)
    safe_t = jnp.clip(local_t, 0, nb_loc - 1)
    kk = k_pool[_pool_index(layer, safe_t)].reshape(B, T * bs, K, -1)
    vv = v_pool[_pool_index(layer, safe_t)].reshape(B, T * bs, K, -1)
    ke = _expand_kv(kk, kv_idx, H)
    ve = _expand_kv(vv, kv_idx, H)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, ke,
                   preferred_element_type=jnp.float32) * scale
    kmask = (jnp.arange(T * bs)[None, None, :] <= positions[:, :, None]) \
        & jnp.repeat(t_owned, bs, axis=1)[:, None, :]
    s = jnp.where(kmask[:, None], s, NEG_INF)
    m = jnp.max(s, axis=-1)                        # (B, H, C)
    if axis is not None:
        m = jax.lax.pmax(m, axis)
    pexp = jnp.exp(s - m[..., None])
    l = jnp.sum(pexp, axis=-1)
    num = jnp.einsum("bhqk,bkhd->bqhd", pexp.astype(ve.dtype), ve,
                     preferred_element_type=jnp.float32)
    if axis is not None:
        l = jax.lax.psum(l, axis)
        num = jax.lax.psum(num, axis)
    o = num / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return o.astype(q.dtype), k_pool, v_pool


def gqa_prefill_paged(p: Params, x: jax.Array, cache: Params, layer,
                      starts: jax.Array, lengths: jax.Array,
                      block_tables: jax.Array, cfg: ArchConfig,
                      plan: ShardPlan):
    """Chunked-prefill step over the paged pool: project a chunk of C
    tokens, scatter its KV into the owned blocks, then attend through the
    block table (cached context + within-chunk causal in one gather).

    x: (B, C, d); cache: the layer-stacked pool ``{"k", "v"}`` (leaves
    (L, n_blocks, bs, K, hd)), written and read at ``layer``; starts: (B,)
    absolute position of x[:, 0]; lengths: (B,) valid tokens per row
    (ragged tails).  Invalid positions are routed to the reserved parking
    block 0, whose contents are never read unmasked.  Returns the whole
    updated stacked pool.
    """
    dt = plan.compute_dtype
    h_pad = plan.h_pad(cfg)
    B, C = x.shape[:2]
    q = jnp.einsum("bsd,dhk->bshk", x, p["w_q"].astype(dt))
    k = jnp.einsum("bsd,dgk->bsgk", x, p["w_k"].astype(dt))
    v = jnp.einsum("bsd,dgk->bsgk", x, p["w_v"].astype(dt))
    if cfg.qk_norm:
        q = L.rms_norm(q, p["q_norm"])
        k = L.rms_norm(k, p["k_norm"])
    positions = starts[:, None] + jnp.arange(C)[None, :]
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    if plan.kv_padded(cfg):
        copies = plan.k_pad(cfg) // cfg.n_kv_heads
        k, v = k[:, :, ::copies], v[:, :, ::copies]
    idx = kv_index(cfg, h_pad)
    scale = 1.0 / math.sqrt(cfg.head_dim)
    if plan.paged_pool_sharded(cfg):
        dp = plan.dp_axes if plan.dp_axes else None
        tp = plan.tp_axis
        pool = P(None, tp, None, None, None)
        in_specs = (P(dp, None, None, None), pool, pool, P(dp, None), P(dp),
                    P(dp), P(dp, None, None, None), P(dp, None, None, None),
                    P())
        out_specs = (P(dp, None, None, None), pool, pool)
        o, k_c, v_c = shard_map_or_call(
            plan,
            lambda ax, *a: _paged_prefill_core(ax, *a, scale=scale, kv_idx=idx),
            in_specs, out_specs, q, cache["k"], cache["v"], block_tables,
            starts, lengths, k, v, layer)
    else:
        bs, K = cache["k"].shape[2], cache["k"].shape[3]
        valid = jnp.arange(C)[None, :] < lengths[:, None]
        safe_pos = jnp.where(valid, positions, 0)
        blk = jnp.take_along_axis(block_tables, safe_pos // bs, axis=1)
        blk = jnp.where(valid, blk, 0)
        off = jnp.where(valid, safe_pos % bs, 0)
        at = (layer, blk.reshape(-1), off.reshape(-1))
        with jax.named_scope("kv_append"):
            k_c = cache["k"].at[at].set(
                k.reshape(B * C, K, -1).astype(cache["k"].dtype))
            v_c = cache["v"].at[at].set(
                v.reshape(B * C, K, -1).astype(cache["v"].dtype))
        o = paged_prefill_attention(q, k_c, v_c, layer, block_tables, starts,
                                    scale=scale, kv_idx=idx)
    out = jnp.einsum("bshk,hkd->bsd", o, p["w_o"].astype(dt))
    return plan.constrain(out, ("batch", "seq", "embed_act"), cfg), \
        {"k": k_c, "v": v_c}


def gqa_decode_paged(p: Params, x: jax.Array, cache: Params, layer,
                     positions: jax.Array, block_tables: jax.Array,
                     cfg: ArchConfig, plan: ShardPlan):
    """Paged-pool decode step: write the new token's KV into its block,
    attend through the block table.  x: (B, d) -> (out (B, d), new cache).

    cache is the layer-stacked pool ``{"k", "v"}`` (leaves (L, n_blocks,
    bs, K, hd)); the write and the gather address it at ``layer``.  The
    write touches exactly one (layer, block, offset) slot per sequence —
    O(active sequences), independent of pool size.  It lands in place
    only because the caller's layer scan carries the stacked pool (see
    ``Model.decode_step_paged``) and jit donates it: scanning the pool as
    per-layer slices would copy it whatever the donation.  On one TPU chip
    the attention is the Pallas kernel over the carried pool, which reads
    only the blocks each lane's position reaches; elsewhere it is the XLA
    gather (``paged_decode_path``).
    """
    dt = plan.compute_dtype
    h_pad = plan.h_pad(cfg)
    q, k_new, v_new = _decode_qkv(p, x, positions, cfg, plan)
    idx = kv_index(cfg, h_pad)
    scale = 1.0 / math.sqrt(cfg.head_dim)
    if plan.paged_pool_sharded(cfg):
        dp = plan.dp_axes if plan.dp_axes else None
        tp = plan.tp_axis
        pool = P(None, tp, None, None, None)
        in_specs = (P(dp, None, None), pool, pool, P(dp, None), P(dp),
                    P(dp, None, None), P(dp, None, None), P())
        out_specs = (P(dp, None, None), pool, pool)
        o, k_c, v_c = shard_map_or_call(
            plan,
            lambda ax, *a: _paged_decode_core(ax, *a, scale=scale, kv_idx=idx),
            in_specs, out_specs, q, cache["k"], cache["v"], block_tables,
            positions, k_new, v_new, layer)
    else:
        bs = cache["k"].shape[2]
        blk = jnp.take_along_axis(block_tables, (positions // bs)[:, None],
                                  axis=1)[:, 0]
        off = positions % bs
        with jax.named_scope("kv_append"):
            k_c = cache["k"].at[layer, blk, off].set(
                k_new.astype(cache["k"].dtype))
            v_c = cache["v"].at[layer, blk, off].set(
                v_new.astype(cache["v"].dtype))
        if paged_decode_path(cfg, plan, k_c.dtype) == "pallas":
            o = paged_decode_kernel(q, k_c, v_c, layer, block_tables,
                                    positions, scale=scale)
        else:
            o = paged_attention(q, k_c, v_c, layer, block_tables, positions,
                                scale=scale, kv_idx=idx)
    out = jnp.einsum("bhk,hkd->bd", o, p["w_o"].astype(dt))
    return plan.constrain(out, ("batch", "embed_act"), cfg), {"k": k_c, "v": v_c}


def _mla_decode_core(axis, qc, qr, ckv, kr, c_new, kr_new, positions, scale):
    """Absorbed MLA flash-decode on an S-shard of the latent cache.

    qc: (B, H, R) absorbed nope-query; qr: (B, H, ropeD);
    ckv: (B, S_loc, R); kr: (B, S_loc, ropeD).
    """
    B, S_loc = ckv.shape[0], ckv.shape[1]
    off = (jax.lax.axis_index(axis) * S_loc) if axis is not None else 0
    local = positions - off
    valid_w = (local >= 0) & (local < S_loc)
    safe = jnp.clip(local, 0, S_loc - 1)
    bidx = jnp.arange(B)
    ckv = ckv.at[bidx, safe].set(jnp.where(valid_w[:, None], c_new, ckv[bidx, safe]))
    kr = kr.at[bidx, safe].set(jnp.where(valid_w[:, None], kr_new, kr[bidx, safe]))
    s = (jnp.einsum("bhr,bsr->bhs", qc, ckv, preferred_element_type=jnp.float32)
         + jnp.einsum("bhp,bsp->bhs", qr, kr, preferred_element_type=jnp.float32)) * scale
    kpos = off + jnp.arange(S_loc)
    mask = kpos[None, None, :] <= positions[:, None, None]
    s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1)
    if axis is not None:
        m = jax.lax.pmax(m, axis)
    pexp = jnp.exp(s - m[..., None])
    l = jnp.sum(pexp, axis=-1)
    num = jnp.einsum("bhs,bsr->bhr", pexp.astype(ckv.dtype), ckv,
                     preferred_element_type=jnp.float32)
    if axis is not None:
        l = jax.lax.psum(l, axis)
        num = jax.lax.psum(num, axis)
    o = num / jnp.maximum(l, 1e-30)[..., None]  # (B, H, R) latent output
    return o.astype(qc.dtype), ckv, kr


def mla_decode(p: Params, x: jax.Array, cache: Params, positions: jax.Array,
               cfg: ArchConfig, plan: ShardPlan):
    dt = plan.compute_dtype
    nope, ropeD = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    cq = L.rms_norm(jnp.einsum("bd,dr->br", x, p["w_dq"].astype(dt)), p["q_norm"])
    q = jnp.einsum("br,rhk->bhk", cq, p["w_uq"].astype(dt))
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = L.apply_rope(q_rope[:, None], positions[:, None], cfg.rope_theta)[:, 0]
    # absorb W_uk into the query: q_c = q_nope . W_uk  -> (B, H, R)
    qc = jnp.einsum("bhn,rhn->bhr", q_nope, p["w_uk"].astype(dt))
    c_new = L.rms_norm(jnp.einsum("bd,dr->br", x, p["w_dkv"].astype(dt)), p["kv_norm"])
    kr_new = L.apply_rope(jnp.einsum("bd,dk->bk", x, p["w_kr"].astype(dt))[:, None, None, :],
                          positions[:, None], cfg.rope_theta)[:, 0, 0]
    scale = 1.0 / math.sqrt(nope + ropeD)
    dp = plan.dp_axes if plan.dp_axes else None
    in_specs = (P(dp, None, None), P(dp, None, None),
                P(dp, "model", None), P(dp, "model", None),
                P(dp, None), P(dp, None), P(dp))
    out_specs = (P(dp, None, None), P(dp, "model", None), P(dp, "model", None))
    o, ckv_c, kr_c = shard_map_or_call(
        plan, lambda ax, *a: _mla_decode_core(ax, *a, scale=scale),
        in_specs, out_specs, qc, q_rope, cache["ckv"], cache["kr"],
        c_new, kr_new, positions)
    # un-absorb: latent output -> per-head v -> output projection
    ov = jnp.einsum("bhr,rhv->bhv", o, p["w_uv"].astype(dt))
    out = jnp.einsum("bhv,hvd->bd", ov, p["w_o"].astype(dt))
    return plan.constrain(out, ("batch", "embed_act"), cfg), {"ckv": ckv_c, "kr": kr_c}


# ---------------------------------------------------------------------------
# cache init
# ---------------------------------------------------------------------------

def init_paged_attn_cache(cfg: ArchConfig, plan: ShardPlan, n_blocks: int,
                          block_size: int, dtype=jnp.bfloat16):
    """Per-layer paged KV pool (GQA families only): one global block pool
    shared by every sequence, indexed through per-request block tables."""
    if cfg.rwkv or cfg.family == "hybrid" or cfg.attn_kind != "gqa":
        raise ValueError(f"{cfg.name}: paged KV cache requires plain GQA "
                         f"attention (got attn_kind={cfg.attn_kind!r})")
    c = {
        "k": jnp.zeros((n_blocks, block_size, cfg.n_kv_heads, cfg.head_dim),
                       dtype),
        "v": jnp.zeros((n_blocks, block_size, cfg.n_kv_heads, cfg.head_dim),
                       dtype),
    }
    if plan.paged_pool_sharded(cfg) and n_blocks % plan.tp:
        raise ValueError(f"paged pool of {n_blocks} blocks does not divide "
                         f"the {plan.tp}-way model axis; round n_blocks up")
    ax = {"k": ("kv_blocks", None, "kv_cache_heads", None),
          "v": ("kv_blocks", None, "kv_cache_heads", None)}
    return c, ax


def init_attn_cache(cfg: ArchConfig, plan: ShardPlan, batch: int, seq_len: int,
                    dtype=jnp.bfloat16):
    """Per-layer (unstacked) cache arrays + logical axes."""
    if cfg.attn_kind == "mla":
        c = {
            "ckv": jnp.zeros((batch, seq_len, cfg.kv_lora_rank), dtype),
            "kr": jnp.zeros((batch, seq_len, cfg.qk_rope_head_dim), dtype),
        }
        ax = {"ckv": ("batch", "cache_seq", None), "kr": ("batch", "cache_seq", None)}
        return c, ax
    if cfg.attn_kind == "swa" and cfg.window:
        w = min(cfg.window, seq_len)
        c = {
            "k": jnp.zeros((batch, w, cfg.n_kv_heads, cfg.head_dim), dtype),
            "v": jnp.zeros((batch, w, cfg.n_kv_heads, cfg.head_dim), dtype),
        }
        ax = {"k": ("batch", "window", "kv_cache_heads", None),
              "v": ("batch", "window", "kv_cache_heads", None)}
        return c, ax
    c = {
        "k": jnp.zeros((batch, seq_len, cfg.n_kv_heads, cfg.head_dim), dtype),
        "v": jnp.zeros((batch, seq_len, cfg.n_kv_heads, cfg.head_dim), dtype),
    }
    ax = {"k": ("batch", "cache_seq", "kv_cache_heads", None),
          "v": ("batch", "cache_seq", "kv_cache_heads", None)}
    return c, ax
