"""Plain float32 forward pass of a dense GQA decoder, the yardstick that
decides ``correct`` for the ``dense_gqa`` family; other families
(``bench/families/``) subclass ``Reference`` with their own layer.

RMSNorm, rotary embeddings (rotate-half), optional RMSNorm of q and k
over the head dimension before RoPE (Qwen3), grouped-query causal
attention, SwiGLU MLP, tied or untied output head: the published
Llama/Qwen3 decoder layer.  Every matrix product runs in float32 under
``default_matmul_precision("highest")``.  The weights come from
``bench/weights.py`` (made from the seed, never read from the program),
one layer at a time, so the pass fits beside nothing else on one chip.

``judge`` teacher-forces each sampled request (prompt plus served
tokens) and returns, for every served token, how far its logit lies
below the reference's best logit at that position.  ``control`` is the
same forward with every matrix product's operands rounded to float8
(e4m3, one absmax scale per weight matrix and per activation row): the
nearest precision below the bfloat16 the configurations are served in.
It reads the gap of the token that the float8 pass puts first.

This module imports nothing of the program.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _q8(x, axis):
    """Round to float8 e4m3 with an absmax scale along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(F8).astype(jnp.float32) * s


def _mm(a, b, quant: bool):
    """(..., n) @ (n, m) in float32; float8 operands for the control."""
    if quant:
        a, b = _q8(a, -1), _q8(b, 0)
    return jnp.matmul(a, b)


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rope(x, pos, theta):
    """x (..., S, n, hd); rotate-half RoPE at integer positions ``pos``."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q, k, v, quant: bool):
    """One sequence: q (S, H, hd), k/v (S, K, hd), causal, GQA."""
    s_len, h, hd = q.shape
    rep = h // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    if quant:
        q, k, v = _q8(q, -1), _q8(k, -1), _q8(v, -1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(hd)
    mask = jnp.arange(s_len)[:, None] >= jnp.arange(s_len)[None, :]
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    if quant:
        p = _q8(p, -1)
    return jnp.einsum("hqk,khd->qhd", p, v)


def _attention(x, w, dm, quant):
    """The attention half of a layer with its residual: x (B, S, d)
    float32; w one layer's weights (float32)."""
    b, s_len, _ = x.shape
    h, k, hd = dm["h"], dm["k"], dm["hd"]
    pos = jnp.arange(s_len)
    y = _rms(x, w["norm1"], dm["eps"])
    q = _mm(y, w["wq"], quant).reshape(b, s_len, h, hd)
    kk = _mm(y, w["wk"], quant).reshape(b, s_len, k, hd)
    vv = _mm(y, w["wv"], quant).reshape(b, s_len, k, hd)
    if dm["qk_norm"]:
        q = _rms(q, w["q_norm"], dm["eps"])
        kk = _rms(kk, w["k_norm"], dm["eps"])
    q, kk = _rope(q, pos, dm["theta"]), _rope(kk, pos, dm["theta"])
    o = jax.lax.map(lambda a: _attend(*a, quant), (q, kk, vv))
    return x + _mm(o.reshape(b, s_len, h * hd), w["wo"], quant)


def _layer(x, w, dm, quant):
    """x (B, S, d) float32; w one layer's weights (float32)."""
    x = _attention(x, w, dm, quant)
    y = _rms(x, w["norm2"], dm["eps"])
    g = _mm(y, w["w_gate"], quant)
    u = _mm(y, w["w_up"], quant)
    return x + _mm(jax.nn.silu(g) * u, w["w_down"], quant)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


class Reference:
    """The float32 forward of one configuration at one seed.

    The four class attributes are what a model family replaces in a
    subclass: its sizes (``dims(cfg)``, with ``layers``, ``d``, ``v``,
    ``eps`` and ``tied``), one layer's seeded tensors made alone
    (``layer_weights(key, l, dm)``), the embedding, final norm and head
    (``outer_weights(key, dm)``: ``embed``, ``final_norm`` and, untied,
    ``lm_head``) and one layer's float32 forward (``layer_forward(x, w,
    dm, quant)``).  The embedding, the layer loop, the head, ``judge`` and
    the float8 control are the same for every family."""

    dims = staticmethod(W.dims)
    layer_weights = staticmethod(W.layer)
    outer_weights = staticmethod(W.outer)
    layer_forward = staticmethod(_layer)

    def __init__(self, cfg: dict, seed: int, *, device=None):
        self.dm = self.dims(cfg)
        self.words = W.seed_words(seed)
        self.device = device or jax.devices()[0]
        dm = self.dm
        layer_weights, outer_weights, layer_forward = \
            self.layer_weights, self.outer_weights, self.layer_forward

        @jax.jit
        def embed(words, tokens):
            o = outer_weights(W.seed_key(words), dm)
            return o["embed"].astype(jnp.float32)[tokens]

        @partial(jax.jit, static_argnames="quant")
        def layer(x, words, l, quant):
            w = _f32(layer_weights(W.seed_key(words), l, dm))
            return layer_forward(x, w, dm, quant)

        @partial(jax.jit, static_argnames="quant")
        def head(x, words, idx, judged, quant):
            """x (B, S, d); idx (B, T) positions; judged (B, T) token ids
            or -1 (the control: judge its own argmax).  Returns the gap
            below the reference's best logit (B, T) and the judged ids."""
            o = _f32(outer_weights(W.seed_key(words), dm))
            w_out = o["embed"].T if dm["tied"] else o["lm_head"]
            xs = jnp.take_along_axis(x, idx[..., None], axis=1)
            xs = _rms(xs, o["final_norm"], dm["eps"])

            def rows(xr):
                return _mm(xr, w_out, quant)

            logits = jax.lax.map(rows, xs)          # (B, T, V)
            pick = jnp.where(judged >= 0, judged,
                             jnp.argmax(logits, axis=-1))
            return logits, pick

        @jax.jit
        def gaps(logits, pick):
            best = jnp.max(logits, axis=-1)
            got = jnp.take_along_axis(logits, pick[..., None], axis=-1)[..., 0]
            return best - got

        self._embed, self._layer, self._head, self._gaps = \
            embed, layer, head, gaps

    def _forward(self, tokens, quant):
        with jax.default_matmul_precision("highest"), \
                jax.default_device(self.device):
            words = jax.device_put(self.words, self.device)
            x = self._embed(words, jnp.asarray(tokens))
            for l in range(self.dm["layers"]):
                x = self._layer(x, words, np.int32(l), quant=quant)
            return x, words

    def logits(self, tokens) -> np.ndarray:
        """Float32 logits at every position of ``tokens`` (B, S)."""
        tokens = np.asarray(tokens, np.int32)
        b, s_len = tokens.shape
        idx = np.broadcast_to(np.arange(s_len, dtype=np.int32), (b, s_len))
        with jax.default_matmul_precision("highest"), \
                jax.default_device(self.device):
            x, words = self._forward(tokens, False)
            out, _ = self._head(x, words, jnp.asarray(idx),
                                jnp.zeros((b, s_len), jnp.int32),
                                quant=False)
            return np.asarray(out)

    def judge(self, seqs: list, *, pad_to: int, judge_len: int,
              control: bool = False) -> list:
        """Per sequence ``(prompt, served)``: the gap of every served token
        (or, with ``control``, of the float8 pass's first choice at the
        same positions) below the float32 reference's best logit.

        Sequences are padded to ``pad_to`` tokens and the judged positions
        to ``judge_len``, so one compiled program serves every run."""
        b = len(seqs)
        tokens = np.zeros((b, pad_to), np.int32)
        idx = np.zeros((b, judge_len), np.int32)
        judged = np.zeros((b, judge_len), np.int32)
        for i, (prompt, served) in enumerate(seqs):
            seq = list(prompt) + list(served)[:-1]
            if len(seq) > pad_to or len(served) > judge_len:
                raise ValueError("a sampled request is longer than the "
                                 "reference's padded shape")
            tokens[i, :len(seq)] = seq
            n = len(served)
            idx[i, :n] = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
            judged[i, :n] = served
        with jax.default_matmul_precision("highest"), \
                jax.default_device(self.device):
            idx_d, judged_d = jnp.asarray(idx), jnp.asarray(judged)
            if control:
                xq, words = self._forward(tokens, True)
                _, pick = self._head(xq, words, idx_d,
                                     jnp.full_like(judged_d, -1), quant=True)
                del xq
            else:
                pick = judged_d
            x, words = self._forward(tokens, False)
            logits, pick = self._head(x, words, idx_d, pick, quant=False)
            g = np.asarray(self._gaps(logits, pick))
        return [g[i, :len(served)] for i, (_, served) in enumerate(seqs)]
