"""Pallas kernel microbench: interpret-mode allclose vs oracle + timing.
(Wall time here is CPU interpret-mode — correctness gate, not TPU perf.)"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, timed
from repro.kernels import ops, ref


def main(quick: bool = True) -> list:
    rows = []
    rng = np.random.default_rng(0)
    arr = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)

    B, H, K, S, D = 1, 4, 2, 256, 64
    q, k, v = arr(B, H, S, D), arr(B, K, S, D), arr(B, K, S, D)
    o, us = timed(lambda: np.asarray(
        ops.flash_attention(q, k, v, block_q=64, block_k=64)))
    err = float(jnp.max(jnp.abs(o - ref.flash_attention_ref(q, k, v))))
    rows.append(emit("kernel_flash_attention", us,
                     {"max_err": err, "ok": err < 1e-4}))

    q1 = arr(B, H, D)
    pos = jnp.asarray([200], jnp.int32)
    kd, vd = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)

    def best_of(fn, n=5):
        fn()  # warm the jit cache so timings are steady-state
        return min(timed(fn)[1] for _ in range(n))

    o = np.asarray(ops.decode_attention(q1, kd, vd, pos, block_k=64))
    us = best_of(lambda: np.asarray(
        ops.decode_attention(q1, kd, vd, pos, block_k=64)))
    err = float(jnp.max(jnp.abs(o - ref.decode_attention_ref(q1, kd, vd, pos))))
    rows.append(emit("kernel_decode_attention", us,
                     {"max_err": err, "ok": err < 1e-4}))

    # paged decode over the same context: S=256 split into 64-token blocks
    bs = 64
    t_blk = S // bs
    kp = jnp.concatenate([jnp.zeros((1, bs, K, D), kd.dtype),
                          kd.reshape(t_blk, bs, K, D)])
    vp = jnp.concatenate([jnp.zeros((1, bs, K, D), vd.dtype),
                          vd.reshape(t_blk, bs, K, D)])
    bt = jnp.arange(1, t_blk + 1, dtype=jnp.int32)[None, :]
    kp, vp = kp[None], vp[None]          # a one-layer stacked pool
    op = np.asarray(ops.paged_decode_attention(q1, kp, vp, 0, bt, pos))
    us_p = best_of(lambda: np.asarray(
        ops.paged_decode_attention(q1, kp, vp, 0, bt, pos)))
    err = float(np.max(np.abs(op - o)))       # must equal the dense result
    ratio = us_p / max(us, 1e-9)
    rows.append(emit("kernel_paged_decode_attention", us_p,
                     {"max_err_vs_dense": err, "time_vs_dense": round(ratio, 3),
                      "ok": err < 1e-4 and ratio <= 1.10}))

    T, Hn, Dn = 128, 2, 32
    r, kk, vv = arr(B, T, Hn, Dn), arr(B, T, Hn, Dn), arr(B, T, Hn, Dn)
    w = jnp.asarray(rng.uniform(0.85, 0.999, (B, T, Hn, Dn)), jnp.float32)
    u, s0 = arr(Hn, Dn), arr(B, Hn, Dn, Dn)
    (y, sf), us = timed(lambda: jax.tree.map(
        np.asarray, ops.rwkv6_wkv(r, kk, vv, w, u, s0, block_t=32)))
    y_ref, sf_ref = ref.rwkv6_wkv_ref(r, kk, vv, w, u, s0)
    err = float(jnp.max(jnp.abs(y - y_ref)))
    rows.append(emit("kernel_rwkv6_wkv", us, {"max_err": err, "ok": err < 1e-3}))

    x, wmat = arr(128, 256), arr(256, 128)
    o, us = timed(lambda: np.asarray(ops.int8_matmul_quantized(x, wmat)))
    xq, sx = ops.quantize_rows(x)
    wq, sw = ops.quantize_cols(wmat)
    err = float(jnp.max(jnp.abs(
        o.astype(jnp.float32)
        - ref.int8_matmul_ref(xq, wq, sx, sw).astype(jnp.float32))))
    rows.append(emit("kernel_int8_matmul", us, {"max_err": err, "ok": err == 0.0}))
    return rows


if __name__ == "__main__":
    main()
