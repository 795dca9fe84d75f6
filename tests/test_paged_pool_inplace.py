"""The paged entry points update the stacked KV pool in place.

Two properties of the layer scan that carries the layer-stacked pool
``(L, n_blocks, bs, K, hd)`` and hands each layer its index:

* **No pool copy** — the optimised HLO of each entry point, compiled with
  the engine's jit bindings (donated pool) at the ``AuditContext``
  geometry, holds no ``copy``, ``broadcast`` or ``dynamic-update-slice``
  whose result has the stacked pool's shape, and no ``dynamic-slice`` of a
  whole layer leaf.  Scanning the pool as per-layer ``xs``/``ys`` slices
  each layer out and restacks it into a fresh buffer, which the compiler
  then copies into the donated output: every one of those ops shows up.
  The CPU backend normalises bf16 scatters to f32, so ``convert`` ops of
  the pool's shape are expected here and allowed (the TPU compiler
  scatters bf16 directly).
* **The layer index writes the right layer** — after one
  ``decode_multi_paged`` launch or one ``prefill_chunk_paged`` call on a
  pool of random values, every slot outside the written
  ``(layer, block, offset)`` set is bit-identical to the input, and the
  written slots hold what a per-layer reference (each block run on its
  own one-layer pool) projects.

Both run unsharded in-process and under a ``(1, 2)`` mesh in a child
process with four virtual CPU devices (``tests/_paged_pool_inplace_main.py``),
since the session pins a single device.
"""
from __future__ import annotations

import os
import pathlib
import re
import subprocess
import sys

import numpy as np
import pytest

_ROOT = pathlib.Path(__file__).resolve().parent.parent

# case -> (registry entry whose jit binding is compiled, AuditContext kwargs)
HLO_CASES = {
    "decode_step_paged": ("decode_step_paged", {}),
    "decode_multi_paged_h1": ("decode_multi_paged_h4", {"horizon": 1}),
    "decode_multi_paged_h4": ("decode_multi_paged_h4", {"horizon": 4}),
    "prefill_chunk_paged_c16": ("prefill_chunk_paged_c16", {}),
    "decode_spec_paged_k4": ("decode_spec_paged_k4", {}),
}
WRITE_CASES = ("decode_multi_paged_h1", "prefill_chunk_paged_c16")
MESH = "@1x2"

_COPY_OPS = ("copy", "copy-start", "broadcast", "dynamic-update-slice")
_OP_LINE = re.compile(r"^\s*(?:ROOT )?%\S+ = \w+\[([\d,]*)\]\S* ([\w-]+)\(")


# ---------------------------------------------------------------------------
# shared with the multi-device child
# ---------------------------------------------------------------------------

def pool_traffic(hlo: str, pool_shape) -> tuple[list, int]:
    """(offending op lines, number of ops of the stacked pool's shape).

    Offending: a copy/broadcast/dynamic-update-slice of the stacked
    pool's shape, or a dynamic-slice of one whole layer leaf.  The count
    guards the check itself: the donated pool is an operand of that
    shape, so zero means the shape looked for was wrong."""
    stacked = ",".join(map(str, pool_shape))
    leaf = tuple(pool_shape[1:])
    layer = {",".join(map(str, leaf)), ",".join(map(str, (1,) + leaf))}
    bad, seen = [], 0
    for line in hlo.splitlines():
        m = _OP_LINE.match(line)
        if not m:
            continue
        shape, op = m.groups()
        seen += shape == stacked
        if (shape == stacked and op in _COPY_OPS) or \
                (shape in layer and op == "dynamic-slice"):
            bad.append(line.strip()[:200])
    return bad, seen


def hlo_case(case: str, shards: int = 1) -> tuple[list, int]:
    """Compile one case's jit binding and scan its HLO (per-device pool
    shape under a mesh)."""
    from repro.analysis.iraudit import (ENTRYPOINTS_BY_NAME, AuditContext,
                                        audit_entry)
    entry, kw = HLO_CASES[case]
    ctx = AuditContext(shards=shards, **kw)
    shape = ctx.cache["attn"]["k"].shape
    shardings = ctx.model.paged_cache_shardings()
    if shardings is not None:
        shape = shardings["attn"]["k"].shard_shape(shape)
    return pool_traffic(audit_entry(ENTRYPOINTS_BY_NAME[entry], ctx).hlo,
                        shape)


def _random_pool(model, n_blocks, block_size, rng):
    import jax
    import jax.numpy as jnp
    shapes = jax.eval_shape(
        lambda: model.init_paged_cache(n_blocks, block_size))
    pool = jax.tree.map(
        lambda s: jnp.asarray(rng.normal(size=s.shape), s.dtype), shapes)
    shardings = model.paged_cache_shardings()
    if shardings is not None:
        pool = jax.device_put(pool, shardings)
    return pool


def _reference_pool(model, params, pool, x, layer_fn):
    """Run each layer on its own one-layer pool (layer index 0) and stack
    what it wrote: the per-layer reference of the carried scan."""
    import jax
    import jax.numpy as jnp
    out_k, out_v = [], []
    for layer in range(model.cfg.num_layers):
        lp = jax.tree.map(lambda a: a[layer], params["layers"])
        one = jax.tree.map(lambda a: a[layer:layer + 1], pool)
        x, got = layer_fn(x, lp, one)
        out_k.append(got["attn"]["k"])
        out_v.append(got["attn"]["v"])
    return {"attn": {"k": jnp.concatenate(out_k), "v": jnp.concatenate(out_v)}}


def write_case(case: str, shards: int = 1) -> str:
    """Run one launch on a random pool; return '' if every slot outside
    the written (layer, block, offset) set is unchanged and every written
    slot equals the per-layer reference, else a description."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.models.transformer import Model
    from repro.serving.spec import serving_plan

    cfg = get_config("llama2-7b").smoke_config()
    model = Model(cfg, serving_plan(shards))
    plan = model.plan
    params = model.init_placed(0)
    bs, T_ = 8, 8
    n_blocks = 26                       # parking block + 25, divides by 2
    rng = np.random.default_rng(7 + shards)
    pool = _random_pool(model, n_blocks, bs, rng)
    tables = np.stack([rng.permutation(np.arange(1, n_blocks))[:T_]
                       for _ in range(3)]).astype(np.int32)

    def embed(tokens):
        x = T.embed_lookup(params["embed"], tokens, cfg, plan)
        if cfg.embed_scale:
            x = x * jnp.asarray(np.sqrt(cfg.d_model), plan.compute_dtype)
        return x

    if case == "decode_multi_paged_h1":
        tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, 3), jnp.int32)
        positions = np.array([13, 40, 5], np.int32)
        active = np.array([True, True, False])
        pos_eff = np.where(active, positions, 0)
        tab_eff = np.where(active[:, None], tables, 0)
        written = [(int(tab_eff[b, pos_eff[b] // bs]), int(pos_eff[b] % bs))
                   for b in range(3)]
        fn = jax.jit(model.decode_multi_paged,
                     static_argnames=("num_steps", "max_len"),
                     donate_argnums=(1,))
        got = fn(params, jax.tree.map(jnp.copy, pool), tokens,
                 jnp.asarray(positions), jnp.asarray(tables),
                 jnp.asarray(active), jnp.full((3,), 5, jnp.int32),
                 jnp.full((3,), -1, jnp.int32), num_steps=1, max_len=64)[-1]

        @jax.jit
        def layer_fn(x, lp, one):
            return T.block_decode_paged(x, lp, one, 0, jnp.asarray(pos_eff),
                                        jnp.asarray(tab_eff), cfg, plan)

        x0 = embed(tokens)
    elif case == "prefill_chunk_paged_c16":
        C = 16
        tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, C)),
                             jnp.int32)
        starts = np.array([0, 24], np.int32)
        lengths = np.array([16, 5], np.int32)
        written = {(0, 0)}               # ragged tail -> parking slot
        for b in range(2):
            for c in range(lengths[b]):
                p = starts[b] + c
                written.add((int(tables[b, p // bs]), int(p % bs)))
        fn = jax.jit(model.prefill_chunk_paged, donate_argnums=(1,))
        got = fn(params, jax.tree.map(jnp.copy, pool), tokens,
                 jnp.asarray(starts), jnp.asarray(lengths),
                 jnp.asarray(tables[:2]))[1]

        @jax.jit
        def layer_fn(x, lp, one):
            return T.block_prefill_paged(
                x, lp, one, 0, jnp.asarray(starts), jnp.asarray(lengths),
                jnp.asarray(tables[:2]), cfg, plan)

        x0 = model._embed_inputs(params, tokens)
    else:
        raise KeyError(case)

    want = _reference_pool(model, params, pool, x0, layer_fn)
    mask = np.zeros((cfg.num_layers, n_blocks, bs), bool)
    for blk, off in written:
        mask[:, blk, off] = True
    problems = []
    for leaf in ("k", "v"):
        before = np.asarray(pool["attn"][leaf].astype(jnp.float32))
        after = np.asarray(got["attn"][leaf].astype(jnp.float32))
        ref = np.asarray(want["attn"][leaf].astype(jnp.float32))
        if not np.array_equal(after[~mask], before[~mask]):
            n = int((after[~mask] != before[~mask]).sum())
            problems.append(f"{leaf}: {n} values changed outside the "
                            f"written slots")
        if not np.array_equal(after[mask], ref[mask]):
            gap = float(np.max(np.abs(after[mask] - ref[mask])))
            problems.append(f"{leaf}: written slots differ from the "
                            f"per-layer reference (max gap {gap})")
        if np.array_equal(after[mask], before[mask]):
            problems.append(f"{leaf}: nothing was written")
    return "; ".join(problems)


# ---------------------------------------------------------------------------
# tests
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def mesh_report():
    """One child process runs every ``@1x2`` case; returns its stdout."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(_ROOT / "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run(
        [sys.executable, str(_ROOT / "tests" / "_paged_pool_inplace_main.py")],
        capture_output=True, text=True, env=env, timeout=900)
    assert proc.returncode == 0, f"{proc.stdout}\n{proc.stderr}"
    return proc.stdout


def _child_result(report: str, kind: str, case: str) -> str:
    for line in report.splitlines():
        if line.startswith(f"{kind} {case}{MESH}:"):
            return line.split(":", 1)[1].strip()
    raise AssertionError(f"child printed no {kind} line for {case}{MESH}:\n"
                         f"{report}")


@pytest.mark.filterwarnings("ignore:Some donated buffers were not usable")
@pytest.mark.parametrize("case", [c + m for m in ("", MESH)
                                  for c in HLO_CASES])
def test_pool_not_copied(case, request):
    if case.endswith(MESH):
        verdict = _child_result(request.getfixturevalue("mesh_report"),
                                "hlo", case[:-len(MESH)])
        assert verdict == "ok", verdict
        return
    bad, seen = hlo_case(case)
    assert bad == [], "pool-sized copies:\n" + "\n".join(bad)
    assert seen, "no op of the stacked pool's shape: wrong shape?"


@pytest.mark.parametrize("case", [c + m for m in ("", MESH)
                                  for c in WRITE_CASES])
def test_layer_index_writes_its_layer(case, request):
    if case.endswith(MESH):
        verdict = _child_result(request.getfixturevalue("mesh_report"),
                                "writes", case[:-len(MESH)])
    else:
        verdict = write_case(case) or "ok"
    assert verdict == "ok", verdict
