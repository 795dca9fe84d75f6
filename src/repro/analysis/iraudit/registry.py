"""Registry of jitted hot-path entrypoints, traced under abstract shapes.

Each :class:`Entrypoint` names one serving hot path and knows how to
rebuild its *exact* jit binding — same donation declaration, same static
arguments — over a fixed smoke-scale geometry (llama2-7b smoke config,
bf16 params, 3 lanes, ``max_seq`` 64, ``block_size`` 8: the engine-test
defaults, so budget numbers stay tiny and meaningful).  Tracing uses
``ShapeDtypeStruct`` avals throughout: no parameters are materialised and
no kernels execute; ``audit_entry`` only traces, lowers and compiles for
CPU, then hands the jaxpr + optimized HLO to the analysis passes.

The pool helpers (``_paged_insert`` & co.) are audited through the very
jitted objects serving calls — a drifted donation declaration in
``serving/kvcache.py`` shows up here, not in a copy.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.models import build_model, local_plan
from repro.models.transformer import Model


def _sds(shape, dtype) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


class AuditContext:
    """Smoke-scale serving geometry shared by every registered entrypoint.

    ``shards > 1`` audits the same geometry under a ``(1, shards)``
    ("data", "model") mesh — the sharded paged pool + LSE-combined decode
    paths — and needs that many visible devices (``scripts/iraudit.py``
    forces a 4-device CPU view; entries carry ``min_devices`` so
    single-device test sessions skip them)."""

    def __init__(self, config_name: str = "llama2-7b", *, n_lanes: int = 3,
                 max_seq: int = 64, block_size: int = 8, horizon: int = 4,
                 chunk: int = 16, bucket: int = 16, shards: int = 1):
        self.config_name = config_name
        self.n_lanes = n_lanes
        self.max_seq = max_seq
        self.block_size = block_size
        self.horizon = horizon
        self.chunk = chunk
        self.bucket = bucket
        self.shards = shards
        self.blocks_per_seq = max_seq // block_size
        self.n_blocks = n_lanes * self.blocks_per_seq + 1   # + parking block
        if self.n_blocks % max(shards, 1):
            self.n_blocks += shards - self.n_blocks % shards
        self.cfg = get_config(config_name).smoke_config()
        if shards > 1:
            from repro.serving.spec import serving_plan
            plan = serving_plan(shards)
        else:
            plan = local_plan(param_dtype=jnp.bfloat16)
        self.model = build_model(self.cfg, plan)
        self.params = jax.eval_shape(self.model.init, jax.random.PRNGKey(0))
        self.cache = jax.eval_shape(
            lambda: self.model.init_paged_cache(self.n_blocks,
                                                self.block_size))
        # stacked prefill cache for one ragged bucket (feeds _paged_insert)
        self.prefill_cache = jax.eval_shape(
            self.model.prefill_ragged, self.params,
            _sds((n_lanes, bucket), jnp.int32),
            _sds((n_lanes,), jnp.int32))[1]

    # -- common abstract operands ------------------------------------------
    def lane_i32(self):
        return _sds((self.n_lanes,), jnp.int32)

    def tables(self):
        return _sds((self.n_lanes, self.blocks_per_seq), jnp.int32)

    def decode_state(self):
        """(tokens, positions, block_tables) for the decode entrypoints."""
        return self.lane_i32(), self.lane_i32(), self.tables()

    def horizon_state(self):
        """active/budgets/eos_ids masks for the fused horizons."""
        return (_sds((self.n_lanes,), jnp.bool_), self.lane_i32(),
                self.lane_i32())

    def sampling_state(self):
        return (_sds((self.n_lanes,), jnp.float32), self.lane_i32(),
                self.lane_i32())

    def hist(self):
        return _sds((self.n_lanes, self.max_seq + 1), jnp.int32)

    def kv_pool_leaf(self):
        """(n_blocks, bs, K, hd) of one layer's K pool leaf."""
        return self.cache["attn"]["k"].shape[1:]


@dataclass(frozen=True)
class Entrypoint:
    """One audited hot path.

    ``build(ctx)`` returns ``(jitted_fn, args, kwargs)`` — the jitted
    callable with its real donation/static declarations, plus abstract
    operands.  ``f32_dot_ok`` marks entries whose graphs *deliberately*
    run f32 matmuls (the Pallas kernel bodies upcast q/k/v for
    flash-attention numerics); everything else must keep dot inputs in
    the configured compute dtype.  ``const_cap_bytes`` bounds the closure
    constants jit re-uploads per call.
    """
    name: str
    kind: str                    # "model" | "pool" | "kernel"
    build: Callable[[AuditContext], tuple]
    donate: tuple = ()           # documented declaration (ground truth is
                                 # read back off the traced args_info)
    f32_dot_ok: bool = False
    const_cap_bytes: int = 2048
    doc: str = ""
    min_devices: int = 1         # mesh entries need this many visible devices


@dataclass
class EntryAudit:
    """Trace + compile artifacts for one entrypoint, input to the passes."""
    entry: Entrypoint
    jaxpr: Any                   # ClosedJaxpr
    hlo: str                     # optimized (compiled) HLO text
    arg_leaves: list             # flat ShapeDtypeStructs of the call args
    donated_idx: tuple           # flat arg-leaf indices declared donated
    out_leaves: list = field(default_factory=list)


# ---------------------------------------------------------------------------
# builders — one per hot path, mirroring the engine's jit bindings exactly
# ---------------------------------------------------------------------------

def _b_decode_step(ctx: AuditContext):
    fn = jax.jit(ctx.model.decode_step_paged, donate_argnums=(1,))
    tok, pos, tab = ctx.decode_state()
    return fn, (ctx.params, ctx.cache, tok, pos, tab), {}


def _multi_args(ctx: AuditContext, sampled: bool):
    tok, pos, tab = ctx.decode_state()
    active, budgets, eos = ctx.horizon_state()
    args = (ctx.params, ctx.cache, tok, pos, tab, active, budgets, eos)
    kwargs = dict(num_steps=ctx.horizon, max_len=ctx.max_seq)
    if sampled:
        temps, top_ks, seeds = ctx.sampling_state()
        kwargs.update(temps=temps, top_ks=top_ks, seeds=seeds)
    return args, kwargs


def _b_decode_multi(ctx: AuditContext, *, sampled: bool = False):
    fn = jax.jit(ctx.model.decode_multi_paged,
                 static_argnames=("num_steps", "max_len"),
                 donate_argnums=(1,))
    args, kwargs = _multi_args(ctx, sampled)
    return fn, args, kwargs


def _b_decode_spec(ctx: AuditContext, *, spec_k: int):
    # engine binding: partial over (self, drafter); ngram drafter => None
    fn = jax.jit(
        functools.partial(Model.decode_spec_paged, ctx.model, None),
        static_argnames=("num_steps", "spec_k", "max_len", "ngram"),
        donate_argnums=(1, 3))
    tok, pos, tab = ctx.decode_state()
    active, budgets, eos = ctx.horizon_state()
    temps, top_ks, seeds = ctx.sampling_state()
    args = (ctx.params, ctx.cache, None, None, ctx.hist(), tok, pos, tab,
            active, budgets, eos, temps, top_ks, seeds)
    return fn, args, dict(num_steps=ctx.horizon, spec_k=spec_k,
                          max_len=ctx.max_seq, ngram=2)


def _b_prefill_ragged(ctx: AuditContext):
    fn = jax.jit(ctx.model.prefill_ragged)
    return fn, (ctx.params, _sds((ctx.n_lanes, ctx.bucket), jnp.int32),
                ctx.lane_i32()), {}


def _b_prefill_chunk(ctx: AuditContext):
    fn = jax.jit(ctx.model.prefill_chunk_paged, donate_argnums=(1,))
    return fn, (ctx.params, ctx.cache,
                _sds((ctx.n_lanes, ctx.chunk), jnp.int32), ctx.lane_i32(),
                ctx.lane_i32(), ctx.tables()), {}


def _b_paged_insert(ctx: AuditContext):
    from repro.serving.kvcache import _paged_insert
    n = -(-ctx.bucket // ctx.block_size)
    return _paged_insert, (ctx.cache, ctx.prefill_cache,
                           _sds((n,), jnp.int32), _sds((), jnp.int32)), {}


def _b_mirror_row(ctx: AuditContext):
    # the single donated mirror-update choke point, at row arity (the
    # block-table adopt path): arr.at[(lane,)].set(row)
    from repro.serving.kvcache import _mirror_update
    return _mirror_update, (ctx.tables(), (_sds((), jnp.int32),),
                            _sds((ctx.blocks_per_seq,), jnp.int32)), {}


@functools.lru_cache(maxsize=None)
def _mesh_ctx(shards: int) -> AuditContext:
    """One cached mesh-geometry context per shard degree (construction
    requires >= ``shards`` visible devices, so it is deferred to build
    time and only reached when ``min_devices`` admits the entry)."""
    return AuditContext(shards=shards)


def _b_decode_step_mesh(_ctx, *, shards: int):
    return _b_decode_step(_mesh_ctx(shards))


def _b_prefill_chunk_mesh(_ctx, *, shards: int):
    return _b_prefill_chunk(_mesh_ctx(shards))


def _b_bad_lane_scan(ctx: AuditContext):
    from repro.serving.kvcache import _bad_lane_scan
    return _bad_lane_scan, (ctx.cache, ctx.tables(), ctx.lane_i32(),
                            _sds((ctx.n_lanes,), jnp.bool_)), {}


def _b_kernel_decode(ctx: AuditContext):
    # the layer-stacked pool the decode step carries, read at a traced layer
    from repro.kernels import ops
    pool = ctx.cache["attn"]["k"]
    h_pad = ctx.model.plan.h_pad(ctx.cfg)
    q = _sds((ctx.n_lanes, h_pad, pool.shape[-1]), jnp.bfloat16)
    return ops.paged_decode_attention, (q, pool, pool, _sds((), jnp.int32),
                                        ctx.tables(), ctx.lane_i32()), \
        dict(interpret=True)


def _b_kernel_prefill(ctx: AuditContext):
    from repro.kernels import ops
    n_blocks, bs, K, hd = ctx.kv_pool_leaf()
    h_pad = ctx.model.plan.h_pad(ctx.cfg)
    pool = _sds((n_blocks, bs, K, hd), jnp.bfloat16)
    q = _sds((ctx.n_lanes, ctx.chunk, h_pad, hd), jnp.bfloat16)
    return ops.paged_prefill_attention, (q, pool, pool, ctx.tables(),
                                         ctx.lane_i32()), dict(interpret=True)


ENTRYPOINTS: tuple = (
    Entrypoint("decode_step_paged", "model", _b_decode_step, donate=(1,),
               doc="single-token paged decode (the horizon's inner step)"),
    Entrypoint("decode_multi_paged_h4", "model",
               functools.partial(_b_decode_multi, sampled=False),
               donate=(1,), doc="fused greedy horizon, num_steps=4"),
    Entrypoint("decode_multi_sampled_h4", "model",
               functools.partial(_b_decode_multi, sampled=True),
               donate=(1,),
               doc="fused horizon with temperature/top-k/seed lanes"),
    Entrypoint("decode_spec_paged_k1", "model",
               functools.partial(_b_decode_spec, spec_k=1), donate=(1, 3),
               doc="speculative horizon, n-gram drafts, K=1"),
    Entrypoint("decode_spec_paged_k4", "model",
               functools.partial(_b_decode_spec, spec_k=4), donate=(1, 3),
               doc="speculative horizon, n-gram drafts, K=4"),
    Entrypoint("prefill_ragged_b16", "model", _b_prefill_ragged,
               doc="batched ragged prefill at bucket 16"),
    Entrypoint("prefill_chunk_paged_c16", "model", _b_prefill_chunk,
               donate=(1,), doc="chunked paged prefill, chunk 16"),
    Entrypoint("pool_paged_insert", "pool", _b_paged_insert, donate=(0,),
               doc="scatter one prefilled request into its pool blocks"),
    Entrypoint("pool_mirror_row", "pool", _b_mirror_row, donate=(0,),
               doc="donated mirror-update choke point, row arity "
                   "(block-table adopt path)"),
    Entrypoint("pool_bad_lane_scan", "pool", _b_bad_lane_scan,
               doc="NaN/Inf quarantine sweep over written KV positions"),
    Entrypoint("kernel_paged_decode", "kernel", _b_kernel_decode,
               doc="Pallas paged decode over the stacked pool, bf16 "
                   "operands to the MXU (interpret mode)"),
    Entrypoint("kernel_paged_prefill", "kernel", _b_kernel_prefill,
               f32_dot_ok=True,
               doc="Pallas paged prefill kernel (interpret mode)"),
    # mesh geometries: the same decode/prefill hot paths under a
    # (data=1, model=2) mesh — per-shard paged attention + LSE combine,
    # with coll_bytes as a live budget column (the name carries the mesh
    # shape so budget rows per geometry stay distinct)
    Entrypoint("decode_step_paged@1x2", "model",
               functools.partial(_b_decode_step_mesh, shards=2),
               donate=(1,), min_devices=2,
               doc="paged decode under a 1x2 mesh (sharded pool, "
                   "LSE-combined)"),
    Entrypoint("prefill_chunk_paged_c16@1x2", "model",
               functools.partial(_b_prefill_chunk_mesh, shards=2),
               donate=(1,), min_devices=2,
               doc="chunked paged prefill under a 1x2 mesh"),
)

ENTRYPOINTS_BY_NAME = {e.name: e for e in ENTRYPOINTS}


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------

def audit_entry(entry: Entrypoint, ctx: AuditContext) -> EntryAudit:
    """Trace, lower and compile one entrypoint; no numerics run."""
    fn, args, kwargs = entry.build(ctx)
    traced = fn.trace(*args, **kwargs)
    lowered = traced.lower()
    hlo = lowered.compile().as_text()
    info_leaves = jax.tree.leaves(traced.args_info)
    donated = tuple(i for i, a in enumerate(info_leaves)
                    if getattr(a, "donated", False))
    arg_leaves = [jax.ShapeDtypeStruct(a.shape, a.dtype)
                  for a in info_leaves]
    out_leaves = [x for x in jax.tree.leaves(traced.out_info)
                  if hasattr(x, "shape")]
    return EntryAudit(entry=entry, jaxpr=traced.jaxpr, hlo=hlo,
                      arg_leaves=arg_leaves, donated_idx=donated,
                      out_leaves=out_leaves)


def audit_all(ctx: AuditContext | None = None,
              names: list | None = None) -> list:
    """Audit every registered entrypoint (or the named subset), in
    registry order."""
    ctx = ctx or AuditContext()
    picked = ENTRYPOINTS if not names else tuple(
        ENTRYPOINTS_BY_NAME[n] for n in names)
    return [audit_entry(e, ctx) for e in picked]
