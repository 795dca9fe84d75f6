"""Logical-axis sharding: map parameter/activation dimensions to mesh axes.

Every parameter pytree has a parallel "axes" pytree of tuples naming each
dimension (e.g. ``("embed", "heads", "qk_dim")``).  ``ShardPlan`` holds the
mesh + rules; ``spec_for``/``tree_shardings`` turn axes into NamedShardings.

TP dims ("heads", "ffn", "vocab", "experts", "d_inner", "kv_heads" where
divisible) shard over the ``model`` axis; training additionally FSDP-shards
"embed" over ``data`` (ZeRO-3 via GSPMD).  Head/expert/vocab counts that do
not divide the TP degree are zero-padded (see DESIGN.md §3).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models.layers import pad_to_multiple

@dataclass(frozen=True)
class ShardPlan:
    mesh: Any = None                     # jax.sharding.Mesh or None (single host)
    tp_axis: str | None = None           # "model"
    dp_axes: tuple = ()                  # ("data",) or ("pod", "data")
    fsdp: bool = False                   # shard "embed" over data (training)
    compute_dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: str = "none"                  # none | full
    attn_temp_budget: int = 512 * 2**20  # bytes budget for score temporaries
    # --- hillclimb knobs (see EXPERIMENTS.md §Perf) ---
    seq_shard_activations: bool = False  # Megatron-style sequence parallelism
    quantize_serve: bool = False         # int8 weights for serve (w8a8 knob)
    kv_pad_enabled: bool = True          # pad kv heads to TP (kills replicated
    #                                      kv-proj compute; off for decode to
    #                                      keep the KV cache at real head count)
    attn_exact_causal: bool = False      # pair-scan: skip above-diagonal tiles
    #                                      (exact causal FLOPs + reads)
    attn_cq: int = 512                   # attention tile size (q and k)
    shard_paged_pool: bool = True        # shard the paged KV block pool over
    #                                      the model axis (LSE-combined decode)

    @property
    def tp(self) -> int:
        if self.mesh is None or self.tp_axis is None:
            return 1
        return self.mesh.shape[self.tp_axis]

    @property
    def dp(self) -> int:
        if self.mesh is None:
            return 1
        n = 1
        for a in self.dp_axes:
            n *= self.mesh.shape[a]
        return n

    # ---------------- padded dims ----------------
    def h_pad(self, cfg: ArchConfig) -> int:
        return pad_to_multiple(cfg.n_heads, self.tp)

    def kv_padded(self, cfg: ArchConfig) -> bool:
        """Pad kv heads to TP degree (shard-aligned copies)?  Legal when the
        GQA group structure aligns with head sharding (see DESIGN.md §3)."""
        k, h, tp = cfg.n_kv_heads, cfg.n_heads, self.tp
        return (self.kv_pad_enabled and cfg.attn_kind in ("gqa", "swa")
                and 0 < k < tp and tp % k == 0 and h % tp == 0)

    def k_pad(self, cfg: ArchConfig) -> int:
        """Effective kv-head count after padding."""
        return self.tp if self.kv_padded(cfg) else cfg.n_kv_heads

    def kv_sharded(self, cfg: ArchConfig) -> bool:
        return cfg.n_kv_heads > 0 and (cfg.n_kv_heads % self.tp == 0
                                       or self.kv_padded(cfg))

    def paged_pool_sharded(self, cfg: ArchConfig | None = None) -> bool:
        """Shard the paged block pool's ``n_blocks`` axis over ``model``?

        The pool shards by *blocks* (rank r owns a contiguous stripe of
        physical block ids), not by heads, so it holds for any kv-head
        count — the per-shard attention masks unowned blocks and an LSE
        max/sum combine merges the partials (see
        ``attention._paged_decode_core``)."""
        return (self.shard_paged_pool and self.mesh is not None
                and self.tp_axis is not None and self.tp > 1)

    def e_pad(self, cfg: ArchConfig) -> int:
        return pad_to_multiple(cfg.n_experts, self.tp) if cfg.n_experts else 0

    def v_pad(self, cfg: ArchConfig) -> int:
        return pad_to_multiple(cfg.vocab_size, self.tp)

    # ---------------- logical -> mesh rules ----------------
    def rules(self, cfg: ArchConfig) -> dict:
        tp = self.tp_axis
        return {
            "batch": self.dp_axes if self.dp_axes else None,
            "seq": None,
            "embed": self.dp_axes[-1] if (self.fsdp and self.dp_axes) else None,
            "embed_act": None,           # activation d_model dim: never sharded
            "vocab": tp,
            "ffn": tp,
            "heads": tp,
            "kv_heads": tp if self.kv_sharded(cfg) else None,
            # decode caches shard along cache_seq; their head dim stays whole
            "kv_cache_heads": None,
            # paged pools shard along physical block ids (stripe per rank)
            "kv_blocks": tp if self.paged_pool_sharded() else None,
            "experts": tp,
            "d_inner": tp,
            "cache_seq": tp,             # decode KV cache sharded along sequence
            "window": None,
            "qk_dim": None,
            "v_dim": None,
            "lora": None,
            "state": None,
            "conv": None,
            None: None,
        }

    def spec_for(self, axes: tuple, cfg: ArchConfig) -> P:
        r = self.rules(cfg)
        return P(*(r.get(a) for a in axes))

    def sharding_for(self, axes: tuple, cfg: ArchConfig):
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, self.spec_for(axes, cfg))

    def tree_shardings(self, axes_tree, cfg: ArchConfig):
        """Map an axes pytree (tuples at leaves) to NamedShardings."""
        return jax.tree.map(lambda ax: self.sharding_for(ax, cfg), axes_tree,
                            is_leaf=lambda x: isinstance(x, tuple))

    def constrain(self, x: jax.Array, axes: tuple, cfg: ArchConfig) -> jax.Array:
        """Activation sharding constraint; no-op without a mesh."""
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, self.spec_for(axes, cfg)))


def local_plan(**kw) -> ShardPlan:
    """Single-device plan (smoke tests, examples)."""
    return ShardPlan(mesh=None, tp_axis=None, dp_axes=(), **kw)


def mesh_plan(mesh: Mesh, *, fsdp: bool = False, **kw) -> ShardPlan:
    axes = mesh.axis_names
    dp_axes = tuple(a for a in axes if a in ("pod", "data"))
    tp_axis = "model" if "model" in axes else None
    return ShardPlan(mesh=mesh, tp_axis=tp_axis, dp_axes=dp_axes, fsdp=fsdp, **kw)


def shard_map_or_call(plan: ShardPlan, fn, in_specs, out_specs, *args):
    """Run ``fn`` under shard_map when a mesh is present, else directly.

    ``fn`` receives ``axis`` (the TP axis name or None) as first argument so
    collectives become no-ops on a single device.
    """
    if plan.mesh is None or plan.tp_axis is None:
        return fn(None, *args)
    mapped = jax.shard_map(
        partial(fn, plan.tp_axis), mesh=plan.mesh,
        in_specs=in_specs, out_specs=out_specs, check_vma=False)
    return mapped(*args)
