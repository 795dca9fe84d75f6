"""One serving construction API.

``EngineSpec`` is the single way to build a serving stack: it turns an
``ArchConfig`` plus a shard count into (mesh, sharded params, paged pool,
``Engine``) in one call, replacing the hand-wired
``build_model``/``init``/``Engine(...)`` chains previously duplicated
across ``examples/``, ``benchmarks/bench_engine.py`` and the backends.

``serving_plan`` is the single mesh entrypoint for serving:
``launch.mesh.make_mesh_for`` + ``models.sharding.mesh_plan`` at
``shards > 1`` (a ``(1, shards)`` ("data", "model") mesh over the first
``shards`` local devices), ``local_plan`` at ``shards = 1`` — so the
shard-count knob is one integer and shard=1 builds byte-identical graphs
to the pre-sharding engine.

``build(share=other_engine)`` aliases another engine's (model, params)
registries instead of re-initialising them — jax arrays are immutable, so
a fleet of engines holds ONE copy of the weights (see
``serving.backend.EngineFleet``).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.launch.mesh import make_mesh_for
from repro.models.sharding import ShardPlan, local_plan, mesh_plan
from repro.models.transformer import Model
from repro.serving.engine import Engine, EngineKnobs, shard_compat


def serving_plan(shards: int = 1, **kw) -> ShardPlan:
    """THE serving mesh entrypoint: one integer picks the parallelism.

    ``shards <= 1`` returns a single-device ``local_plan``; otherwise a
    ``(1, shards)`` ("data", "model") mesh over the first ``shards``
    local devices, so the whole decode batch stays on every rank and only
    the paged pool (and TP params) shard."""
    kw.setdefault("param_dtype", jnp.bfloat16)
    if shards <= 1:
        return local_plan(**kw)
    if jax.device_count() < shards:
        raise ValueError(
            f"serving_plan(shards={shards}) needs {shards} chips; "
            f"{jax.device_count()} visible")
    mesh = make_mesh_for(shards, want_model=shards)
    if mesh.shape["model"] != shards:
        raise ValueError(f"make_mesh_for could not build a model={shards} "
                         f"mesh (got {dict(mesh.shape)})")
    return mesh_plan(mesh, **kw)


@dataclass(frozen=True)
class EngineSpec:
    """Declarative description of one serving engine.

    ``variants`` / ``drafters`` are ``(name, ArchConfig)`` pairs; their
    models are built under the same plan and registered on the engine
    (params keyed off ``seed`` so repeated builds are deterministic).
    """
    cfg: ArchConfig
    shards: int = 1
    max_seq: int = 512
    n_slots: int = 8
    max_batch: int | None = None        # default: n_slots
    block_size: int = 16
    n_blocks: int | None = None
    horizon: int = 1
    prefill_chunk: int | None = None
    prefix_share: bool = False
    spec_k: int = 4
    draft: str | None = None            # None | "ngram" | a drafters name
    ngram: int = 2
    seed: int = 0
    param_dtype: Any = jnp.bfloat16
    variants: tuple = ()                # ((name, ArchConfig), ...)
    drafters: tuple = ()                # ((name, ArchConfig), ...)

    def replace(self, **kw) -> "EngineSpec":
        return dataclasses.replace(self, **kw)

    def plan(self) -> ShardPlan:
        return serving_plan(self.shards, param_dtype=self.param_dtype)

    def validate(self) -> None:
        for name, cfg in (("full", self.cfg), *self.variants, *self.drafters):
            err = shard_compat(self.shards, cfg)
            if err is not None:
                raise ValueError(f"EngineSpec ({name!r}): {err}")

    def _materialize(self, cfg: ArchConfig, plan: ShardPlan, seed: int):
        model = Model(cfg, plan)
        return model, model.init_placed(seed)

    def build(self, *, share: Engine | None = None) -> Engine:
        """Build (mesh, sharded params, pool, Engine) in one call.

        ``share=`` aliases an existing engine's model/param registries
        (it must come from a spec with the same cfg/shards/variants) so N
        engines hold one copy of the weights; each engine still gets its
        own pool and jit bindings."""
        self.validate()
        plan = self.plan()
        if share is not None:
            model, params = share.variants["full"]
        else:
            model, params = self._materialize(self.cfg, plan, self.seed)
        eng = Engine(
            model, params, max_seq=self.max_seq, n_slots=self.n_slots,
            knobs=EngineKnobs(max_batch=self.max_batch or self.n_slots),
            paged=True, block_size=self.block_size, n_blocks=self.n_blocks,
            horizon=self.horizon, prefill_chunk=self.prefill_chunk,
            prefix_share=self.prefix_share, spec_k=self.spec_k,
            draft=self.draft if self.draft in (None, "ngram") else None,
            ngram=self.ngram, seed=self.seed)
        if share is not None:
            for name, (m, p) in share.variants.items():
                if name != "full":
                    eng.add_variant(name, m, p)
            for name, (m, p) in share.drafters.items():
                eng.add_drafter(name, m, p)
        else:
            for i, (name, vcfg) in enumerate(self.variants):
                eng.add_variant(name,
                                *self._materialize(vcfg, plan,
                                                   self.seed + 10 + i))
            for i, (name, dcfg) in enumerate(self.drafters):
                eng.add_drafter(name,
                                *self._materialize(dcfg, plan,
                                                   self.seed + 100 + i))
        if self.draft is not None and self.draft != "ngram":
            eng.set_drafter(self.draft)
        eng.spec = self
        return eng
